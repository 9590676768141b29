"""Another architecture added with new files only.

A checkout of the tiny spec is given what a later configuration brings: the
module ``data/dense_swiglu.py`` as ``bench/models/dense_swiglu.py``, a
configuration file that names it, a limits file, and the configuration and
its cell as new entries of ``BENCHMARK.json``.  The harness, the weights,
the reference's shared parts and the check are the repo's, unchanged, and a
whole run on the CPU comes out correct while the module's fp8 control does
not."""
import json
import os
import shutil

import pytest

import bench_tiny
import calibrate
import harness
import run
from bench_tiny import tiny  # noqa: F401  (a fixture)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SEED = 2**31 + 211
CELL = "tiny-swiglu.chat"
MODEL = dict(bench_tiny.MODEL, intermediate_size=256, mlp_activation="silu_glu",
             norm_type="rms_norm", use_bias=False)
CONFIG = dict(bench_tiny.CONFIG, name="tiny-swiglu", reference_module="dense_swiglu",
              model=MODEL,
              repro={"arch": "stablelm-12b",
                     "overrides": dict(bench_tiny.DIMS, d_ff=256, rope_theta=10000.0)})


def _plant(root) -> None:
    bench = root / "bench"
    shutil.copy(os.path.join(DATA, "dense_swiglu.py"), bench / "models")
    (bench / "configs" / "tiny-swiglu.json").write_text(json.dumps(CONFIG))
    (bench / "limits" / f"{CELL}.json").write_text(
        json.dumps({"max_logit_gap": {"limit": bench_tiny.LIMIT}}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-swiglu", "source": "test",
                            "file": "bench/configs/tiny-swiglu.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny-swiglu",
                              "traffic": "chat", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny.chat" in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def test_a_planted_architecture_runs_whole_and_correct(tiny):
    _plant(tiny)
    cell, spec = harness.load_cell(CELL)
    assert os.path.basename(cell.arch.__file__) == "dense_swiglu.py"
    result = run.main(["--workload", CELL, "--seed", str(SEED), "--seconds", "3",
                       "--trace", "0"], device=bench_tiny.DEVICE)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    c = result["checks"]["max_logit_gap"]
    assert 0 <= c["value"] <= c["limit"] == bench_tiny.LIMIT
    assert set(result["metrics"]) == {
        m["name"] for m in harness.cell_metrics(spec, CELL, "end_to_end")}
    assert all(v["value"] > 0 for v in result["metrics"].values())


def test_the_planted_reference_fails_its_fp8_control(tiny):
    _plant(tiny)
    rows = calibrate.main(["--workload", CELL, "--seeds", "21,22,23",
                           "--control-seeds", "21,22,23", "--seconds", "3"],
                          device=bench_tiny.DEVICE)
    for r in rows:
        assert r["tokens"] > 0
        assert r["max_logit_gap"] <= bench_tiny.LIMIT < r["control_max_logit_gap"]
        assert r["correct"] is True and r["control_correct"] is False


@pytest.mark.parametrize("change", [{"mlp_activation": "gelu_tanh"},
                                    {"norm_type": "layer_norm"}],
                         ids=["activation", "norm"])
def test_the_planted_reference_refuses_what_it_does_not_compute(tiny, change):
    _plant(tiny)
    arch = harness.load_cell(CELL)[0].arch
    with pytest.raises(ValueError, match="SwiGLU MLP under RMS norms"):
        arch.ref_config(dict(MODEL, **change))
