"""``bench/run.py`` rehearsed on the CPU at a reduced size: a whole run with
the look for a chip skipped, its refusal to run without a TPU, the faults
that must make ``correct`` false, the fp8 control, and ``BENCHMARK.json``
against the rules every later PR is held to."""
import json
import os
import re
import subprocess
import sys

import pytest

import bench_tiny
import calibrate
import harness
import run
from bench_tiny import tiny  # noqa: F401  (a fixture)
from repro.serving import ServingEngine

REPO = bench_tiny.REPO
SEED = 2**31 + 101


def _run(cell: str, trace: int = 0, seconds: float = 3.0) -> dict:
    return run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                     str(seconds), "--trace", str(trace)], device=bench_tiny.DEVICE)


def _reported(cell: str, kind: str) -> set[str]:
    return {m["name"] for m in harness.cell_metrics(bench_tiny.spec(), cell, kind)}


@pytest.mark.parametrize("cell,trace", [("tiny.chat", 0), ("tiny.decode", 0),
                                        ("tiny.chat", 1)])
def test_a_whole_run_at_reduced_size(tiny, capsys, cell, trace):
    result = _run(cell, trace)
    out, err = capsys.readouterr()
    last = json.loads(out.strip().splitlines()[-1])
    assert last == json.loads(json.dumps(result))
    assert list(last)[-1] == "checks"
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert last["device"]["kind"] == "TPU v5 lite" and "memory_peak_bytes" in last["device"]
    c = last["checks"]["max_logit_gap"]
    assert 0 <= c["value"] <= c["limit"] == bench_tiny.LIMIT
    assert err.strip().splitlines()[-1].startswith("check max_logit_gap ")
    names = set(last["metrics"])
    if trace:
        # the CPU trace has no TPU plane: device metrics find nothing to read
        assert "breakdown" in last and "busy_s" in last["device"]
        assert names <= _reported(cell, "per_layer")
        assert {"tune_s", "compile_s", "tuned_share.ttft", "prefill_pad_share"} <= names
        assert not names & {"mfu.prefill", "mfu.decode", "decode_roofline"}
    else:
        assert names == _reported(cell, "end_to_end")
        assert all(v["value"] > 0 for v in last["metrics"].values())


def test_no_limit_means_not_correct(tiny):
    os.remove(tiny / "bench" / "limits" / "tiny.chat.json")
    result = _run("tiny.chat")
    assert result["correct"] is False
    assert result["checks"]["max_logit_gap"]["limit"] is None


def _wrap_decode(monkeypatch, fault):
    make = ServingEngine._make_fns

    def patched(self):
        make(self)
        decode = self._decode
        self._decode = lambda params, cache, toks: fault(decode, params, cache, toks)

    monkeypatch.setattr(ServingEngine, "_make_fns", patched)


def _state_unchanged(decode, params, cache, toks):
    logits, _ = decode(params, cache, toks)
    return logits, cache


def _half_the_batch(decode, params, cache, toks):
    logits, new = decode(params, cache, toks)
    half = logits.shape[0] // 2
    return logits.at[half:].set(logits[:half]), new


@pytest.mark.parametrize("fault", [_state_unchanged, _half_the_batch],
                         ids=["state_unchanged", "half_the_batch"])
def test_a_broken_decode_step_is_not_correct(tiny, monkeypatch, fault):
    _wrap_decode(monkeypatch, fault)
    result = _run("tiny.decode")
    assert result["correct"] is False and result["failed"] > 0
    assert result["checks"]["max_logit_gap"]["value"] > bench_tiny.LIMIT


def test_a_token_altered_where_it_is_produced_is_not_correct(tiny, monkeypatch):
    step = ServingEngine.step

    def patched(self):
        done = step(self)
        for req in list(self.active.values()) + done:
            if len(req.generated) == 4:
                req.generated[-1] = (req.generated[-1] + 1) % 512
        return done

    monkeypatch.setattr(ServingEngine, "step", patched)
    result = _run("tiny.chat")
    assert result["correct"] is False
    assert result["checks"]["max_logit_gap"]["value"] > bench_tiny.LIMIT


def test_the_fp8_control_fails_the_limit_the_program_passes(tiny):
    rows = calibrate.main(["--workload", "tiny.chat", "--seeds", "11,12,13",
                           "--control-seeds", "11,12,13", "--seconds", "3"],
                          device=bench_tiny.DEVICE)
    assert len(rows) == 3
    for r in rows:
        assert r["tokens"] > 0
        assert r["max_logit_gap"] <= bench_tiny.LIMIT < r["control_max_logit_gap"]
        assert r["correct"] is True and r["control_correct"] is False


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench", "run.py"), "--workload",
         "minitron-4b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=REPO)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"correct"' not in out.stdout


# ---------------------------------------------------------------------------
# BENCHMARK.json against the contract's rules
# ---------------------------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTH = re.compile(r"(_dim$|_rank$|_size$|intermediate|latent|expan|factor|"
                   r"head|experts_per_tok)", re.I)


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_paths(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "bench/run.py"]
    assert spec["paths"] == ["bench"]
    assert 1 <= spec["run_seconds"] <= 51 and isinstance(spec["run_seconds"], int)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


def test_configs_are_files_under_paths_with_their_cuts(spec):
    files = set()
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["source"].startswith("https://")
        assert c["file"].startswith("bench/") and c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(REPO, c["file"])) as f:
            conf = json.load(f)
        assert conf["source"] == c["source"] and conf["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key)
            assert key in conf["model"] and key in conf["published"]
            assert conf["model"][key] != conf["published"][key]
        # the file is what the program runs
        harness.arch_module(conf, c["file"]).program_config(conf)


def test_cells_metrics_and_their_files(spec):
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    cells = {w["name"]: w for w in spec["workloads"]}
    configs = {c["name"] for c in spec["configs"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert os.path.exists(os.path.join(REPO, "bench", "metrics", f"{m['name']}.py"))
        assert set(m.get("workloads", [])) <= set(cells)
    for m in spec["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e and "\n" not in m["layer"]
        for cell in m.get("workloads", cells):
            assert cell in e2e[m["moves"]].get("workloads", cells)
    for name, w in cells.items():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(name) and w["config"] in configs and w["chips"] == 1
        assert len(w["why"]) <= 200
        assert os.path.exists(os.path.join(REPO, "bench", "traffic", f"{w['traffic']}.json"))
        reported = {m for m in e2e if name in e2e[m].get("workloads", [name])}
        assert "setup_s" in reported and len(reported) >= 2
        assert harness.cell_metrics(spec, name, "per_layer")
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) == len(cells)
