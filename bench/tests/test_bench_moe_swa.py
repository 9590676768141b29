"""The mixture-of-experts module with sliding and full layers
(``bench/models/moe_swa.py``): its layout against the program's parameter
tree, its counts against a hand count at the published widths, its plain
reference against the program at a small size on the CPU, and a whole
benchmark run of a small configuration planted beside the tiny spec."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
import calibrate
import harness
import run
import weights
from bench_tiny import tiny  # noqa: F401  (a fixture)

CONFIG_FILE = os.path.join(bench_tiny.BENCH, "configs", "mellum2-12b-a2.5b.json")
with open(CONFIG_FILE) as _f:
    CONF = json.load(_f)
ARCH = harness.arch_module(CONF, "bench/configs/mellum2-12b-a2.5b.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

#: Four layers (sliding, sliding, sliding, full), a window of 8 and four
#: experts (top 2) at d_model 64; YaRN as published.
SMALL = {"num_hidden_layers": 4, "hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "moe_intermediate_size": 128,
         "vocab_size": 512, "num_experts": 4, "num_experts_per_tok": 2,
         "sliding_window": 8}
OVERRIDES = {"n_layers": 4, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
             "head_dim": 16, "d_ff": 128, "vocab_size": 512, "n_experts": 4,
             "moe_topk": 2, "window": 8}


def small_config(dtype: str) -> dict:
    """The configuration file cut to ``SMALL`` in ``dtype``."""
    m = dict(CONF["model"], **SMALL, torch_dtype=dtype)
    top = {k: v for k, v in CONF.items() if k not in m}
    return dict(top, name="tiny-moe", model=m,
                repro={"arch": "mellum2-12b-a2.5b",
                       "overrides": dict(OVERRIDES, dtype=dtype)})


def _program(conf: dict, **change):
    from repro.models import build_model

    cfg = dataclasses.replace(ARCH.program_config(conf), **change)
    return cfg, build_model(cfg)


def test_layout_is_the_program_parameter_tree():
    """Every leaf the module draws is one the program's model holds, at the
    same shape, for the full configuration (the program keeps its own
    router in float32 and casts the drawn one)."""
    _, model = _program(CONF)
    program = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    drawn = jax.eval_shape(weights.init_fn(ARCH, CONF["model"]), jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(drawn) == jax.tree_util.tree_structure(program)
    for a, b in zip(jax.tree_util.tree_leaves(drawn), jax.tree_util.tree_leaves(program)):
        assert a.shape == b.shape and a.dtype == jnp.bfloat16
    layers = CONF["model"]["num_hidden_layers"]
    assert jax.tree_util.tree_leaves(drawn["groups"]["3"]["moe"]["w_in"])[0].shape == \
        (layers // 4, 64, 2304, 2 * 896)


def test_counts_match_a_hand_count_at_the_published_widths():
    d = ARCH.dims(CONF["model"])
    assert d.layers == 12 and sum(d.full) == 3
    attention = 2304 * (32 + 2 * 4) * 128 + 32 * 128 * 2304     # 21.2 M weights
    expert = 3 * 2304 * 896                                     # 6.19 M weights
    assert d.expert_flops(1) == 2 * 8 * expert == 99_090_432
    assert d.linear_flops_per_token() == 2 * (attention + 2304 * 64 + 8 * expert)
    # each expert of a layer read once: 0.79 GB a layer; rows in and out on top
    assert d.expert_bytes(2048) == 2 * (64 * expert + 2048 * 8 * 2 * (2304 + 896))
    assert d.expert_bytes(2048) - 2 * 64 * expert == 2048 * 102_400
    assert 2 * 64 * expert == 792_723_456
    # the ridge: weights alone cross 240.5 operations a byte near 1,925
    # tokens; with the routed rows' bytes near 2,561
    ridge = PEAKS["bf16_flops_per_s"] / PEAKS["hbm_bytes_per_s"]
    assert 1920 < ridge * 2 * 64 * expert / d.expert_flops(1) < 1930
    bound = [n for n in range(1, 4097)
             if d.expert_flops(n) / PEAKS["bf16_flops_per_s"]
             >= d.expert_bytes(n) / PEAKS["hbm_bytes_per_s"]]
    assert bound[0] == 2561 and bound == list(range(2561, 4097))
    assert d.expert_least_s(2048, PEAKS) == pytest.approx(
        12 * d.expert_bytes(2048) / PEAKS["hbm_bytes_per_s"])
    # 9 sliding layers see at most 1024 keys, 3 full layers the triangle
    n = 2048
    keys = 9 * (1024 * 1025 // 2 + 1024 * 1024) + 3 * n * (n + 1) // 2
    assert d.prefill_flops(n) == (12 * n * d.linear_flops_per_token()
                                  + 4 * 32 * 128 * keys + 2 * 2304 * 98304)


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_reference_matches_the_program_forward(backend):
    """Float32 weights, 20 tokens through a window of 8 with YaRN on the
    full layer: the reference and the program's forward agree to float32
    rounding; the program without YaRN, or without the window, does not."""
    from repro.kernels import ops

    conf = small_config("float32")
    m = conf["model"]
    w = weights.make(ARCH, m, 7)
    toks = np.random.default_rng(0).integers(1, 512, 20).astype(np.int32)
    want = np.asarray(ARCH.final_hidden(w, ARCH.ref_config(m), toks) @ w["lm_head"])

    def logits(**change):
        _, model = _program(conf, **change)
        with ops.use_backend(backend):
            out, _ = model.forward(w, {"tokens": jnp.asarray(toks)[None]}, remat=False)
        return np.asarray(out[0])

    # float32 on both sides: summation order is the only difference
    np.testing.assert_allclose(logits(), want, rtol=1e-4, atol=1e-4)
    for change in ({"yarn": None}, {"window": 0}):
        assert np.abs(logits(**change) - want).max() > 1e-2, change


def test_engine_prefill_then_decode_through_ring_caches_matches_the_reference():
    """The slot engine on the Pallas path: a 13-token prompt (past the window
    of 8) prefilled at its 16 bucket, then six decode steps through the ring
    caches; each step's logits equal the reference's full pass over the
    prompt and the tokens served."""
    from repro.kernels import ops
    from repro.serving import ServingEngine

    conf = small_config("float32")
    m = conf["model"]
    w = weights.make(ARCH, m, 11)
    _, model = _program(conf)
    prompt = [int(t) for t in np.random.default_rng(1).integers(1, 512, 13)]
    with ops.use_backend("pallas"):
        eng = ServingEngine(model, w, slots=2, max_len=32)
        req = eng.add_request(prompt, max_new_tokens=7)
        steps = []
        while not req.done:
            eng.step()
            steps.append(np.asarray(eng.last_logits[0]))
    assert eng.prefill_padded_tokens == 16 and len(steps) == 6
    seq = np.asarray(prompt + req.generated[:-1], np.int32)
    ref = np.asarray(ARCH.final_hidden(w, ARCH.ref_config(m), seq) @ w["lm_head"])
    # float32 weights and activations on both paths: the kernels' tiled
    # sums against the reference's einsums differ by rounding alone
    np.testing.assert_allclose(np.stack(steps), ref[len(prompt):], rtol=1e-4, atol=1e-4)
    assert req.generated[1:] == [int(np.argmax(r)) for r in ref[len(prompt):]]


def test_reference_refuses_what_it_does_not_compute():
    m = CONF["model"]
    for change, match in ((dict(hidden_act="gelu"), "SiLU"),
                          (dict(attention_bias=True), "attention bias"),
                          (dict(norm_topk_prob=False), "renormalises"),
                          (dict(mlp_layer_types=["dense"] * 28), "sparse")):
        with pytest.raises(ValueError, match=match):
            ARCH.ref_config(dict(m, **change))
    with pytest.raises(ValueError, match="program config differs"):
        ARCH.program_config(dict(CONF, model=dict(m, sliding_window=2048),
                                 sliding_window=2048))
    with pytest.raises(ValueError, match="top level differ"):
        ARCH.program_config(dict(CONF, vocab_size=1))


# ---------------------------------------------------------------------------
# A small configuration of this module, planted as a cell of the tiny spec
# ---------------------------------------------------------------------------

CELL = "tiny-moe.chat"
SEED = 2**31 + 307


def _plant(root) -> None:
    """The small configuration in float32, its limits file and its cell: at
    d_model 64 with four experts a bf16 rounding can tip a near tie of two
    experts' router weights, which moves a logit by up to a quarter (CPU,
    seeds 21-25); the published widths' limit is read on the chip."""
    bench = root / "bench"
    (bench / "configs" / "tiny-moe.json").write_text(json.dumps(small_config("float32")))
    (bench / "limits" / f"{CELL}.json").write_text(
        json.dumps({"max_logit_gap": {"limit": bench_tiny.LIMIT}}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-moe", "source": "test",
                            "file": "bench/configs/tiny-moe.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny-moe", "traffic": "chat",
                              "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny.chat" in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def test_a_planted_moe_configuration_runs_whole_and_correct(tiny):
    """The module loads from its file in the checkout, and a whole run
    through the slot engine (prefill buckets past the window of 8, ring
    caches, ragged expert GEMMs) comes out correct."""
    _plant(tiny)
    cell, spec = harness.load_cell(CELL)
    assert cell.arch.__file__ == str(tiny / "bench" / "models" / "moe_swa.py")
    result = run.main(["--workload", CELL, "--seed", str(SEED), "--seconds", "3",
                       "--trace", "0"], device=bench_tiny.DEVICE)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {
        m["name"] for m in harness.cell_metrics(spec, CELL, "end_to_end")}


def test_the_planted_moe_reference_fails_its_fp8_control(tiny):
    _plant(tiny)
    rows = calibrate.main(["--workload", CELL, "--seeds", "21,22,23",
                           "--control-seeds", "21,22,23", "--seconds", "3"],
                          device=bench_tiny.DEVICE)
    for r in rows:
        assert r["tokens"] > 0
        assert r["max_logit_gap"] <= bench_tiny.LIMIT < r["control_max_logit_gap"]
        assert r["correct"] is True and r["control_correct"] is False
