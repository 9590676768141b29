"""The per-layer metrics that read the engine's own spans and the kernel
classes the trace names (``engine_trace.py``), on hand-made records with
hand-checked numbers, and None where what they read is absent."""
import dataclasses

import pytest

import bench_tiny
import counts
import engine_trace
import run

DIMS = bench_tiny.arch().dims(bench_tiny.MODEL)   # 2 layers, d 64, ff 128, vocab 512
PEAKS = counts.PEAKS["TPU v5 lite"]


def _program(parent, host_and_read):
    """A traced window holding ``parent`` calls, each ``(length, read
    length)`` in seconds, with a read child each, as the trace holds the
    engine's spans (ns on the profiler's clock)."""
    host, t = [("bench.window", 0, 10**9)], 1000
    for length, read in host_and_read:
        a, b = t, t + round(length * 1e9)
        host += [(f"engine.{parent}", a, b),
                 (f"engine.{parent}.read", a + 10**6, a + 10**6 + round(read * 1e9))]
        t = b
    return {"trace": {"window_s": 1.0},
            "engine_trace": engine_trace.reduce_events(host, {})}


@pytest.mark.parametrize("metric,parent", [("step_host_ms", "decode_step"),
                                           ("admit_host_ms", "prefill")])
def test_host_time_is_the_span_less_its_read(metric, parent):
    rec = _program(parent, [(0.040, 0.038), (0.050, 0.046)])
    assert run.reader(metric)(rec) == pytest.approx(3.0)     # mean of 2 and 4 ms


@pytest.mark.parametrize("metric,parent", [("step_host_ms", "decode_step"),
                                           ("admit_host_ms", "prefill")])
def test_host_time_is_none_without_the_engine_spans(metric, parent):
    read = run.reader(metric)
    assert read({"trace": None}) is None
    assert read({}) is None
    # an engine whose span wraps the dispatch alone has no read child
    host = [("bench.window", 0, 100), (f"engine.{parent}", 10, 20)]
    assert read({"trace": {"window_s": 1e-7},
                 "engine_trace": engine_trace.reduce_events(host, {})}) is None


@dataclasses.dataclass
class _Served:
    prompt: list


def _trace(module, ops):
    return {"op_module_s": {module: ops}}


def test_mfu_flash_attention_counts_the_traced_prompts_causal_triangle():
    admitted = [_Served([1] * 1000), _Served([1] * 7), _Served([1] * 9)]
    rec = {"engine_trace": _trace("jit_prefill_fn", {"flash_attention_causal": 1e-6,
                                              "matmul": 5e-6}),
           "traced": {"prefills": 2}, "admitted": admitted, "dims": DIMS,
           "peaks": PEAKS}
    # 2 layers x 4 flops x 4 heads x 16 dims per (query, key) pair, over
    # 7*8/2 + 9*10/2 = 73 pairs of the last two prompts
    flops = 2 * 4 * 4 * 16 * 73
    assert run.reader("mfu.flash_attention")(rec) == pytest.approx(
        100.0 * flops / (1e-6 * 197e12))


def test_mfu_flash_attention_is_none_where_the_trace_names_no_class():
    read = run.reader("mfu.flash_attention")
    base = {"traced": {"prefills": 1}, "admitted": [_Served([1, 2])], "dims": DIMS,
            "peaks": PEAKS}
    assert read(dict(base, trace=None)) is None
    assert read(dict(base, engine_trace=_trace("jit_prefill_fn", {"closed_call": 1e-3}))) is None
    assert read(dict(base, engine_trace=None)) is None      # no trace of this run found
    assert read(dict(base, engine_trace=_trace("jit_prefill_fn", {"flash_attention_causal": 1e-3}),
                     traced={"prefills": 0})) is None


def test_roofline_decode_matmul_counts_each_named_class_weights_once_per_step():
    ops = {"matmul": 2e-6, "matmul_bias_gelu": 1e-6, "matmul_lmhead": 1e-6,
           "matmul_silu_glu": 9.0,            # no weight this file knows: left out
           "dynamic-slice_bitcast_fusion": 9.0}
    rec = {"engine_trace": _trace("jit_decode_fn", ops), "traced": {"steps": 10},
           "dims": DIMS, "peaks": PEAKS}
    # the tiny model has MLP biases: q/k/v/o run as matmul, the up
    # projection (+ bias) as matmul_bias_gelu, the head as matmul_lmhead;
    # its down projection, matmul_bias, is not in the trace
    proj = 64 * (4 + 2 * 2) * 16 + 4 * 16 * 64
    weights = 2 * proj + 2 * (64 * 128 + 128) + 64 * 512
    want = 100.0 * 10 * weights * 2 / (4e-6 * 819e9)
    assert run.reader("roofline.decode_matmul")(rec) == pytest.approx(want)


def test_roofline_decode_matmul_puts_an_unbiased_down_projection_in_matmul():
    read = run.reader("roofline.decode_matmul")
    plain = dataclasses.replace(DIMS, mlp_bias=False)
    rec = {"engine_trace": _trace("jit_decode_fn", {"matmul": 1e-6}), "traced": {"steps": 1},
           "dims": plain, "peaks": PEAKS}
    proj = 64 * (4 + 2 * 2) * 16 + 4 * 16 * 64
    assert read(rec) == pytest.approx(100.0 * 2 * (proj + 128 * 64) * 2 / (1e-6 * 819e9))


def test_roofline_decode_matmul_is_none_where_the_trace_names_no_class():
    read = run.reader("roofline.decode_matmul")
    base = {"traced": {"steps": 3}, "dims": DIMS, "peaks": PEAKS}
    assert read(dict(base, trace=None)) is None
    assert read(dict(base, engine_trace=None)) is None
    assert read(dict(base, engine_trace=_trace("jit_decode_fn", {"closed_call": 1e-3,
                                                          "decode_fn": 1e-3}))) is None
    assert read(dict(base, engine_trace=_trace("jit_prefill_fn", {"matmul": 1e-3}))) is None
    assert read(dict(base, engine_trace=_trace("jit_decode_fn", {"matmul": 1e-3}),
                     traced={"steps": 0})) is None
