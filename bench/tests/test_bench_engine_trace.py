"""The reduction of the engine's spans and the kernel classes in a profile
(``engine_trace.py``), on hand-made events with hand-checked numbers, on a
CPU profile of a real engine, and as a traced run finds its trace."""
import glob
import os
import shutil

import pytest

import bench_tiny  # noqa: F401  (puts bench/ on the path)
import engine_trace as et
import harness
import trace_reduce as tr
from bench_tiny import tiny  # noqa: F401  (the tiny checkout fixture)
from test_bench_run import _run
from test_bench_trace_reduce import DEVICE, HOST

CPU_TRACE = os.path.join(os.path.dirname(__file__), "data", "cpu.xplane.pb")

# The engine's own spans nest inside the harness's: a decode step inside
# bench.step, a prefill inside bench.admit.  Ops run 100-300 (a loop whose
# body holds a matmul at 110-200), 350-450 (the head), 600-700 (flash
# attention) and 700-800 in the two modules, and 950-980 outside any.
# Gaps: 0-100, 300-350, 450-600, 800-950 and 980-1000; idle_spans splits
# each across the innermost spans it overlaps (0-100: bench.step 10, the
# step's prepare 30, dispatch 40, read 20; 450-600: read 20, commit 20,
# bench.step 10, bench.admit 10, the prefill's prepare 30, dispatch 40,
# read 20; 800-950: read 80, splice 10, bench.admit 10, no span 50).
ENGINE_HOST = [
    ("bench.window", 0, 1000),
    ("bench.step", 0, 500), ("engine.decode_step", 10, 490),
    ("engine.decode_step.prepare", 10, 40), ("engine.decode_step.dispatch", 40, 80),
    ("engine.decode_step.read", 80, 470), ("engine.decode_step.commit", 470, 490),
    ("bench.admit", 500, 900), ("engine.prefill", 510, 890),
    ("engine.prefill.prepare", 510, 540), ("engine.prefill.dispatch", 540, 580),
    ("engine.prefill.read", 580, 880), ("engine.prefill.splice", 880, 890)]
ENGINE_DEVICE = {"/device:TPU:0": {
    "XLA Modules": [("jit_decode_fn(3)", 100, 450), ("jit_prefill_fn(4)", 600, 800)],
    "XLA Ops": [("%while.1 = (s32[]) while(%t)", 100, 300),
                ("%matmul.3 = bf16[8,64]{1,0} custom-call(%a, %b)", 110, 200),
                ("%matmul_lmhead.2 = bf16[8,512]{1,0} custom-call(%h, %w)", 350, 450),
                ("%flash_attention_causal.5 = bf16[1,4,64,16]{3,2,1,0} "
                 "custom-call(%q, %k, %v)", 600, 700),
                ("%matmul.9 = bf16[64,64]{1,0} custom-call(%x, %y)", 700, 800),
                ("%copy.1 = bf16[8]{0} copy(%c)", 950, 980)]}}


def test_op_module_s_keys_each_op_by_the_module_that_holds_it():
    r = et.reduce_events(ENGINE_HOST, ENGINE_DEVICE)
    om = r["op_module_s"]
    assert set(om) == {"jit_decode_fn", "jit_prefill_fn", et.NO_MODULE}
    assert om["jit_decode_fn"] == {"while": pytest.approx(110e-9),
                                   "matmul": pytest.approx(90e-9),
                                   "matmul_lmhead": pytest.approx(100e-9)}
    assert om["jit_prefill_fn"] == {"flash_attention_causal": pytest.approx(100e-9),
                                    "matmul": pytest.approx(100e-9)}
    assert om[et.NO_MODULE] == {"copy": pytest.approx(30e-9)}
    # the same self times, summed over modules, are trace_reduce's device_ops
    total: dict = {}
    for ops in om.values():
        for k, v in ops.items():
            total[k] = total.get(k, 0.0) + v
    assert total == pytest.approx(dict(tr.reduce_events(ENGINE_HOST,
                                                        ENGINE_DEVICE)["device_ops"]))


def test_idle_spans_name_each_gap_by_the_innermost_span():
    r = et.reduce_events(ENGINE_HOST, ENGINE_DEVICE)
    assert dict(r["idle_spans"]) == {
        "bench.step": pytest.approx(20e-9),
        "engine.decode_step.prepare": pytest.approx(30e-9),
        "engine.decode_step.dispatch": pytest.approx(40e-9),
        "engine.decode_step.read": pytest.approx(90e-9),
        "engine.decode_step.commit": pytest.approx(20e-9),
        "bench.admit": pytest.approx(20e-9),
        "engine.prefill.prepare": pytest.approx(30e-9),
        "engine.prefill.dispatch": pytest.approx(40e-9),
        "engine.prefill.read": pytest.approx(100e-9),
        "engine.prefill.splice": pytest.approx(10e-9),
        "host.other": pytest.approx(70e-9)}
    assert r["idle_spans"][0][0] == "engine.prefill.read"
    # the same idle time trace_reduce finds, there by harness span at each
    # gap's midpoint
    base = tr.reduce_events(ENGINE_HOST, ENGINE_DEVICE)
    assert dict(base["idle_gaps"]) == {"bench.step": pytest.approx(150e-9),
                                       "bench.admit": pytest.approx(300e-9),
                                       "host.other": pytest.approx(20e-9)}
    assert sum(t for _, t in r["idle_spans"]) == pytest.approx(
        base["window_s"] - base["busy_s"]) == pytest.approx(470e-9)


def test_a_harness_only_trace_gives_idle_spans_of_harness_spans():
    # The gap 400-600 lies in two bench.step spans; of 700-1050, 700-1000
    # lies in bench.sleep and 1000-1050 in none (idle_gaps puts the whole
    # gap under bench.sleep, which holds its midpoint).
    r = et.reduce_events(HOST, DEVICE)
    assert r["idle_spans"] == [["bench.sleep", pytest.approx(300e-9)],
                               ["bench.step", pytest.approx(200e-9)],
                               ["host.other", pytest.approx(50e-9)]]
    assert sum(t for _, t in r["idle_spans"]) == \
        pytest.approx(sum(t for _, t in tr.reduce_events(HOST, DEVICE)["idle_gaps"]))
    assert r["host_ms"] == {"decode_step": None, "prefill": None}


@pytest.mark.parametrize("spans,point,name", [
    # nested: the innermost open span, and the parent on either side of it
    ([("p", 0, 10), ("c", 2, 5)], 3, "c"),
    ([("p", 0, 10), ("c", 2, 5)], 7, "p"),
    ([("p", 0, 10), ("c", 2, 5)], 1, "p"),
    # outside every span
    ([("p", 0, 10)], 11, "none"),
    # two that do not nest: the later-started one where both are open
    ([("a", 0, 6), ("b", 4, 9)], 5, "b"),
    ([("a", 0, 6), ("b", 4, 9)], 8, "b"),
])
def test_pieces_name_the_innermost_span(spans, point, name):
    assert et._name_at(et._pieces(spans), [point], "none") == [name]


def test_host_ms_pairs_each_call_with_the_read_inside_it():
    r = et.reduce_events(ENGINE_HOST, ENGINE_DEVICE)
    # decode step 480 ns less its 390 ns read; prefill 380 less 300
    assert r["host_ms"]["decode_step"] == pytest.approx(90e-6)
    assert r["host_ms"]["prefill"] == pytest.approx(80e-6)
    # a call cut by the window's end counts in neither
    cut = [("bench.window", 0, 485)] + ENGINE_HOST[1:]
    assert et.reduce_events(cut, {})["host_ms"] == {"decode_step": None,
                                                    "prefill": None}


def test_the_default_engine_lands_its_spans_in_a_profile_inside_bench_step(tmp_path):
    """A CPU profile recorded as ``data/record_trace.py`` records one, around
    a slot engine left with its default tracer."""
    import jax

    from repro.configs import get_arch, reduced
    from repro.models import build_model
    from repro.serving import ServingEngine

    model = build_model(reduced(get_arch("minitron-4b")))
    eng = ServingEngine(model, model.init(jax.random.PRNGKey(0)), slots=2, max_len=32)
    eng.add_request([1, 2, 3], max_new_tokens=8)      # compiled outside the profile
    eng.step()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.admit"):
                eng.add_request([4, 5, 6], max_new_tokens=8)
            with jax.profiler.TraceAnnotation("bench.step"):
                eng.step()
    finally:
        jax.profiler.stop_trace()
    assert eng.tracer.spans == []                     # recorded nothing itself
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True))[-1]
    host, _ = et.load_events(path)
    got = {n: (a, b) for n, a, b in host}
    parts = ["prepare", "dispatch", "read", "commit"]
    assert set(got) == ({"bench.window", "bench.admit", "bench.step",
                         "engine.prefill", "engine.decode_step"}
                        | {f"engine.prefill.{p}" for p in parts[:3] + ["splice"]}
                        | {f"engine.decode_step.{p}" for p in parts})
    nest = [("engine.prefill", "bench.admit"), ("engine.decode_step", "bench.step")]
    nest += [(f"engine.decode_step.{p}", "engine.decode_step") for p in parts]
    for inner, outer in nest:
        assert got[outer][0] <= got[inner][0] <= got[inner][1] <= got[outer][1]
    ordered = [got[f"engine.decode_step.{p}"] for p in parts]
    assert all(a[1] <= b[0] for a, b in zip(ordered, ordered[1:]))
    r = et.reduce(path)
    assert r["host_ms"]["decode_step"] > 0 and r["host_ms"]["prefill"] > 0


def test_of_finds_the_trace_whose_window_the_run_reduced(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "CACHE", str(tmp_path))
    os.makedirs(tmp_path / "trace" / "cell" / "run")
    shutil.copy(CPU_TRACE, tmp_path / "trace" / "cell" / "run" / "t.xplane.pb")
    rec = {"trace": tr.reduce(CPU_TRACE)}
    r = et.of(rec)
    assert r is not None and r["window_s"] == rec["trace"]["window_s"]
    assert et.of(rec) is r                             # reduced once per run
    assert et.of({"trace": dict(rec["trace"], window_s=1.0)}) is None
    assert et.of({"trace": None}) is None


def test_a_traced_tiny_run_reports_the_engine_host_time(tiny):  # noqa: F811
    """On the CPU the trace has the engine's spans and no TPU plane."""
    result = _run("tiny.chat", 1)
    names = set(result["metrics"])
    assert "admit_host_ms" in names
    assert result["metrics"]["admit_host_ms"]["value"] > 0
    assert not names & {"mfu.flash_attention", "roofline.decode_matmul"}
