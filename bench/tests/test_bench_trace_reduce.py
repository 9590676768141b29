"""The trace reduction on hand-made events and on a recorded trace, with
hand-checked numbers."""
import os

import pytest

import bench_tiny  # noqa: F401  (puts bench/ on the path)
import trace_reduce as tr

# One chip, a window of 100 ns..1100 ns.  Ops at 100-300 and 250-400 (they
# overlap: busy 100-400), 600-700, and 1050-1200 (clipped to 1050-1100).
# Busy: 300 + 100 + 50 = 450 ns.  Idle: 400-600 (host in bench.step),
# 700-1050 (host sleeping from 650 to 1000, so the gap's midpoint 875 is in
# bench.sleep).
HOST = [("bench.window", 100, 1100), ("bench.step", 90, 450),
        ("bench.step", 450, 650), ("bench.sleep", 650, 1000)]
DEVICE = {"/device:TPU:0": {
    "XLA Modules": [("jit_decode_fn(42)", 100, 400), ("jit_prefill_fn(7)", 600, 700),
                    ("jit_decode_fn(42)", 1050, 1200)],
    "XLA Ops": [("fusion.12", 100, 300), ("fusion.7", 250, 400),
                ("custom-call.3", 600, 700), ("copy.1", 1050, 1200)]}}


def test_busy_window_and_modules():
    r = tr.reduce_events(HOST, DEVICE)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(450e-9)
    assert r["chips"] == 1
    assert r["module_s"]["jit_decode_fn"] == pytest.approx(350e-9)   # 300 + 50
    assert r["module_s"]["jit_prefill_fn"] == pytest.approx(100e-9)
    assert r["module_n"] == {"jit_decode_fn": 2, "jit_prefill_fn": 1}


def test_ops_and_gaps_ranked_by_time():
    r = tr.reduce_events(HOST, DEVICE)
    ops = dict(r["device_ops"])
    assert ops["fusion"] == pytest.approx(350e-9)        # 200 + 150
    assert ops["custom-call"] == pytest.approx(100e-9)
    assert ops["copy"] == pytest.approx(50e-9)
    assert r["device_ops"][0][0] == "fusion"
    assert r["idle_gaps"] == [["bench.sleep", pytest.approx(350e-9)],
                              ["bench.step", pytest.approx(200e-9)]]


def test_nested_ops_count_at_their_self_time():
    # A loop 0-100 whose body ops run 10-40 and 50-90, named as the TPU
    # names them: by their whole HLO text.
    dev = {"/device:TPU:0": {"XLA Ops": [
        ("%while.3 = (s32[], bf16[8]) while(%tuple.1)", 0, 100),
        ("%fusion.21 = bf16[8]{0} fusion(bf16[8]{0} %p)", 10, 40),
        ("%closed_call.5 = bf16[8]{0} custom-call(bf16[8]{0} %f)", 50, 90)]}}
    r = tr.reduce_events([("bench.window", 0, 100)], dev)
    assert dict(r["device_ops"]) == {"while": pytest.approx(30e-9),
                                     "fusion": pytest.approx(30e-9),
                                     "closed_call": pytest.approx(40e-9)}
    assert r["busy_s"] == pytest.approx(100e-9)


def test_gap_outside_every_span_is_host_other():
    host = [("bench.window", 0, 100)]
    dev = {"/device:TPU:0": {"XLA Ops": [("a", 0, 40)]}}
    r = tr.reduce_events(host, dev)
    assert r["idle_gaps"] == [["host.other", pytest.approx(60e-9)]]


def test_two_chips_average_busy():
    dev = {"/device:TPU:0": {"XLA Ops": [("a", 0, 40)]},
           "/device:TPU:1": {"XLA Ops": [("a", 0, 80)]}}
    r = tr.reduce_events([("bench.window", 0, 100)], dev)
    assert r["busy_s"] == pytest.approx(60e-9) and r["chips"] == 2


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce_events([("bench.step", 0, 1)], DEVICE)


# A trace recorded on the CPU by ``data/record_trace.py``: its host plane
# holds the harness's spans; the CPU has no /device:TPU plane.  The numbers
# below were read off ``record_trace.py --dump`` by hand.
CPU_TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                         "cpu.xplane.pb")


def test_recorded_cpu_trace_host_spans():
    host, devices = tr.load_events(CPU_TRACE)
    assert [n for n, _, _ in host] == ["bench.window", "bench.step", "bench.step",
                                       "bench.sleep", "bench.admit"]
    assert host[0][1:] == (8493.0, 8493.0 + 64421056.0)
    assert host[3][2] - host[3][1] == 20082134.0        # the 20 ms sleep
    assert devices == {}


def test_recorded_cpu_trace_reduces_to_its_window():
    r = tr.reduce(CPU_TRACE)
    assert r["window_s"] == pytest.approx(0.064421056)
    assert r["busy_s"] == 0 and r["chips"] == 0 and r["module_s"] == {}
