"""Cost-model behaviour: determinism, schedule sensitivity, validity."""
import pytest

from repro.core.cost_model import evaluate, kernel_seconds, measure, model_seconds
from repro.core.schedule import Schedule, ScheduleInvalid, concretize, default_schedule
from repro.core.workload import KernelInstance, KernelUse
from repro.hw.specs import TPU_V5E


def g(m=1024, n=1024, k=1024):
    return KernelInstance.make("matmul", M=m, N=n, K=k)


def test_measure_deterministic_given_seed():
    sched = Schedule.make("matmul", {"M": 128, "N": 256, "K": 128})
    a = measure(g(), sched, seed=7)
    b = measure(g(), sched, seed=7)
    assert a.seconds == b.seconds
    c = measure(g(), sched, seed=8)
    assert c.seconds != a.seconds  # noise varies with seed


def test_noise_zero_matches_evaluate():
    sched = Schedule.make("matmul", {"M": 128, "N": 256, "K": 128})
    m = measure(g(), sched, noise_sigma=0.0)
    assert m.seconds == pytest.approx(evaluate(concretize(sched, g())).seconds)


def test_bigger_tiles_reduce_hbm_traffic():
    """Reuse grows with tile size: the memory term must reflect it."""
    small = evaluate(concretize(Schedule.make("matmul", {"M": 8, "N": 128, "K": 128}), g()))
    big = evaluate(concretize(Schedule.make("matmul", {"M": 256, "N": 256, "K": 128}), g()))
    assert big.hbm_bytes < small.hbm_bytes


def test_order_changes_traffic():
    """Reorder (paper primitive) of the outer axes must change the modeled
    HBM bytes: with K in one block, the operand indexed by the outer axis
    alone stays resident across the inner one."""
    t = {"M": 64, "N": 128, "K": 1024}
    a = evaluate(concretize(Schedule.make("matmul", t, order=("M", "N", "K")), g()))
    b = evaluate(concretize(Schedule.make("matmul", t, order=("N", "M", "K")), g()))
    assert a.hbm_bytes != b.hbm_bytes
    # M outer streams all of w once per M block (16), N outer all of x once
    # per N block (8): N outer moves fewer bytes
    assert b.hbm_bytes < a.hbm_bytes


@pytest.mark.parametrize("class_id,tiles,order,legal_order,extents", [
    ("matmul", {"M": 128, "N": 128, "K": 128}, ("M", "K", "N"), ("N", "M", "K"),
     dict(M=1024, N=1024, K=1024)),
    ("flash_attention_causal", {"Q": 128, "KV": 128}, ("KV", "Q"), ("Q", "KV"),
     dict(Q=1024, KV=1024, H=8, D=128)),
    ("rglru_scan", {"T": 128, "C": 512}, ("T", "C"), ("C", "T"),
     dict(T=1024, C=2560)),
])
def test_reduction_not_innermost_invalid(class_id, tiles, order, legal_order,
                                         extents):
    """The kernels run the reduction innermost only; no other order is
    priced (parallel=0 marks nothing parallel, isolating the order rule)."""
    inst = KernelInstance.make(class_id, **extents)
    sched = Schedule.make(class_id, tiles, order=order, parallel=0)
    with pytest.raises(ScheduleInvalid, match="not innermost"):
        evaluate(concretize(sched, inst))
    assert not measure(inst, sched).valid
    legal = Schedule.make(class_id, tiles, order=legal_order, parallel=0)
    assert measure(inst, legal).valid


def test_vmem_overflow_invalid():
    sched = Schedule.make("matmul", {"M": 4096, "N": 4096, "K": 4096})
    inst = g(4096, 4096, 4096)
    with pytest.raises(ScheduleInvalid):
        evaluate(concretize(sched, inst))
    assert not measure(inst, sched).valid


def test_parallel_reduction_invalid():
    sched = Schedule.make("matmul", {"M": 128, "N": 128, "K": 128},
                          order=("K", "M", "N"), parallel=1)
    with pytest.raises(ScheduleInvalid):
        evaluate(concretize(sched, g()))


def test_alignment_penalty():
    """Misaligned (non-128) N tiles waste MXU lanes -> slower compute term.
    The block rule leaves the full extent as the only misaligned N tile."""
    odd = KernelInstance.make("matmul", M=1024, N=1000, K=1024)
    aligned = evaluate(concretize(Schedule.make("matmul", {"M": 128, "N": 128, "K": 128}), odd))
    mis = evaluate(concretize(Schedule.make("matmul", {"M": 128, "N": 1000, "K": 128}), odd))
    assert mis.compute_s > aligned.compute_s


def test_roofline_floor():
    """No schedule may beat the ideal roofline for its kernel."""
    inst = g()
    ideal = max(2 * 1024**3 / TPU_V5E.peak_flops_bf16,
                3 * 1024 * 1024 * 2 / TPU_V5E.hbm_bandwidth)
    for tiles in ({"M": 128, "N": 128, "K": 128}, {"M": 512, "N": 512, "K": 128},
                  {"M": 1024, "N": 256, "K": 512}):
        bd = evaluate(concretize(Schedule.make("matmul", tiles), inst))
        assert bd.seconds >= ideal * 0.99


def test_model_seconds_uses_counts():
    u = [KernelUse(g(), use_count=3)]
    assert model_seconds(u) == pytest.approx(3 * kernel_seconds(g()))


def test_attention_window_cheaper():
    full = KernelInstance.make("flash_attention_causal", Q=4096, KV=4096, H=8, D=128, B=1)
    swa = KernelInstance.make("flash_attention_swa", Q=4096, KV=4096, H=8, D=128, B=1,
                              window=512)
    s_full = kernel_seconds(full)
    s_swa = kernel_seconds(swa)
    assert s_swa < s_full


def test_scan_families():
    rw = KernelInstance.make("rwkv6_scan", T=4096, C=2048, D=64, B=4)
    rg = KernelInstance.make("rglru_scan", T=4096, C=2560, B=4)
    assert kernel_seconds(rw) > 0 and kernel_seconds(rg) > 0
