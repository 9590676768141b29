"""Auto-scheduler (Ansor analogue) behaviour."""
import random

import pytest

from repro.core.autoscheduler import (
    KernelTask,
    Surrogate,
    crossover,
    featurize,
    mutate,
    random_schedule,
    tune_kernel,
    tune_model,
)
from repro.core.cost_model import kernel_seconds, measure
from repro.core.schedule import REDUCTION_AXIS, default_schedule, is_valid
from repro.core.workload import KernelInstance, KernelUse


def g(m=1024, n=1024, k=1024):
    return KernelInstance.make("matmul", M=m, N=n, K=k)


def test_random_schedules_valid_on_source():
    rng = random.Random(0)
    inst = g(768, 768, 768)
    for _ in range(50):
        s = random_schedule(inst, rng)
        assert is_valid(s, inst), s


def test_mutation_preserves_validity():
    rng = random.Random(1)
    inst = g(512, 512, 512)
    s = random_schedule(inst, rng)
    for _ in range(50):
        s = mutate(s, inst, rng)
        assert is_valid(s, inst), s


@pytest.mark.parametrize("inst", [
    g(512, 512, 512),
    KernelInstance.make("moe_gemm_silu_glu", M=512, N=1024, K=256, E=8),
    KernelInstance.make("flash_attention_causal", Q=256, KV=256, H=8, D=128),
    KernelInstance.make("rglru_scan", T=256, C=2560),
], ids=lambda i: i.class_id)
def test_search_keeps_reduction_innermost(inst):
    """Proposals, mutations and crossovers only emit the order the kernels
    run, so no trial is spent on a schedule that is never realized."""
    rng = random.Random(2)
    red = REDUCTION_AXIS[inst.family]
    pool = [random_schedule(inst, rng) for _ in range(20)]
    for _ in range(40):
        pool.append(mutate(rng.choice(pool), inst, rng))
        pool.append(crossover(*rng.sample(pool, 2), rng))
    assert all(s.order[-1] == red for s in pool)
    # the outer axes are still permuted where there are two or more
    if len(inst.axes) > 2:
        assert len({s.order for s in pool}) > 1


def test_tuning_improves_over_default():
    inst = g()
    res = tune_kernel(inst, trials=96, seed=0)
    untuned = kernel_seconds(inst, default_schedule(inst))
    # the default is a sensible generic schedule (TVM-analogue), so the
    # headroom is real but bounded
    assert res.best_seconds < untuned / 1.5


def test_trace_monotone_nonincreasing():
    res = tune_kernel(g(512, 512, 512), trials=64, seed=1)
    best = [p.best_seconds for p in res.trace]
    assert all(a >= b for a, b in zip(best, best[1:]))
    times = [p.search_time_s for p in res.trace]
    assert all(a <= b for a, b in zip(times, times[1:]))


def test_reproducible_given_seed():
    a = tune_kernel(g(512, 512, 512), trials=48, seed=3)
    b = tune_kernel(g(512, 512, 512), trials=48, seed=3)
    assert a.best_seconds == b.best_seconds and a.best == b.best


def test_task_scheduler_prioritizes_expensive_kernel():
    """Ansor-style allocation: the dominant kernel gets more trials."""
    cheap = KernelUse(g(128, 128, 128), use_count=1)
    costly = KernelUse(g(4096, 4096, 4096), use_count=8)
    res = tune_model([cheap, costly], "m", total_trials=128, seed=0)
    trials = {r.instance.workload_key(): r.trials for r in res.records}
    assert trials[costly.instance.workload_key()] > trials[cheap.instance.workload_key()]
    assert res.speedup > 1.0


def test_surrogate_learns_ranking():
    inst = g()
    rng = random.Random(0)
    sur = Surrogate()
    pool = [random_schedule(inst, rng) for _ in range(60)]
    measured = [(s, measure(inst, s, seed=0)) for s in pool]
    measured = [(s, m.seconds) for s, m in measured if m.valid]
    train, test = measured[:40], measured[40:]
    assert len(test) >= 5
    for s, sec in train:
        sur.add(featurize(s, inst), sec)
    import numpy as np

    pred = sur.predict([featurize(s, inst) for s, _ in test])
    actual = np.array([sec for _, sec in test])
    # rank correlation must be positive (the model guides search usefully)
    rho = np.corrcoef(np.argsort(np.argsort(pred)), np.argsort(np.argsort(actual)))[0, 1]
    assert rho > 0.2


def test_search_time_accounted():
    task = KernelTask(g(512, 512, 512), seed=0)
    task.step(16)
    assert task.trials == 16
    assert task.search_time_s > 16 * 1.0  # >= compile time per trial
