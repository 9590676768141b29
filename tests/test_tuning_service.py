"""TuningService: tiered lookup, background upgrades, dedup, no-downgrade."""
import threading

import pytest

from repro.core.autoscheduler import tune_kernel
from repro.core.database import Record, ScheduleDB
from repro.core.runner import AnalyticalRunner, CachedRunner
from repro.core.schedule import Schedule
from repro.core.transfer import transfer_tune
from repro.core.workload import KernelInstance, KernelUse
from repro.service import ScheduleRegistry, TuningService

DONOR_SIZES = {"donor_a": 512, "donor_b": 768}
TARGET = KernelInstance.make("matmul", M=256, N=1024, K=512)


def g(size):
    return KernelInstance.make("matmul", M=size, N=size, K=size)


@pytest.fixture(scope="module")
def donor_records():
    out = []
    for model, size in DONOR_SIZES.items():
        res = tune_kernel(g(size), trials=96, seed=0)
        out.append(Record(g(size), res.best, res.best_seconds, model))
    return out


@pytest.fixture
def registry(tmp_path, donor_records):
    reg = ScheduleRegistry(str(tmp_path / "reg"))
    reg.publish(donor_records)
    return reg


def make_service(registry, **kw):
    kw.setdefault("model_id", "target")
    kw.setdefault("runner", CachedRunner(AnalyticalRunner()))
    kw.setdefault("max_workers", 0)
    kw.setdefault("seed", 0)
    return TuningService(registry, **kw)


def test_exact_tier_for_donor_workload(registry, donor_records):
    svc = make_service(registry)
    res = svc.lookup(g(512))
    assert res.tier == "exact"
    assert res.schedule == donor_records[0].schedule
    assert res.source_model == "donor_a"
    assert svc.stats()["jobs_enqueued"] == 0     # exact hits don't search


def test_transfer_tier_probes_same_class(registry):
    svc = make_service(registry)
    res = svc.lookup(TARGET)
    assert res.tier == "transfer"
    assert res.seconds < res.untuned_seconds
    assert res.source_model in DONOR_SIZES
    assert svc.stats()["jobs_enqueued"] == 1     # miss still queues the upgrade


def test_default_tier_without_candidates(registry):
    svc = make_service(registry, donors=[])      # empty donor pool
    res = svc.lookup(TARGET)
    assert res.tier == "default" and res.schedule is None
    assert res.seconds == res.untuned_seconds


def test_background_job_upgrades_to_exact(registry):
    svc = make_service(registry, probe_candidates=0)
    first = svc.lookup(TARGET)
    assert first.tier == "default"
    assert svc.drain() == 1
    second = svc.lookup(TARGET)
    stats = svc.stats()
    assert second.tier == "exact"
    assert second.seconds < first.seconds
    assert stats["upgrades"] == 1
    assert stats["search_seconds_spent"] > 0
    assert stats["generation"] > 1
    # upgrade is persistent: a fresh service over the same dir serves it
    svc2 = make_service(ScheduleRegistry(registry.root))
    assert svc2.lookup(TARGET).tier == "exact"


def test_jobs_dedupe_by_workload_key(registry):
    svc = make_service(registry)
    for _ in range(5):
        svc.lookup(TARGET)
    stats = svc.stats()
    assert stats["jobs_enqueued"] == 1
    assert stats["jobs_deduped"] == 4
    assert svc.drain() == 1
    # attempted keys are not re-enqueued even when the job published nothing
    svc.lookup(TARGET)
    assert svc.stats()["jobs_enqueued"] == 1


class HeldRunner(CachedRunner):
    """Holds the background job's measurements until ``release`` is set; the
    lookups' own probes, on threads named ``lookup-*``, pass at once."""

    def __init__(self):
        super().__init__(AnalyticalRunner())
        self.release = threading.Event()

    def measure_many(self, *args, **kwargs):
        if not threading.current_thread().name.startswith("lookup-"):
            assert self.release.wait(timeout=60), "job never released"
        return super().measure_many(*args, **kwargs)


def test_concurrent_misses_one_job(registry):
    # Hold the job until all eight lookups have returned: a job that
    # published first would turn the last lookups into exact hits, not
    # deduplicated misses.
    runner = HeldRunner()
    svc = make_service(registry, max_workers=2, runner=runner)
    barrier = threading.Barrier(8)

    def hit():
        barrier.wait()
        svc.lookup(TARGET)

    threads = [threading.Thread(target=hit, name=f"lookup-{i}")
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    runner.release.set()
    svc.drain()
    stats = svc.stats()
    assert stats["jobs_enqueued"] == 1
    assert stats["jobs_deduped"] == 7
    assert stats["jobs_completed"] == 1
    assert svc.lookup(TARGET).tier == "exact"
    svc.close()


def test_budget_enforced_for_already_queued_jobs(registry):
    """Jobs admitted while the budget was unspent must not run once a
    previous job exhausts it."""
    svc = make_service(registry, budget_s=1e-6, probe_candidates=0)
    svc.lookup(TARGET)
    svc.lookup(KernelInstance.make("matmul", M=128, N=512, K=1024))
    assert svc.stats()["jobs_enqueued"] == 2     # both admitted at spent=0
    svc.drain()
    stats = svc.stats()
    assert stats["jobs_completed"] == 1          # first job spends past budget
    assert stats["jobs_rejected_budget"] == 1    # second refused at run time
    assert stats["in_flight"] == 0


def test_exact_tier_falls_back_to_own_mode_record(registry):
    """A faster mode-incompatible record must not shadow a valid same-mode
    exact record for the workload."""
    svc = make_service(registry, probe_candidates=0)
    svc.lookup(TARGET)
    svc.drain()
    good = svc.lookup(TARGET)
    assert good.tier == "exact"
    # K=96 does not divide TARGET's K=512: strict-invalid, adaptive-valid —
    # and recorded faster, so it wins db(None).exact()
    shadow = Schedule.make("matmul", {"M": 64, "N": 128, "K": 96},
                           order=("M", "N", "K"))
    registry.publish([Record(TARGET, shadow, good.seconds / 10, "adaptive_prod")],
                     mode="adaptive")
    after = svc.lookup(TARGET)
    assert after.tier == "exact"
    assert after.schedule == good.schedule and after.seconds == good.seconds


def test_snapshot_db_views_are_frozen(registry):
    db = registry.snapshot().db()
    with pytest.raises(RuntimeError, match="frozen"):
        db.add(Record(TARGET, db.records()[0].schedule, 1.0, "x"))


def test_budget_bounds_background_search(registry):
    svc = make_service(registry, budget_s=0.0, probe_candidates=0)
    assert svc.lookup(TARGET).tier == "default"
    stats = svc.stats()
    assert stats["jobs_rejected_budget"] == 1
    assert stats["jobs_enqueued"] == 0
    assert svc.drain() == 0
    assert svc.stats()["search_seconds_spent"] == 0.0


def test_never_downgrades_published_schedule(registry):
    svc = make_service(registry, probe_candidates=0)
    svc.lookup(TARGET)
    svc.drain()
    best = svc.lookup(TARGET)
    # a stale/worse publish (e.g. a slower concurrent producer) must not win
    worse = Record(TARGET, best.schedule, best.seconds * 10, "slow_producer")
    registry.publish([worse])
    after = svc.lookup(TARGET)
    assert after.tier == "exact"
    assert after.seconds == best.seconds
    # and the service itself skips publishing non-improvements
    assert svc._publish(TARGET, best.schedule, best.seconds * 2, "x") is False
    assert svc.stats()["publish_skipped"] == 1


def test_drained_service_matches_offline_transfer(registry, donor_records):
    """The online path converges to the offline transfer_tune answer."""
    targets = [TARGET, KernelInstance.make("matmul", M=128, N=512, K=1024)]
    svc = make_service(registry, probe_candidates=0,
                       donors=list(DONOR_SIZES))
    for inst in targets:
        svc.lookup(inst)
    svc.drain()

    offline = transfer_tune([KernelUse(i) for i in targets],
                            ScheduleDB(donor_records), model_id="target",
                            donors=list(DONOR_SIZES), mode="strict", seed=0)
    for inst, k in zip(targets, offline.kernels):
        served = svc.lookup(inst)
        assert served.schedule == k.chosen
        if k.chosen is not None:
            assert served.tier == "exact"
            assert served.seconds == k.seconds


def test_close_drains_deferred_jobs(registry):
    """serve.py promises queued jobs are drained at exit even with
    --tuning-workers 0 — close() must run deferred jobs, not drop them."""
    svc = make_service(registry, probe_candidates=0)   # max_workers=0
    svc.lookup(TARGET)
    assert svc.stats()["in_flight"] == 1
    svc.close()
    stats = svc.stats()
    assert stats["in_flight"] == 0
    assert stats["jobs_completed"] == 1
    assert svc.lookup(TARGET).tier == "exact"


def test_stats_shape(registry):
    svc = make_service(registry)
    svc.lookup(g(512))
    svc.lookup(TARGET)
    s = svc.stats()
    assert s["lookups"] == 2
    assert s["exact_hits"] == 1 and s["transfer_hits"] == 1
    assert s["exact_hit_rate"] == 0.5
    assert s["in_flight"] == 1
    assert s["probe_search_s"] > 0


def test_threaded_pool_claims_highest_priority_first(registry):
    """max_workers=1 with the lone worker blocked: jobs enqueued while the
    pool is busy are claimed priority-first (FIFO within a priority), not
    submission order — ``completed_order`` makes the claim order observable."""
    svc = make_service(registry, max_workers=1, probe_candidates=0)
    gate = threading.Event()
    svc._pool.submit(gate.wait)          # occupy the only worker
    a = KernelInstance.make("matmul", M=192, N=192, K=192)
    b = KernelInstance.make("matmul", M=224, N=224, K=224)
    c = KernelInstance.make("matmul", M=288, N=288, K=288)
    assert svc.prefetch(a, priority=0.0)
    assert svc.prefetch(b, priority=0.0)
    assert svc.prefetch(c, priority=5.0)  # enqueued last, must run first
    gate.set()
    svc.close()
    keys = [a.workload_key(), b.workload_key(), c.workload_key()]
    order = [k for k in svc.completed_order if k in keys]
    assert order == [keys[2], keys[0], keys[1]]


# ---------------------------------------------------------------------------
# Queue health telemetry (stats) and starvation accounting
# ---------------------------------------------------------------------------


def test_stats_queue_health_uses_owner_clock(registry):
    """Queue ages are measured on the owner's clock (fleets pass their
    virtual now), surfaced in stats() and sampled into registry gauges."""
    t = {"v": 0.0}
    svc = make_service(registry, probe_candidates=0, clock=lambda: t["v"])
    a = KernelInstance.make("matmul", M=192, N=192, K=192)
    b = KernelInstance.make("matmul", M=224, N=224, K=224)
    assert svc.prefetch(a, priority=0.0)
    t["v"] = 5.0
    assert svc.prefetch(b, priority=1.0)
    t["v"] = 9.0
    s = svc.stats()
    assert s["queue_depth_unstarted"] == 2
    assert s["queue_age_mean_s"] == pytest.approx((9.0 + 4.0) / 2)
    assert s["oldest_unstarted_age_s"] == pytest.approx(9.0)
    rows = s["queue_jobs"]                    # oldest first
    assert [r["key"] for r in rows] == [a.workload_key(), b.workload_key()]
    assert rows[0]["age_s"] == pytest.approx(9.0)
    assert rows[1]["priority"] == 1.0
    assert not rows[0]["starved"] and rows[0]["skips"] == 0
    g = svc.metrics.get(f"tuning.{svc.target}.queue_age_mean_s")
    assert g.samples[-1] == (9.0, pytest.approx(6.5))
    g2 = svc.metrics.get(f"tuning.{svc.target}.oldest_unstarted_age_s")
    assert g2.samples[-1][1] == pytest.approx(9.0)

    svc.drain()
    s2 = svc.stats()
    assert s2["queue_depth_unstarted"] == 0
    assert s2["queue_age_mean_s"] == 0.0 and s2["queue_jobs"] == []
    svc.close()


def test_starvation_accounting_marks_passed_over_jobs(registry):
    """A low-priority job passed over more than STARVATION_SKIPS times by
    higher-priority claims is counted starved exactly once — the audit the
    advisor's anti-starvation headroom floor is checked against."""
    svc = make_service(registry, probe_candidates=0, clock=lambda: 0.0)
    low = KernelInstance.make("matmul", M=176, N=176, K=176)
    assert svc.prefetch(low, priority=0.0)
    for i in range(TuningService.STARVATION_SKIPS + 1):
        size = 320 + 32 * i
        hot = KernelInstance.make("matmul", M=size, N=size, K=size)
        assert svc.prefetch(hot, priority=10.0)
        assert svc.drain(max_jobs=1) == 1     # claims hot, passes over low
    s = svc.stats()
    row = next(r for r in s["queue_jobs"] if r["key"] == low.workload_key())
    assert row["skips"] == TuningService.STARVATION_SKIPS + 1
    assert row["starved"] is True
    assert s["jobs_starved"] == 1             # counted once, not per skip
    svc.close()
