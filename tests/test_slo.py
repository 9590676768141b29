"""Closed-loop observability: SLOs, critical-path profiler, ledger, advisor.

Unit-level pins for the PR-10 loop: declarative :class:`SLO` validation and
per-kind badness, :class:`SLOMonitor` multi-window burn-rate math on a fake
metrics window, :func:`profiler.critical_path` attribution over a hand-built
trace, :class:`SpeedupLedger` aggregation identities, and
:class:`TuningAdvisor` ranking (donor-prior headroom, exhaustion skips,
deterministic order).
"""
import dataclasses

import pytest

from repro.core.autoscheduler import tune_kernel
from repro.core.database import Record, ScheduleDB
from repro.core.runner import AnalyticalRunner
from repro.core.workload import KernelInstance, KernelUse
from repro.fleet.advisor import TuningAdvisor
from repro.obs import SLO, KINDS, SLOMonitor, SpeedupLedger, Tracer
from repro.obs.export import _records
from repro.obs.ledger import LedgerEntry
from repro.obs.profiler import critical_path, live_workload_seconds, span_cell


# ---------------------------------------------------------------------------
# Fakes shared across the module
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FakeRequest:
    """The outcome fields SLO.is_bad and SLOMonitor._seen consume."""

    arrival_s: float = 0.0
    finished_s: float = None
    latency_s: float = None
    prefill_done_s: float = None
    deadline_s: float = None
    shed: bool = False
    shed_s: float = None


def done(fin, lat, **kw):
    return FakeRequest(arrival_s=fin - lat, finished_s=fin, latency_s=lat,
                       **kw)


class FakeFleetMetrics:
    def __init__(self):
        self.completed = []
        self.shed = []


# ---------------------------------------------------------------------------
# SLO declaration + per-kind badness
# ---------------------------------------------------------------------------


def test_slo_validation():
    with pytest.raises(ValueError, match="unknown SLO kind"):
        SLO("x", "throughput")
    for bad_obj in (0.0, 1.0, -0.5, 1.5):
        with pytest.raises(ValueError, match="objective"):
            SLO("x", "shed", objective=bad_obj)
    for kind in ("latency", "ttft"):
        with pytest.raises(ValueError, match="threshold_s"):
            SLO("x", kind)
    with pytest.raises(ValueError, match="fast_windows"):
        SLO("x", "shed", fast_windows=0)
    with pytest.raises(ValueError, match="fast_windows"):
        SLO("x", "shed", fast_windows=3, slow_windows=2)
    assert SLO("x", "shed", objective=0.98).budget == pytest.approx(0.02)


def test_slo_is_bad_per_kind():
    lat = SLO("l", "latency", threshold_s=5.0)
    assert lat.is_bad(done(10.0, 6.0))
    assert not lat.is_bad(done(10.0, 5.0))          # boundary is good

    ttft = SLO("t", "ttft", threshold_s=2.0)
    assert ttft.is_bad(done(10.0, 4.0, prefill_done_s=9.0))   # arrival 6 -> 3
    assert not ttft.is_bad(done(10.0, 4.0, prefill_done_s=7.5))
    # No prefill mark: first token falls back to the finish instant.
    assert ttft.is_bad(done(10.0, 3.0))
    assert not ttft.is_bad(done(10.0, 1.5))

    shed = SLO("s", "shed", objective=0.98)
    assert not shed.is_bad(done(10.0, 100.0))        # slow completion is good
    ddl = SLO("d", "deadline")
    assert ddl.is_bad(done(10.0, 1.0, deadline_s=9.0))
    assert not ddl.is_bad(done(10.0, 1.0, deadline_s=11.0))
    assert not ddl.is_bad(done(10.0, 1.0))           # no deadline -> good

    dropped = FakeRequest(shed=True, shed_s=3.0)
    for slo in (lat, ttft, shed, ddl):               # shed is bad everywhere
        assert slo.is_bad(dropped)
    assert len(KINDS) == 4


# ---------------------------------------------------------------------------
# SLOMonitor burn-rate math and alert lifecycle
# ---------------------------------------------------------------------------


def _monitor(slos, window_s=10.0, tracer=None):
    fm = FakeFleetMetrics()
    return SLOMonitor(slos, fm, window_s=window_s, tracer=tracer), fm


def test_monitor_rejects_bad_config():
    with pytest.raises(ValueError, match="window_s"):
        SLOMonitor([], FakeFleetMetrics(), window_s=0.0)
    with pytest.raises(ValueError, match="duplicate"):
        SLOMonitor([SLO("a", "shed"), SLO("a", "deadline")],
                   FakeFleetMetrics(), window_s=1.0)


def test_burn_rate_math_and_empty_window():
    slo = SLO("p95", "latency", objective=0.8, threshold_s=5.0)
    mon, fm = _monitor([slo])
    fm.completed += [done(2.0, 3.0), done(4.0, 7.0), done(6.0, 7.0),
                     done(8.0, 7.0)]
    # bad 3 of 4 seen -> bad fraction .75 over budget .2 -> burn 3.75
    assert mon.burn_rate(slo, 0.0, 10.0) == (pytest.approx(3.75), 4)
    # Window binning is [t0, t1): the t=8 finisher is outside [0, 8).
    assert mon.burn_rate(slo, 0.0, 8.0)[1] == 3
    # An empty window burns 0 — a quiet fleet never alerts.
    assert mon.burn_rate(slo, 20.0, 30.0) == (0.0, 0)


def test_sheds_count_against_every_kind():
    slo = SLO("shed", "shed", objective=0.9)
    mon, fm = _monitor([slo])
    fm.completed.append(done(5.0, 1.0))
    fm.shed.append(FakeRequest(shed=True, shed_s=6.0))
    burn, seen = mon.burn_rate(slo, 0.0, 10.0)
    assert seen == 2 and burn == pytest.approx((1 / 2) / 0.1)


def test_alert_needs_both_windows():
    """A fast-window blip that the slow window dilutes must not alert."""
    slo = SLO("p", "latency", objective=0.5, threshold_s=5.0,
              fast_windows=1, slow_windows=2)
    mon, fm = _monitor([slo])
    fm.completed += [done(t, 1.0) for t in (1.0, 3.0, 5.0, 7.0)]  # good burst
    fm.completed += [done(12.0, 9.0), done(14.0, 9.0)]            # bad blip
    (st,) = mon.evaluate(20.0)
    assert st.burn_fast == pytest.approx(2.0)          # [10, 20): all bad
    assert st.burn_slow == pytest.approx((2 / 6) / 0.5)  # [0, 20): diluted
    assert not st.alerting


def test_alert_clear_lifecycle_events_and_summary():
    tr = Tracer(clock=lambda: 0.0)
    slo = SLO("p95", "latency", objective=0.8, threshold_s=5.0,
              fast_windows=1, slow_windows=2)
    mon, fm = _monitor([slo], tracer=tr)
    fm.completed += [done(t, 9.0) for t in (2.0, 4.0, 6.0)]
    (st,) = mon.evaluate(10.0)
    assert st.alerting and st.changed and st.seen_fast == 3
    (st2,) = mon.evaluate(20.0)          # fast [10,20) empty -> burn 0
    assert not st2.alerting and st2.changed
    (st3,) = mon.evaluate(30.0)
    assert not st3.alerting and not st3.changed

    assert mon.metrics.get("slo.alerts").value == 1
    assert mon.metrics.get("slo.clears").value == 1
    assert mon.metrics.get("slo.p95.alerting").samples == [
        (10.0, 1.0), (20.0, 0.0), (30.0, 0.0)]
    names = [e.name for e in tr.events]
    assert names == ["slo_alert", "slo_clear"]
    assert tr.events[0].attrs["slo"] == "p95"

    assert mon.alerting() == []
    assert mon.last_alert_end() == 10.0
    s = mon.summary()["p95"]
    assert s["evaluations"] == 3 and s["alerting_windows"] == 1
    assert s["alert_share"] == pytest.approx(1 / 3)
    assert not s["alerting_now"] and s["last_alert_end_s"] == 10.0


def test_never_alerted_reads_zero():
    mon, _ = _monitor([SLO("s", "shed")])
    mon.evaluate(10.0)
    assert mon.last_alert_end() == 0.0
    assert mon.summary()["s"]["alerting_windows"] == 0


# ---------------------------------------------------------------------------
# Critical-path profiler
# ---------------------------------------------------------------------------


def test_span_cell_mapping():
    def rec(name, **attrs):
        return {"name": name, "cat": None, "attrs": attrs}

    assert span_cell(rec("prefill", bucket=16)) == ("prefill:16", 1.0)
    assert span_cell(rec("chunk", len=8)) == ("prefill:8", 1.0)
    assert span_cell(rec("decode_step")) == ("decode", 1.0)
    assert span_cell(rec("decode")) == ("decode", 1.0)
    assert span_cell(rec("verify")) == ("verify", 1.0)
    assert span_cell(rec("draft_burst", steps=4)) == ("draft_decode", 4.0)
    assert span_cell(rec("draft_sync", len=16)) == ("draft_sync:16", 1.0)
    assert span_cell(rec("step")) is None            # container, not a cell
    assert span_cell({"name": "prefill", "cat": "request",
                      "attrs": {}}) is None          # async phase span


def _profiled_tracer():
    """Two finished requests + cell spans + workload maps on one replica."""
    tr = Tracer(clock=lambda: 0.0)
    for uid, (arr, adm, pd, fin) in {"1": (0.0, 1.0, 2.0, 6.0),
                                     "2": (1.0, 1.5, 3.0, 9.0)}.items():
        tr.add_async_span("request", "replica-0", arr, fin, "request", uid,
                          uid=int(uid))
        tr.add_async_span("queue", "replica-0", arr, adm, "request", uid)
        tr.add_async_span("prefill", "replica-0", adm, pd, "request", uid)
        tr.add_async_span("decode", "replica-0", pd, fin, "request", uid)
    tr.event("cell_workloads", "replica-0", t=0.0, cell="prefill:8",
             workloads=[["wkA", 0.2], ["wkC", 0.3]])
    tr.event("cell_workloads", "replica-0", t=0.0, cell="verify",
             workloads=[["wkA", 0.1]])
    tr.event("cell_workloads", "replica-0", t=0.0, cell="draft_decode",
             workloads=[["wkB", 0.05]])
    # Plan generation flip: verify re-priced before the second execution.
    tr.event("cell_workloads", "replica-0", t=2.2, cell="verify",
             workloads=[["wkA", 0.4]])
    tr.add_span("chunk", "replica-0", 1.0, 2.0, len=8)
    tr.add_span("verify", "replica-0", 2.0, 2.5)
    tr.add_span("draft_burst", "replica-0", 2.5, 3.0, steps=4)
    tr.add_span("verify", "replica-0", 3.0, 3.2)
    return tr


def test_critical_path_attribution():
    cp = critical_path(_records(_profiled_tracer()))
    assert cp["requests"] == 2
    # Latencies [6, 8]: p50 interpolates, p95 via the shared percentile.
    assert cp["latency_s"]["p50"] == pytest.approx(7.0)
    assert cp["segments"]["queue"] == pytest.approx(1.0 + 0.5)
    assert cp["segments"]["prefill"] == pytest.approx(1.0 + 1.5)
    assert cp["segments"]["decode"] == pytest.approx(4.0 + 6.0)

    assert cp["by_cell"]["prefill:8"] == {"seconds": pytest.approx(1.0),
                                          "executions": 1.0}
    assert cp["by_cell"]["verify"]["executions"] == 2.0
    assert cp["by_cell"]["draft_decode"]["executions"] == 4.0

    # First verify execution priced by the t=0 map, second by the t=2.2
    # map (latest emission at or before span start); draft_burst multiplies
    # by its step count.
    assert cp["by_workload"]["wkA"] == pytest.approx(0.2 + 0.1 + 0.4)
    assert cp["by_workload"]["wkB"] == pytest.approx(4 * 0.05)
    assert cp["by_workload"]["wkC"] == pytest.approx(0.3)
    assert cp["attributed_frac"] == 1.0


def test_critical_path_unmapped_cells_lower_attribution():
    tr = Tracer(clock=lambda: 0.0)
    tr.add_span("verify", "replica-0", 0.0, 1.0)     # no cell_workloads map
    cp = critical_path(_records(tr))
    assert cp["attributed_frac"] == 0.0 and cp["by_workload"] == {}
    assert cp["by_cell"]["verify"]["seconds"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Fake replica shared by the live profiler / ledger / advisor tests
# ---------------------------------------------------------------------------

INST_A = KernelInstance.make("matmul", M=128, N=128, K=128)
INST_B = KernelInstance.make("matmul", M=160, N=160, K=160)


class FakeResolution:
    def __init__(self, schedule, tier, source_model):
        self.schedule = schedule
        self.tier = tier
        self.source_model = source_model


class FakeReplica:
    """Cell counters + plan-derived costs: the live profiler's whole input."""

    target = "tpu-v5e"
    service = None

    def __init__(self, counts, uses, served_s, untuned_s):
        self.cell_counts = dict(counts)
        self._uses = uses                 # cell -> [KernelUse]
        self._served = served_s           # workload_key -> seconds
        self._untuned = untuned_s

    def cell_uses(self, cell):
        return self._uses.get(cell, [])

    def cell_workload_seconds(self, cell):
        return [(u, u.use_count * self._served[u.instance.workload_key()])
                for u in self.cell_uses(cell)]

    def use_resolution(self, instance):
        return FakeResolution(object(), "transfer", "donor_a")

    def use_seconds(self, instance, schedule):
        key = instance.workload_key()
        return self._untuned[key] if schedule is None else self._served[key]


def _fake_replica():
    return FakeReplica(
        counts={"verify": 3, "draft_decode": 10},
        uses={"verify": [KernelUse(INST_A, use_count=2)],
              "draft_decode": [KernelUse(INST_B, use_count=1)]},
        served_s={INST_A.workload_key(): 1.0, INST_B.workload_key(): 0.25},
        untuned_s={INST_A.workload_key(): 2.0, INST_B.workload_key(): 0.25})


def test_live_workload_seconds():
    live = live_workload_seconds([_fake_replica()])
    a = live[(INST_A.workload_key(), "tpu-v5e")]
    b = live[(INST_B.workload_key(), "tpu-v5e")]
    assert a["seconds"] == pytest.approx(3 * 2 * 1.0)   # execs x use_count x s
    assert b["seconds"] == pytest.approx(10 * 1 * 0.25)
    assert a["instance"] is INST_A


# ---------------------------------------------------------------------------
# Speedup ledger
# ---------------------------------------------------------------------------


def test_ledger_entry_properties():
    e = LedgerEntry(key="k", target="t", class_id="c", tier="transfer",
                    source_model="d", untuned_s=2.0, served_s=1.0,
                    best_s=0.8, weight=4.0)
    assert e.realized_speedup == pytest.approx(2.0)
    assert e.attainable_speedup == pytest.approx(2.5)
    assert e.headroom_s == pytest.approx(0.2)
    e2 = dataclasses.replace(e, best_s=None)
    assert e2.attainable_speedup == pytest.approx(2.0)  # falls back to served
    assert e2.headroom_s == 0.0


def test_ledger_update_from_replicas_and_gauges():
    led = SpeedupLedger()
    agg = led.update([_fake_replica()], now=7.0)
    a = led.entries[(INST_A.workload_key(), "tpu-v5e")]
    assert a.weight == 3 * 2 and a.tier == "transfer"
    assert a.untuned_s == 2.0 and a.served_s == 1.0 and a.best_s is None
    # decode is always included, but with no uses it adds no entry.
    assert agg["workloads"] == 2 and agg["tuned_workloads"] == 0
    un = 6 * 2.0 + 10 * 0.25
    sv = 6 * 1.0 + 10 * 0.25
    assert agg["realized_speedup"] == pytest.approx(un / sv)
    assert agg["realized_fraction"] == 1.0   # best unknown -> served is best
    g = led.metrics.get("ledger.realized_speedup")
    assert g.samples == [(7.0, pytest.approx(un / sv))]


def test_ledger_aggregate_weight_fallback_and_speedup_for():
    led = SpeedupLedger()
    led.entries = {
        ("a", "t"): LedgerEntry("a", "t", "c", "exact", "d", 2.0, 1.0, 1.0),
        ("b", "t"): LedgerEntry("b", "t", "c", "default", "", 1.0, 1.0, 0.5),
    }
    agg = led.aggregates()                 # all weights 0 -> uniform weights
    assert agg["realized_speedup"] == pytest.approx(3.0 / 2.0)
    assert agg["attainable_speedup"] == pytest.approx(3.0 / 1.5)
    assert agg["realized_fraction"] == pytest.approx(1.5 / 2.0)
    assert agg["tiers"] == {"exact": 1, "default": 1}

    uses = [KernelUse(INST_A, use_count=3)]
    led.entries = {(INST_A.workload_key(), "t"):
                   LedgerEntry(INST_A.workload_key(), "t", "c", "transfer",
                               "d", 2.0, 1.0, 0.5)}
    s = led.speedup_for(uses, "t")
    assert s["realized_speedup"] == pytest.approx(2.0)
    assert s["attainable_speedup"] == pytest.approx(4.0)
    assert s["missing"] == []
    s2 = led.speedup_for([KernelUse(INST_B)], "t")
    assert s2["missing"] == [INST_B.workload_key()]
    assert s2["realized_speedup"] == 1.0


def test_ledger_top_headroom_orders_by_weighted_headroom():
    led = SpeedupLedger()
    led.entries = {
        ("small", "t"): LedgerEntry("small", "t", "c", "transfer", "d",
                                    2.0, 1.0, 0.5, weight=1.0),
        ("big", "t"): LedgerEntry("big", "t", "c", "transfer", "d",
                                  2.0, 1.0, 0.9, weight=100.0),
    }
    assert [e.key for e in led.top_headroom(2)] == ["big", "small"]
    top = led.summary()["top_headroom"]
    assert top[0]["key"] == "big"
    assert top[0]["headroom_s"] == pytest.approx(100.0 * 0.1)


# ---------------------------------------------------------------------------
# Tuning advisor
# ---------------------------------------------------------------------------


class FakeSnapshot:
    def __init__(self, db):
        self._db = db

    def db(self, mode=None):
        return self._db


class FakeRegistry:
    def __init__(self, db):
        self._db = db

    def snapshot(self):
        return FakeSnapshot(self._db)


class FakeService:
    target = "tpu-v5e"
    donor_target = "tpu-v5e"

    def __init__(self, db, attempted=()):
        self.registry = FakeRegistry(db)
        self.runner = AnalyticalRunner()
        self._attempted = set(attempted)

    def donor_models(self, db):
        return ["donor_a"]

    def attempted(self, key):
        return key in self._attempted


class FakeFleet:
    def __init__(self, replicas, services):
        self.replicas = replicas
        self.services = services

    def live_replicas(self):
        return self.replicas


@pytest.fixture(scope="module")
def donor_schedule():
    return tune_kernel(INST_A, trials=16, seed=0).best


def _db_with(*records):
    db = ScheduleDB()
    for r in records:
        db.add(r)
    return db


def test_class_headroom_prior_from_donor_pool(donor_schedule):
    runner = AnalyticalRunner()
    donor_inst = KernelInstance.make("matmul", M=192, N=192, K=192)
    untuned = runner.seconds(donor_inst, None)
    db = _db_with(Record(donor_inst, donor_schedule, 0.25 * untuned,
                         "donor_a"))
    svc = FakeService(db)
    adv = TuningAdvisor()
    # Best donor of the class runs at .25x untuned -> 75% headroom prior.
    assert adv.class_headroom(INST_A, svc, db) == pytest.approx(0.75)
    # Cached per (class, target): mutating the db does not change the prior.
    db2 = _db_with()
    assert adv.class_headroom(INST_A, svc, db2) == pytest.approx(0.75)


def test_class_headroom_default_and_clamp(donor_schedule):
    adv = TuningAdvisor(default_headroom=0.4, min_headroom=0.1)
    svc = FakeService(_db_with())
    assert adv.class_headroom(INST_A, svc,
                              svc.registry.snapshot().db()) == 0.4
    # A donor pool with no headroom clamps to the anti-starvation floor.
    runner = AnalyticalRunner()
    donor_inst = KernelInstance.make("matmul", M=192, N=192, K=192)
    untuned = runner.seconds(donor_inst, None)
    db = _db_with(Record(donor_inst, donor_schedule, untuned, "donor_a"))
    adv2 = TuningAdvisor(min_headroom=0.1)
    assert adv2.class_headroom(INST_A, FakeService(db), db) == \
        pytest.approx(0.1)


def test_rank_skips_exhausted_and_sorts_deterministically(donor_schedule):
    rep = _fake_replica()
    inst_c = KernelInstance.make("matmul", M=96, N=96, K=96)
    rep.cell_counts["prefill:8"] = 1
    rep._uses["prefill:8"] = [KernelUse(inst_c, use_count=1)]
    rep._served[inst_c.workload_key()] = 6.0
    rep._untuned[inst_c.workload_key()] = 6.0

    svc = FakeService(_db_with(), attempted=[inst_c.workload_key()])
    adv = TuningAdvisor(default_headroom=0.5)
    fleet = FakeFleet([rep], {"tpu-v5e": svc})
    ranked = adv.rank(fleet)
    # inst_c is attempted -> skipped; A (6s) outranks B (2.5s), same prior.
    assert [r.instance.workload_key() for r in ranked] == \
        [INST_A.workload_key(), INST_B.workload_key()]
    assert ranked[0].priority == pytest.approx(6.0 * 0.5)
    assert ranked[0].critical_s == pytest.approx(6.0)

    # Publishing an exact record for A exhausts it too.
    svc2 = FakeService(_db_with(Record(INST_A, donor_schedule, 0.5,
                                       "target_model")))
    ranked2 = TuningAdvisor().rank(FakeFleet([rep], {"tpu-v5e": svc2}))
    assert INST_A.workload_key() not in \
        [r.instance.workload_key() for r in ranked2]


def test_rank_tie_breaks_by_workload_key(donor_schedule):
    rep = FakeReplica(
        counts={"verify": 1},
        uses={"verify": [KernelUse(INST_A), KernelUse(INST_B)]},
        served_s={INST_A.workload_key(): 1.0, INST_B.workload_key(): 1.0},
        untuned_s={INST_A.workload_key(): 1.0, INST_B.workload_key(): 1.0})
    ranked = TuningAdvisor().rank(
        FakeFleet([rep], {"tpu-v5e": FakeService(_db_with())}))
    keys = [r.instance.workload_key() for r in ranked]
    assert keys == sorted(keys)            # equal priority -> key order
