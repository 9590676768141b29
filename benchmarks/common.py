"""Shared benchmark infrastructure.

The benchmarks reproduce the paper's tables/figures over the 10 assigned
architectures.  Full auto-scheduling of every arch (the "Ansor 20k-trials"
analogue, scaled to FULL_TRIALS) is expensive, so each arch's tuning result
— records, untuned/tuned seconds, and the full search trace — is cached
under benchmarks/results/tuning/ and reused across benchmark modules.

Conventions: all times are *cost-model seconds* (kernel runtimes) or
*virtual search seconds* (the simulated measurement harness); see DESIGN.md.
Mesh-local extents use the production single-pod mesh (dp=16, tp=16).
"""
from __future__ import annotations

import json
import os
import time

from repro.configs import ARCH_IDS
from repro.core.autoscheduler import TracePoint, tune_model
from repro.core.database import Record, ScheduleDB
from repro.core.extract import extract_kernels
from repro.core.tuner import arch_uses
# The one quantile implementation (repro.obs) — benchmarks and the fleet
# metrics share it, so bench numbers and serving summaries always agree.
from repro.obs import percentile  # noqa: F401  (re-export)

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")
TUNING_DIR = os.path.join(RESULTS_DIR, "tuning")

FULL_TRIALS = 1536     # "recommended full budget" analogue (scaled from 20k)
DP, TP = 16, 16        # production single-pod mesh
SHAPE = "train_4k"
SEED = 0
#: Printed with every table: which clock ``us_per_call`` is on.
CLOCK_NOTE = ("times are cost-model microseconds or virtual search seconds "
              "(runner_cache: host wall time of the search), never a device time")


def _tuning_path(arch: str, shape: str = SHAPE) -> str:
    os.makedirs(TUNING_DIR, exist_ok=True)
    return os.path.join(TUNING_DIR, f"{arch}__{shape}.json")


def tune_arch_cached(arch: str, shape: str = SHAPE, trials: int = FULL_TRIALS,
                     seed: int = SEED) -> dict:
    """Full-budget tuning of one arch; cached to disk with its search trace."""
    path = _tuning_path(arch, shape)
    if os.path.exists(path):
        with open(path) as f:
            d = json.load(f)
        if d["trials"] >= trials:
            return d
    uses = arch_uses(arch, shape, dp=DP, tp=TP)
    t0 = time.monotonic()
    res = tune_model(uses, model_id=arch, total_trials=trials, seed=seed)
    out = {
        "arch": arch,
        "shape": shape,
        "trials": res.total_trials,
        "untuned_seconds": res.untuned_seconds,
        "tuned_seconds": res.tuned_seconds,
        "search_time_s": res.search_time_s,
        "wall_time_s": round(time.monotonic() - t0, 2),
        "records": [r.to_json() for r in res.records],
        "trace": [[p.search_time_s, p.best_seconds, p.trials] for p in res.trace],
    }
    with open(path, "w") as f:
        json.dump(out, f)
    return out


def full_db(shape: str = SHAPE) -> ScheduleDB:
    """ScheduleDB holding every arch's full-budget tuning records."""
    db = ScheduleDB()
    for arch in ARCH_IDS:
        d = tune_arch_cached(arch, shape)
        for r in d["records"]:
            db.add(Record.from_json(r))
    return db


def trace_points(d: dict) -> list[TracePoint]:
    return [TracePoint(t, s, n) for t, s, n in d["trace"]]


def speedup_at_time(d: dict, budget_s: float) -> float:
    """Ansor's speedup given `budget_s` virtual search seconds (trace lookup)."""
    best = d["untuned_seconds"]
    for t, s, _ in d["trace"]:
        if t <= budget_s:
            best = min(best, s)
        else:
            break
    return d["untuned_seconds"] / best


def time_to_reach(d: dict, target_seconds: float) -> float | None:
    """Virtual search seconds Ansor needs to reach `target_seconds` model time."""
    for t, s, _ in d["trace"]:
        if s <= target_seconds:
            return t
    return None


def emit(rows: list[tuple], header: str | None = None) -> None:
    """CSV lines: name,us_per_call,derived (the harness contract), under the
    table's header and the clock its times are on."""
    if header:
        print(f"# {header}")
    print(f"# {CLOCK_NOTE}")
    for name, us, derived in rows:
        print(f"{name},{us},{derived}")


def save_result(name: str, payload: dict, *,
                metrics: "dict[str, float] | None" = None,
                gated: "dict[str, str] | None" = None) -> None:
    """Write ``benchmarks/results/<name>.json`` in the common envelope.

    Every benchmark artifact shares one schema so CI uploads are stable
    (``BENCH_<name>.json``) and :mod:`benchmarks.compare` can diff any two
    runs without per-bench knowledge:

    * ``name`` / ``preset`` / ``pass`` / ``timestamp`` — identity and the
      bench's own verdict (``preset``/``pass`` lifted from the payload);
    * ``metrics`` — flat ``name -> float`` of the numbers worth tracking
      across runs;
    * ``gated`` — ``metric -> "lower" | "higher"`` (which direction is
      *better*): the subset of ``metrics`` whose >10% regression fails CI;
    * ``detail`` — the full bench-specific payload, unchanged.

    Callers that predate the envelope pass only ``payload``; they get
    identity + detail with empty metrics, still schema-valid.
    """
    os.makedirs(RESULTS_DIR, exist_ok=True)
    metrics = dict(metrics or {})
    gated = dict(gated or {})
    bad = set(gated) - set(metrics)
    if bad:
        raise ValueError(f"gated metrics missing from metrics: {sorted(bad)}")
    bad_dir = {m: d for m, d in gated.items() if d not in ("lower", "higher")}
    if bad_dir:
        raise ValueError(f"gated direction must be lower|higher: {bad_dir}")
    envelope = {
        "name": name,
        "preset": payload.get("preset"),
        "pass": payload.get("pass"),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "metrics": {k: float(v) for k, v in sorted(metrics.items())},
        "gated": {k: gated[k] for k in sorted(gated)},
        "detail": payload,
    }
    with open(os.path.join(RESULTS_DIR, f"{name}.json"), "w") as f:
        json.dump(envelope, f, indent=1)
