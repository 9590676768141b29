"""Serving driver: batched inference with continuous batching.

Loads a (reduced or full) arch and runs a stream of requests through the
slot-based engine, reporting throughput and per-request latency.

Schedule resolution is pluggable:

* ``--tuning-db db.json`` — frozen offline store: a ScheduleDB snapshot is
  loaded once and installed as a static provider (the pre-registry path).
* ``--tuning-registry DIR`` — online path: kernels resolve through a
  :class:`~repro.service.TuningService` over a segmented
  :class:`~repro.service.ScheduleRegistry`.  Unseen workloads are served
  untuned *once*, background transfer-tuning jobs publish upgrades, and
  later requests pick them up — the service's ``stats()`` land in the
  result JSON.  ``--tuning-workers 0`` defers jobs (drained at exit);
  the provider only affects the ``pallas`` backend (``--backend``).

Either way resolution runs through the staged
:class:`~repro.core.resolution.ResolutionPipeline` and the engine holds a
pre-resolved :class:`~repro.core.resolution.ExecutionPlan` for its serving
shapes: steady-state kernel calls are plan/cache dict hits, and when a
background job publishes an upgrade the engine re-plans at a decode-step
boundary — the result JSON reports per-tier resolution counts, plan tier
composition, and re-plan count.

``--target`` selects the hardware namespace served (schedules tuned for one
chip never silently serve another); ``--tuning-donor-target`` optionally
draws transfer donors from a different chip's namespace (explicit
cross-target serving, re-validated under ``--target``'s spec).

``--trace-out trace.json`` records the engine's wall-clock spans — each
prefill and decode step from its input upload to the host read of its
tokens, split into prepare / dispatch / read / commit, and one async span
per request; a step that compiled carries ``compiled=True``, so a slow
step can be told from a recompile — plus resolution/replan events
(Perfetto-loadable; DESIGN.md §10).  The same spans are entered as ``jax.profiler`` annotations
(``engine.decode_step.read`` …), so a JAX profile taken around the run
shows them on its own clock beside the device's operations.
``--metrics-out`` dumps the resolution metrics registry.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs.base import get_arch, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.core.database import ScheduleDB
from repro.fleet.traffic import sample_prompts
from repro.kernels.ops import (ScheduleProvider, interpret_mode,
                               set_default_provider, use_backend)
from repro.targets import DEFAULT_TARGET, list_targets
from repro.models.build import build_model
from repro.serving import ServingEngine, SlotsFull


def make_provider(args) -> tuple[ScheduleProvider, object | None]:
    """Build the schedule provider (and the service, when online) from args."""
    service = None
    schedule_map = {}
    if args.tuning_db:
        db = ScheduleDB.load(args.tuning_db)
        # Only this target's namespace: a record tuned for another chip must
        # never serve here, even through the frozen offline path.
        schedule_map = {r.instance.workload_key(): r.schedule
                       for r in db.records() if r.target == args.target}
    if args.tuning_registry:
        from repro.service import ScheduleRegistry, TuningService

        registry = ScheduleRegistry(args.tuning_registry)
        service = TuningService(registry, model_id=f"serve/{args.arch}",
                                max_workers=args.tuning_workers,
                                budget_s=args.tuning_budget_s,
                                target=args.target,
                                donor_target=args.tuning_donor_target)
    return ScheduleProvider(schedule_map, service=service), service


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description="serve an assigned architecture")
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--preset", choices=["smoke", "full"], default="smoke")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--backend", choices=["ref", "pallas"], default="ref")
    ap.add_argument("--target", choices=list_targets(), default=DEFAULT_TARGET,
                    help="hardware target to serve schedules for; the tuning "
                         "service only reads/publishes this chip's namespace")
    ap.add_argument("--tuning-donor-target", choices=list_targets(), default=None,
                    help="draw transfer donors from another chip's namespace "
                         "(cross-target serving; default: --target)")
    ap.add_argument("--tuning-db", default="")
    ap.add_argument("--tuning-registry", default="",
                    help="schedule-registry dir: serve through TuningService")
    ap.add_argument("--tuning-workers", type=int, default=2)
    ap.add_argument("--tuning-budget-s", type=float, default=float("inf"),
                    help="virtual search seconds for background tuning jobs")
    ap.add_argument("--seed", type=int, default=0,
                    help="request-stream seed (shared sampler with the fleet "
                         "traffic generator): runs are reproducible per seed "
                         "but vary across seeds")
    ap.add_argument("--trace-out", default="",
                    help="write a Perfetto-loadable Chrome trace: wall-clock "
                         "spans of each prefill and decode step from input "
                         "upload to the host read of its tokens, with their "
                         "prepare/dispatch/read/commit parts, and one span "
                         "per request")
    ap.add_argument("--metrics-out", default="",
                    help="write the engine's resolution metrics as JSON")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.preset == "smoke":
        cfg = reduced(cfg)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    provider, service = make_provider(args)
    prev_provider = set_default_provider(provider)

    extras = {}
    if cfg.family == "audio":
        extras["frames"] = np.zeros((cfg.encoder_seq, cfg.d_model), np.float32)
    if cfg.vision_tokens:
        extras["patch_embeds"] = np.zeros((cfg.vision_tokens, cfg.d_model), np.float32)

    # The provider (and hence plan construction, which runs service lookups
    # and enqueues background tuning) is wired in only for the pallas
    # backend: ref-backend ops never consult schedules, and planning for
    # them would spend tuning budget on kernels that never execute.
    engine = ServingEngine(
        model, params, slots=args.slots, max_len=args.max_len, extras=extras,
        provider=provider if args.backend == "pallas" else None)
    tracer = None
    if args.trace_out:
        from repro.obs import Tracer

        # A standalone engine has no virtual clock: spans are wall-clock
        # around the real work (engine.trace_compute default), and a
        # wall-clock tracer also enters them as profiler annotations.
        tracer = Tracer()
        engine.tracer = tracer
        provider.pipeline.tracer = tracer
    rng = np.random.default_rng(args.seed)
    pending = sample_prompts(rng, args.requests, cfg.vocab_size)
    done, t0, steps = [], time.monotonic(), 0
    try:
        with use_backend(args.backend):
            while pending or engine.active:
                while pending and engine.free_slots:
                    try:
                        req = engine.add_request(pending[0],
                                                 max_new_tokens=args.new_tokens)
                    except SlotsFull:
                        break
                    pending.pop(0)
                    if req.done:  # finished by the prefill itself
                        done.append(req)
                done.extend(engine.step())
                steps += 1
                if steps > 10_000:
                    raise RuntimeError("serving did not converge")
    finally:
        set_default_provider(prev_provider)
        if service is not None:
            # Also on error paths: a live worker pool with queued jobs would
            # otherwise keep the process alive after a serving failure.
            service.close()
    dt = time.monotonic() - t0
    toks = sum(len(r.generated) for r in done)
    result = {"requests": len(done), "decode_steps": steps,
              "tokens": toks, "tok_per_s": round(toks / dt, 1),
              "serve_s": dt, "backend": args.backend,
              # Pallas kernels compile only on a TPU backend; anywhere else
              # they run in interpret mode (null: the ref backend runs none).
              "pallas_interpret": (interpret_mode() if args.backend == "pallas"
                                   else None),
              "target": args.target,
              "schedule_hits": provider.hits, "schedule_misses": provider.misses,
              "resolution": provider.stats(),
              "replans": engine.replans,
              "prefill_traces": engine.prefill_trace_count}
    if engine.plan is not None:
        result["plan"] = {"entries": len(engine.plan),
                          "generation": engine.plan.generation,
                          "tiers": engine.plan.tier_counts()}
    if service is not None:
        result["tuning_service"] = service.stats()
    if tracer is not None:
        from repro.obs.export import write_chrome_trace

        write_chrome_trace(args.trace_out, tracer)
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(provider.pipeline.metrics.to_json(), f, indent=1,
                      sort_keys=True)
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
