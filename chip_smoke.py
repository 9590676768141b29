#!/usr/bin/env python3
"""Smoke run of the transfer-tuned serving path on the chip.

    python chip_smoke.py             # one chip: serve gemma2-2b at full width
    python chip_smoke.py --chips 4   # four chips: the sharded train step only

One chip, in one process, through the normal entry points:

1. **device** — JAX's first device must be a TPU whose ``device_kind`` maps
   to a registered target (:func:`repro.targets.target_for_device`).
2. **tune** — a fresh schedule registry under ``.chip_smoke/``;
   the donor (recurrentgemma-2b, the other Gemma-family config) is tuned on
   its own serving shapes with the cost model, from a seed.
3. **plan** — gemma2-2b's serving plan is resolved through a
   ``TuningService``, and the transfer-tuning jobs its misses queue are
   drained before serving.  Each job measures the donor's schedules on one
   of gemma2-2b's own workloads and publishes the best under that
   workload's key, so the served plan shows them as exact-tier entries
   (``exact_from_donor`` counts those that are donor schedules) and its
   transfer tier is empty.
4. **serve** — ``repro.launch.serve.main`` serves ``REQUESTS`` requests with
   ``--backend pallas`` at the published width and depth; the kernels must
   have compiled (not interpreted) and most plan entries must be tuned.
5. **logits** — the prefill logits of the pallas and ref backends on
   ``PROMPTS`` prompts agree within ``LOGIT_RTOL``; greedy-token agreement
   is reported.

Four chips: gemma2-2b at full width trains ``TRAIN_STEPS`` steps on a 2x2
mesh with finite losses, and a 2-layer cut's losses over ``TRAIN_STEPS``
steps on the mesh match one device within ``LOSS_RTOL``.  The first is a
forward pass; the later ones follow one and two optimizer updates, so they
also check the gradient reduction across chips and the sharded update.

Each phase prints one JSON line; the last line is
``{"ok": true, "device": {...}}``.  Any failed phase exits non-zero with no
such line, and so does a run that finds no TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import shutil
import sys
import time

# libtpu logs under /tmp unless told otherwise; this run writes only inside
# its checkout.
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, ".chip_smoke")

ARCH, DONOR = "gemma2-2b", "recurrentgemma-2b"
SEED = 0
SLOTS, MAX_LEN, REQUESTS, NEW_TOKENS = 8, 256, 8, 16
DONOR_TRIALS_PER_KERNEL = 16      # cost-model trials per donor kernel
PROMPTS, PROMPT_LEN = 8, 8
#: Pallas vs ref prefill logits: max |difference| over max |ref logit|.
#: Both backends accumulate in f32 and round every op's output to bf16; the
#: kernels tile the reductions differently, so through 26 layers the logits
#: differ by bf16 rounding noise, which this bounds at 2% of the logit range.
LOGIT_RTOL = 2e-2
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 3, 8, 128
#: 2-layer losses, 2x2 mesh vs one device, at every step: the same bf16
#: model with its reductions (forward, gradient, update) split across chips.
LOSS_RTOL = 1e-3


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def emit(phase: str, payload: dict) -> None:
    print(json.dumps({"phase": phase, **payload}), flush=True)


def tpu_devices(chips: int) -> dict:
    """The device block of the last line; exits unless JAX sees TPUs."""
    import jax

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        fail(f"no TPU found: JAX's first device is {d.platform!r} "
             f"({d.device_kind})")
    if len(devices) < chips:
        fail(f"{chips} chips asked for, JAX sees {len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def _cfg(arch: str, preset: str):
    from repro.configs.base import get_arch, reduced

    cfg = get_arch(arch)
    return reduced(cfg) if preset == "smoke" else cfg


def tune_and_plan(preset: str, registry_dir: str, target: str):
    """Tune the donor into a fresh registry, then resolve and drain the
    served model's plan.  Returns (service, summary)."""
    from repro.core.autoscheduler import tune_model
    from repro.core.resolution import ResolutionPipeline, plan_serving
    from repro.service import ScheduleRegistry, TuningService
    from repro.serving.engine import prefill_bucket_lengths

    shutil.rmtree(registry_dir, ignore_errors=True)
    # gemma2-2b's local window exceeds MAX_LEN, so its engine pads prompts
    # to these buckets
    shapes = dict(slots=SLOTS, max_len=MAX_LEN,
                  prefill_lengths=prefill_bucket_lengths(MAX_LEN))
    donor_uses = plan_serving(_cfg(DONOR, preset),
                              ResolutionPipeline.build(target=target),
                              **shapes).uses
    t0 = time.monotonic()
    tuned = tune_model(donor_uses, DONOR, seed=SEED, target=target,
                       total_trials=DONOR_TRIALS_PER_KERNEL * len(donor_uses))
    registry = ScheduleRegistry(registry_dir)
    registry.publish(tuned.records)
    tune_s = time.monotonic() - t0

    service = TuningService(registry, model_id=f"serve/{ARCH}", seed=SEED,
                            max_workers=0, target=target)
    pipeline = ResolutionPipeline.build(service=service)
    plan = plan_serving(_cfg(ARCH, preset), pipeline, **shapes)
    before = plan.tier_counts()
    t0 = time.monotonic()
    drained = service.drain()
    plan = plan.refresh(pipeline)
    donor_schedules = {r.schedule for r in tuned.records}
    resolved = {u.instance.workload_key(): r for u, r in plan.items()}.values()
    return service, {
        "donor": DONOR, "donor_kernels": len(donor_uses),
        "donor_trials": tuned.total_trials, "donor_records": len(tuned.records),
        "tune_wall_s": tune_s, "plan_entries": len(plan),
        "tiers_before_drain": before, "jobs_drained": drained,
        "drain_wall_s": time.monotonic() - t0, "tiers": plan.tier_counts(),
        "exact_from_donor": sum(r.tier == "exact" and r.schedule in donor_schedules
                                for r in resolved),
        "rejected_illegal": pipeline.stats()["rejected_illegal"]}


def serve(preset: str, registry_dir: str, target: str) -> dict:
    """Serve REQUESTS requests through ``repro.launch.serve.main``."""
    from repro.launch import serve as serve_mod

    t0 = time.monotonic()
    result = serve_mod.main([
        "--arch", ARCH, "--preset", preset, "--backend", "pallas",
        "--slots", str(SLOTS), "--max-len", str(MAX_LEN),
        "--requests", str(REQUESTS), "--new-tokens", str(NEW_TOKENS),
        "--tuning-registry", registry_dir, "--tuning-workers", "0",
        "--target", target, "--seed", str(SEED)])
    wall = time.monotonic() - t0
    tiers = result["plan"]["tiers"]
    tuned = tiers["exact"] + tiers["transfer"]
    if result["requests"] != REQUESTS:
        fail(f"served {result['requests']} of {REQUESTS} requests")
    if 2 * tuned <= result["plan"]["entries"]:
        fail(f"only {tuned} of {result['plan']['entries']} plan entries came "
             f"from the exact or transfer tier: {tiers}")
    return {"wall_s": wall, "serve_s": result["serve_s"],
            "requests": result["requests"], "tokens": result["tokens"],
            "pallas_interpret": result["pallas_interpret"],
            "plan_entries": result["plan"]["entries"], "plan_tiers": tiers,
            "tuned_share": tuned / result["plan"]["entries"],
            "prefill_traces": result["prefill_traces"],
            "replans": result["replans"]}


def compare_logits(preset: str, service) -> dict:
    """Prefill logits of the pallas backend (schedules from ``service``)
    against the ref backend, on PROMPTS seeded prompts."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import ScheduleProvider, use_backend
    from repro.models.build import build_model

    cfg = _cfg(ARCH, preset)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    provider = ScheduleProvider(service=service)
    prompts = np.random.default_rng(SEED).integers(
        1, cfg.vocab_size, size=(PROMPTS, PROMPT_LEN))

    def logits(backend: str) -> np.ndarray:
        fn = jax.jit(lambda p, toks: model.prefill(
            p, {"tokens": toks}, max_len=MAX_LEN, provider=provider)[0])
        with use_backend(backend):
            return np.stack([np.asarray(fn(params, jnp.asarray(t[None]))[0],
                                        np.float32) for t in prompts])

    pallas, ref = logits("pallas"), logits("ref")
    if not (np.isfinite(pallas).all() and np.isfinite(ref).all()):
        fail("non-finite prefill logits")
    err = float(np.abs(pallas - ref).max())
    scale = float(np.abs(ref).max())
    out = {"prompts": PROMPTS, "prompt_len": PROMPT_LEN, "max_abs_err": err,
           "mean_abs_err": float(np.abs(pallas - ref).mean()),
           "max_abs_ref": scale, "rel_err": err / scale, "rtol": LOGIT_RTOL,
           "greedy_agree": int((pallas.argmax(-1) == ref.argmax(-1)).sum())}
    if not err <= LOGIT_RTOL * scale:
        fail(f"pallas vs ref prefill logits differ beyond tolerance: {out}")
    return out


def one_chip(preset: str, target: str, *, require_compiled: bool = True) -> None:
    registry_dir = os.path.join(OUT, "registry")
    service, plan = tune_and_plan(preset, registry_dir, target)
    emit("plan", plan)
    served = serve(preset, registry_dir, target)
    emit("serve", served)
    if require_compiled and served["pallas_interpret"] is not False:
        fail("Pallas kernels ran in interpret mode")
    gc.collect()   # the server's weights, before a second copy is made
    emit("logits", compare_logits(preset, service))


def four_chips(preset: str) -> None:
    from repro.launch import train

    base = ["--arch", ARCH, "--preset", preset, "--batch", str(TRAIN_BATCH),
            "--seq", str(TRAIN_SEQ), "--mesh-model", "2", "--log-every", "1"]
    t0 = time.monotonic()
    full = train.main(base + ["--steps", str(TRAIN_STEPS)])
    full_s = time.monotonic() - t0
    if len(full["losses"]) != TRAIN_STEPS or not all(
            math.isfinite(x) for x in full["losses"]):
        fail(f"full-depth sharded training gave losses {full['losses']}")
    cut = base + ["--steps", str(TRAIN_STEPS), "--layers", "2"]
    mesh = train.main(cut)["losses"]
    one = train.main(cut + ["--devices", "1"])["losses"]
    rel = [abs(a - b) / abs(b) for a, b in zip(mesh, one)]
    out = {"full_losses": full["losses"], "full_devices": full["devices"],
           "full_wall_s": full_s, "layers2_mesh_losses": mesh,
           "layers2_one_device_losses": one, "rel_diffs": rel,
           "rel_diff": max(rel), "rtol": LOSS_RTOL}
    if len(mesh) != TRAIN_STEPS or len(one) != TRAIN_STEPS or not all(
            r <= LOSS_RTOL for r in rel):
        fail(f"2-layer losses on the mesh differ from one device: {out}")
    emit("train", out)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1)
    args = ap.parse_args(argv)

    device = tpu_devices(args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    from repro.targets import target_for_device

    target = target_for_device(device["kind"]).name
    emit("device", {**device, "target": target,
                    "compile_cache": enable_compile_cache()})
    t0 = time.monotonic()
    if args.chips == 4:
        four_chips("full")
    else:
        one_chip("full", target)
    emit("done", {"wall_s": time.monotonic() - t0})
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
