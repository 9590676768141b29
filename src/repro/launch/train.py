"""Training driver: config → mesh → sharded train loop with fault tolerance.

Production loop structure:
  * deterministic data pipeline (step number is the data cursor — restarts
    resume the exact stream),
  * jit'd train step with param/optimizer donation,
  * async checkpointing every ``--ckpt-every`` steps (atomic commit),
  * straggler monitor + preemption handler (SIGTERM → checkpoint → exit),
  * optional int8 gradient compression and gradient accumulation,
  * transfer-tuned schedule DB applied to the kernel ops (``--tuning-db``).

Runs identically on this CPU container with ``--preset smoke`` (reduced
config, 1-device mesh) and, via the dry-run, on the production meshes.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import time

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs.base import get_arch, reduced
from repro.core.database import ScheduleDB
from repro.data import DataConfig, Pipeline
from repro.distributed import StragglerMonitor, PreemptionHandler
from repro.distributed import sharding as shd
from repro.distributed.context import activation_sharding, set_remat_policy
from repro.kernels.ops import ScheduleProvider
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_test_mesh
from repro.models.build import build_model
from repro.optim.adamw import AdamWConfig


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description="train an assigned architecture")
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--preset", choices=["smoke", "full"], default="smoke")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--tuning-db", default="", help="transfer-tuned ScheduleDB json")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the config to this many layers (0: its own depth)")
    ap.add_argument("--devices", type=int, default=0,
                    help="train on the first N devices (0: all); a mesh "
                         "spans them when N > 1")
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--strategy", choices=["auto", "dp", "fsdp_tp"], default="auto",
                    help="auto: pure-DP/ZeRO-3 for small models (EXPERIMENTS §Perf it-7)")
    ap.add_argument("--remat-policy", choices=["full", "dots"], default="full")
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.preset == "smoke":
        cfg = reduced(cfg)
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    model = build_model(cfg)

    provider = None
    if args.tuning_db:
        db = ScheduleDB.load(args.tuning_db)
        provider = ScheduleProvider({r.instance.workload_key(): r.schedule
                                     for r in db.records()})

    n_devices = args.devices or len(jax.devices())
    mesh = (make_test_mesh(n_devices, model=args.mesh_model)
            if n_devices > 1 else None)

    opt_cfg = AdamWConfig(peak_lr=args.lr, warmup_steps=max(args.steps // 10, 2),
                          total_steps=args.steps)
    step_fn = steps_mod.make_train_step(model, opt_cfg, grad_accum=args.grad_accum,
                                        compress_grads=args.compress_grads)
    init_opt = functools.partial(steps_mod.init_opt_state,
                                 compress_grads=args.compress_grads)
    key = jax.random.PRNGKey(0)

    if mesh is not None:
        if args.strategy == "dp":
            dp_only = True
        elif args.strategy == "fsdp_tp":
            dp_only = False
        else:
            dp_only = shd.dp_dominant(cfg, mesh, kind="train", global_batch=args.batch)
        p_shard = shd.param_shardings(jax.eval_shape(model.init, key), cfg, mesh,
                                      dp_only)
        o_shard = {**shd.opt_state_shardings(p_shard, mesh)}
        if args.compress_grads:
            o_shard["residuals"] = p_shard
        # Params and optimizer state are born sharded: at published widths
        # the f32 state alone outgrows one chip.
        params = jax.jit(model.init, out_shardings=p_shard)(key)
        opt_state = jax.jit(init_opt, out_shardings=o_shard)(params)
        jitted = jax.jit(step_fn, in_shardings=(p_shard, o_shard, None),
                         out_shardings=(p_shard, o_shard, None), donate_argnums=(0, 1))
        act = shd.activation_sharding(mesh, cfg, dp_only)
    else:
        params = jax.jit(model.init)(key)
        opt_state = jax.jit(init_opt)(params)
        jitted = jax.jit(step_fn, donate_argnums=(0, 1))
        act = None

    start_step = 0
    manager = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    if manager and args.resume and manager.latest_step() is not None:
        bundle = {"params": params, "opt": opt_state}
        start_step, restored = manager.restore(bundle)
        params, opt_state = restored["params"], restored["opt"]
        print(f"resumed from step {start_step}")

    data = Pipeline(DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                               global_batch=args.batch), start_step=start_step)
    monitor = StragglerMonitor()
    preempt = PreemptionHandler(install_signal=False)

    losses = []
    set_remat_policy(args.remat_policy)
    ctx = activation_sharding(act) if act is not None else _null_ctx()
    with ctx:
        for step, np_batch in data:
            if step >= args.steps or preempt.requested:
                break
            t0 = time.monotonic()
            batch = {"tokens": jax.numpy.asarray(np_batch["tokens"])}
            params, opt_state, metrics = jitted(params, opt_state, batch)
            loss = float(metrics["loss"])
            dt = time.monotonic() - t0
            if monitor.record(step, dt):
                print(f"[straggler] step {step} took {dt:.2f}s (ewma {monitor.ewma:.2f}s)")
            losses.append(loss)
            if args.log_every and step % args.log_every == 0:
                print(f"step {step:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} {dt*1e3:.0f}ms", flush=True)
            if manager and args.ckpt_every and step and step % args.ckpt_every == 0:
                manager.save(step, {"params": params, "opt": opt_state}, blocking=False)
    data.close()
    if manager:
        manager.save(len(losses) + start_step, {"params": params, "opt": opt_state})
        manager.wait()
    result = {"first_loss": losses[0] if losses else None,
              "last_loss": losses[-1] if losses else None,
              "losses": losses, "devices": n_devices,
              "steps": len(losses), "stragglers": len(monitor.flagged)}
    print(json.dumps(result))
    return result


class _null_ctx:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
