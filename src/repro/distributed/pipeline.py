"""Pipeline parallelism over the ``pod`` axis (GPipe via shard_map).

For deployments where cross-pod bandwidth makes pure DP over pods
unattractive, layer groups can instead be placed per pod and microbatches
streamed through with ``jax.lax.ppermute`` boundary transfers.

``pipeline_apply`` is self-contained: it takes a per-stage ``stage_fn`` and
stage-stacked params, splits the batch into microbatches, and runs the
classic GPipe schedule (n_micro + n_stages - 1 ticks).  Each device holds
one stage; at every tick it applies its stage to its current microbatch and
ppermutes activations to the next stage.  Bubble fraction =
(S-1)/(M+S-1), reported by :func:`bubble_fraction` so launch configs can
size microbatch counts.

Tested under a subprocess with 8 host devices (tests/test_distributed.py);
selectable in the launcher via ``--pipeline``.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_shard_map = functools.partial(jax.shard_map, check_vma=False)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,          # pytree with leading dim = n_stages
    x: jax.Array,               # (batch, ...) global batch
    *,
    mesh: Mesh,
    axis: str = "pod",
    n_microbatches: int | None = None,
) -> jax.Array:
    """Run x through n_stages sequential stages, one stage per `axis` shard."""
    n_stages = mesh.shape[axis]
    n_micro = n_microbatches or n_stages
    b = x.shape[0]
    assert b % n_micro == 0, (b, n_micro)
    mb = b // n_micro

    micro = x.reshape(n_micro, mb, *x.shape[1:])
    ticks = n_micro + n_stages - 1

    def per_stage(params_stage, micro_all):
        """Runs on ONE device (stage s). micro_all: all microbatches (only
        stage 0 consumes them; others receive via ppermute)."""
        s = jax.lax.axis_index(axis)
        buf = jnp.zeros_like(micro_all[0])  # current activation
        outs = jnp.zeros_like(micro_all)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 injects microbatch t (if in range)
            inject = micro_all[jnp.clip(t, 0, n_micro - 1)]
            buf = jnp.where((s == 0) & (t < n_micro), inject, buf)
            # active if this stage holds microbatch (t - s) in [0, n_micro)
            active = (t >= s) & (t - s < n_micro)
            y = stage_fn(params_stage, buf)
            buf_out = jnp.where(active, y, buf)
            # last stage records its finished microbatch
            done_idx = jnp.clip(t - s, 0, n_micro - 1)
            record = (s == n_stages - 1) & active
            outs = jnp.where(
                record,
                outs.at[done_idx].set(buf_out),
                outs,
            )
            # forward activations to next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf_next = jax.lax.ppermute(buf_out, axis, perm)
            return buf_next, outs

        buf, outs = jax.lax.fori_loop(0, ticks, tick, (buf, outs))
        # only the last stage recorded real outputs; make the replicated
        # out_spec well-defined by summing across stages (others hold zeros)
        return jax.lax.psum(outs, axis)

    in_specs = (P(axis), P())          # params: stage-sharded; micro: replicated
    out_specs = P()                    # outputs gathered (replicated) per stage
    fn = _shard_map(
        lambda p, m: per_stage(jax.tree_util.tree_map(lambda l: l[0], p), m),
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
    )
    outs = fn(stage_params, micro)
    return outs.reshape(b, *x.shape[1:])
