"""Schedule-driven Pallas RG-LRU kernel (Griffin / RecurrentGemma).

Diagonal linear recurrence  h_t = a_t ⊙ h_{t-1} + sqrt(1 - a_t²) ⊙ x_t —
memory-bound and embarrassingly parallel over channels, sequential over
time.  Schedule axes: ``T`` time-chunk and ``C`` channel block: the channel
grid axis is parallel; the f32 state scratch (one row per channel block)
persists across the sequential T trip, and a ``fori_loop`` walks the chunk's
rows.

Grid: (B, C/bc, T/ct) — T innermost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.schedule import ConcreteSchedule
from repro.hw.specs import TPU_V5E


def _kernel(x_ref, a_ref, h0_ref, y_ref, hT_ref, h_ref, xs_ref, as_ref, *,
            ct: int, t_trips: int, out_dtype):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    # Stage the chunk in f32 scratch: Mosaic lowers neither a scan over
    # value slices nor single-row access to packed (bf16) refs, but it does
    # dynamic row access to 32-bit refs.  Row t of xs_ref is overwritten by
    # h_t once read, so it ends holding the chunk's outputs.
    xs_ref[...] = x_ref[0].astype(jnp.float32)   # (ct, bc)
    as_ref[...] = a_ref[0].astype(jnp.float32)

    def step(t, h):
        xt = xs_ref[pl.ds(t, 1), :]
        at = as_ref[pl.ds(t, 1), :]
        h = at * h + jnp.sqrt(jnp.maximum(1.0 - at * at, 0.0)) * xt
        xs_ref[pl.ds(t, 1), :] = h
        return h

    h_final = jax.lax.fori_loop(0, ct, step, h_ref[...])
    h_ref[...] = h_final
    y_ref[0] = xs_ref[...].astype(out_dtype)

    @pl.when(ti == t_trips - 1)
    def _():
        hT_ref[0] = h_final


def rglru_scan(x: jax.Array, a: jax.Array, state: jax.Array,
               cs: ConcreteSchedule, *, interpret: bool,
               vmem_limit_bytes: int = TPU_V5E.vmem_capacity
               ) -> tuple[jax.Array, jax.Array]:
    """x, a: (B, T, C); state: (B, C) f32. Returns (y, state_out)."""
    b, t, c = x.shape
    ct = min(cs.t["T"], t)
    bc = min(cs.t["C"], c)
    grid = (b, pl.cdiv(c, bc), pl.cdiv(t, ct))

    # The state rides as (B, 1, C) so its block's last two dims are
    # (1, bc): full extent and lane-tiled, whatever B is.
    in_specs = [
        pl.BlockSpec((1, ct, bc), lambda bi, ci, ti: (bi, ti, ci)),
        pl.BlockSpec((1, ct, bc), lambda bi, ci, ti: (bi, ti, ci)),
        pl.BlockSpec((1, 1, bc), lambda bi, ci, ti: (bi, 0, ci)),
    ]
    out_specs = [
        pl.BlockSpec((1, ct, bc), lambda bi, ci, ti: (bi, ti, ci)),
        pl.BlockSpec((1, 1, bc), lambda bi, ci, ti: (bi, 0, ci)),
    ]
    y, h_out = pl.pallas_call(
        functools.partial(_kernel, ct=ct, t_trips=grid[2], out_dtype=x.dtype),
        name="rglru_scan",     # the op's name in the HLO and the device trace
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct((b, t, c), x.dtype),
            jax.ShapeDtypeStruct((b, 1, c), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bc), jnp.float32),
                        pltpu.VMEM((ct, bc), jnp.float32),
                        pltpu.VMEM((ct, bc), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )(x, a, state.reshape(b, 1, c))
    return y, h_out.reshape(b, c)
