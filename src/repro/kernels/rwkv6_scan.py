"""Schedule-driven Pallas RWKV6 (Finch) wkv time-mix kernel.

The wkv recurrence is the sequential hot-spot of RWKV6: per (batch, head)
a (D×D) state is decayed per-channel (data-dependent ``w``) and updated
with rank-1 outer products.  TPU adaptation: the state lives in an f32 VMEM
scratch that persists across the sequential time-chunk grid axis; tokens
inside a chunk run in a ``fori_loop`` over VMEM-resident rows.

Schedule axes: ``T`` (time-chunk length, tiles the sequential axis — larger
chunks amortize DMA, cost VMEM) and ``C`` (channel/head blocking — here the
grid over batch·heads; the C tile gates how many heads share one program).

Grid: (B·H, T/ct) — T innermost so the state scratch survives the trip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.schedule import ConcreteSchedule
from repro.hw.specs import TPU_V5E


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref, s_ref,
            rs_ref, ks_ref, vs_ref, ws_ref, *, ct: int, d: int, t_trips: int,
            out_dtype):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _():
        s_ref[...] = s0_ref[0].astype(jnp.float32)

    # Stage the chunk in f32 scratch (Mosaic has dynamic row access to
    # 32-bit refs, not to packed ones nor to values); rs_ref row t is
    # overwritten by y_t once read.
    for src, dst in ((r_ref, rs_ref), (k_ref, ks_ref), (v_ref, vs_ref),
                     (w_ref, ws_ref)):
        dst[...] = src[0].astype(jnp.float32)       # (ct, D)
    u = u_ref[0].astype(jnp.float32)                # (1, D) bonus
    eye = (jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (d, d), 1)).astype(jnp.float32)

    def col(row):  # (1, D) -> (D, 1) without a transpose
        return jnp.sum(eye * row, axis=1, keepdims=True)

    def step(t, s):
        rt = rs_ref[pl.ds(t, 1), :]
        kt = ks_ref[pl.ds(t, 1), :]
        vt = vs_ref[pl.ds(t, 1), :]
        wt = ws_ref[pl.ds(t, 1), :]
        # y = r @ (s + diag(u) k^T v) = r @ s + (r·(u⊙k)) v
        bonus = jnp.sum(rt * u * kt, axis=1, keepdims=True)          # (1, 1)
        rs_ref[pl.ds(t, 1), :] = (jnp.sum(col(rt) * s, axis=0, keepdims=True)
                                  + bonus * vt)
        return col(wt) * s + col(kt) * vt                            # (D, D)

    s_final = jax.lax.fori_loop(0, ct, step, s_ref[...])
    s_ref[...] = s_final
    y_ref[0] = rs_ref[...].astype(out_dtype)

    @pl.when(ti == t_trips - 1)
    def _():
        sT_ref[0] = s_final


def rwkv6_scan(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
               u: jax.Array, state: jax.Array, cs: ConcreteSchedule, *,
               interpret: bool,
               vmem_limit_bytes: int = TPU_V5E.vmem_capacity
               ) -> tuple[jax.Array, jax.Array]:
    """r/k/v/w: (B, H, T, D); u: (H, D); state: (B, H, D, D) f32.

    Returns (y: (B, H, T, D), state_out: (B, H, D, D) f32).
    """
    b, h, t, d = r.shape
    ct = min(cs.t["T"], t)
    grid = (b * h, pl.cdiv(t, ct))

    def flat(x):
        return x.reshape(b * h, t, d)

    rf, kf, vf, wf = flat(r), flat(k), flat(v), flat(w)
    sf = state.reshape(b * h, d, d)

    # u rides as (H, 1, D) so its block's last two dims are (1, D): full
    # extent whatever H is.
    in_specs = [
        pl.BlockSpec((1, ct, d), lambda bh, ti: (bh, ti, 0)),
        pl.BlockSpec((1, ct, d), lambda bh, ti: (bh, ti, 0)),
        pl.BlockSpec((1, ct, d), lambda bh, ti: (bh, ti, 0)),
        pl.BlockSpec((1, ct, d), lambda bh, ti: (bh, ti, 0)),
        pl.BlockSpec((1, 1, d), lambda bh, ti: (bh % h, 0, 0)),   # u per head
        pl.BlockSpec((1, d, d), lambda bh, ti: (bh, 0, 0)),       # initial state
    ]
    out_specs = [
        pl.BlockSpec((1, ct, d), lambda bh, ti: (bh, ti, 0)),
        pl.BlockSpec((1, d, d), lambda bh, ti: (bh, 0, 0)),
    ]
    y, s_out = pl.pallas_call(
        functools.partial(_kernel, ct=ct, d=d, t_trips=grid[1],
                          out_dtype=r.dtype),
        name="rwkv6_scan",     # the op's name in the HLO and the device trace
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t, d), r.dtype),
            jax.ShapeDtypeStruct((b * h, d, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)]
        + [pltpu.VMEM((ct, d), jnp.float32) for _ in range(4)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )(rf, kf, vf, wf, u.reshape(h, 1, d), sf)
    return y.reshape(b, h, t, d), s_out.reshape(b, h, d, d)
