"""Schedule-driven Pallas flash-attention kernel.

Online-softmax attention with BlockSpec tiling over the query (``Q`` tile)
and key/value (``KV`` tile) axes — the two loop axes the auto-scheduler
tunes for the ``flash_attention_*`` kernel classes.  Supports:

* causal and bidirectional masking,
* sliding/local windows (mixtral SWA, gemma2 local, griffin local),
* attention logit softcapping (gemma2),
* GQA: the kv-head index map divides the query-head program id,
* decode (Sq=1 with a long KV context) — same kernel, bq clamps to Sq.

Grid: (batch·q_heads, Q/bq, KV/bkv) with KV innermost so the f32 softmax
state (m, l, acc scratch) persists across the KV trip.  It is the only
order the kernel realizes, so the legality rule (:mod:`repro.core.legality`)
refuses attention schedules whose ``order`` puts KV outside Q.

Validated against ref.attention / ref.chunked_attention in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.schedule import ConcreteSchedule
from repro.hw.specs import TPU_V5E

NEG_INF = -1e30


def _kernel(off_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            kv_trips: int, bq: int, bkv: int, sq: int, skv: int,
            causal: bool, window: int, softcap: float, scale: float,
            out_dtype):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    q_offset = off_ref[0]

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = q_offset + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    kv_pos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    ok = kv_pos < skv  # padding guard
    if causal:
        ok &= kv_pos <= q_pos
    if window > 0:
        ok &= kv_pos > q_pos - window

    # Skip fully-masked tiles (beyond the causal frontier / outside window).
    def tile_live() -> jax.Array:
        live = jnp.array(True)
        if causal:
            live &= (ki * bkv) <= (q_offset + qi * bq + bq - 1)
        if window > 0:
            live &= (ki * bkv + bkv) > (q_offset + qi * bq - window)
        return live

    @pl.when(tile_live())
    def _():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(ok, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_new
        v = v_ref[0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)

    @pl.when(ki == kv_trips - 1)
    def _():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(out_dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    cs: ConcreteSchedule, *,
                    class_id: str = "flash_attention_causal", causal: bool = True,
                    window: int = 0, softcap: float = 0.0,
                    q_offset: int | jax.Array = 0, scale: float | None = None,
                    interpret: bool,
                    vmem_limit_bytes: int = TPU_V5E.vmem_capacity
                    ) -> jax.Array:
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D). Returns (B, Hq, Sq, D).

    ``q_offset`` (absolute position of query row 0) may be traced: it reaches
    the kernel as a scalar-prefetch (SMEM) operand, so one compiled kernel
    serves every chunk offset."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    bq = min(cs.t["Q"], sq)
    bkv = min(cs.t["KV"], skv)

    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, d)

    grid = (b * hq, pl.cdiv(sq, bq), pl.cdiv(skv, bkv))

    def kv_head(bh):
        # program id over b*hq -> row index into (b*hkv) k/v arrays
        return (bh // hq) * hkv + (bh % hq) // group

    # Index maps take the scalar-prefetch ref as a trailing argument.
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda bh, qi, ki, off: (bh, qi, 0)),
        pl.BlockSpec((1, bkv, d), lambda bh, qi, ki, off: (kv_head(bh), ki, 0)),
        pl.BlockSpec((1, bkv, d), lambda bh, qi, ki, off: (kv_head(bh), ki, 0)),
    ]
    out_specs = pl.BlockSpec((1, bq, d), lambda bh, qi, ki, off: (bh, qi, 0))

    kernel = functools.partial(
        _kernel,
        kv_trips=grid[2], bq=bq, bkv=bkv, sq=sq, skv=skv,
        causal=causal, window=window, softcap=softcap, scale=scale,
        out_dtype=q.dtype,
    )
    out = pl.pallas_call(
        kernel,
        name=class_id,     # the op's name in the HLO and the device trace
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((bq, d), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )(jnp.reshape(jnp.asarray(q_offset, jnp.int32), (1,)), qf, kf, vf)
    return out.reshape(b, hq, sq, d)
