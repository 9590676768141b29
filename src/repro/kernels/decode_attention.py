"""Pallas decode attention: one query token per slot over a layer's KV cache,
read in place from the stacked cache.

The decode step's layer scan carries each attention group's cache as one
stack ``(L, B, KV, T, hd)`` and hands this kernel the stack with the layer
index as a scalar-prefetch operand, as the matmul kernel takes its weights:
the DMA reads the layer's blocks straight from the stack, with no sliced copy
of the layer's cache in between.  A plain per-layer cache ``(B, KV, T, hd)``
is a stack of one at layer 0.

Semantics are those of :func:`repro.kernels.ref.masked_attention` with
the mask ``slot < lim[b]``, where the caller passes ``lim = min(pos + 1, T)``:
the causal mask of a full cache, and the written slots of a ring cache no
longer than its window.  q and the cache share a dtype.  Scores, the softmax,
its sums and the output are float32.  Over a bf16 cache, q·k multiplies bf16
operands exactly into float32 sums, and the probabilities enter p·v as a pair
of bf16 terms (high and low parts, 16 significant bits) whose products are
exact, rather than one bf16 rounding.

Grid: (slot, KV block), the KV blocks innermost with an online softmax in
VMEM scratch across them.  A block holds every KV head of its slot and
``block_kv`` rows (:func:`kv_block`: the whole cache, or a power of two of at
least 512 rows), so a layer is ``B · T / block_kv`` grid steps.  The query
block is full-extent, so any number of query rows per KV head is legal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.hw.specs import TPU_V5E
from repro.kernels.matmul import _layer_operand

NEG_INF = -1e30
# K and V bytes a grid step brings into VMEM (double-buffered: twice this).
BLOCK_BYTES = 4 * 1024**2
_NT = (((1,), (1,)), ((), ()))   # contract the last dims: q @ k.T


def kv_block(t: int, row_bytes: int) -> int:
    """Rows of one KV block for a cache of ``t`` rows whose K and V take
    ``row_bytes`` bytes a row (all heads): the whole cache where it fits
    :data:`BLOCK_BYTES`, else the largest power of two, of at least 512,
    that fits and divides ``t`` (the whole cache if none does)."""
    if t * row_bytes <= BLOCK_BYTES:
        return t
    b = 512
    while 2 * b * row_bytes <= BLOCK_BYTES:
        b *= 2
    while b >= 512 and t % b:
        b //= 2
    return b if b >= 512 else t


def _pv(p: jax.Array, v: jax.Array) -> jax.Array:
    """p (G, bkv) float32 @ v (bkv, hd), float32 sums.  Below float32, p is
    split into high and low parts of v's dtype, two exact passes."""
    if v.dtype == jnp.float32:
        return jnp.dot(p, v, preferred_element_type=jnp.float32)
    hi = p.astype(v.dtype)
    lo = (p - hi.astype(jnp.float32)).astype(v.dtype)
    return (jnp.dot(hi, v, preferred_element_type=jnp.float32)
            + jnp.dot(lo, v, preferred_element_type=jnp.float32))


def _kernel(layer_ref, lim_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
            acc_ref, *, block_kv: int, kv_trips: int, softcap: float,
            scale: float):
    """One (slot, KV block) step over every KV head of the slot.

    ``layer_ref`` is read only by the cache's index map."""
    del layer_ref
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = q_ref.shape[1]
    slot = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, (g, block_kv), 1)
    ok = slot < lim_ref[b]
    for h in range(q_ref.shape[0]):
        s = jax.lax.dot_general(q_ref[h], k_ref[h], _NT,
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        l_ref[h] = alpha * l_ref[h] + p.sum(axis=1, keepdims=True)
        m_ref[h] = m_new
        acc_ref[h] = alpha * acc_ref[h] + _pv(p, v_ref[h])

    @pl.when(j == kv_trips - 1)
    def _():
        o_ref[...] = acc_ref[...] / l_ref[...]


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, lim: jax.Array,
                     *, layer: jax.Array | int = 0, softcap: float = 0.0,
                     block_kv: int | None = None, interpret: bool,
                     vmem_limit_bytes: int = TPU_V5E.vmem_capacity
                     ) -> jax.Array:
    """q (B, KV, G, hd): G query rows per KV head.  k, v: stacks
    (L, B, KV, T, hd) of q's dtype, read in place at ``layer`` (an int or a
    traced scalar), or one layer's (B, KV, T, hd).  lim (B,) int32: slot b
    attends cache rows ``< lim[b]`` (at least 1).  Returns (B, KV, G, hd)
    float32.  ``block_kv`` defaults to :func:`kv_block` and must divide T."""
    ks = k[None] if k.ndim == 4 else k   # a free bitcast
    vs = v[None] if v.ndim == 4 else v
    b, hkv, g, d = q.shape
    t = ks.shape[3]
    bkv = block_kv or kv_block(t, 2 * hkv * d * ks.dtype.itemsize)
    if t % bkv:
        raise ValueError(f"KV block {bkv} does not divide the cache's {t} rows")
    kv_trips = t // bkv

    def q_map(bb, j, layer_ref, lim_ref):
        return bb, 0, 0, 0

    def kv_map(bb, j, layer_ref, lim_ref):
        return layer_ref[0], bb, 0, j, 0

    kernel = functools.partial(_kernel, block_kv=bkv, kv_trips=kv_trips,
                               softcap=softcap, scale=d ** -0.5)
    call = pl.pallas_call(
        kernel,
        name="decode_attention",   # the op's name in the HLO and the device trace
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, kv_trips),
            in_specs=[pl.BlockSpec((None, hkv, g, d), q_map),
                      pl.BlockSpec((None, None, hkv, bkv, d), kv_map),
                      pl.BlockSpec((None, None, hkv, bkv, d), kv_map)],
            out_specs=pl.BlockSpec((None, hkv, g, d), q_map),
            scratch_shapes=[pltpu.VMEM((hkv, g, 1), jnp.float32),
                            pltpu.VMEM((hkv, g, 1), jnp.float32),
                            pltpu.VMEM((hkv, g, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )
    return call(_layer_operand(layer), lim.astype(jnp.int32), q, ks, vs)
