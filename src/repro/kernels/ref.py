"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` of the contract).

These are the ground truth the kernels are validated against in interpret
mode, and the XLA execution path on non-TPU backends (this container).  The
attention oracle also has a *chunked* online-softmax variant used by the
models so the dry-run memory profile matches the flash kernel's (no S×S
materialization at 32k+).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.schedule import glu_chunk

# ---------------------------------------------------------------------------
# Matmul + fused epilogues
# ---------------------------------------------------------------------------


def _glu(y: jax.Array, act: Callable[[jax.Array], jax.Array]) -> jax.Array:
    """Chunk-interleaved GLU: columns are packed (gate chunk, up chunk, ...)
    in chunks of :func:`~repro.core.schedule.glu_chunk` columns.

    The Pallas kernel applies the epilogue per N-block, which requires the
    gate/up pair to live in the same block — hence interleaved packing (the
    framework owns the weight layout; see models/common.py pack_glu).
    """
    *lead, n = y.shape
    f = n // 2
    c = glu_chunk(f)
    y = y.reshape(*lead, f // c, 2, c)
    return (act(y[..., 0, :]) * y[..., 1, :]).reshape(*lead, f)


def apply_epilogue(y: jax.Array, class_id: str, *, bias: jax.Array | None = None,
                   residual: jax.Array | None = None, softcap: float = 0.0) -> jax.Array:
    if bias is not None:
        y = y + bias
    if class_id in ("matmul", "matmul_bias", "matmul_lmhead", "moe_router", "moe_gemm"):
        pass
    elif class_id == "matmul_bias_gelu":
        y = jax.nn.gelu(y)
    elif class_id in ("matmul_silu_glu", "moe_gemm_silu_glu"):
        y = _glu(y, jax.nn.silu)
    elif class_id == "matmul_gelu_glu":
        y = _glu(y, jax.nn.gelu)
    elif class_id == "matmul_residual":
        assert residual is not None
        y = y + residual
    elif class_id == "matmul_lmhead_softcap":
        assert softcap > 0.0
        y = jnp.tanh(y / softcap) * softcap
    else:
        raise ValueError(f"unknown matmul epilogue class {class_id!r}")
    return y


def matmul(x: jax.Array, w: jax.Array, class_id: str = "matmul", *,
           bias: jax.Array | None = None, residual: jax.Array | None = None,
           softcap: float = 0.0) -> jax.Array:
    """Oracle for the matmul kernel family. x: (..., K), w: (K, N)."""
    y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    y = apply_epilogue(y, class_id, bias=bias, residual=residual, softcap=softcap)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _mask_bias(sq: int, skv: int, q_offset: int, causal: bool, window: int,
               dtype=jnp.float32) -> jax.Array:
    """Additive mask bias (0 / -inf) for a (sq, skv) score tile.

    ``q_offset`` is the absolute position of query row 0 (kv rows are
    absolute 0..skv). Supports causal and sliding-window (local) masks.
    """
    q_pos = q_offset + jnp.arange(sq)[:, None]
    kv_pos = jnp.arange(skv)[None, :]
    ok = jnp.ones((sq, skv), dtype=bool)
    if causal:
        ok &= kv_pos <= q_pos
    if window > 0:
        ok &= kv_pos > q_pos - window
    return jnp.where(ok, 0.0, -jnp.inf).astype(dtype)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
              window: int = 0, softcap: float = 0.0, q_offset: int = 0,
              scale: float | None = None) -> jax.Array:
    """Naive full-materialization oracle.

    q: (B, Hq, Sq, D), k/v: (B, Hkv, Skv, D) with Hq % Hkv == 0 (GQA).
    """
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    qg = q.reshape(b, hkv, group, sq, d)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    s = s + _mask_bias(sq, k.shape[2], q_offset, causal, window)[None, None, None]
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return o.reshape(b, hq, sq, d).astype(q.dtype)


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: int = 0, softcap: float = 0.0,
                      q_offset: int = 0, chunk: int = 1024,
                      scale: float | None = None) -> jax.Array:
    """Online-softmax attention chunked over KV: O(Sq·chunk) live memory.

    Numerically equivalent to :func:`attention` (validated by tests); the
    execution-path analogue of the flash kernel for XLA backends, used by
    the models so 32k+ dry-runs don't materialize S×S scores.
    """
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    chunk = min(chunk, skv)
    if skv % chunk:
        pad = chunk - skv % chunk
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        skv_p = skv + pad
    else:
        skv_p = skv
    n_chunks = skv_p // chunk
    qg = (q.reshape(b, hkv, group, sq, d) * scale).astype(jnp.float32)
    kc = k.reshape(b, hkv, n_chunks, chunk, d).astype(jnp.float32)
    vc = v.reshape(b, hkv, n_chunks, chunk, d).astype(jnp.float32)

    q_pos = q_offset + jnp.arange(sq)

    def step(carry, xs):
        m, l, acc = carry
        kb, vb, idx = xs
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kb)
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        kv_pos = idx * chunk + jnp.arange(chunk)
        ok = kv_pos[None, :] < skv
        if causal:
            ok = ok & (kv_pos[None, :] <= q_pos[:, None])
        if window > 0:
            ok = ok & (kv_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(ok[None, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # guard fully-masked rows (m_new == -inf)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(ok[None, None, None], p, 0.0)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum("bhgqk,bhkd->bhgqd", p, vb)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, group, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, hkv, group, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, group, sq, d), jnp.float32)
    kc_t = jnp.moveaxis(kc, 2, 0)
    vc_t = jnp.moveaxis(vc, 2, 0)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kc_t, vc_t, jnp.arange(n_chunks)))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return o.reshape(b, hq, sq, d).astype(q.dtype)


def masked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid: jax.Array, softcap: float = 0.0) -> jax.Array:
    """Attention with an explicit validity mask, for the masks the flash
    kernel's causal/window parameters cannot say: a decode cache's (B, 1, T),
    a ring prefix's shared (C, T), a speculative verify's per-lane (B, C, T).
    q (B, Hq, C, hd); k, v (B, KV, T, hd); ``valid`` broadcasts to (B, C, T).
    Scores, softmax and sums are float32."""
    b, hq, c, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    group = hq // hkv
    qg = q.reshape(b, hkv, group, c, d).astype(jnp.float32) * d ** -0.5
    s = jnp.einsum("bhgqd,bhtd->bhgqt", qg, k.astype(jnp.float32))
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    valid = jnp.broadcast_to(valid, (b, c, t))
    s = jnp.where(valid[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqt,bhtd->bhgqd", p, v.astype(jnp.float32))
    return o.reshape(b, hq, c, d).astype(q.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, pos: jax.Array,
                     softcap: float = 0.0) -> jax.Array:
    """The decode kernel's oracle: slot b attends cache rows
    ``< min(pos[b] + 1, size)``."""
    size = k.shape[2]
    valid = jnp.arange(size)[None, :] < jnp.minimum(pos + 1, size)[:, None]
    return masked_attention(q, k, v, valid[:, None, :], softcap)


# ---------------------------------------------------------------------------
# RWKV6 time-mix scan (Finch wkv: data-dependent per-channel decay + bonus)
# ---------------------------------------------------------------------------


def rwkv6_scan(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
               u: jax.Array, state: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Oracle wkv6 recurrence.

    r/k/v/w: (B, H, T, D); u: (H, D); state: (B, H, D, D) mapping k-dim->v-dim.
      y_t   = (S_t + (u ⊙ k_t) v_tᵀ)ᵀ r_t
      S_t+1 = diag(w_t) S_t + k_t v_tᵀ          (w_t = exp(-exp(ŵ_t)) ∈ (0,1))
    """
    rf, kf, vf, wf = (x.astype(jnp.float32) for x in (r, k, v, w))
    uf = u.astype(jnp.float32)

    def step(s, xs):
        rt, kt, vt, wt = xs  # (B,H,D) each
        kv = kt[..., :, None] * vt[..., None, :]           # (B,H,D,D)
        y = jnp.einsum("bhk,bhkv->bhv", rt, s + uf[None, :, :, None] * kv)
        s_new = wt[..., :, None] * s + kv
        return s_new, y

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (rf, kf, vf, wf))
    s_final, ys = jax.lax.scan(step, state.astype(jnp.float32), xs)
    y = jnp.moveaxis(ys, 0, 2)  # (B,H,T,D)
    return y.astype(r.dtype), s_final


# ---------------------------------------------------------------------------
# RG-LRU scan (Griffin / RecurrentGemma)
# ---------------------------------------------------------------------------


def rglru_scan(x: jax.Array, a: jax.Array, state: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Oracle RG-LRU recurrence.

    x, a: (B, T, C) — pre-gated input and per-step decay a_t ∈ (0,1);
    state: (B, C).   h_t = a_t ⊙ h_{t-1} + sqrt(1 - a_t²) ⊙ x_t
    """
    xf, af = x.astype(jnp.float32), a.astype(jnp.float32)

    def step(h, xs):
        xt, at = xs
        h_new = at * h + jnp.sqrt(jnp.maximum(1.0 - at * at, 0.0)) * xt
        return h_new, h_new

    xs = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(af, 1, 0))
    h_final, hs = jax.lax.scan(step, state.astype(jnp.float32), xs)
    return jnp.moveaxis(hs, 0, 1).astype(x.dtype), h_final
