"""Attention blocks: GQA/MQA projections, RoPE, global + local/SWA variants,
logit softcapping, and KV caches (full for global layers, ring buffer sized
to the window for local/SWA layers — what makes long_500k decode feasible).

Three entry points per layer kind:
  * ``attn_forward``   — full-sequence (train / prefill), returns new cache
  * ``attn_decode``    — single-token step against the cache
  * ``init_attn_cache``

All heavy math routes through :mod:`repro.kernels.ops` so tuned schedules
(transfer-tuned or native) apply.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.context import sharded
from repro.kernels import ops, ref
from repro.models.common import apply_norm, apply_rope, dense_init, dtype_of, norm_params


def attn_params(key: jax.Array, cfg: ArchConfig) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    dt = dtype_of(cfg.dtype)
    return {
        "wq": dense_init(kq, d, cfg.n_heads * hd, dt),
        "wk": dense_init(kk, d, cfg.n_kv_heads * hd, dt),
        "wv": dense_init(kv, d, cfg.n_kv_heads * hd, dt),
        "wo": dense_init(ko, cfg.n_heads * hd, d, dt),
    }


def _attn_class(cfg: ArchConfig, kind: str, cross: bool = False) -> str:
    if cross:
        return "flash_attention_cross"
    if kind == "L":
        return "flash_attention_swa" if len(set(cfg.layer_kinds)) == 1 else "flash_attention_local"
    if cfg.attn_softcap > 0:
        return "flash_attention_softcap"
    return "flash_attention_causal"


def _qkv(p: dict, cfg: ArchConfig, x: jax.Array, provider) -> tuple[jax.Array, jax.Array, jax.Array]:
    b, s, _ = x.shape
    hd = cfg.head_dim
    q = ops.matmul(x, p["wq"], provider=provider).reshape(b, s, cfg.n_heads, hd)
    k = ops.matmul(x, p["wk"], provider=provider).reshape(b, s, cfg.n_kv_heads, hd)
    v = ops.matmul(x, p["wv"], provider=provider).reshape(b, s, cfg.n_kv_heads, hd)
    return q, k, v


def _rope_qk(cfg: ArchConfig, q, k, positions, kind: str):
    """Rotary embedding of q and k; ``cfg.yarn`` scales it on G layers."""
    if cfg.pos != "rope":
        return q, k
    yarn = cfg.yarn if kind == "G" else None
    return (apply_rope(q, positions, cfg.rope_theta, yarn),
            apply_rope(k, positions, cfg.rope_theta, yarn))


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------


def init_attn_cache(cfg: ArchConfig, kind: str, batch: int, max_len: int) -> dict:
    """Full cache for global layers; window-sized ring for local/SWA."""
    size = max_len if (kind == "G" or cfg.window == 0) else min(cfg.window, max_len)
    dt = dtype_of(cfg.dtype)
    return {
        "k": jnp.zeros((batch, cfg.n_kv_heads, size, cfg.head_dim), dt),
        "v": jnp.zeros((batch, cfg.n_kv_heads, size, cfg.head_dim), dt),
    }


def _cache_size(cache: dict) -> int:
    """Rows of a layer's cache, plain or a :class:`~repro.kernels.ops.LayerRef`."""
    k = cache["k"]
    return (k.stack if isinstance(k, ops.LayerRef) else k).shape[-2]


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def attn_forward(p: dict, cfg: ArchConfig, x: jax.Array, kind: str, *,
                 positions: jax.Array, provider=None,
                 cache: dict | None = None,
                 true_len=None) -> tuple[jax.Array, dict | None]:
    """x: (B, S, D) normalized input. Returns (attn_out, updated_cache).

    ``true_len`` (traced or static; None means S) counts the real positions
    of a right-padded prompt: a ring cache keeps the last ``size`` of those,
    never pad rows."""
    b, s, _ = x.shape
    q, k, v = _qkv(p, cfg, x, provider)
    q = jnp.swapaxes(q, 1, 2)  # (B, H, S, hd)
    k = jnp.swapaxes(k, 1, 2)
    v = jnp.swapaxes(v, 1, 2)
    q, k = _rope_qk(cfg, q, k, positions, kind)

    window = cfg.window if kind == "L" else 0
    out = ops.flash_attention(
        q, k, v,
        class_id=_attn_class(cfg, kind),
        causal=True,
        window=window,
        softcap=cfg.attn_softcap if kind == "G" else 0.0,
        provider=provider,
    )
    out = jnp.swapaxes(out, 1, 2).reshape(b, s, cfg.n_heads * cfg.head_dim)
    y = ops.matmul(out, p["wo"], provider=provider)

    new_cache = None
    if cache is not None:
        size = _cache_size(cache)
        if size >= s:
            new_cache = {
                "k": jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, 0, 0)),
                "v": jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, 0, 0)),
            }
        else:  # ring prefill: keep the last `size` true positions, slot p % size
            n = jnp.asarray(s if true_len is None else true_len, jnp.int32)
            start = jnp.maximum(n - size, 0)
            new_cache = {
                name: jnp.roll(jax.lax.dynamic_slice_in_dim(a, start, size, axis=2),
                               start % size, axis=2)
                for name, a in (("k", k), ("v", v))}
    return y, new_cache


# ---------------------------------------------------------------------------
# Chunked prefill (a prompt slice against a partially filled cache)
# ---------------------------------------------------------------------------


def attn_chunk(p: dict, cfg: ArchConfig, x: jax.Array, kind: str, *,
               positions: jax.Array, off: jax.Array, cache: dict,
               provider=None) -> tuple[jax.Array, dict]:
    """One prefill chunk: queries at absolute positions ``off .. off+C-1``
    attend to the cache prefix (positions ``< off``) plus the chunk itself.

    ``off`` may be a traced scalar — masks are position arithmetic, so one
    trace per chunk *length* covers every offset.  Full-length caches get
    the chunk spliced in before a causally masked pass over the whole
    buffer; ring caches attend over [ring prefix ‖ chunk] with explicit
    position masks and are updated *after* attention (pre-writing a chunk
    into the ring would overwrite positions earlier in-chunk queries still
    need).
    """
    b, s, _ = x.shape
    off = jnp.asarray(off, jnp.int32)
    q, k, v = _qkv(p, cfg, x, provider)
    q = jnp.swapaxes(q, 1, 2)  # (B, H, C, hd)
    k = jnp.swapaxes(k, 1, 2)  # (B, KV, C, hd)
    v = jnp.swapaxes(v, 1, 2)
    q, k = _rope_qk(cfg, q, k, positions, kind)

    size = _cache_size(cache)
    window = cfg.window if kind == "L" else 0
    if kind == "G" or cfg.window == 0:
        # Full-length buffer: splice the chunk at [off, off+C), then one
        # causal pass over the whole buffer — positions beyond off+C hold
        # garbage but the causal mask (kv_pos <= q_pos < off+C) hides them.
        ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, 0, off, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, 0, off, 0))
        out = ops.flash_attention(
            q, ck, cv,
            class_id=_attn_class(cfg, kind),
            causal=True, window=0,
            softcap=cfg.attn_softcap if kind == "G" else 0.0,
            q_offset=off, provider=provider,
        )
        new_cache = {"k": ck, "v": cv}
    else:
        # Ring cache (slot convention p % size): reconstruct each slot's
        # absolute position — the latest p < off congruent to the slot —
        # and attend over [ring ‖ chunk] under causal+window+validity masks.
        slots = jnp.arange(size)
        ring_pos = off - 1 - jnp.mod(off - 1 - slots, size)  # < 0 -> unwritten
        kv_pos = jnp.concatenate([ring_pos, off + jnp.arange(s)])
        q_pos = off + jnp.arange(s)
        ok = (kv_pos[None, :] >= 0) & (kv_pos[None, :] <= q_pos[:, None])
        if window > 0:
            ok = ok & (kv_pos[None, :] > q_pos[:, None] - window)
        kk = jnp.concatenate([cache["k"].astype(k.dtype), k], axis=2)
        vv = jnp.concatenate([cache["v"].astype(v.dtype), v], axis=2)
        out = ref.masked_attention(q, kk, vv, ok,
                                   softcap=cfg.attn_softcap if kind == "G" else 0.0)
        # Write-after-attention: slot (off+i) % size takes position off+i;
        # ascending i means later (newer) positions win on wrap.
        if s >= size:
            shift = jnp.mod(off + s, size)
            new_cache = {
                "k": jnp.roll(k[:, :, s - size:, :].astype(cache["k"].dtype),
                              shift, axis=2),
                "v": jnp.roll(v[:, :, s - size:, :].astype(cache["v"].dtype),
                              shift, axis=2),
            }
        else:
            wslots = jnp.mod(off + jnp.arange(s), size)
            new_cache = {
                "k": cache["k"].at[:, :, wslots, :].set(k.astype(cache["k"].dtype)),
                "v": cache["v"].at[:, :, wslots, :].set(v.astype(cache["v"].dtype)),
            }
    out = jnp.swapaxes(out, 1, 2).reshape(b, s, cfg.n_heads * cfg.head_dim)
    y = ops.matmul(out, p["wo"], provider=provider)
    return y, new_cache


# ---------------------------------------------------------------------------
# Decode (single token against cache)
# ---------------------------------------------------------------------------


def attn_decode(p: dict, cfg: ArchConfig, x: jax.Array, kind: str, *,
                pos: jax.Array, cache: dict, provider=None) -> tuple[jax.Array, dict]:
    """x: (B, 1, D); pos: (B,) per-sequence absolute positions (continuous
    batching: every slot may be at a different decode position).

    ``cache`` is this layer's ``{"k", "v"}``, each (B, KV, T, hd), or, in
    the decode step's layer scan, each an :class:`~repro.kernels.ops.LayerRef`
    to the stack (L, B, KV, T, hd) the scan carries: the B new rows are then
    written into the stack at the layer, the attention reads the layer in
    place, and the returned cache is the same form over the updated stack.
    Under a donated cache the write is in place, so the caller must not read
    the cache it passed in again.  Under a mesh the attention is the einsum
    of the ``ref`` backend, as the sharding rules lay the cache out."""
    b = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    q, k, v = _qkv(p, cfg, x, provider)
    q = jnp.swapaxes(q, 1, 2)   # (B, H, 1, hd)
    k = jnp.swapaxes(k, 1, 2)   # (B, KV, 1, hd)
    v = jnp.swapaxes(v, 1, 2)
    q, k = _rope_qk(cfg, q, k, pos[:, None], kind)

    size = _cache_size(cache)
    slot = jnp.where(size > pos, pos, pos % size)           # (B,) ring for local
    bi = jnp.arange(b)[:, None]
    hi = jnp.arange(cfg.n_kv_heads)[None, :]
    new_cache = {}
    for name, row in (("k", k), ("v", v)):
        c = cache[name]
        if isinstance(c, ops.LayerRef):
            stack = c.stack.at[c.index, bi, hi, slot[:, None]].set(
                row[:, :, 0, :].astype(c.stack.dtype))
            new_cache[name] = ops.LayerRef(stack, c.index)
        else:
            new_cache[name] = c.at[bi, hi, slot[:, None], :].set(
                row[:, :, 0, :].astype(c.dtype))

    # A full cache attends slots <= pos; a ring cache (L layers: its size is
    # at most the window, so the window holds by construction) its written
    # slots, < min(pos + 1, size): one mask, slot < min(pos + 1, size).
    out = ops.decode_attention(q, new_cache["k"], new_cache["v"], pos,
                               softcap=cfg.attn_softcap if kind == "G" else 0.0,
                               provider=provider,
                               backend="ref" if sharded() else None)
    out = jnp.swapaxes(out, 1, 2).reshape(b, 1, cfg.n_heads * cfg.head_dim)
    y = ops.matmul(out, p["wo"], provider=provider)
    return y, new_cache


# ---------------------------------------------------------------------------
# Speculative verify (k+1 draft positions against cache, per-lane offsets)
# ---------------------------------------------------------------------------


def attn_verify(p: dict, cfg: ArchConfig, x: jax.Array, kind: str, *,
                off: jax.Array, cache: dict, provider=None) -> tuple[jax.Array, dict]:
    """x: (B, C, D); off: (B,) per-lane absolute write offsets.

    The speculative analogue of :func:`attn_chunk`, batched across lanes
    that each sit at a *different* cache offset (continuous batching), which
    is exactly what ``attn_chunk``'s shared scalar ``off`` cannot express.
    Batching matters: verifying lanes one at a time streams the full weights
    per lane (memory-bound, ≈ one decode step each) and erases the
    speculative win; one batched call streams them once.

    Rows ``off+C .. size-1`` may hold garbage from a previous over-write
    (rejected draft positions) — the validity mask hides them, and later
    steps overwrite them in order, so no explicit rollback pass is needed.
    Full-length caches only (ring/local layers lose rejected-position
    history); callers gate on :func:`repro.serving.speculative.spec_exact_reason`.
    """
    b, s, _ = x.shape
    off = jnp.broadcast_to(jnp.asarray(off, jnp.int32), (b,))
    q, k, v = _qkv(p, cfg, x, provider)
    q = jnp.swapaxes(q, 1, 2)   # (B, H, C, hd)
    k = jnp.swapaxes(k, 1, 2)   # (B, KV, C, hd)
    v = jnp.swapaxes(v, 1, 2)
    positions = off[:, None] + jnp.arange(s)                # (B, C)
    q, k = _rope_qk(cfg, q, k, positions, kind)

    size = _cache_size(cache)
    bi = jnp.arange(b)[:, None, None]
    hi = jnp.arange(cfg.n_kv_heads)[None, :, None]
    rows = positions[:, None, :]                            # (B, 1, C)
    ck = cache["k"].at[bi, hi, rows, :].set(k.astype(cache["k"].dtype))
    cv = cache["v"].at[bi, hi, rows, :].set(v.astype(cache["v"].dtype))

    slots = jnp.arange(size)
    ok = slots[None, None, :] <= positions[:, :, None]      # (B, C, T)
    out = ref.masked_attention(q, ck, cv, ok,
                               softcap=cfg.attn_softcap if kind == "G" else 0.0)
    out = jnp.swapaxes(out, 1, 2).reshape(b, s, cfg.n_heads * cfg.head_dim)
    y = ops.matmul(out, p["wo"], provider=provider)
    return y, {"k": ck, "v": cv}
