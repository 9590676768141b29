"""Shared model substrate: norms, RoPE, initializers, GLU weight packing.

Parameters are plain nested dicts of jnp arrays (pytrees) — no framework
dependency.  All init functions are pure in their PRNG key so they can be
traced by ``jax.eval_shape`` for the allocation-free dry-run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.schedule import glu_chunk


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key: jax.Array, fan_in: int, fan_out: int, dtype) -> jax.Array:
    scale = (2.0 / (fan_in + fan_out)) ** 0.5
    return (jax.random.normal(key, (fan_in, fan_out), jnp.float32) * scale).astype(dtype)


def embed_init(key: jax.Array, vocab: int, dim: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (vocab, dim), jnp.float32) * dim ** -0.5).astype(dtype)


def pack_glu(w_gate: jax.Array, w_up: jax.Array) -> jax.Array:
    """Interleave gate/up column chunks: (K, F) + (K, F) -> (K, 2F) with
    columns (gate chunk 0, up chunk 0, gate chunk 1, ...), chunks of
    :func:`~repro.core.schedule.glu_chunk` columns.  Required by the fused
    GLU kernel epilogue — each N-block then holds complete (gate, up) pairs
    it splits with lane-aligned slices."""
    k, f = w_gate.shape
    c = glu_chunk(f)
    return jnp.stack([w_gate.reshape(k, f // c, c), w_up.reshape(k, f // c, c)],
                     axis=2).reshape(k, 2 * f)


def glu_init(key: jax.Array, d: int, f: int, dtype) -> jax.Array:
    kg, ku = jax.random.split(key)
    return pack_glu(dense_init(kg, d, f, dtype), dense_init(ku, d, f, dtype))


# ---------------------------------------------------------------------------
# Norms (computed in f32, cast back)
# ---------------------------------------------------------------------------


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return y.astype(x.dtype)


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def norm_params(d: int, kind: str, dtype) -> dict:
    if kind == "rmsnorm":
        return {"scale": jnp.zeros((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def apply_norm(p: dict, x: jax.Array, kind: str) -> jax.Array:
    if kind == "rmsnorm":
        return rmsnorm(x, p["scale"])
    return layernorm(x, p["scale"], p["bias"])


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               yarn=None) -> jax.Array:
    """x: (B, H, S, D); positions: (B, S) or (S,) absolute positions.
    ``yarn`` (a :class:`~repro.configs.base.Yarn`) replaces the frequencies
    by its blended ones and scales cos and sin by its attention factor."""
    d = x.shape[-1]
    if yarn is None:
        freqs = rope_freqs(d, theta)                  # (D/2,)
    else:
        freqs = jnp.asarray(yarn.inv_freq(d, theta), jnp.float32)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    cos = jnp.cos(ang)[:, None, :, :]
    sin = jnp.sin(ang)[:, None, :, :]
    if yarn is not None:
        cos, sin = cos * yarn.attention_factor, sin * yarn.attention_factor
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x1 * sin + x2 * cos
    return jnp.stack([y1, y2], axis=-1).reshape(x.shape).astype(x.dtype)


def count_params(tree) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(tree))
