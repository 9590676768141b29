"""The plain float32 reference's shared parts, and the readings taken from it.

Each architecture's forward pass lives in its module ``bench/models/<name>.py``
(``final_hidden``, see ``harness.arch_module``); this file holds what those
passes share and what reads their result.  Nothing here imports the program.
Weights are the benchmark's own (``bench/weights.py``) in their served dtype;
every matmul upcasts them to float32 and runs at HIGHEST precision, one
sequence at a time so that it fits beside the weights.

``quant="fp8"`` is the control: both operands of every matmul (projections,
attention scores and values, the head) rounded to float8 e4m3 under a
per-tensor scale, as a lower-precision serving path would.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0            # largest finite float8 e4m3 value
QUERY_BLOCK = 512         # attention rows per block: bounds the score tile
VOCAB_BLOCK = 32768       # head columns per block: bounds the f32 logits


def _q8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 under a per-tensor scale, back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(spec: str, a, b, quant):
    """``einsum(spec, a, b)`` in float32 at HIGHEST precision; with
    ``quant="fp8"`` both operands are rounded first (the control)."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if quant == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def layer_norm(x, p, eps: float):
    """Layer norm with a scale and a bias."""
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"].astype(jnp.float32) + \
        p["bias"].astype(jnp.float32)


def rms_norm(x, p, eps: float):
    """RMS norm scaled by (1 + scale), as the program's RMS norm is."""
    scale = p["scale"].astype(jnp.float32)
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + scale)


def rope(x, theta: float):
    """Rotary embedding on interleaved (even, odd) pairs of every head dim;
    x: (S, heads, head_dim) at positions 0..S-1."""
    s, _, d = x.shape
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = jnp.asarray(np.arange(s)[:, None] * inv[None, :], jnp.float32)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).reshape(x.shape)


def attention(q, k, v, quant):
    """Causal grouped-query softmax attention scaled by head_dim^-1/2, in
    blocks of query rows; q: (S, H, hd), k/v: (S, KV, hd) -> (S, H*hd)."""
    s, heads, hd = q.shape
    kv_heads = k.shape[1]
    q = q.reshape(s, kv_heads, heads // kv_heads, hd) * hd ** -0.5
    out = []
    for lo in range(0, s, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, s)
        sc = mm("qkgd,tkd->kgqt", q[lo:hi], k[:hi], quant)
        mask = np.arange(hi)[None, :] <= np.arange(lo, hi)[:, None]
        sc = jnp.where(mask, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        out.append(mm("kgqt,tkd->qkgd", p, v[:hi], quant))
    return jnp.concatenate(out, 0).reshape(s, heads * hd)


@jax.jit
def embed(table, tokens):
    """Rows of the embedding table, in float32."""
    return table[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("size", "control"))
def _head_block(weights, h, hc, served, state, lo, size: int, control: bool):
    """Fold head columns [lo, lo+size) into the running readings."""
    w = jax.lax.dynamic_slice_in_dim(weights["lm_head"], lo, size, 1)
    logits = mm("sd,dv->sv", h, w, None)
    cols = lo + jnp.arange(size)
    ref_max = jnp.maximum(state["ref_max"], logits.max(-1))
    hit = cols[None, :] == served[:, None]
    ref_served = state["ref_served"] + jnp.where(hit, logits, 0.0).sum(-1)
    out = {"ref_max": ref_max, "ref_served": ref_served}
    if control:
        lc = mm("sd,dv->sv", hc, w, "fp8")
        bmax, barg = lc.max(-1), lc.argmax(-1)
        better = bmax > state["ctrl_max"]
        at = jnp.take_along_axis(logits, barg[:, None], -1)[:, 0]
        out["ctrl_max"] = jnp.where(better, bmax, state["ctrl_max"])
        out["ref_at_ctrl"] = jnp.where(better, at, state["ref_at_ctrl"])
    return out


def bucket(n: int) -> int:
    """Sequences are padded to a power of two (at least 128) so that the
    reference compiles once per bucket; causal attention makes the padding
    inert."""
    return max(128, 1 << (n - 1).bit_length())


def gaps(weights, final_hidden, prompt: list[int], served: list[int], *,
         control: bool = False) -> dict:
    """Teacher-forced readings over ``prompt`` followed by the ``served``
    tokens, through ``final_hidden(tokens, quant)`` (an architecture's pass,
    bound to its weights) and the head ``weights["lm_head"]``: at each
    position that produced a served token, ``served`` is the reference's
    best logit minus its logit of the served token and, with ``control``,
    ``control`` is the same gap for the token the fp8 pass puts first."""
    seq = list(prompt) + list(served[:-1])
    s = bucket(len(seq))
    tokens = np.zeros(s, np.int32)
    tokens[:len(seq)] = seq
    target = np.full(s, -1, np.int32)
    start = len(prompt) - 1
    target[start:start + len(served)] = served
    h = final_hidden(tokens, None)
    hc = final_hidden(tokens, "fp8") if control else h
    neg = jnp.full((s,), -jnp.inf, jnp.float32)
    state = {"ref_max": neg, "ref_served": jnp.zeros((s,), jnp.float32)}
    if control:
        state |= {"ctrl_max": neg, "ref_at_ctrl": jnp.zeros((s,), jnp.float32)}
    served_j = jnp.asarray(target)
    vocab = weights["lm_head"].shape[1]
    for lo in range(0, vocab, VOCAB_BLOCK):
        size = min(VOCAB_BLOCK, vocab - lo)
        state = _head_block(weights, h, hc, served_j, state, jnp.int32(lo),
                            size, control)
    st = {k: np.asarray(v)[start:start + len(served)] for k, v in state.items()}
    out = {"served": st["ref_max"] - st["ref_served"]}
    if control:
        out["control"] = st["ref_max"] - st["ref_at_ctrl"]
    return out
