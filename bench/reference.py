"""Plain float32 forward pass of a dense GQA decoder, and its fp8 control.

The equations are those of the program's ``ArchConfig`` as a configuration
file's ``model`` block states them: token embedding;
per layer a pre-norm, q/k/v projections, rotary embedding on interleaved
(even, odd) pairs of every head dim, causal grouped-query softmax attention
scaled by head_dim^-1/2, the output projection and a residual add, then a
pre-norm, a non-gated MLP with tanh-GELU (with biases where ``use_bias``)
and a residual add; a final norm and the head.  Nothing here imports the
program.  Weights are the benchmark's own (``bench/weights.py``) in their
served dtype; every matmul upcasts them to float32 and runs at HIGHEST
precision, one sequence and one layer at a time so that it fits beside
the weights.

``quant="fp8"`` is the control: the same pass with both operands of every
matmul (projections, attention scores and values, the head) rounded to
float8 e4m3 under a per-tensor scale, as a lower-precision serving path
would.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0            # largest finite float8 e4m3 value
QUERY_BLOCK = 512         # attention rows per block: bounds the score tile
VOCAB_BLOCK = 32768       # head columns per block: bounds the f32 logits


@dataclasses.dataclass(frozen=True)
class RefCfg:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    layer_norm: bool
    eps: float
    rope_theta: float
    mlp_bias: bool

    @classmethod
    def from_model(cls, m: dict) -> "RefCfg":
        if m["mlp_activation"] != "gelu_tanh":
            raise ValueError(f"reference runs tanh-GELU, not {m['mlp_activation']!r}")
        if m.get("rotary_fraction", 1.0) != 1.0:
            raise ValueError("reference rotates whole heads only")
        if m.get("tie_word_embeddings", False):
            raise ValueError("reference reads an untied head")
        return cls(layers=m["num_hidden_layers"], d_model=m["hidden_size"],
                   heads=m["num_attention_heads"],
                   kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
                   vocab=m["vocab_size"],
                   layer_norm=m["norm_type"] == "layer_norm",
                   eps=float(m["norm_epsilon"]), rope_theta=float(m["rope_theta"]),
                   mlp_bias=bool(m.get("use_bias", False)))


def _q8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 under a per-tensor scale, back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec: str, a, b, quant):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if quant == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _norm(x, p, cfg: RefCfg):
    scale = p["scale"].astype(jnp.float32)
    if cfg.layer_norm:
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + cfg.eps) * scale + p["bias"].astype(jnp.float32)
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + cfg.eps) * (1.0 + scale)


def _rope(x, cfg: RefCfg):
    """x: (S, heads, head_dim) at positions 0..S-1."""
    s, _, d = x.shape
    inv = cfg.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = jnp.asarray(np.arange(s)[:, None] * inv[None, :], jnp.float32)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).reshape(x.shape)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _attention(q, k, v, cfg: RefCfg, quant):
    """Causal GQA attention; q: (S, H, hd), k/v: (S, KV, hd) -> (S, H*hd)."""
    s = q.shape[0]
    g = cfg.heads // cfg.kv_heads
    q = q.reshape(s, cfg.kv_heads, g, cfg.head_dim) * cfg.head_dim ** -0.5
    out = []
    for lo in range(0, s, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, s)
        sc = _mm("qkgd,tkd->kgqt", q[lo:hi], k[:hi], quant)
        mask = np.arange(hi)[None, :] <= np.arange(lo, hi)[:, None]
        sc = jnp.where(mask, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        out.append(_mm("kgqt,tkd->qkgd", p, v[:hi], quant))
    return jnp.concatenate(out, 0).reshape(s, cfg.heads * cfg.head_dim)


@functools.partial(jax.jit, static_argnames=("cfg", "quant"))
def _layer(h, stack, l, cfg: RefCfg, quant):
    p = jax.tree_util.tree_map(lambda a: a[l], stack)
    s = h.shape[0]
    x = _norm(h, p["ln1"], cfg)
    a = p["attn"]
    q = _mm("sd,dn->sn", x, a["wq"], quant).reshape(s, cfg.heads, cfg.head_dim)
    k = _mm("sd,dn->sn", x, a["wk"], quant).reshape(s, cfg.kv_heads, cfg.head_dim)
    v = _mm("sd,dn->sn", x, a["wv"], quant).reshape(s, cfg.kv_heads, cfg.head_dim)
    q, k = _rope(q, cfg), _rope(k, cfg)
    h = h + _mm("sn,nd->sd", _attention(q, k, v, cfg, quant), a["wo"], quant)
    x = _norm(h, p["ln2"], cfg)
    m = p["mlp"]
    u = _mm("sd,df->sf", x, m["w_in"], quant)
    if cfg.mlp_bias:
        u = u + m["b_in"].astype(jnp.float32)
    y = _mm("sf,fd->sd", _gelu_tanh(u), m["w_out"], quant)
    if cfg.mlp_bias:
        y = y + m["b_out"].astype(jnp.float32)
    return h + y


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _final_norm(h, p, cfg: RefCfg):
    return _norm(h, p, cfg)


def final_hidden(weights, cfg: RefCfg, tokens: np.ndarray, quant=None) -> jax.Array:
    """Final-norm hidden states (S, D) of one token sequence."""
    h = _embed(weights["embed"], jnp.asarray(tokens, jnp.int32))
    stack = weights["groups"]["0"]
    for l in range(cfg.layers):
        h = _layer(h, stack, jnp.int32(l), cfg, quant)
    return _final_norm(h, weights["final_norm"], cfg)


@functools.partial(jax.jit, static_argnames=("size", "control"))
def _head_block(weights, h, hc, served, state, lo, size: int, control: bool):
    """Fold head columns [lo, lo+size) into the running readings."""
    w = jax.lax.dynamic_slice_in_dim(weights["lm_head"], lo, size, 1)
    logits = _mm("sd,dv->sv", h, w, None)
    cols = lo + jnp.arange(size)
    ref_max = jnp.maximum(state["ref_max"], logits.max(-1))
    hit = cols[None, :] == served[:, None]
    ref_served = state["ref_served"] + jnp.where(hit, logits, 0.0).sum(-1)
    out = {"ref_max": ref_max, "ref_served": ref_served}
    if control:
        lc = _mm("sd,dv->sv", hc, w, "fp8")
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


def gaps(weights, cfg: RefCfg, prompt: list[int], served: list[int], *,
         control: bool = False) -> dict:
    """Teacher-forced readings over ``prompt`` followed by the ``served``
    tokens: at each position that produced a served token, ``served`` is
    the reference's best logit minus its logit of the served token and, with
    ``control``, ``control`` is the same gap for the token the fp8 pass puts
    first."""
    seq = list(prompt) + list(served[:-1])
    s = bucket(len(seq))
    tokens = np.zeros(s, np.int32)
    tokens[:len(seq)] = seq
    target = np.full(s, -1, np.int32)
    start = len(prompt) - 1
    target[start:start + len(served)] = served
    h = final_hidden(weights, cfg, tokens)
    hc = final_hidden(weights, cfg, tokens, "fp8") if control else h
    neg = jnp.full((s,), -jnp.inf, jnp.float32)
    state = {"ref_max": neg, "ref_served": jnp.zeros((s,), jnp.float32)}
    if control:
        state |= {"ctrl_max": neg, "ref_at_ctrl": jnp.zeros((s,), jnp.float32)}
    served_j = jnp.asarray(target)
    for lo in range(0, cfg.vocab, VOCAB_BLOCK):
        size = min(VOCAB_BLOCK, cfg.vocab - lo)
        state = _head_block(weights, h, hc, served_j, state, jnp.int32(lo),
                            size, control)
    st = {k: np.asarray(v)[start:start + len(served)] for k, v in state.items()}
    out = {"served": st["ref_max"] - st["ref_served"]}
    if control:
        out["control"] = st["ref_max"] - st["ref_at_ctrl"]
    return out
