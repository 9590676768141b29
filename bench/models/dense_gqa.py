"""Dense GQA decoder with RoPE and a non-gated tanh-GELU MLP (minitron-4b,
starcoder2-7b): what the benchmark needs to know of this architecture.

The equations are those of the program's ``ArchConfig`` as a configuration
file's ``model`` block states them: token embedding; per layer a pre-norm,
q/k/v projections, rotary embedding on interleaved (even, odd) pairs of
every head dim, causal grouped-query softmax attention scaled by
head_dim^-1/2, the output projection and a residual add, then a pre-norm, a
non-gated MLP with tanh-GELU (with biases where ``use_bias``) and a residual
add; a final norm and the head.  Weights are scanned over layers in one
group: each leaf of ``groups["0"]`` carries a leading layer axis.

It gives the five names every ``bench/models/<name>.py`` gives
(``harness.arch_module``): ``program_config``, ``layout``, ``ref_config``,
``final_hidden`` and ``dims``.  Nothing here but ``program_config`` reads
the program.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import reference


def program_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file, checked
    against the file's ``model`` block so that the file is what runs."""
    from repro.configs.base import get_arch

    r = conf["repro"]
    cfg = dataclasses.replace(get_arch(r["arch"]), **r["overrides"])
    m = conf["model"]
    want = {
        "n_layers": m["num_hidden_layers"], "d_model": m["hidden_size"],
        "d_ff": m["intermediate_size"], "n_heads": m["num_attention_heads"],
        "n_kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
        "vocab_size": m["vocab_size"], "rope_theta": m["rope_theta"],
        "mlp_bias": m["use_bias"], "tie_embeddings": m["tie_word_embeddings"],
        "dtype": m["torch_dtype"],
        "norm": {"layer_norm": "layernorm", "rms_norm": "rmsnorm"}[m["norm_type"]],
        "mlp_kind": {"gelu_tanh": "gelu"}[m["mlp_activation"]],
        "family": "dense", "layer_pattern": ("G",), "window": 0, "pos": "rope",
        "attn_softcap": 0.0, "final_softcap": 0.0, "n_experts": 0,
        "vision_tokens": 0, "encoder_layers": 0,
    }
    wrong = {k: (getattr(cfg, k), v) for k, v in want.items() if getattr(cfg, k) != v}
    if wrong:
        raise ValueError(f"{conf['name']}: program config differs from the file "
                         f"(program, file): {wrong}")
    return cfg


def layout(m: dict) -> dict:
    """Leaf shapes in the program's parameter tree, each ``(shape, mean,
    std)`` of its normal draw: projections N(0, 1/fan_in), embedding rows
    N(0, 1), and norm scales (around 1 for a layer norm, around 0 for the
    program's (1 + scale) RMS norm), norm biases and MLP biases drawn around
    their neutral values so that a path that drops one of them shows."""
    L, D, F, V = (m["num_hidden_layers"], m["hidden_size"],
                  m["intermediate_size"], m["vocab_size"])
    H, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    layer_norm = m["norm_type"] == "layer_norm"

    def norm(*lead):
        out = {"scale": (lead + (D,), 1.0 if layer_norm else 0.0, 0.1)}
        if layer_norm:
            out["bias"] = (lead + (D,), 0.0, 0.1)
        return out

    mlp = {"w_in": ((L, D, F), 0.0, D ** -0.5),
           "w_out": ((L, F, D), 0.0, F ** -0.5)}
    if m.get("use_bias"):
        mlp["b_in"] = ((L, F), 0.0, 0.1)
        mlp["b_out"] = ((L, D), 0.0, 0.1)
    tree = {
        "embed": ((V, D), 0.0, 1.0),
        "groups": {"0": {
            "ln1": norm(L),
            "attn": {"wq": ((L, D, H * hd), 0.0, D ** -0.5),
                     "wk": ((L, D, KV * hd), 0.0, D ** -0.5),
                     "wv": ((L, D, KV * hd), 0.0, D ** -0.5),
                     "wo": ((L, H * hd, D), 0.0, (H * hd) ** -0.5)},
            "ln2": norm(L),
            "mlp": mlp,
        }},
        "tail": [],
        "final_norm": norm(),
    }
    if not m.get("tie_word_embeddings"):
        tree["lm_head"] = ((D, V), 0.0, D ** -0.5)
    return tree


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------


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


def ref_config(m: dict) -> RefCfg:
    """The reference's settings for a ``model`` block (hashable, a static
    argument of ``jit``); refuses what this pass does not compute."""
    if m["mlp_activation"] != "gelu_tanh":
        raise ValueError(f"reference runs tanh-GELU, not {m['mlp_activation']!r}")
    if m.get("rotary_fraction", 1.0) != 1.0:
        raise ValueError("reference rotates whole heads only")
    if m.get("tie_word_embeddings", False):
        raise ValueError("reference reads an untied head")
    return RefCfg(layers=m["num_hidden_layers"], d_model=m["hidden_size"],
                  heads=m["num_attention_heads"],
                  kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
                  vocab=m["vocab_size"],
                  layer_norm=m["norm_type"] == "layer_norm",
                  eps=float(m["norm_epsilon"]), rope_theta=float(m["rope_theta"]),
                  mlp_bias=bool(m.get("use_bias", False)))


def _norm(x, p, cfg: RefCfg):
    if cfg.layer_norm:
        return reference.layer_norm(x, p, cfg.eps)
    return reference.rms_norm(x, p, cfg.eps)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("cfg", "quant"))
def _layer(h, stack, l, cfg: RefCfg, quant):
    mm = reference.mm
    p = jax.tree_util.tree_map(lambda a: a[l], stack)
    s = h.shape[0]
    x = _norm(h, p["ln1"], cfg)
    a = p["attn"]
    q = mm("sd,dn->sn", x, a["wq"], quant).reshape(s, cfg.heads, cfg.head_dim)
    k = mm("sd,dn->sn", x, a["wk"], quant).reshape(s, cfg.kv_heads, cfg.head_dim)
    v = mm("sd,dn->sn", x, a["wv"], quant).reshape(s, cfg.kv_heads, cfg.head_dim)
    q, k = reference.rope(q, cfg.rope_theta), reference.rope(k, cfg.rope_theta)
    h = h + mm("sn,nd->sd", reference.attention(q, k, v, quant), a["wo"], quant)
    x = _norm(h, p["ln2"], cfg)
    m = p["mlp"]
    u = mm("sd,df->sf", x, m["w_in"], quant)
    if cfg.mlp_bias:
        u = u + m["b_in"].astype(jnp.float32)
    y = mm("sf,fd->sd", _gelu_tanh(u), m["w_out"], quant)
    if cfg.mlp_bias:
        y = y + m["b_out"].astype(jnp.float32)
    return h + y


@functools.partial(jax.jit, static_argnames=("cfg",))
def _final_norm(h, p, cfg: RefCfg):
    return _norm(h, p, cfg)


def final_hidden(weights, cfg: RefCfg, tokens: np.ndarray, quant=None) -> jax.Array:
    """Final-norm hidden states (S, D) of one token sequence, one layer at a
    time; ``quant="fp8"`` is the control (``reference.mm``)."""
    h = reference.embed(weights["embed"], jnp.asarray(tokens, jnp.int32))
    stack = weights["groups"]["0"]
    for l in range(cfg.layers):
        h = _layer(h, stack, jnp.int32(l), cfg, quant)
    return _final_norm(h, weights["final_norm"], cfg)


# ---------------------------------------------------------------------------
# Operations and bytes
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Dims:
    """The shapes the counts need.  The counts follow the reference's
    equations above, not the program's kernels, so a change to a kernel
    cannot change what it is measured against.  A multiply-add is two
    operations."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    mlp_bias: bool = False
    layernorm: bool = True
    dtype_bytes: int = 2

    # -- operations -----------------------------------------------------------
    def linear_flops_per_token(self) -> int:
        """q, k, v and output projections plus the two MLP matmuls, per
        layer and token."""
        d, hd = self.d_model, self.head_dim
        qkv = 2 * d * (self.heads + 2 * self.kv_heads) * hd
        out = 2 * self.heads * hd * d
        mlp = 2 * 2 * d * self.d_ff
        return qkv + out + mlp

    def attention_flops(self, attended: int) -> int:
        """Scores and weighted values for one query over ``attended`` keys,
        per layer."""
        return 4 * self.heads * self.head_dim * attended

    def head_flops(self, rows: int) -> int:
        return 2 * self.d_model * self.vocab * rows

    def prefill_flops(self, n: int) -> int:
        """One prompt of ``n`` true tokens: every layer at every position,
        causal attention, and the head at the last position only."""
        per_layer = n * self.linear_flops_per_token() + \
            self.attention_flops(1) * n * (n + 1) // 2
        return self.layers * per_layer + self.head_flops(1)

    def decode_flops(self, attended: list[int]) -> int:
        """One decode step; ``attended[i]`` is the number of cache rows the
        i-th active sequence attends to, its new row included."""
        b = len(attended)
        per_layer = b * self.linear_flops_per_token() + \
            sum(self.attention_flops(c) for c in attended)
        return self.layers * per_layer + self.head_flops(b)

    # -- bytes ----------------------------------------------------------------
    def norm_params(self) -> int:
        return (2 if self.layernorm else 1) * self.d_model   # scale (, bias)

    def layer_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        n = d * (self.heads + 2 * self.kv_heads) * hd + self.heads * hd * d
        n += 2 * d * self.d_ff + 2 * self.norm_params()
        if self.mlp_bias:
            n += self.d_ff + d
        return n

    def weight_bytes(self) -> int:
        """Every weight a decode step streams once: the layers, the final
        norm and the head, which is the embedding table when they are tied
        (an untied table is read row by row)."""
        n = (self.layers * self.layer_params() + self.norm_params()
             + self.d_model * self.vocab)
        return n * self.dtype_bytes

    def kv_row_bytes(self) -> int:
        """Keys and values of one position, all layers."""
        return self.layers * 2 * self.kv_heads * self.head_dim * self.dtype_bytes

    def decode_bytes(self, attended: list[int]) -> int:
        """Least bytes one decode step moves: the weights once, each active
        sequence's embedding row, its live cache rows read, its new rows
        written."""
        b = len(attended)
        return (self.weight_bytes() + b * self.d_model * self.dtype_bytes
                + self.kv_row_bytes() * (sum(attended) + b))


def dims(m: dict) -> Dims:
    """The counts' shapes from a configuration file's ``model`` block."""
    return Dims(layers=m["num_hidden_layers"], d_model=m["hidden_size"],
                heads=m["num_attention_heads"],
                kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
                d_ff=m["intermediate_size"], vocab=m["vocab_size"],
                mlp_bias=bool(m.get("use_bias", False)),
                layernorm=m["norm_type"] == "layer_norm",
                dtype_bytes={"bfloat16": 2, "float32": 4}[m["torch_dtype"]])
