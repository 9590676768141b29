"""Dense GQA decoder with a SwiGLU MLP and RMS norms, as the program runs
``mlp_kind="swiglu"``: an architecture module that the tests plant in a
checkout as ``bench/models/dense_swiglu.py``, beside a configuration, a
limits file and a cell of their own, to show that another architecture is
added with new files only.

The equations: token embedding; per layer an RMS pre-norm (scaled by
1 + scale), q/k/v projections, rotary embedding on interleaved (even, odd)
pairs of every head dim, causal grouped-query softmax attention, the output
projection and a residual add, then an RMS pre-norm, the MLP
``w_out(silu(gate) * up)`` and a residual add; a final RMS norm and an untied
head.  The MLP's input projection packs gate and up columns as the program
does: chunks of 128 columns (the whole width where 128 does not divide it)
alternate (gate chunk 0, up chunk 0, gate chunk 1, ...).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

import reference

GLU_CHUNK = 128


def program_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file, checked
    against the file's ``model`` block."""
    from repro.configs.base import get_arch

    r = conf["repro"]
    cfg = dataclasses.replace(get_arch(r["arch"]), **r["overrides"])
    m = conf["model"]
    want = {
        "n_layers": m["num_hidden_layers"], "d_model": m["hidden_size"],
        "d_ff": m["intermediate_size"], "n_heads": m["num_attention_heads"],
        "n_kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
        "vocab_size": m["vocab_size"], "rope_theta": m["rope_theta"],
        "tie_embeddings": m["tie_word_embeddings"], "dtype": m["torch_dtype"],
        "norm": {"rms_norm": "rmsnorm"}[m["norm_type"]],
        "mlp_kind": {"silu_glu": "swiglu"}[m["mlp_activation"]],
        "mlp_bias": False, "family": "dense", "layer_pattern": ("G",), "window": 0,
        "pos": "rope", "attn_softcap": 0.0, "final_softcap": 0.0, "n_experts": 0,
        "vision_tokens": 0, "encoder_layers": 0,
    }
    wrong = {k: (getattr(cfg, k), v) for k, v in want.items() if getattr(cfg, k) != v}
    if wrong:
        raise ValueError(f"{conf['name']}: program config differs from the file "
                         f"(program, file): {wrong}")
    return cfg


def layout(m: dict) -> dict:
    """Leaf shapes and draws, ``(shape, mean, std)``, in the program's tree."""
    L, D, F, V = (m["num_hidden_layers"], m["hidden_size"],
                  m["intermediate_size"], m["vocab_size"])
    H, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]

    def norm(*lead):
        return {"scale": (lead + (D,), 0.0, 0.1)}

    return {
        "embed": ((V, D), 0.0, 1.0),
        "groups": {"0": {
            "ln1": norm(L),
            "attn": {"wq": ((L, D, H * hd), 0.0, D ** -0.5),
                     "wk": ((L, D, KV * hd), 0.0, D ** -0.5),
                     "wv": ((L, D, KV * hd), 0.0, D ** -0.5),
                     "wo": ((L, H * hd, D), 0.0, (H * hd) ** -0.5)},
            "ln2": norm(L),
            "mlp": {"w_in": ((L, D, 2 * F), 0.0, D ** -0.5),
                    "w_out": ((L, F, D), 0.0, F ** -0.5)},
        }},
        "tail": [],
        "final_norm": norm(),
        "lm_head": ((D, V), 0.0, D ** -0.5),
    }


@dataclasses.dataclass(frozen=True)
class RefCfg:
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    eps: float
    rope_theta: float


def ref_config(m: dict) -> RefCfg:
    """The reference's settings; refuses what this pass does not compute."""
    if m["mlp_activation"] != "silu_glu" or m["norm_type"] != "rms_norm":
        raise ValueError("reference runs a SwiGLU MLP under RMS norms only")
    if m.get("tie_word_embeddings", False) or m.get("use_bias", False):
        raise ValueError("reference reads an untied head and no MLP biases")
    return RefCfg(layers=m["num_hidden_layers"], heads=m["num_attention_heads"],
                  kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
                  d_ff=m["intermediate_size"], eps=float(m["norm_epsilon"]),
                  rope_theta=float(m["rope_theta"]))


@functools.partial(jax.jit, static_argnames=("cfg", "quant"))
def _layer(h, stack, l, cfg: RefCfg, quant):
    mm = reference.mm
    p = jax.tree_util.tree_map(lambda a: a[l], stack)
    s = h.shape[0]
    x = reference.rms_norm(h, p["ln1"], cfg.eps)
    a = p["attn"]
    q = mm("sd,dn->sn", x, a["wq"], quant).reshape(s, cfg.heads, cfg.head_dim)
    k = mm("sd,dn->sn", x, a["wk"], quant).reshape(s, cfg.kv_heads, cfg.head_dim)
    v = mm("sd,dn->sn", x, a["wv"], quant).reshape(s, cfg.kv_heads, cfg.head_dim)
    q, k = reference.rope(q, cfg.rope_theta), reference.rope(k, cfg.rope_theta)
    h = h + mm("sn,nd->sd", reference.attention(q, k, v, quant), a["wo"], quant)
    x = reference.rms_norm(h, p["ln2"], cfg.eps)
    c = GLU_CHUNK if cfg.d_ff % GLU_CHUNK == 0 else cfg.d_ff
    u = mm("sd,df->sf", x, p["mlp"]["w_in"], quant).reshape(s, cfg.d_ff // c, 2, c)
    glu = (jax.nn.silu(u[:, :, 0]) * u[:, :, 1]).reshape(s, cfg.d_ff)
    return h + mm("sf,fd->sd", glu, p["mlp"]["w_out"], quant)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _final_norm(h, p, cfg: RefCfg):
    return reference.rms_norm(h, p, cfg.eps)


def final_hidden(weights, cfg: RefCfg, tokens: np.ndarray, quant=None) -> jax.Array:
    """Final-norm hidden states (S, D) of one token sequence."""
    h = reference.embed(weights["embed"], jnp.asarray(tokens, jnp.int32))
    for l in range(cfg.layers):
        h = _layer(h, weights["groups"]["0"], jnp.int32(l), cfg, quant)
    return _final_norm(h, weights["final_norm"], cfg)


@dataclasses.dataclass(frozen=True)
class Dims:
    """Operations (a multiply-add is two) and least bytes of this decoder."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    dtype_bytes: int

    def _linear(self) -> int:       # weights of one layer's matmuls
        d, hd = self.d_model, self.head_dim
        return d * (self.heads + 2 * self.kv_heads) * hd + self.heads * hd * d + \
            3 * d * self.d_ff

    def attention_flops(self, attended: int) -> int:
        return 4 * self.heads * self.head_dim * attended

    def prefill_flops(self, n: int) -> int:
        return self.layers * (2 * n * self._linear()
                              + self.attention_flops(1) * n * (n + 1) // 2) + \
            2 * self.d_model * self.vocab

    def decode_flops(self, attended: list[int]) -> int:
        b = len(attended)
        return self.layers * (2 * b * self._linear()
                              + sum(self.attention_flops(c) for c in attended)) + \
            2 * self.d_model * self.vocab * b

    def decode_bytes(self, attended: list[int]) -> int:
        b, d = len(attended), self.d_model
        weights = self.layers * (self._linear() + 2 * d) + d + d * self.vocab
        kv_row = self.layers * 2 * self.kv_heads * self.head_dim
        return self.dtype_bytes * (weights + b * d + kv_row * (sum(attended) + b))


def dims(m: dict) -> Dims:
    return Dims(layers=m["num_hidden_layers"], d_model=m["hidden_size"],
                heads=m["num_attention_heads"], kv_heads=m["num_key_value_heads"],
                head_dim=m["head_dim"], d_ff=m["intermediate_size"],
                vocab=m["vocab_size"],
                dtype_bytes={"bfloat16": 2, "float32": 4}[m["torch_dtype"]])
