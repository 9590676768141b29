"""Random weights from the run's seed, made on the device in one jitted call.

The benchmark makes the weights itself, in the program's parameter layout
(a dense GQA stack scanned over layers: leaves of the layer group carry a
leading layer axis), so that the plain reference reads weights that the
program did not make.  Every leaf is drawn in the served dtype.  Scales keep
activations near unit size: projections N(0, 1/fan_in), embedding rows
N(0, 1), and norm scales, norm biases and MLP biases drawn around their
neutral values so that a path that drops one of them shows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed32(seed: int, stream: int = 0) -> int:
    """A 32-bit key word for ``seed`` of any size (JAX keeps only the low
    32 bits of a larger seed, so large seeds would collide)."""
    return int(np.random.SeedSequence([seed, stream]).generate_state(1)[0])


def layout(m: dict) -> dict:
    """Leaf shapes and how to draw each: ``(shape, kind, std)`` with kind
    ``normal`` (mean 0), ``scale`` (mean 1 for a layer norm, 0 for the
    program's (1 + scale) RMS norm) or ``bias``."""
    L, D, F, V = (m["num_hidden_layers"], m["hidden_size"],
                  m["intermediate_size"], m["vocab_size"])
    H, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    layer_norm = m["norm_type"] == "layer_norm"

    def norm(*lead):
        out = {"scale": (lead + (D,), "scale", 0.1)}
        if layer_norm:
            out["bias"] = (lead + (D,), "bias", 0.1)
        return out

    mlp = {"w_in": ((L, D, F), "normal", D ** -0.5),
           "w_out": ((L, F, D), "normal", F ** -0.5)}
    if m.get("use_bias"):
        mlp["b_in"] = ((L, F), "bias", 0.1)
        mlp["b_out"] = ((L, D), "bias", 0.1)
    tree = {
        "embed": ((V, D), "normal", 1.0),
        "groups": {"0": {
            "ln1": norm(L),
            "attn": {"wq": ((L, D, H * hd), "normal", D ** -0.5),
                     "wk": ((L, D, KV * hd), "normal", D ** -0.5),
                     "wv": ((L, D, KV * hd), "normal", D ** -0.5),
                     "wo": ((L, H * hd, D), "normal", (H * hd) ** -0.5)},
            "ln2": norm(L),
            "mlp": mlp,
        }},
        "tail": [],
        "final_norm": norm(),
    }
    if not m.get("tie_word_embeddings"):
        tree["lm_head"] = ((D, V), "normal", D ** -0.5)
    return tree


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], str)


def init_fn(m: dict):
    """The function from a PRNG key to the weights of configuration ``m``
    (a config file's ``model`` block)."""
    spec = layout(m)
    leaves, treedef = jax.tree_util.tree_flatten(spec, is_leaf=_is_leaf)
    dtype = jnp.dtype(m["torch_dtype"])
    layer_norm = m["norm_type"] == "layer_norm"

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, kind, std) in zip(keys, leaves):
            x = jax.random.normal(k, shape, jnp.float32) * std
            if kind == "scale" and layer_norm:
                x = x + 1.0
            out.append(x.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return build


def make(m: dict, seed: int):
    """The weights of configuration ``m`` for ``seed``, on the default
    device, in one jitted call."""
    return jax.jit(init_fn(m))(jax.random.PRNGKey(seed32(seed)))
