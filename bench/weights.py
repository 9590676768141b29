"""Random weights from the run's seed, made on the device in one jitted call.

The benchmark makes the weights itself, in the program's parameter layout
as the configuration's architecture module gives it (``layout`` of
``bench/models/<name>.py``), so that the plain reference reads weights that
the program did not make.  Every leaf is one normal draw, ``(shape, mean,
std)``, in the served dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed32(seed: int, stream: int = 0) -> int:
    """A 32-bit key word for ``seed`` of any size (JAX keeps only the low
    32 bits of a larger seed, so large seeds would collide)."""
    return int(np.random.SeedSequence([seed, stream]).generate_state(1)[0])


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def init_fn(arch, m: dict):
    """The function from a PRNG key to the weights of configuration ``m``
    (a config file's ``model`` block) laid out by the module ``arch``."""
    leaves, treedef = jax.tree_util.tree_flatten(arch.layout(m), is_leaf=_is_leaf)
    dtype = jnp.dtype(m["torch_dtype"])

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, mean, std) in zip(keys, leaves):
            x = jax.random.normal(k, shape, jnp.float32) * std
            if mean:
                x = x + mean
            out.append(x.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return build


def make(arch, m: dict, seed: int):
    """The weights of configuration ``m`` for ``seed``, on the default
    device, in one jitted call."""
    return jax.jit(init_fn(arch, m))(jax.random.PRNGKey(seed32(seed)))
