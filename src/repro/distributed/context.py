"""Activation-sharding context: how the launcher injects the residual-stream
constraint into model code without threading mesh objects through every
layer.  ``set_activation_sharding`` is called before tracing (dry-run,
trainer); ``constrain`` is a no-op when unset (single-device tests)."""
from __future__ import annotations

import contextlib
import threading

import jax

_tls = threading.local()


def set_activation_sharding(sharding) -> None:
    _tls.sharding = sharding


@contextlib.contextmanager
def activation_sharding(sharding):
    prev = getattr(_tls, "sharding", None)
    set_activation_sharding(sharding)
    try:
        yield
    finally:
        set_activation_sharding(prev)


def constrain(x: jax.Array) -> jax.Array:
    """Apply the residual-stream constraint to a (B, S, D) activation."""
    s = getattr(_tls, "sharding", None)
    if s is None or x.ndim != 3:
        return x
    return jax.lax.with_sharding_constraint(x, s)


# -- named internal-activation rules (set by the launcher per mesh/config) --


def set_sharding_rules(rules: dict | None) -> None:
    """rules: name -> jax.sharding.Sharding for named internal activations
    (e.g. 'moe_buf' for the MoE dispatch buffer).  Unset names are no-ops."""
    _tls.rules = rules or {}


def sharded() -> bool:
    """Whether a mesh's constraints are set: the residual-stream sharding or
    any named rule.  Code with a layout the rules do not cover (the ragged
    MoE dispatch) takes its sharded form then."""
    return (getattr(_tls, "sharding", None) is not None
            or bool(getattr(_tls, "rules", None)))


def constrain_named(x: jax.Array, name: str) -> jax.Array:
    rules = getattr(_tls, "rules", None)
    if not rules or name not in rules:
        return x
    return jax.lax.with_sharding_constraint(x, rules[name])


# -- remat policy (set by the launcher; models read it at trace time) -------


def set_remat_policy(name: str | None) -> None:
    """'full' (default: recompute everything, save residual boundaries only)
    or 'dots' (save matmul outputs: −25% train compute for +activation HBM —
    pair with gradient accumulation; see EXPERIMENTS.md §Perf)."""
    _tls.remat_policy = name


def remat_policy():
    name = getattr(_tls, "remat_policy", None) or "full"
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint_policies.nothing_saveable
