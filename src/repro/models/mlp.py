"""MLP and Mixture-of-Experts blocks.

Dense MLPs: gated (swiglu/geglu, interleaved-packed for the fused kernel)
and plain gelu (optionally biased — starcoder2/whisper).

MoE: a softmax router, the top-k experts of each token with their weights
renormalised, then one of two dispatches, chosen by whether a mesh's
sharding constraints are set (:func:`repro.distributed.context.sharded`):

* **dropless** (one device: training, eval loss and every serving path):
  the token-expert pairs are sorted by expert and the routed rows go
  through the ragged expert GEMMs (:func:`repro.kernels.ops.moe_gemm` with
  ``group_sizes``), each expert over exactly the rows routed to it; no pair
  is dropped and no (E, capacity, D) buffer exists.  Pairs of pad tokens
  (``valid`` false) route to no expert and take no rows.
* **capacity** (on a mesh: the sharded train step and dry runs):
  token-dropping sort-based dispatch (Megablocks/MaxText style, adapted to
  XLA): pairs sorted by expert id, packed into a fixed (E, capacity, D)
  buffer (overflow drops), pushed through grouped GEMMs, and combined back
  with router weights.  This avoids the O(T·E·cap) GShard dispatch mask —
  the structure that makes 4k×256-token MoE layers compile at dbrx/mixtral
  scale — and its buffer and output are what the sharding rules pin
  (``moe_buf``, ``moe_out`` in ``distributed/sharding.py``); the ragged
  rows have no such rule.  Capacity factor 1.25 by default; below 4096
  pairs the capacity is every token, so nothing drops there.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.context import constrain_named, sharded
from repro.kernels import ops
from repro.models.common import dense_init, dtype_of, glu_init

CAPACITY_FACTOR = 1.25


# ---------------------------------------------------------------------------
# Dense MLP
# ---------------------------------------------------------------------------


def mlp_params(key: jax.Array, cfg: ArchConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    dt = dtype_of(cfg.dtype)
    k1, k2 = jax.random.split(key)
    if cfg.mlp_kind in ("swiglu", "geglu"):
        p = {"w_in": glu_init(k1, d, f, dt), "w_out": dense_init(k2, f, d, dt)}
    else:
        p = {"w_in": dense_init(k1, d, f, dt), "w_out": dense_init(k2, f, d, dt)}
        if cfg.mlp_bias:
            p["b_in"] = jnp.zeros((f,), dt)
            p["b_out"] = jnp.zeros((d,), dt)
    return p


def mlp_apply(p: dict, cfg: ArchConfig, x: jax.Array, provider=None) -> jax.Array:
    if cfg.mlp_kind == "swiglu":
        h = ops.matmul(x, p["w_in"], class_id="matmul_silu_glu", provider=provider)
        return ops.matmul(h, p["w_out"], provider=provider)
    if cfg.mlp_kind == "geglu":
        h = ops.matmul(x, p["w_in"], class_id="matmul_gelu_glu", provider=provider)
        return ops.matmul(h, p["w_out"], provider=provider)
    bias_in = p.get("b_in")
    bias_out = p.get("b_out")
    h = ops.matmul(x, p["w_in"], class_id="matmul_bias_gelu", bias=bias_in, provider=provider)
    cls = "matmul_bias" if bias_out is not None else "matmul"
    return ops.matmul(h, p["w_out"], class_id=cls, bias=bias_out, provider=provider)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------


def moe_params(key: jax.Array, cfg: ArchConfig) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    dt = dtype_of(cfg.dtype)
    kr, ki, ko = jax.random.split(key, 3)
    w_in = jnp.stack([glu_init(k, d, f, dt) for k in jax.random.split(ki, e)])
    w_out = jnp.stack([dense_init(k, f, d, dt) for k in jax.random.split(ko, e)])
    return {
        "router": dense_init(kr, d, e, jnp.float32),  # router kept f32
        "w_in": w_in,    # (E, D, 2F) interleaved glu packing
        "w_out": w_out,  # (E, F, D)
    }


def moe_apply(p: dict, cfg: ArchConfig, x: jax.Array, provider=None,
              capacity_factor: float = CAPACITY_FACTOR, *,
              valid: jax.Array | None = None) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D). Returns (out, aux_loss) — aux is the load-balance loss.
    The dispatch is the module docstring's; ``valid`` (B, S), if given,
    marks the real tokens (dropless only): a pad token's output is zero."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.moe_topk
    t = b * s
    xf = x.reshape(t, d)

    logits = ops.matmul(xf.astype(jnp.float32), p["router"].astype(jnp.float32),
                        class_id="moe_router", provider=provider)   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)                 # (T, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # Load-balance auxiliary loss (Switch-style): E * Σ_e f_e · p_e
    me = probs.mean(axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[expert_idx.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * ce)

    if sharded():
        out = _capacity(p, xf, expert_idx, gate_vals, e, capacity_factor, provider)
    else:
        out = _dropless(p, xf, expert_idx, gate_vals, e,
                        None if valid is None else valid.reshape(t), provider)
    return out.reshape(b, s, d), aux


def _capacity(p: dict, xf: jax.Array, expert_idx: jax.Array, gate_vals: jax.Array,
              e: int, capacity_factor: float, provider) -> jax.Array:
    """The sort-based capacity dispatch: xf (T, D), expert_idx and gate_vals
    (T, k) -> (T, D), pairs past an expert's capacity dropped."""
    (t, d), k = xf.shape, expert_idx.shape[1]
    # Dropless for small token counts (decode steps, small eval batches):
    # worst-case per-expert load is `t`, so cap=t guarantees no drops there.
    # At training scale the usual capacity-factor dropping applies.
    if t * k <= 4096:
        cap = t
    else:
        cap = int(max(1, round(t * k / e * capacity_factor)))
    flat_e = expert_idx.reshape(-1)                                  # (T*k,)
    flat_t = jnp.repeat(jnp.arange(t), k)
    flat_g = gate_vals.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    counts = jnp.zeros((e,), jnp.int32).at[se].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(t * k) - starts[se]
    keep = pos < cap
    slot = jnp.where(keep, se * cap + pos, e * cap)                  # overflow row

    buf = jnp.zeros((e * cap + 1, d), xf.dtype).at[slot].set(xf[st])
    buf = buf[:-1].reshape(e, cap, d)
    # Pin the dispatch buffer's layout: without this, GSPMD materializes the
    # scatter through a replicated buffer + all-reduce per layer (measured
    # ~160 GiB/step on mixtral train_4k — see EXPERIMENTS.md §Perf).
    buf = constrain_named(buf, "moe_buf")

    h = ops.moe_gemm(buf, p["w_in"], class_id="moe_gemm_silu_glu", provider=provider)
    y = ops.moe_gemm(h, p["w_out"], class_id="moe_gemm", provider=provider)  # (E, cap, D)
    y = constrain_named(y, "moe_buf")

    y_flat = y.reshape(e * cap, d)
    contrib = jnp.where(keep, sg, 0.0)[:, None].astype(xf.dtype)
    gathered = y_flat[jnp.where(keep, se * cap + pos, 0)] * contrib
    out = jnp.zeros((t, d), xf.dtype).at[st].add(gathered)
    return constrain_named(out, "moe_out")   # combine lands in the token layout


def _dropless(p: dict, xf: jax.Array, expert_idx: jax.Array, gates: jax.Array,
              e: int, valid: jax.Array | None, provider) -> jax.Array:
    """Every token-expert pair through the ragged expert GEMMs: xf (T, D),
    expert_idx and gates (T, k) -> (T, D)."""
    t, k = expert_idx.shape
    flat_e = expert_idx.reshape(-1)                                  # (T*k,)
    if valid is not None:          # a pad token's pairs go to no expert (e)
        flat_e = jnp.where(jnp.repeat(valid, k), flat_e, e)
    order = jnp.argsort(flat_e, stable=True)          # by expert, pads last
    sizes = jnp.zeros((e,), jnp.int32).at[flat_e].add(1, mode="drop")
    rows = xf[order // k]                             # the routed rows, sorted
    h = ops.moe_gemm(rows, p["w_in"], group_sizes=sizes,
                     class_id="moe_gemm_silu_glu", provider=provider)
    y = ops.moe_gemm(h, p["w_out"], group_sizes=sizes, class_id="moe_gemm",
                     provider=provider)
    y = y[jnp.argsort(order)]                         # back to pair order
    # a pad pair's row is no expert's: the Pallas kernel may leave it unwritten
    routed = (flat_e < e)[:, None]
    y = jnp.where(routed, y.astype(jnp.float32), 0.0).reshape(t, k, -1)
    return jnp.einsum("tk,tkd->td", gates, y).astype(xf.dtype)
