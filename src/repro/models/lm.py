"""Decoder-only LM stack covering dense / MoE / SSM / hybrid / VLM families.

Layers follow ``cfg.layer_pattern`` (e.g. gemma2 ("L","G"), griffin
("R","R","L")).  The stack is executed as ``jax.lax.scan`` over *pattern
groups* — params are stacked with leading dim = full pattern repeats — plus
explicit tail layers for the remainder (griffin's 26 = 8×3 + 2).  Scan keeps
the HLO (and compile time) independent of depth; the group body is wrapped
in ``jax.checkpoint`` for training (save-residual-boundaries remat policy).
The serving scans (prefill, chunked prefill, verify, decode) scan over the
layer index instead and close over the stacks, so the matmul kernel reads
each layer's projections in place rather than a sliced copy of them; the
decode scan also carries the attention caches' stacks, so the decode
attention kernel reads each layer's cache in place and the new rows are
written into the stacks.

Three entry points (built per-config by :mod:`repro.models.build`):
  forward(params, batch)          — full-sequence logits (+aux), train/eval
  prefill(params, batch, max_len) — logits of last position + filled cache
  decode_step(params, cache, tok) — one token, updated cache
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.context import constrain, remat_policy, sharded
from repro.kernels import ops
from repro.models import attention as attn
from repro.models import mlp as mlpm
from repro.models import recurrent as rec
from repro.models.common import apply_norm, dense_init, dtype_of, embed_init, norm_params


# ---------------------------------------------------------------------------
# Per-block params / apply
# ---------------------------------------------------------------------------


def block_params(key: jax.Array, cfg: ArchConfig, kind: str) -> dict:
    if kind == "R":
        if cfg.family == "ssm":
            return rec.rwkv_params(key, cfg)
        k1, k2, k3, k4 = jax.random.split(key, 4)
        return {
            "ln1": norm_params(cfg.d_model, cfg.norm, dtype_of(cfg.dtype)),
            "rnn": rec.griffin_params(k1, cfg),
            "ln2": norm_params(cfg.d_model, cfg.norm, dtype_of(cfg.dtype)),
            "mlp": mlpm.mlp_params(k2, cfg),
        }
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": norm_params(cfg.d_model, cfg.norm, dtype_of(cfg.dtype)),
        "attn": attn.attn_params(k1, cfg),
        "ln2": norm_params(cfg.d_model, cfg.norm, dtype_of(cfg.dtype)),
    }
    if cfg.n_experts > 0:
        p["moe"] = mlpm.moe_params(k2, cfg)
    else:
        p["mlp"] = mlpm.mlp_params(k2, cfg)
    return p


def apply_block(p: dict, cfg: ArchConfig, kind: str, x: jax.Array, *,
                positions: jax.Array | None, pos: jax.Array | None,
                cache: dict | None, decode: bool, off: jax.Array | None = None,
                verify: bool = False, true_len=None,
                provider=None) -> tuple[jax.Array, dict | None, jax.Array]:
    """Returns (x, new_cache, aux_loss).  ``off`` selects the chunked-prefill
    attention path: the slice starts at absolute position ``off`` against a
    partially filled cache (recurrent blocks already carry state through
    their cache, so R layers need no separate chunk path).  ``verify``
    reinterprets ``off`` as per-lane (B,) offsets for the speculative
    verify path (attention layers only).  ``true_len`` (prefill) counts the
    real positions of a right-padded prompt: pad tokens route to no expert."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "R":
        if verify:
            raise ValueError("speculative verify does not support recurrent layers")
        if cfg.family == "ssm":
            x, c = rec.rwkv_block(p, cfg, x, cache=cache, provider=provider)
            return x, c, aux
        xn = apply_norm(p["ln1"], x, cfg.norm)
        out, c = rec.griffin_block(p["rnn"], cfg, xn, cache=cache, provider=provider)
        x = constrain(x + out)
        xn2 = apply_norm(p["ln2"], x, cfg.norm)
        x = constrain(x + mlpm.mlp_apply(p["mlp"], cfg, xn2, provider=provider))
        return x, c, aux

    xn = apply_norm(p["ln1"], x, cfg.norm)
    if decode:
        a, c = attn.attn_decode(p["attn"], cfg, xn, kind, pos=pos, cache=cache,
                                provider=provider)
    elif verify:
        a, c = attn.attn_verify(p["attn"], cfg, xn, kind, off=off, cache=cache,
                                provider=provider)
    elif off is not None:
        a, c = attn.attn_chunk(p["attn"], cfg, xn, kind, positions=positions,
                               off=off, cache=cache, provider=provider)
    else:
        a, c = attn.attn_forward(p["attn"], cfg, xn, kind, positions=positions,
                                 cache=cache, true_len=true_len,
                                 provider=provider)
    # constrain the residual after every sub-block: otherwise GSPMD
    # replicates intermediate residuals inside multi-layer pattern groups
    # and pays full all-reduces instead of staying D-sharded (§Perf it-6)
    x = constrain(x + a)
    xn2 = apply_norm(p["ln2"], x, cfg.norm)
    if cfg.n_experts > 0:
        valid = None if true_len is None else positions < true_len
        y, aux = mlpm.moe_apply(p["moe"], cfg, xn2, provider=provider, valid=valid)
        x = constrain(x + y)
    else:
        x = constrain(x + mlpm.mlp_apply(p["mlp"], cfg, xn2, provider=provider))
    return x, c, aux


def init_block_cache(cfg: ArchConfig, kind: str, batch: int, max_len: int) -> dict:
    if kind == "R":
        if cfg.family == "ssm":
            return rec.init_rwkv_cache(cfg, batch)
        return rec.init_griffin_cache(cfg, batch)
    return attn.init_attn_cache(cfg, kind, batch, max_len)


# ---------------------------------------------------------------------------
# Stack construction
# ---------------------------------------------------------------------------


def _pattern_split(cfg: ArchConfig) -> tuple[tuple[str, ...], int, tuple[str, ...]]:
    pat = cfg.layer_pattern
    reps, rem = divmod(cfg.n_layers, len(pat))
    return pat, reps, pat[:rem]


def _layer_params(groups: dict, l: jax.Array) -> dict:
    """Layer ``l`` of the stacked group params, for the serving scans.  The
    dense projections of attention and MLP blocks (the 3-D leaves of every
    ``attn`` / ``mlp`` group) and the expert stacks of MoE blocks (the 4-D
    leaves of ``moe``) become :class:`~repro.kernels.ops.LayerRef`, which
    the Pallas matmuls read in place from the stack; the rest (norm scales,
    biases, the router, recurrent leaves) is sliced: small, or read by code
    other than the matmul."""
    def take(leaf):
        return jax.lax.dynamic_index_in_dim(leaf, l, keepdims=False)

    in_place = {"attn": 3, "mlp": 3, "moe": 4}    # block -> stacked ndim

    def block(stacked: dict) -> dict:
        return {name: ({k: ops.LayerRef(w, l) if w.ndim == in_place[name]
                        else take(w) for k, w in sub.items()}
                       if name in in_place
                       else jax.tree_util.tree_map(take, sub))
                for name, sub in stacked.items()}

    return {i: block(stacked) for i, stacked in groups.items()}


def init_params(key: jax.Array, cfg: ArchConfig) -> dict:
    pat, reps, tail = _pattern_split(cfg)
    keys = jax.random.split(key, 8)
    dt = dtype_of(cfg.dtype)
    params: dict[str, Any] = {"embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dt)}
    if cfg.vision_tokens:
        params["vis_proj"] = dense_init(keys[1], cfg.d_model, cfg.d_model, dt)

    group: dict[str, Any] = {}
    gkeys = jax.random.split(keys[2], max(reps, 1) * len(pat)).reshape(max(reps, 1), len(pat), 2)
    for i, kind in enumerate(pat):
        layers = [block_params(gkeys[r, i], cfg, kind) for r in range(reps)]
        group[str(i)] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers) if layers else {}
    params["groups"] = group
    params["tail"] = [
        block_params(k, cfg, kind)
        for k, kind in zip(jax.random.split(keys[3], max(len(tail), 1)), tail)
    ]
    params["final_norm"] = norm_params(cfg.d_model, cfg.norm, dt)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[4], cfg.d_model, cfg.vocab_size, dt)
    return params


def _lm_head(params: dict, cfg: ArchConfig, h: jax.Array, provider=None) -> jax.Array:
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    if cfg.final_softcap > 0:
        return ops.matmul(h, w, class_id="matmul_lmhead_softcap",
                          softcap=cfg.final_softcap, provider=provider)
    return ops.matmul(h, w, class_id="matmul_lmhead", provider=provider)


def _embed(params: dict, cfg: ArchConfig, tokens: jax.Array) -> jax.Array:
    h = params["embed"][tokens]
    if cfg.tie_embeddings:  # gemma-family embedding scaling
        h = (h.astype(jnp.float32) * cfg.d_model ** 0.5).astype(h.dtype)
    return h


# ---------------------------------------------------------------------------
# Full-sequence pass (train / eval / prefill)
# ---------------------------------------------------------------------------


def _stack_pass(params: dict, cfg: ArchConfig, h: jax.Array, *,
                positions: jax.Array, caches: dict | None, remat: bool,
                off: jax.Array | None = None, verify: bool = False,
                true_len=None,
                provider=None) -> tuple[jax.Array, dict | None, jax.Array]:
    """Run all layers. caches: {"groups": {i: stacked}, "tail": [...]} or None.
    ``off`` (with caches) runs the chunked-prefill path for attention layers;
    ``verify`` the speculative verify path (``off`` per-lane); ``true_len``
    the real length of a right-padded prefill."""
    pat, reps, tail = _pattern_split(cfg)

    def group_body(carry, xs):
        hh, aux = carry
        layer_params, layer_cache = xs
        new_cache = {}
        for i, kind in enumerate(pat):
            c_in = layer_cache[str(i)] if layer_cache is not None else None
            hh, c_out, a = apply_block(layer_params[str(i)], cfg, kind, hh,
                                       positions=positions, pos=None, cache=c_in,
                                       decode=False, off=off, verify=verify,
                                       true_len=true_len, provider=provider)
            aux = aux + a
            if c_out is not None:
                new_cache[str(i)] = c_out
        return (constrain(hh), aux), new_cache

    body = jax.checkpoint(group_body, policy=remat_policy()) if remat else group_body

    aux = jnp.zeros((), jnp.float32)
    new_caches = {"groups": {}, "tail": []} if caches is not None else None
    if reps > 0:
        if caches is None:
            (h, aux), _ = jax.lax.scan(
                lambda c, lp: body(c, (lp, None)), (h, aux), params["groups"]
            )
        else:
            # the stacked weights are closed over, not scanned: each layer's
            # projections are read in place (_layer_params)
            def serve_body(carry, xs):
                l, layer_cache = xs
                return body(carry, (_layer_params(params["groups"], l),
                                    layer_cache))

            (h, aux), ys = jax.lax.scan(serve_body, (h, aux),
                                        (jnp.arange(reps), caches["groups"]))
            new_caches["groups"] = ys
    for j, kind in enumerate(tail):
        c_in = caches["tail"][j] if caches is not None else None
        h, c_out, a = apply_block(params["tail"][j], cfg, kind, h,
                                  positions=positions, pos=None, cache=c_in,
                                  decode=False, off=off, verify=verify,
                                  true_len=true_len, provider=provider)
        aux = aux + a
        if caches is not None:
            new_caches["tail"].append(c_out)
    return h, new_caches, aux


def forward(params: dict, cfg: ArchConfig, batch: dict, *, remat: bool = True,
            provider=None) -> tuple[jax.Array, jax.Array]:
    """Full-sequence logits. batch: tokens (B,S) [+ patch_embeds (B,P,D)].
    Returns (logits over the full (vlm-prefixed) sequence, aux_loss)."""
    tokens = batch["tokens"]
    h = _embed(params, cfg, tokens)
    if cfg.vision_tokens:
        vis = ops.matmul(batch["patch_embeds"].astype(h.dtype), params["vis_proj"],
                         provider=provider)
        h = jnp.concatenate([vis, h], axis=1)
    b, s, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    h, _, aux = _stack_pass(params, cfg, h, positions=positions, caches=None,
                            remat=remat, provider=provider)
    h = apply_norm(params["final_norm"], h, cfg.norm)
    return _lm_head(params, cfg, h, provider=provider), aux


def loss_fn(params: dict, cfg: ArchConfig, batch: dict, *, remat: bool = True,
            provider=None) -> tuple[jax.Array, dict]:
    logits, aux = forward(params, cfg, batch, remat=remat, provider=provider)
    p = cfg.vision_tokens
    tokens = batch["tokens"]
    if p:
        pred = logits[:, p - 1:-1, :]   # positions P-1 .. P+S-2 predict tokens 0..S-1
        tgt = tokens
    else:
        pred = logits[:, :-1, :]
        tgt = tokens[:, 1:]
    logp = jax.nn.log_softmax(pred.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1).squeeze(-1)
    mask = batch.get("mask")
    if mask is not None:
        m = (mask[:, 1:] if not p else mask).astype(jnp.float32)
        ce = (nll * m).sum() / jnp.maximum(m.sum(), 1.0)
    else:
        ce = nll.mean()
    total = ce + 0.01 * aux
    return total, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> dict:
    """max_len counts *text* positions; the vision prefix is added here."""
    max_len = max_len + cfg.vision_tokens
    pat, reps, tail = _pattern_split(cfg)
    groups = {}
    for i, kind in enumerate(pat):
        layers = [init_block_cache(cfg, kind, batch, max_len) for _ in range(reps)]
        groups[str(i)] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers) if layers else {}
    return {
        "groups": groups,
        "tail": [init_block_cache(cfg, kind, batch, max_len) for kind in tail],
        "t": jnp.zeros((batch,), jnp.int32),   # per-slot decode positions
    }


def prefill(params: dict, cfg: ArchConfig, batch: dict, *, max_len: int,
            provider=None, true_len=None) -> tuple[jax.Array, dict]:
    """Process the prompt; returns (last-position logits, cache).

    ``true_len`` (static or traced int) marks the number of *real* text
    tokens when the prompt is right-padded to a trace bucket: logits come
    from the last real position and the cache's decode position starts
    there.  Right padding is inert for causal attention (real positions
    never attend to pads; pad cache rows sit beyond the decode position and
    are overwritten before they become visible), a ring cache keeps the
    last true positions, never pads, and pad tokens route to no expert."""
    tokens = batch["tokens"]
    h = _embed(params, cfg, tokens)
    if cfg.vision_tokens:
        vis = ops.matmul(batch["patch_embeds"].astype(h.dtype), params["vis_proj"],
                         provider=provider)
        h = jnp.concatenate([vis, h], axis=1)
    b, s, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    caches = init_cache(cfg, b, max_len)
    t = None if true_len is None else (jnp.asarray(true_len, jnp.int32)
                                       + cfg.vision_tokens)
    h, new_caches, _ = _stack_pass(params, cfg, h, positions=positions,
                                   caches=caches, remat=False, true_len=t,
                                   provider=provider)
    if t is None:
        t = jnp.asarray(s, jnp.int32)
        h_last = h[:, -1:, :]
    else:
        h_last = jax.lax.dynamic_slice_in_dim(h, t - 1, 1, axis=1)
    new_caches["t"] = jnp.full((b,), t, jnp.int32)
    h_last = apply_norm(params["final_norm"], h_last, cfg.norm)
    logits = _lm_head(params, cfg, h_last, provider=provider)
    return logits[:, 0, :], new_caches


def prefill_chunk(params: dict, cfg: ArchConfig, cache: dict, tokens: jax.Array,
                  off, *, provider=None) -> tuple[jax.Array, dict]:
    """Process one prompt chunk against a partially filled cache.

    ``tokens``: (B, C) — the prompt slice covering absolute positions
    ``off .. off+C-1``; ``off`` may be traced, so one trace per chunk
    *length* serves every offset (the paged engine always runs the final
    chunk at its exact remainder length — no padding anywhere, which both
    eliminates padding waste and keeps ring/recurrent state exact).

    Returns (last-position logits (B, V), updated cache).  Calling with
    ``off=0`` then successive offsets is numerically identical to one-shot
    :func:`prefill` — the equivalence tests assert it bit-exactly.
    """
    if cfg.vision_tokens:
        raise ValueError("chunked prefill does not support vision-prefix archs")
    b, s = tokens.shape
    off = jnp.asarray(off, jnp.int32)
    h = _embed(params, cfg, tokens)
    positions = jnp.broadcast_to(off + jnp.arange(s, dtype=jnp.int32), (b, s))
    h, new_caches, _ = _stack_pass(params, cfg, h, positions=positions,
                                   caches=cache, remat=False, off=off,
                                   provider=provider)
    new_caches["t"] = jnp.full((b,), off + s, jnp.int32)
    h_last = apply_norm(params["final_norm"], h[:, -1:, :], cfg.norm)
    logits = _lm_head(params, cfg, h_last, provider=provider)
    return logits[:, 0, :], new_caches


def verify_step(params: dict, cfg: ArchConfig, cache: dict, tokens: jax.Array,
                off, *, provider=None) -> tuple[jax.Array, dict]:
    """Speculative verify: run ``tokens`` (B, C) — the pending token plus the
    draft burst — through the stack at per-lane absolute offsets ``off``
    (B,), returning logits for *every* position (B, C, V) plus the updated
    cache.

    ``logits[:, j]`` is the target distribution after the first ``j`` draft
    tokens, so greedy acceptance compares ``argmax(logits[:, j])`` against
    draft token ``j+1``.  The cache gains all C rows; rejected rows are
    "rolled back" implicitly — validity masks hide rows at or beyond each
    lane's committed length, and later bursts overwrite them in order
    (full-length caches only; see :func:`repro.models.attention.attn_verify`).
    """
    if cfg.vision_tokens:
        raise ValueError("speculative verify does not support vision-prefix archs")
    b, s = tokens.shape
    off = jnp.broadcast_to(jnp.asarray(off, jnp.int32), (b,))
    h = _embed(params, cfg, tokens)
    positions = off[:, None] + jnp.arange(s, dtype=jnp.int32)
    h, new_caches, _ = _stack_pass(params, cfg, h, positions=positions,
                                   caches=cache, remat=False, off=off,
                                   verify=True, provider=provider)
    new_caches["t"] = off + s
    h = apply_norm(params["final_norm"], h, cfg.norm)
    logits = _lm_head(params, cfg, h, provider=provider)
    return logits, new_caches


def decode_step(params: dict, cfg: ArchConfig, cache: dict, tokens: jax.Array, *,
                provider=None) -> tuple[jax.Array, dict]:
    """tokens: (B,) — one new token per sequence. Returns (logits (B,V), cache).

    On one device the layer scan carries the stacked K and V of every
    attention group of the pattern: each layer writes its B new rows into
    the stacks at its index and reads its cache in place through
    :class:`~repro.kernels.ops.LayerRef` (``attn.attn_decode``), so no
    layer's cache is sliced out or restacked.  Jitted with the cache donated
    (``ServingEngine``), the step updates it in place: a caller must not
    read the cache it passed in again.  Recurrent states (``R`` groups) are
    scanned and restacked, and tail layers take their plain caches, as they
    are small.  Under a mesh every group's caches are scanned and
    restacked."""
    pat, reps, tail = _pattern_split(cfg)
    pos = cache["t"]
    h = _embed(params, cfg, tokens[:, None])
    carried = {} if sharded() else {
        str(i): cache["groups"][str(i)] for i, kind in enumerate(pat) if kind != "R"}
    scanned = {i: c for i, c in cache["groups"].items() if i not in carried}

    def group_body(carry, xs):
        hh, stacks = carry
        l, layer_cache = xs
        layer_params = _layer_params(params["groups"], l)
        new_stacks, new_cache = {}, {}
        for i, kind in enumerate(pat):
            key = str(i)
            c_in = ({name: ops.LayerRef(s, l) for name, s in stacks[key].items()}
                    if key in stacks else layer_cache[key])
            hh, c_out, _ = apply_block(layer_params[key], cfg, kind, hh,
                                       positions=None, pos=pos, cache=c_in,
                                       decode=True, provider=provider)
            if key in stacks:
                new_stacks[key] = {name: r.stack for name, r in c_out.items()}
            else:
                new_cache[key] = c_out
        return (hh, new_stacks), new_cache

    new_cache = {"groups": {}, "tail": [], "t": pos + 1}
    if reps > 0:
        (h, stacks), ys = jax.lax.scan(group_body, (h, carried),
                                       (jnp.arange(reps), scanned))
        new_cache["groups"] = {**ys, **stacks}
    for j, kind in enumerate(tail):
        h, c_out, _ = apply_block(params["tail"][j], cfg, kind, h,
                                  positions=None, pos=pos, cache=cache["tail"][j],
                                  decode=True, provider=provider)
        new_cache["tail"].append(c_out)
    h = apply_norm(params["final_norm"], h, cfg.norm)
    logits = _lm_head(params, cfg, h, provider=provider)
    return logits[:, 0, :], new_cache
