"""Mixture-of-experts decoder with sliding-window and full attention layers
(mellum2-12b-a2.5b): what the benchmark needs to know of this architecture.

The equations are those of the configuration file's ``model`` block, which
copies the source's ``config.json``.  Token embedding; then for layer l, of
kind ``layer_types[l]``:

1. ``a = RMSNorm(h)`` (scaled by 1 + scale, as the program's RMS norm is);
2. ``q = a Wq``, ``k = a Wk``, ``v = a Wv`` (grouped-query heads, no bias);
3. rotary embedding on interleaved (even, odd) pairs of every head dim:
   default rope on sliding layers; on full layers YaRN as transformers'
   ``rope_type: "yarn"`` computes it (inverse frequencies blended between
   interpolation, divided by ``factor``, and extrapolation by a linear ramp
   over the correction range that ``beta_fast`` and ``beta_slow`` rotations
   give at ``original_max_position_embeddings``; cos and sin scaled by
   ``attention_factor``);
4. causal softmax attention scaled by head_dim^-1/2; a sliding layer's query
   at position p sees keys at positions > p - ``sliding_window``;
5. ``h += o Wo``;
6. ``r = RMSNorm(h)``, ``p = softmax(r Wr)`` over the experts, the top
   ``num_experts_per_tok`` by p with gates ``p_top / sum(p_top)``, and
   ``h += sum_i g_i W2_e(silu(W1_e r) * W3_e r)``, each expert of width
   ``moe_intermediate_size``;

then a final RMS norm and the untied head.  The layers are stacked in the
program's tree by the period of ``layer_types``: group i holds layers
i, i + period, ...; each leaf carries a leading axis over them.  The expert
input projection packs gate and up columns as the program does: chunks of
128 columns alternate (gate chunk 0, up chunk 0, gate chunk 1, ...).

It gives the five names every ``bench/models/<name>.py`` gives
(``harness.arch_module``).  Nothing here but ``program_config`` reads the
program.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import reference

GLU_CHUNK = 128
EXPERT_ROWS = 128     # rows of one expert the reference multiplies at a time


def _kinds(m: dict) -> tuple[bool, ...]:
    """Whether each layer is full attention (else sliding)."""
    n = m["num_hidden_layers"]
    types = m["layer_types"][:n]
    if len(types) != n or set(types) - {"full_attention", "sliding_attention"}:
        raise ValueError(f"layer_types must name {n} full or sliding layers")
    return tuple(t == "full_attention" for t in types)


def _period(kinds: tuple[bool, ...]) -> int:
    """The shortest period of the layer kinds that whole repeats make up."""
    n = len(kinds)
    return next(p for p in range(1, n + 1)
                if n % p == 0 and kinds == kinds[:p] * (n // p))


def _rope_params(m: dict) -> tuple[dict, dict]:
    r = m["rope_parameters"]
    return r["sliding_attention"], r["full_attention"]


def program_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file, checked
    against the file's ``model`` block so that the file is what runs; the
    block must also agree with the file's copy of the source's config at
    its top level."""
    from repro.configs.base import get_arch

    m = conf["model"]
    differ = sorted(k for k in m if k in conf and conf[k] != m[k])
    if differ:
        raise ValueError(f"{conf['name']}: model block and top level differ: {differ}")
    r = conf["repro"]
    cfg = dataclasses.replace(get_arch(r["arch"]), **r["overrides"])
    kinds = _kinds(m)
    sliding, full = _rope_params(m)
    y = cfg.yarn
    want = {
        "n_layers": m["num_hidden_layers"], "d_model": m["hidden_size"],
        "d_ff": m["moe_intermediate_size"], "n_heads": m["num_attention_heads"],
        "n_kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
        "vocab_size": m["vocab_size"], "rope_theta": sliding["rope_theta"],
        "window": m["sliding_window"], "n_experts": m["num_experts"],
        "moe_topk": m["num_experts_per_tok"],
        "layer_kinds": tuple("G" if f else "L" for f in kinds),
        "layer_pattern": tuple("G" if f else "L" for f in kinds[:_period(kinds)]),
        "yarn": (full["factor"], full["original_max_position_embeddings"],
                 full["beta_fast"], full["beta_slow"], full["attention_factor"],
                 full["rope_theta"]),
        "tie_embeddings": m["tie_word_embeddings"], "dtype": m["torch_dtype"],
        "norm": "rmsnorm", "mlp_kind": {"silu": "swiglu"}[m["hidden_act"]],
        "mlp_bias": False, "family": "moe", "pos": "rope", "attn_softcap": 0.0,
        "final_softcap": 0.0, "vision_tokens": 0, "encoder_layers": 0,
    }
    have = {k: getattr(cfg, k) for k in want}
    have["yarn"] = y and (y.factor, y.original_max_positions, y.beta_fast,
                          y.beta_slow, y.attention_factor, cfg.rope_theta)
    wrong = {k: (have[k], v) for k, v in want.items() if have[k] != v}
    if wrong:
        raise ValueError(f"{conf['name']}: program config differs from the file "
                         f"(program, file): {wrong}")
    return cfg


#: The router's draw gives its logits a standard deviation of about this
#: much: about 0.6 of a token's softmax on its first expert and 0.95 on
#: its top 8 of 64.  An assumption, with no published figure behind it:
#: the source gives no trained weights, and this is a decided router, where
#: N(0, 1/fan_in) gives a near-uniform 64-way softmax (0.43 on the top 8)
#: in which a token's eighth expert is often a near tie that a bf16
#: rounding tips one way and the float32 reference the other.  The scale
#: moves the gates only: a token's top 8 at the first layer do not change.
ROUTER_LOGIT_STD = 4.0


def layout(m: dict) -> dict:
    """Leaf shapes in the program's parameter tree, each ``(shape, mean,
    std)`` of its normal draw: projections and experts N(0, 1/fan_in), the
    router ``ROUTER_LOGIT_STD`` times that, embedding rows N(0, 1), RMS
    norm scales around 0 (the program's (1 + scale) norm)."""
    kinds = _kinds(m)
    period = _period(kinds)
    per = m["num_hidden_layers"] // period
    D, V, E = m["hidden_size"], m["vocab_size"], m["num_experts"]
    F = m["moe_intermediate_size"]
    H, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]

    def norm(*lead):
        return {"scale": (lead + (D,), 0.0, 0.1)}

    def block():
        return {
            "ln1": norm(per),
            "attn": {"wq": ((per, D, H * hd), 0.0, D ** -0.5),
                     "wk": ((per, D, KV * hd), 0.0, D ** -0.5),
                     "wv": ((per, D, KV * hd), 0.0, D ** -0.5),
                     "wo": ((per, H * hd, D), 0.0, (H * hd) ** -0.5)},
            "ln2": norm(per),
            "moe": {"router": ((per, D, E), 0.0, ROUTER_LOGIT_STD * D ** -0.5),
                    "w_in": ((per, E, D, 2 * F), 0.0, D ** -0.5),
                    "w_out": ((per, E, F, D), 0.0, F ** -0.5)},
        }

    return {
        "embed": ((V, D), 0.0, 1.0),
        "groups": {str(i): block() for i in range(period)},
        "tail": [],
        "final_norm": norm(),
        "lm_head": ((D, V), 0.0, D ** -0.5),
    }


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RefCfg:
    full: tuple[bool, ...]     # per layer: full attention, else sliding
    period: int
    heads: int
    kv_heads: int
    head_dim: int
    window: int
    experts: int
    topk: int
    expert_width: int
    eps: float
    rope_theta: float
    yarn_theta: float
    yarn: tuple[float, ...]    # factor, original positions, beta fast, slow, attention factor


def ref_config(m: dict) -> RefCfg:
    """The reference's settings for a ``model`` block (hashable, a static
    argument of ``jit``); refuses what this pass does not compute."""
    n = m["num_hidden_layers"]
    if m["hidden_act"] != "silu":
        raise ValueError(f"reference runs SiLU-gated experts, not {m['hidden_act']!r}")
    if set(m["mlp_layer_types"][:n]) != {"sparse"}:
        raise ValueError("reference runs sparse (expert) MLPs on every layer")
    if m.get("attention_bias") or m.get("tie_word_embeddings"):
        raise ValueError("reference reads no attention bias and an untied head")
    if not m.get("norm_topk_prob") or not m.get("use_sliding_window"):
        raise ValueError("reference renormalises the top-k gates and windows "
                         "the sliding layers")
    sliding, full = _rope_params(m)
    if sliding["rope_type"] != "default" or full["rope_type"] != "yarn":
        raise ValueError("reference runs default rope on sliding layers and "
                         "YaRN on full ones")
    kinds = _kinds(m)
    return RefCfg(full=kinds, period=_period(kinds),
                  heads=m["num_attention_heads"], kv_heads=m["num_key_value_heads"],
                  head_dim=m["head_dim"], window=m["sliding_window"],
                  experts=m["num_experts"], topk=m["num_experts_per_tok"],
                  expert_width=m["moe_intermediate_size"],
                  eps=float(m["rms_norm_eps"]),
                  rope_theta=float(sliding["rope_theta"]),
                  yarn_theta=float(full["rope_theta"]),
                  yarn=(float(full["factor"]),
                        float(full["original_max_position_embeddings"]),
                        float(full["beta_fast"]), float(full["beta_slow"]),
                        float(full["attention_factor"])))


def yarn_inv_freq(head_dim: int, theta: float, yarn: tuple[float, ...]) -> np.ndarray:
    """YaRN's inverse frequencies, float64 (transformers'
    ``_compute_yarn_parameters`` with its default ``truncate``)."""
    factor, original, fast, slow, _ = yarn
    dim = head_dim

    def correction(rotations: float) -> float:
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction(fast)), 0)
    high = min(math.ceil(correction(slow)), dim - 1)
    if low == high:
        high += 0.001
    extrapolation = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp            # the share of each frequency extrapolated
    return extrapolation / factor * (1.0 - keep) + extrapolation * keep


def _yarn_rope(x, cfg: RefCfg):
    """YaRN rotary embedding on interleaved pairs; x: (S, heads, head_dim)."""
    s, _, d = x.shape
    inv = yarn_inv_freq(d, cfg.yarn_theta, cfg.yarn)
    ang = jnp.asarray(np.arange(s)[:, None] * inv[None, :], jnp.float32)[:, None, :]
    cos, sin = jnp.cos(ang) * cfg.yarn[4], jnp.sin(ang) * cfg.yarn[4]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).reshape(x.shape)


def _window_attention(q, k, v, window: int, quant):
    """Causal grouped-query attention in which a query at position p sees
    keys at positions in (p - window, p], in blocks of query rows and only
    over the keys a block can see; q: (S, H, hd), k/v: (S, KV, hd)."""
    s, heads, hd = q.shape
    kv_heads = k.shape[1]
    q = q.reshape(s, kv_heads, heads // kv_heads, hd) * hd ** -0.5
    out = []
    for lo in range(0, s, reference.QUERY_BLOCK):
        hi = min(lo + reference.QUERY_BLOCK, s)
        k0 = max(0, lo - window + 1)
        sc = reference.mm("qkgd,tkd->kgqt", q[lo:hi], k[k0:hi], quant)
        qp, kp = np.arange(lo, hi)[:, None], np.arange(k0, hi)[None, :]
        sc = jnp.where((kp <= qp) & (kp > qp - window), sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        out.append(reference.mm("kgqt,tkd->qkgd", p, v[k0:hi], quant))
    return jnp.concatenate(out, 0).reshape(s, heads * hd)


def _experts(x, moe, cfg: RefCfg, quant):
    """The routed experts' sum for every row of x (S, D): the router in
    float32, then each expert over only the rows routed to it, one expert
    and ``EXPERT_ROWS`` of its rows at a time."""
    s, d = x.shape
    mm, k, f = reference.mm, cfg.topk, cfg.expert_width
    probs = jax.nn.softmax(mm("sd,de->se", x, moe["router"], quant), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    gates = (top / top.sum(-1, keepdims=True)).reshape(-1)
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    pad = EXPERT_ROWS                # a slice past the last row stays in bounds
    token = jnp.concatenate([order // k, jnp.zeros((pad,), order.dtype)])
    gate = jnp.concatenate([gates[order], jnp.zeros((pad,), gates.dtype)])
    rows = x[token]
    counts = jnp.zeros((cfg.experts,), jnp.int32).at[flat].add(1)
    starts = jnp.cumsum(counts) - counts
    c = GLU_CHUNK if f % GLU_CHUNK == 0 else f

    def expert(e, out):
        w_in, w_out = moe["w_in"][e], moe["w_out"][e]

        def block(j, out):
            lo = starts[e] + j * EXPERT_ROWS
            live = jnp.arange(EXPERT_ROWS) < counts[e] - j * EXPERT_ROWS
            xr = jnp.where(live[:, None],
                           jax.lax.dynamic_slice_in_dim(rows, lo, EXPERT_ROWS), 0.0)
            u = mm("cd,df->cf", xr, w_in, quant).reshape(EXPERT_ROWS, f // c, 2, c)
            glu = (jax.nn.silu(u[:, :, 0]) * u[:, :, 1]).reshape(EXPERT_ROWS, f)
            y = mm("cf,fd->cd", glu, w_out, quant)
            g = jnp.where(live, jax.lax.dynamic_slice_in_dim(gate, lo, EXPERT_ROWS), 0.0)
            t = jax.lax.dynamic_slice_in_dim(token, lo, EXPERT_ROWS)
            return out.at[t].add(y * g[:, None])

        blocks = (counts[e] + EXPERT_ROWS - 1) // EXPERT_ROWS
        return jax.lax.fori_loop(0, blocks, block, out)

    return jax.lax.fori_loop(0, cfg.experts, expert, jnp.zeros((s, d), jnp.float32))


@functools.partial(jax.jit, static_argnames=("cfg", "quant", "full"))
def _layer(h, stack, r, cfg: RefCfg, quant, full: bool):
    mm = reference.mm
    p = jax.tree_util.tree_map(lambda a: a[r], stack)
    s = h.shape[0]
    x = reference.rms_norm(h, p["ln1"], cfg.eps)
    a = p["attn"]
    q = mm("sd,dn->sn", x, a["wq"], quant).reshape(s, cfg.heads, cfg.head_dim)
    k = mm("sd,dn->sn", x, a["wk"], quant).reshape(s, cfg.kv_heads, cfg.head_dim)
    v = mm("sd,dn->sn", x, a["wv"], quant).reshape(s, cfg.kv_heads, cfg.head_dim)
    if full:
        o = reference.attention(_yarn_rope(q, cfg), _yarn_rope(k, cfg), v, quant)
    else:
        o = _window_attention(reference.rope(q, cfg.rope_theta),
                              reference.rope(k, cfg.rope_theta), v, cfg.window, quant)
    h = h + mm("sn,nd->sd", o, a["wo"], quant)
    return h + _experts(reference.rms_norm(h, p["ln2"], cfg.eps), p["moe"], cfg, quant)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _final_norm(h, p, cfg: RefCfg):
    return reference.rms_norm(h, p, cfg.eps)


def final_hidden(weights, cfg: RefCfg, tokens: np.ndarray, quant=None) -> jax.Array:
    """Final-norm hidden states (S, D) of one token sequence, one layer at a
    time, at HIGHEST matmul precision; ``quant="fp8"`` is the control
    (``reference.mm``)."""
    with jax.default_matmul_precision("highest"):
        h = reference.embed(weights["embed"], jnp.asarray(tokens, jnp.int32))
        for l, full in enumerate(cfg.full):
            h = _layer(h, weights["groups"][str(l % cfg.period)],
                       jnp.int32(l // cfg.period), cfg, quant, full)
        return _final_norm(h, weights["final_norm"], cfg)


# ---------------------------------------------------------------------------
# Operations and bytes
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Dims:
    """The shapes the counts need.  The counts follow the reference's
    equations above, not the program's kernels: a multiply-add is two
    operations, a token's experts are its top-k, a sliding layer's query
    attends to at most ``window`` keys."""
    full: tuple[bool, ...]
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    experts: int
    topk: int
    expert_width: int
    window: int
    dtype_bytes: int = 2

    @property
    def layers(self) -> int:
        return len(self.full)

    # -- operations -----------------------------------------------------------
    def attention_flops(self, attended: int) -> int:
        """Scores and weighted values for one query over ``attended`` keys,
        per layer."""
        return 4 * self.heads * self.head_dim * attended

    def expert_params(self) -> int:
        """Weights of one expert: gate, up and down projections."""
        return 3 * self.d_model * self.expert_width

    def expert_flops(self, n: int) -> int:
        """The routed experts of ``n`` tokens, per layer."""
        return 2 * n * self.topk * self.expert_params()

    def linear_flops_per_token(self) -> int:
        """Projections, router and the token's routed experts, per layer."""
        d, hd = self.d_model, self.head_dim
        attn = 2 * d * (self.heads + 2 * self.kv_heads) * hd + 2 * self.heads * hd * d
        return attn + 2 * d * self.experts + self.expert_flops(1)

    def keys(self, n: int, full: bool) -> int:
        """Keys the queries of a prompt of ``n`` tokens attend to in one
        layer: the causal triangle, cut to the window on sliding layers."""
        if full or n <= self.window:
            return n * (n + 1) // 2
        w = self.window
        return w * (w + 1) // 2 + (n - w) * w

    def prefill_flops(self, n: int) -> int:
        """One prompt of ``n`` true tokens: every layer at every position and
        the head at the last position only."""
        return sum(n * self.linear_flops_per_token()
                   + self.attention_flops(1) * self.keys(n, f)
                   for f in self.full) + 2 * self.d_model * self.vocab

    def _attended(self, c: int, full: bool) -> int:
        return c if full else min(c, self.window)

    def decode_flops(self, attended: list[int]) -> int:
        """One decode step; ``attended[i]`` is the number of positions the
        i-th active sequence has, its new one included."""
        b = len(attended)
        return sum(b * self.linear_flops_per_token()
                   + sum(self.attention_flops(self._attended(c, f)) for c in attended)
                   for f in self.full) + 2 * self.d_model * self.vocab * b

    # -- bytes ----------------------------------------------------------------
    def experts_touched(self, n: int) -> int:
        """Experts ``n`` tokens can reach in one layer, at most every one."""
        return min(self.experts, n * self.topk)

    def expert_bytes(self, n: int) -> int:
        """Least bytes the expert GEMMs of ``n`` tokens move in one layer:
        each expert touched read once, and the routed rows in and out of
        both GEMMs (d_model in and expert_width out of the first, the
        reverse of the second)."""
        rows = n * self.topk * 2 * (self.d_model + self.expert_width)
        return (self.experts_touched(n) * self.expert_params() + rows) * self.dtype_bytes

    def expert_least_s(self, n: int, peaks: dict) -> float:
        """Least time the expert GEMMs of a prompt of ``n`` tokens take over
        every layer: the larger of their operations over the bf16 peak and
        their least bytes over the HBM bandwidth."""
        return self.layers * max(self.expert_flops(n) / peaks["bf16_flops_per_s"],
                                 self.expert_bytes(n) / peaks["hbm_bytes_per_s"])

    def decode_bytes(self, attended: list[int]) -> int:
        """Least bytes one decode step moves: the attention, router and norm
        weights, the experts the batch can reach, the final norm and the
        head once; each sequence's embedding row, the cache rows it attends
        to and its new rows."""
        b, d, hd = len(attended), self.d_model, self.head_dim
        attn = d * (self.heads + 2 * self.kv_heads) * hd + self.heads * hd * d
        layer = attn + d * self.experts + 2 * d + \
            self.experts_touched(b) * self.expert_params()
        rows = sum(self._attended(c, f) + 1 for c in attended for f in self.full)
        n = (self.layers * layer + d + d * self.vocab + b * d
             + rows * 2 * self.kv_heads * hd)
        return n * self.dtype_bytes


def dims(m: dict) -> Dims:
    """The counts' shapes from a configuration file's ``model`` block."""
    return Dims(full=_kinds(m), d_model=m["hidden_size"],
                heads=m["num_attention_heads"], kv_heads=m["num_key_value_heads"],
                head_dim=m["head_dim"], vocab=m["vocab_size"],
                experts=m["num_experts"], topk=m["num_experts_per_tok"],
                expert_width=m["moe_intermediate_size"], window=m["sliding_window"],
                dtype_bytes={"bfloat16": 2, "float32": 4}[m["torch_dtype"]])
