"""Per-architecture smoke tests: reduced config of the same family, one
forward + one train step on CPU, asserting output shapes + no NaNs — plus
prefill/decode equivalence for every family (the serving contract)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_arch, reduced
from repro.kernels import ops
from repro.launch import steps as steps_mod
from repro.models import build_model
from repro.models.common import count_params
from repro.optim.adamw import AdamWConfig


def _batch(cfg, rng, b=2, s=12, extra_tok=0):
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (b, s + extra_tok)), jnp.int32)
    batch = {"tokens": toks}
    if cfg.family == "audio":
        batch["frames"] = jnp.asarray(rng.normal(size=(b, cfg.encoder_seq, cfg.d_model)),
                                      jnp.float32)
    if cfg.vision_tokens:
        batch["patch_embeds"] = jnp.asarray(
            rng.normal(size=(b, cfg.vision_tokens, cfg.d_model)), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch, rng):
    cfg = reduced(get_arch(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert count_params(params) > 0
    b, s = 2, 12
    batch = _batch(cfg, rng, b, s)

    logits, aux = model.forward(params, batch)
    seq = s + (cfg.vision_tokens or 0)
    assert logits.shape == (b, seq, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all()), f"{arch}: non-finite logits"

    step = steps_mod.make_train_step(model, AdamWConfig(peak_lr=1e-3, warmup_steps=1,
                                                        total_steps=10))
    opt = steps_mod.init_opt_state(params)
    params2, opt2, metrics = jax.jit(step)(params, opt, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # params must actually change
    moved = jax.tree_util.tree_map(
        lambda a, b_: bool(jnp.any(a != b_)), params, params2)
    assert any(jax.tree_util.tree_leaves(moved)), f"{arch}: no param moved"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_matches_forward(arch, rng):
    cfg = reduced(get_arch(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    b, s = 2, 10
    full = _batch(cfg, rng, b, s, extra_tok=1)
    batch = dict(full)
    batch["tokens"] = full["tokens"][:, :s]

    logits_full, _ = model.forward(params, full, remat=False)
    lp, cache = model.prefill(params, batch, max_len=s + 4)
    off = cfg.vision_tokens if cfg.family != "audio" else 0
    np.testing.assert_allclose(lp, logits_full[:, off + s - 1, :], rtol=2e-4, atol=2e-4)
    ld, cache = model.decode_step(params, cache, full["tokens"][:, s])
    np.testing.assert_allclose(ld, logits_full[:, off + s, :], rtol=2e-4, atol=2e-4)


def test_grad_accumulation_matches_single_batch(rng):
    """grad_accum=2 over the split batch ≈ one step over the full batch."""
    cfg = reduced(get_arch("minitron-4b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    batch = _batch(cfg, rng, b=4, s=8)
    ocfg = AdamWConfig(peak_lr=1e-3, warmup_steps=1, total_steps=10)
    one = steps_mod.make_train_step(model, ocfg, grad_accum=1)
    acc = steps_mod.make_train_step(model, ocfg, grad_accum=2)
    p1, _, m1 = jax.jit(one)(params, steps_mod.init_opt_state(params), batch)
    p2, _, m2 = jax.jit(acc)(params, steps_mod.init_opt_state(params), batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    l1 = jax.tree_util.tree_leaves(p1)
    l2 = jax.tree_util.tree_leaves(p2)
    for a, b_ in zip(l1, l2):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b_, np.float32),
                                   rtol=5e-3, atol=5e-3)


def test_moe_aux_loss_nonzero(rng):
    cfg = reduced(get_arch("dbrx-132b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    _, metrics = model.loss_fn(params, _batch(cfg, rng))
    assert float(metrics["aux"]) > 0.0


def test_long_context_ring_cache_memory(rng):
    """Local-attention cache is window-sized, not context-sized."""
    cfg = reduced(get_arch("mixtral-8x22b"))  # all-SWA
    model = build_model(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(1, 1 << 16))
    k_leaves = [l for p, l in jax.tree_util.tree_flatten_with_path(cache)[0]
                if "'k'" in jax.tree_util.keystr(p)]
    assert k_leaves and all(l.shape[-2] == cfg.window for l in k_leaves)


# ---------------------------------------------------------------------------
# Serving scans read each layer's weights in place from the stacks
# ---------------------------------------------------------------------------


def _lm_with_biases(arch):
    cfg = reduced(get_arch(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    if cfg.mlp_bias:   # zero at init: give the bias epilogues something to add
        r = np.random.default_rng(3)
        mlp = params["groups"]["0"]["mlp"]
        for name in ("b_in", "b_out"):
            mlp[name] = jnp.asarray(r.normal(size=mlp[name].shape) * 0.5,
                                    mlp[name].dtype)
    return cfg, model, params


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in its params."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("arch", ["minitron-4b", "starcoder2-7b",
                                  "recurrentgemma-2b", "mixtral-8x22b",
                                  "mellum2-12b-a2.5b"])
def test_pallas_serving_matches_ref_over_decode_steps(arch, rng):
    """Prefill and three decode steps through the Pallas kernels (interpret
    mode), which read every layer's attention and MLP projections and expert
    stacks in place from the stacks, match the ref backend, which slices
    them: dense with and without MLP biases, recurrent blocks' MLPs, MoE
    blocks' attention and ragged expert GEMMs, ring caches and YaRN."""
    cfg, model, params = _lm_with_biases(arch)
    b, s, steps = 2, 10, 3
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (b, s + steps)), jnp.int32)

    def run(backend):
        with ops.use_backend(backend):   # fresh jits: the backend is read at trace
            prefill = jax.jit(lambda p, t: model.prefill(
                p, {"tokens": t}, max_len=s + steps))
            decode = jax.jit(lambda p, c, t: model.decode_step(p, c, t))
            logits, cache = prefill(params, toks[:, :s])
            out = [logits]
            for i in range(steps):
                logits, cache = decode(params, cache, toks[:, s + i])
                out.append(logits)
        return out

    for got, want in zip(run("pallas"), run("ref")):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("arch", ["minitron-4b", "starcoder2-7b"])
def test_decode_step_copies_no_stacked_weight(arch):
    """The traced decode step neither slices a stacked (L, K, N) weight nor
    scans over one: the matmuls take the stacks whole (6 per layer, counted
    by the provider) and only the untied lm head is a plain weight."""
    cfg, model, params = _lm_with_biases(arch)
    cache = model.init_cache(2, 16)
    provider = ops.ScheduleProvider()
    with ops.use_backend("pallas"):
        closed = jax.make_jaxpr(lambda p, c, t: model.decode_step(
            p, c, t, provider=provider))(params, cache, jnp.zeros((2,), jnp.int32))
    stacks = {w.shape for w in jax.tree_util.tree_leaves(params["groups"])
              if w.ndim == 3}
    copied = []
    for eqn in _eqns(closed.jaxpr):
        if eqn.primitive.name in ("dynamic_slice", "slice", "gather"):
            operands = eqn.invars[:1]
        elif eqn.primitive.name == "scan":   # xs follow the consts and carry
            operands = eqn.invars[eqn.params["num_consts"] + eqn.params["num_carry"]:]
        else:
            continue
        copied += [eqn for v in operands if getattr(v.aval, "shape", ()) in stacks]
    assert not copied
    assert provider.stats()["matmul_weights"] == {"in_place": 6 * cfg.n_layers,
                                                  "plain": 1}


@pytest.mark.parametrize("arch", ["minitron-4b", "mellum2-12b-a2.5b",
                                  "recurrentgemma-2b"])
def test_decode_step_carries_the_kv_cache_in_place(arch, rng):
    """Through the Pallas kernels (interpret mode) the decode step reads
    every attention layer's cache in place from the stacks its layer scan
    carries: the provider counts each group's attention layers in place and
    none sliced, and no cache stack is sliced or scanned over.  Jitted with
    the cache donated, six greedy steps give the ref backend's tokens and
    logits: dense GQA, sliding layers whose ring caches wrap and YaRN full
    layers, and recurrent layers beside an attention layer."""
    cfg = reduced(get_arch(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    b, s, steps = 2, 10, 6
    prompt = jnp.asarray(rng.integers(1, cfg.vocab_size, (b, s)), jnp.int32)
    pat, reps = cfg.layer_pattern, cfg.n_layers // len(cfg.layer_pattern)
    attn_layers = reps * sum(kind != "R" for kind in pat)
    assert attn_layers > 0

    def run(backend, provider):
        with ops.use_backend(backend):
            decode = jax.jit(lambda p, c, t: model.decode_step(
                p, c, t, provider=provider), donate_argnums=(1,))
            logits, cache = model.prefill(params, {"tokens": prompt},
                                          max_len=s + steps)
            out = []
            for _ in range(steps):
                logits, cache = decode(params, cache, jnp.argmax(logits, -1))
                out.append(logits)
        return out

    provider = ops.ScheduleProvider()
    got = run("pallas", provider)
    want = run("ref", None)
    assert provider.stats()["kv_cache"] == {"in_place": attn_layers, "sliced": 0}
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.argmax(g, -1), np.argmax(w, -1))
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)

    cache = model.init_cache(b, s + steps)
    stacks = {c.shape for c in jax.tree_util.tree_leaves(cache["groups"])
              if c.ndim == 5}
    assert stacks
    with ops.use_backend("pallas"):
        closed = jax.make_jaxpr(lambda p, c, t: model.decode_step(p, c, t))(
            params, cache, jnp.zeros((b,), jnp.int32))
    for eqn in _eqns(closed.jaxpr):
        if eqn.primitive.name in ("dynamic_slice", "slice", "gather"):
            operands = eqn.invars[:1]
        elif eqn.primitive.name == "scan":   # xs follow the consts and carry
            operands = eqn.invars[eqn.params["num_consts"] + eqn.params["num_carry"]:]
        else:
            continue
        assert not [v for v in operands if getattr(v.aval, "shape", ()) in stacks]


# ---------------------------------------------------------------------------
# Experts on the serving paths: dropless, pads routed nowhere, read in place
# ---------------------------------------------------------------------------


def _expert(p, e, x):
    """One expert's SwiGLU over rows x, from the interleaved (gate, up)
    packing of ``w_in``."""
    from repro.kernels import ref

    h = ref.matmul(x, p["w_in"][e], "matmul_silu_glu")
    return ref.matmul(h, p["w_out"][e], "matmul")


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_dropless_moe_keeps_every_pair_past_4096(backend, rng):
    """A router forced onto expert 0 (every token's top-1, expert 1 its
    second by the tie order) puts all 2100 tokens on two experts: 4200
    pairs, past the 4096 where the capacity dispatch caps each expert at
    1312 rows and drops the rest.  The dropless dispatch, which ``moe_apply``
    takes off a mesh, equals each token's two experts computed directly;
    the capacity dispatch does not."""
    from repro.models import mlp as mlpm

    cfg = reduced(get_arch("mellum2-12b-a2.5b"))
    p = mlpm.moe_params(jax.random.PRNGKey(4), cfg)
    p["router"] = jnp.zeros_like(p["router"]).at[:, 0].set(1.0)
    t = 2100
    assert t * cfg.moe_topk > 4096
    x = jnp.asarray(np.abs(rng.normal(size=(1, t, cfg.d_model))) + 0.1, jnp.float32)
    probs = jax.nn.softmax(x[0] @ p["router"], axis=-1)
    g = probs[:, :2] / probs[:, :2].sum(-1, keepdims=True)
    want = g[:, :1] * _expert(p, 0, x[0]) + g[:, 1:] * _expert(p, 1, x[0])
    with ops.use_backend(backend):
        got, _ = mlpm.moe_apply(p, cfg, x)
        probs_t = jax.nn.softmax(x[0] @ p["router"], axis=-1)
        top_g, top_e = jax.lax.top_k(probs_t, cfg.moe_topk)
        top_g = top_g / top_g.sum(-1, keepdims=True)
        capped = mlpm._capacity(p, x[0], top_e, top_g, cfg.n_experts,
                                mlpm.CAPACITY_FACTOR, None)
    # f32 throughout: the two paths differ by summation order alone
    np.testing.assert_allclose(got[0], want, rtol=1e-4, atol=1e-4)
    assert not np.allclose(capped, want, rtol=1e-2, atol=1e-2)


def test_bucketed_ring_prefill_past_the_window_equals_exact_length(rng):
    """A 13-token prompt past the reduced window of 8 pads to the engine's
    16 bucket: the ring caches keep the last 8 true positions (not pads),
    the full caches the 13 true rows, pad tokens take no expert rows, and
    the logits of the prefill and of a decode step after it equal an
    exact-length prefill's."""
    from repro.serving import ServingEngine

    cfg = reduced(get_arch("mellum2-12b-a2.5b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(5))
    n, max_len = 13, 32
    assert ServingEngine(model, params, slots=1, max_len=max_len).bucket_for(n) == 16
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (1, n + 1)), jnp.int32)
    padded = jnp.pad(toks[:, :n], ((0, 0), (0, 16 - n)), constant_values=7)
    exact_logits, exact = model.prefill(params, {"tokens": toks[:, :n]}, max_len=max_len)
    bucket_logits, bucket = model.prefill(params, {"tokens": padded}, max_len=max_len,
                                          true_len=jnp.int32(n))
    # shapes differ (13 against 16 rows): reductions may round differently
    np.testing.assert_allclose(bucket_logits, exact_logits, rtol=1e-5, atol=1e-5)
    for path, a in jax.tree_util.tree_flatten_with_path(exact)[0]:
        b = dict(jax.tree_util.tree_flatten_with_path(bucket)[0])[path]
        rows = a.shape[-2] if a.ndim > 1 and a.shape[-2] == cfg.window else n
        np.testing.assert_allclose(b[..., :rows, :] if a.ndim > 1 else b,
                                   a[..., :rows, :] if a.ndim > 1 else a,
                                   rtol=1e-5, atol=1e-5, err_msg=str(path))
    step_exact, _ = model.decode_step(params, exact, toks[:, n])
    step_bucket, _ = model.decode_step(params, bucket, toks[:, n])
    np.testing.assert_allclose(step_bucket, step_exact, rtol=1e-5, atol=1e-5)


def test_serving_scans_read_expert_stacks_in_place():
    """Traced prefill and decode read each layer's expert stacks in place:
    two ragged GEMMs per layer on a (L, E, K, N) stack, none on a slice,
    and no 4-D stack is sliced or scanned over."""
    cfg = reduced(get_arch("mellum2-12b-a2.5b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(6))
    stacks = {w.shape for w in jax.tree_util.tree_leaves(params["groups"])
              if w.ndim == 4}
    assert stacks
    calls = {
        "prefill": lambda p, c, t, provider: model.prefill(
            p, {"tokens": t[:, None]}, max_len=8, provider=provider),
        "decode": lambda p, c, t, provider: model.decode_step(
            p, c, t, provider=provider),
    }
    for name, call in calls.items():
        provider = ops.ScheduleProvider()
        with ops.use_backend("pallas"):
            closed = jax.make_jaxpr(lambda p, c, t: call(p, c, t, provider))(
                params, model.init_cache(2, 8), jnp.zeros((2,), jnp.int32))
        assert provider.stats()["expert_weights"] == {
            "in_place": 2 * cfg.n_layers, "plain": 0}, name
        for eqn in _eqns(closed.jaxpr):
            if eqn.primitive.name in ("dynamic_slice", "slice", "gather"):
                operands = eqn.invars[:1]
            elif eqn.primitive.name == "scan":   # xs follow the consts and carry
                operands = eqn.invars[eqn.params["num_consts"] + eqn.params["num_carry"]:]
            else:
                continue
            assert not [v for v in operands
                        if getattr(v.aval, "shape", ()) in stacks], (name, eqn)
