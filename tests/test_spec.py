"""Speculative decoding: acceptance math, draft-then-verify exactness, the
verify workload's extraction geometry, and the fleet's acceptance-aware
routing surfaces."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import ShapeConfig, get_arch, reduced
from repro.core.extract import extract_kernels
from repro.core.resolution import spec_verify_uses
from repro.fleet import AcceptanceTracker, ServingFleet, TrafficGenerator
from repro.fleet.traffic import load_trace, save_trace
from repro.kernels.ops import use_backend
from repro.models import build_model
from repro.serving import (
    PagedServingEngine,
    expected_committed_tokens,
    make_self_draft,
    spec_exact_reason,
    spec_gain,
)


@pytest.fixture(scope="module")
def small_lm():
    cfg = reduced(get_arch("minitron-4b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def drafted(small_lm):
    """(target_model, damped_target_params, draft_model, draft_params) with
    damp=0: the damped target computes exactly the draft's function, so
    greedy proposals always match (acceptance rate 1)."""
    cfg, model, params = small_lm
    dcfg, dparams, tparams = make_self_draft(cfg, params, keep_layers=1,
                                             damp=0.0)
    return model, tparams, build_model(dcfg), dparams


def _prompts(cfg, lens=(3, 11, 6)):
    rng = np.random.default_rng(5)
    return [[int(t) for t in rng.integers(1, cfg.vocab_size, size=n)]
            for n in lens]


def _run(model, params, prompts, *, mnt=8, **kw):
    kw.setdefault("decode_batch", len(prompts))
    kw.setdefault("max_ctx", 32)
    kw.setdefault("page_size", 4)
    kw.setdefault("chunk", 8)
    eng = PagedServingEngine(model, params, **kw)
    reqs = [eng.add_request(p, max_new_tokens=mnt) for p in prompts]
    eng.run_to_completion(max_steps=512)
    assert all(r.done for r in reqs)
    return reqs, eng


# ---------------------------------------------------------------------------
# Acceptance math (pure)
# ---------------------------------------------------------------------------


def test_expected_committed_tokens():
    assert expected_committed_tokens(0, 0.5) == 1.0
    assert expected_committed_tokens(4, 0.0) == 1.0   # all-reject: correction
    assert expected_committed_tokens(4, 1.0) == 5.0   # all-accept: k+1
    # geometric series: 1 + a + a^2 for k=2
    assert expected_committed_tokens(2, 0.5) == pytest.approx(1.75)
    # monotone in both k and alpha
    assert (expected_committed_tokens(4, 0.8)
            > expected_committed_tokens(2, 0.8)
            > expected_committed_tokens(2, 0.4))


def test_spec_gain_break_even():
    kw = dict(draft_cost_s=0.1, verify_cost_s=1.0, decode_cost_s=1.0)
    assert spec_gain(0, 0.9, **kw) == 1.0             # k=0: no speculation
    assert spec_gain(4, 1.0, **kw) == pytest.approx(5.0 / 1.5)
    assert spec_gain(4, 0.0, **kw) == pytest.approx(1.0 / 1.5)  # pure loss
    # free draft, all-reject: burst == one decode == one token -> break even
    assert spec_gain(3, 0.0, draft_cost_s=0.0, verify_cost_s=1.0,
                     decode_cost_s=1.0) == pytest.approx(1.0)


def test_spec_exact_reason_gates_families():
    assert spec_exact_reason(get_arch("minitron-4b")) == ""
    assert "recurrent" in spec_exact_reason(get_arch("recurrentgemma-2b"))
    assert "ring" in spec_exact_reason(get_arch("mixtral-8x22b"))


# ---------------------------------------------------------------------------
# Draft-then-verify on the paged engine: bit-exactness in every regime
# ---------------------------------------------------------------------------


def test_all_accept_commits_k_plus_one_and_matches_plain(small_lm, drafted):
    """damp=0 makes the draft identical to the damped target: every draft
    token is accepted, bursts commit k+1, and the stream is bit-exact vs
    the plain paged engine on the same params."""
    cfg, _, _ = small_lm
    model, tparams, draft, dparams = drafted
    prompts = _prompts(cfg)
    plain, _ = _run(model, tparams, prompts)
    spec, eng = _run(model, tparams, prompts, draft_model=draft,
                     draft_params=dparams, spec_k=3)
    for pr, sr in zip(plain, spec):
        assert pr.generated == sr.generated
    assert eng.spec_bursts > 0
    assert eng.spec_accepted == eng.spec_proposed  # alpha == 1
    # every burst commits its k accepted drafts + the bonus token, except a
    # final burst truncated by max_new_tokens
    events = eng.drain_spec_events()
    assert all(1 <= ev["committed"] <= 4 for ev in events)
    assert sum(ev["committed"] for ev in events) == eng.spec_committed


def test_all_reject_commits_exactly_one_and_matches_plain(small_lm, drafted):
    """Adversarial head: the draft's lm head is the target's with columns
    rolled by one, so its greedy proposal is always (target greedy + 1) mod
    V — never accepted.  Every burst must commit exactly 1 token (the
    correction), and the stream stays bit-exact vs plain decode."""
    cfg, _, _ = small_lm
    model, tparams, draft, dparams = drafted
    bad = dict(dparams)
    bad["lm_head"] = np.roll(np.asarray(dparams["lm_head"]), 1, axis=1)
    prompts = _prompts(cfg)
    plain, _ = _run(model, tparams, prompts)
    spec, eng = _run(model, tparams, prompts, draft_model=draft,
                     draft_params=bad, spec_k=3)
    for pr, sr in zip(plain, spec):
        assert pr.generated == sr.generated
    assert eng.spec_bursts > 0
    assert eng.spec_accepted == 0
    assert eng.spec_committed == eng.spec_bursts  # 1 per burst


def test_partial_acceptance_is_bit_exact(small_lm):
    """damp>0: the draft disagrees with the damped target some of the time;
    greedy verify still reproduces plain decode token-for-token."""
    cfg, model, params = small_lm
    dcfg, dparams, tparams = make_self_draft(cfg, params, keep_layers=1,
                                             damp=0.05)
    draft = build_model(dcfg)
    prompts = _prompts(cfg)
    plain, _ = _run(model, tparams, prompts)
    spec, eng = _run(model, tparams, prompts, draft_model=draft,
                     draft_params=dparams, spec_k=3)
    for pr, sr in zip(plain, spec):
        assert pr.generated == sr.generated
    assert 0 < eng.spec_accepted < eng.spec_proposed  # genuinely partial


def test_partial_acceptance_is_bit_exact_on_pallas_kernels(small_lm):
    """Partial acceptance through the Pallas kernels (interpret mode): the
    verify step reads each layer's weights in place as decode does, and
    reproduces plain decode token-for-token."""
    cfg, model, params = small_lm
    dcfg, dparams, tparams = make_self_draft(cfg, params, keep_layers=1,
                                             damp=0.05)
    draft = build_model(dcfg)
    prompts = _prompts(cfg)
    with use_backend("pallas"):
        plain, _ = _run(model, tparams, prompts)
        spec, eng = _run(model, tparams, prompts, draft_model=draft,
                         draft_params=dparams, spec_k=3)
    for pr, sr in zip(plain, spec):
        assert pr.generated == sr.generated
    assert 0 < eng.spec_accepted < eng.spec_proposed


def test_spec_k0_degrades_to_plain(small_lm, drafted):
    """spec_k=0 disables speculation entirely: no draft cache, no bursts,
    and the engine is the plain paged engine."""
    cfg, _, _ = small_lm
    model, tparams, draft, dparams = drafted
    prompts = _prompts(cfg)
    plain, _ = _run(model, tparams, prompts)
    spec, eng = _run(model, tparams, prompts, draft_model=draft,
                     draft_params=dparams, spec_k=0)
    assert not eng._spec and eng.spec_bursts == 0
    for pr, sr in zip(plain, spec):
        assert pr.generated == sr.generated


def test_per_request_opt_out(small_lm, drafted):
    """speculative=False on one request keeps it on the plain decode path
    while its neighbors burst; streams stay bit-exact either way."""
    cfg, _, _ = small_lm
    model, tparams, draft, dparams = drafted
    prompts = _prompts(cfg, lens=(4, 9))
    plain, _ = _run(model, tparams, prompts)
    eng = PagedServingEngine(model, tparams, decode_batch=2, max_ctx=32,
                             page_size=4, chunk=8, draft_model=draft,
                             draft_params=dparams, spec_k=3)
    a = eng.add_request(prompts[0], max_new_tokens=8, speculative=False)
    b = eng.add_request(prompts[1], max_new_tokens=8)
    eng.run_to_completion(max_steps=512)
    assert a.generated == plain[0].generated
    assert b.generated == plain[1].generated
    events = eng.drain_spec_events()
    assert events and all(ev["uid"] == b.uid for ev in events)


def test_preemption_rollback_is_bit_exact(small_lm, drafted):
    """An oversubscribed pool preempts speculating lanes mid-stream;
    recompute-on-resume plus verify rollback must reproduce the exact
    token streams of an unconstrained plain run."""
    cfg, _, _ = small_lm
    model, tparams, draft, dparams = drafted
    prompts = [[i + 1] * 5 for i in range(4)]
    plain, _ = _run(model, tparams, prompts, mnt=6, decode_batch=4)
    spec, eng = _run(model, tparams, prompts, mnt=6, decode_batch=4,
                     page_size=2, pool_pages=15, draft_model=draft,
                     draft_params=dparams, spec_k=3)
    assert eng.preemptions > 0
    assert eng.spec_bursts > 0
    for pr, sr in zip(plain, spec):
        assert pr.generated == sr.generated
    assert eng.table.used_pages == 0


# ---------------------------------------------------------------------------
# The verify workload class: extraction geometry + transfer seeding
# ---------------------------------------------------------------------------


def test_verify_cell_geometry(small_lm):
    """Verify attends like chunk_prefill (Q=k+1 over the full cached
    context) but projects *all* positions through the lm head (M = B*(k+1),
    not B) — its logits feed k+1 acceptance decisions per lane."""
    cfg, _, _ = small_lm
    b, k, ctx = 2, 3, 32
    verify = spec_verify_uses(cfg, decode_batch=b, max_ctx=ctx, spec_k=k)
    chunk = extract_kernels(
        cfg, ShapeConfig("c", k + 1, b, "chunk_prefill", ctx_len=ctx),
        dp=1, tp=1)

    def by_class(uses):
        return {u.instance.class_id: dict(u.instance.params) for u in uses}

    v, c = by_class(verify), by_class(chunk)
    attn = v["flash_attention_causal"]
    assert attn["Q"] == k + 1 and attn["KV"] == ctx and attn["B"] == b
    assert attn == c["flash_attention_causal"]  # transfer-seeds exactly
    assert v["matmul_lmhead"]["M"] == b * (k + 1)   # all positions
    assert c["matmul_lmhead"]["M"] == b             # final position only
    # every non-head kernel is workload-identical to the chunk cell
    vk = {u.instance.workload_key() for u in verify
          if u.instance.class_id != "matmul_lmhead"}
    ck = {u.instance.workload_key() for u in chunk
          if u.instance.class_id != "matmul_lmhead"}
    assert vk == ck


def test_engine_plan_covers_spec_cells(small_lm, drafted):
    """A speculating engine's execution plan pre-resolves the verify cell
    and the draft's decode/chunk cells — no default-tier surprises at the
    first burst."""
    from repro.kernels.ops import ScheduleProvider

    cfg, _, _ = small_lm
    model, tparams, draft, dparams = drafted
    eng = PagedServingEngine(model, tparams, decode_batch=2, max_ctx=32,
                             page_size=4, chunk=8, draft_model=draft,
                             draft_params=dparams, spec_k=3,
                             provider=ScheduleProvider())
    assert eng.plan is not None
    for u in spec_verify_uses(cfg, decode_batch=2, max_ctx=32, spec_k=3):
        assert eng.plan.lookup(u.instance) is not None


# ---------------------------------------------------------------------------
# AcceptanceTracker
# ---------------------------------------------------------------------------


def test_acceptance_tracker_prior_and_evidence():
    t = AcceptanceTracker(prior_alpha=0.6, prior_weight=10.0)
    assert t.alpha("chat") == pytest.approx(0.6)  # cold: pure prior
    t.record("chat", proposed=90, accepted=90)
    # 90 accepted of 90 + 6 pseudo-accepted of 10 pseudo-proposed
    assert t.alpha("chat") == pytest.approx(96.0 / 100.0)
    assert t.alpha("bulk") == pytest.approx(0.6)  # classes are independent
    t.record("bulk", proposed=50, accepted=0)
    assert t.alpha("bulk") == pytest.approx(6.0 / 60.0)
    assert t.observed("chat") == pytest.approx(90.0)


def test_acceptance_tracker_decay_tracks_drift():
    t = AcceptanceTracker(half_life_s=10.0, prior_alpha=0.5,
                          prior_weight=0.0)
    t.record("c", 100, 100, t=0.0)
    assert t.alpha("c") == pytest.approx(1.0)
    # one half-life later the old evidence weighs half as much as new
    t.record("c", 100, 0, t=10.0)
    assert t.alpha("c") == pytest.approx(50.0 / 150.0)
    # many half-lives: ancient evidence evaporates entirely
    t.record("c", 10, 0, t=500.0)
    assert t.alpha("c") == pytest.approx(0.0, abs=1e-6)


def test_acceptance_tracker_validation():
    with pytest.raises(ValueError):
        AcceptanceTracker(half_life_s=0.0)
    with pytest.raises(ValueError):
        AcceptanceTracker(prior_alpha=1.5)
    t = AcceptanceTracker()
    with pytest.raises(ValueError):
        t.record("c", proposed=3, accepted=4)


# ---------------------------------------------------------------------------
# Traffic classes + fleet routing surfaces
# ---------------------------------------------------------------------------


def test_traffic_class_mix_is_seeded_and_rng_preserving(tmp_path):
    mix = {"chat": 0.7, "bulk": 0.3}
    a = TrafficGenerator(seed=11, class_mix=mix).trace(20)
    b = TrafficGenerator(seed=11, class_mix=mix).trace(20)
    assert [r.request_class for r in a] == [r.request_class for r in b]
    assert {"chat", "bulk"} == {r.request_class for r in a}
    # class_mix=None must not consume RNG: legacy traces stay byte-identical
    legacy = TrafficGenerator(seed=11).trace(20)
    plain = TrafficGenerator(seed=11, class_mix=None).trace(20)
    assert [(r.arrival_s, r.prompt, r.max_new_tokens) for r in legacy] \
        == [(r.arrival_s, r.prompt, r.max_new_tokens) for r in plain]
    # request_class round-trips through save/load
    path = str(tmp_path / "trace.jsonl")
    save_trace(path, a)
    loaded = load_trace(path)
    assert [r.request_class for r in loaded] == [r.request_class for r in a]


def test_fleet_speculative_serving_and_acceptance_accounting(small_lm,
                                                            drafted):
    """speculative=True fleet: every admit speculates, burst events flow
    into the per-class AcceptanceTracker, and the summary reports them."""
    cfg, _, _ = small_lm
    model, tparams, draft, dparams = drafted
    gen = TrafficGenerator(seed=4, vocab_size=cfg.vocab_size,
                           arrival_rate=1.0, new_tokens=(6, 10),
                           prompt_cap=12,
                           class_mix={"chat": 0.5, "bulk": 0.5})
    fleet = ServingFleet(cfg, model, tparams, replicas=1, engine="paged",
                         decode_batch=2, max_len=32, page_size=4, chunk=8,
                         speculative=True, draft_model=draft,
                         draft_params=dparams, spec_k=3)
    try:
        s = fleet.serve(gen.trace(8))
    finally:
        fleet.close()
    assert s["completed"] == 8
    spec = s["speculative"]
    assert spec["mode"] == "all" and spec["counters"]["admit_spec"] == 8
    assert spec["counters"]["bursts"] > 0
    # damp=0 draft: every proposed token accepted; the blended per-class
    # estimate sits between the prior (0.7) and the measured rate (1.0)
    assert spec["counters"]["accepted"] == spec["counters"]["proposed"] > 0
    for cls in spec["acceptance"]["classes"].values():
        assert 0.7 < cls["alpha"] <= 1.0
    rep = fleet.replicas[0]
    assert rep.spec_capable
    # gain is monotone in alpha and the per-token estimate never exceeds
    # plain decode (auto admission would refuse a losing trade)
    assert rep.spec_gain(1.0) >= rep.spec_gain(0.5) >= rep.spec_gain(0.0)
    assert rep.expected_token_s("chat") <= rep.decode_cost() + 1e-12


def test_fleet_speculative_validation(small_lm):
    cfg, model, params = small_lm
    with pytest.raises(ValueError, match="paged"):
        ServingFleet(cfg, model, params, replicas=1, engine="slot",
                     speculative=True, draft_model=object(), draft_params={})
    with pytest.raises(ValueError, match="draft_model"):
        ServingFleet(cfg, model, params, replicas=1, engine="paged",
                     speculative="auto")
