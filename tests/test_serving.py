"""Serving engine: continuous batching semantics."""
import jax
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models import build_model
from repro.serving import ServingEngine, SlotsFull


@pytest.fixture(scope="module")
def small_lm():
    cfg = reduced(get_arch("minitron-4b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_fills_slots_and_rejects_overflow(small_lm):
    cfg, model, params = small_lm
    eng = ServingEngine(model, params, slots=2, max_len=32)
    assert eng.free_slots == 2 and eng.utilization() == 0.0
    assert eng.add_request([1, 2, 3]) is not None
    assert eng.add_request([4, 5]) is not None
    assert eng.free_slots == 0 and eng.utilization() == 1.0
    with pytest.raises(SlotsFull):
        eng.add_request([6])  # full batch: explicit backpressure signal
    eng.run_to_completion()
    assert not eng.active
    assert eng.free_slots == 2


def test_prompt_longer_than_max_len_rejected(small_lm):
    cfg, model, params = small_lm
    eng = ServingEngine(model, params, slots=1, max_len=8)
    with pytest.raises(ValueError, match="max_len"):
        eng.add_request(list(range(1, 10)))
    assert not eng.active  # nothing was admitted


def test_zero_new_tokens_finishes_at_admission(small_lm):
    cfg, model, params = small_lm
    eng = ServingEngine(model, params, slots=1, max_len=32)
    r = eng.add_request([1, 2, 3], max_new_tokens=0)
    # prefill emits the one (free) token; no decode slot is ever held
    assert r.done and len(r.generated) == 1
    assert not eng.active and eng.free_slots == 1
    # the slot is immediately reusable
    r2 = eng.add_request([4, 5], max_new_tokens=2)
    eng.run_to_completion()
    assert r2.done


def test_eos_on_prefill_token_finishes_at_admission(small_lm):
    cfg, model, params = small_lm
    probe = ServingEngine(model, params, slots=1, max_len=32)
    first = probe.add_request([1, 2, 3], max_new_tokens=4).generated[0]

    eng = ServingEngine(model, params, slots=1, max_len=32)
    r = eng.add_request([1, 2, 3], max_new_tokens=4, eos_id=first)
    assert r.done and r.generated == [first]
    assert not eng.active


def test_slot_reuse_after_completion(small_lm):
    cfg, model, params = small_lm
    eng = ServingEngine(model, params, slots=1, max_len=32)
    r1 = eng.add_request([1, 2], max_new_tokens=2)
    eng.run_to_completion()
    assert r1.done
    r2 = eng.add_request([3, 4], max_new_tokens=2)
    assert r2 is not None
    eng.run_to_completion()
    assert r2.done


def test_continuous_equals_solo(small_lm):
    """A request joining mid-flight sees the same distribution it would see
    alone.  Token trajectories can diverge from fp near-ties across batch
    shapes, so the contract is logit-level: first token identical (same
    prefill computation), joint-decode logits allclose to solo logits."""
    import numpy as np

    cfg, model, params = small_lm
    eng = ServingEngine(model, params, slots=3, max_len=48)
    eng.add_request([5, 6, 7, 8], max_new_tokens=6)
    eng.step()
    eng.step()
    late = eng.add_request([9, 10, 11], max_new_tokens=5)
    late_slot = next(s for s, r in eng.active.items() if r is late)
    eng.step()
    joint_logits = np.asarray(eng.last_logits)[late_slot]

    solo_eng = ServingEngine(model, params, slots=1, max_len=48)
    solo = solo_eng.add_request([9, 10, 11], max_new_tokens=5)
    assert late.generated[0] == solo.generated[0]  # prefill is identical math
    solo_eng.step()
    solo_logits = np.asarray(solo_eng.last_logits)[0]
    np.testing.assert_allclose(joint_logits, solo_logits, rtol=2e-4, atol=2e-4)


def test_max_new_tokens_is_exact(small_lm):
    """``max_new_tokens=N`` yields exactly N tokens, counting the free
    prefill token — pins the historical off-by-one that emitted N+1."""
    cfg, model, params = small_lm
    eng = ServingEngine(model, params, slots=1, max_len=32)
    for n in (1, 2, 5):
        r = eng.add_request([1, 2, 3], max_new_tokens=n)
        eng.run_to_completion()
        assert r.done and len(r.generated) == n


def test_eos_stops_early(small_lm):
    cfg, model, params = small_lm
    eng = ServingEngine(model, params, slots=1, max_len=32)
    r = eng.add_request([1, 2, 3], max_new_tokens=30)
    # force EOS = whatever it generates next
    eos = None
    while not r.done:
        if eos is None and r.generated:
            eos = r.generated[-1]
            r.eos_id = eos
        eng.step()
    assert len(r.generated) <= 31


def test_prefill_buckets_bound_traces_and_preserve_output(small_lm):
    """Prompts of many distinct lengths share O(log max_len) prefill traces,
    and right-padding + true_len is exact: same tokens as unbucketed."""
    cfg, model, params = small_lm
    for prompt in ([1, 2, 3], [9, 10, 11, 12, 13], [4] * 7):
        bucketed = ServingEngine(model, params, slots=1, max_len=32)
        exact = ServingEngine(model, params, slots=1, max_len=32,
                              prefill_buckets=False)
        rb = bucketed.add_request(list(prompt), max_new_tokens=3)
        re_ = exact.add_request(list(prompt), max_new_tokens=3)
        bucketed.step()
        exact.step()
        np.testing.assert_allclose(np.asarray(bucketed.last_logits)[0],
                                   np.asarray(exact.last_logits)[0],
                                   rtol=1e-5, atol=1e-5)
        bucketed.run_to_completion()
        exact.run_to_completion()
        assert rb.generated == re_.generated

    eng = ServingEngine(model, params, slots=1, max_len=32)
    for n in range(1, 9):  # 8 distinct prompt lengths -> buckets {1,2,4,8}
        r = eng.add_request(list(range(1, n + 1)), max_new_tokens=1)
        eng.run_to_completion()
        assert r.done
    assert eng.prefill_trace_count <= 4


def test_recurrent_arch_skips_bucketing():
    """Padding a recurrent scan would fold pad steps into the state — the
    engine must fall back to exact-length prefill for R-layer archs."""
    cfg = reduced(get_arch("recurrentgemma-2b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, slots=1, max_len=32)
    assert not eng.prefill_buckets
    r = eng.add_request([1, 2, 3], max_new_tokens=3)
    eng.run_to_completion()
    assert r.done


def test_plan_replans_at_step_boundary_and_serves_upgrade(small_lm, tmp_path):
    """A schedule published mid-serve reaches the live engine: the plan is
    swapped at a decode-step boundary (never mid-step) and the upgraded
    schedule becomes the plan's exact-tier entry."""
    import dataclasses

    from repro.core.database import Record
    from repro.core.schedule import default_schedule
    from repro.kernels.ops import ScheduleProvider
    from repro.service import ScheduleRegistry, TuningService

    cfg, model, params = small_lm
    registry = ScheduleRegistry(str(tmp_path / "reg"))
    service = TuningService(registry, model_id="serve", max_workers=0,
                            probe_candidates=0)
    provider = ScheduleProvider(service=service)
    eng = ServingEngine(model, params, slots=2, max_len=32, provider=provider)
    assert eng.plan is not None and len(eng.plan) > 0

    eng.add_request([1, 2, 3], max_new_tokens=8)
    eng.add_request([4, 5, 6, 7], max_new_tokens=8)
    eng.step()
    eng.step()
    g0 = eng.plan.generation
    assert eng.replans == 0

    inst = next(u.instance for u in eng.plan.uses
                if u.instance.class_id == "matmul")
    assert eng.plan.lookup(inst).tier == "default"
    upgraded = dataclasses.replace(default_schedule(inst), unroll=4,
                                   source="background")
    registry.publish([Record(instance=inst, schedule=upgraded,
                             seconds=service.runner.seconds(inst, upgraded),
                             model_id="background", target=service.target)])
    # nothing swaps until the next step boundary
    assert eng.plan.generation == g0

    eng.run_to_completion()
    assert eng.replans == 1
    entry = eng.plan.lookup(inst)
    assert entry.tier == "exact" and entry.schedule == upgraded
    assert not eng.active  # the stream kept serving through the swap

    gens = [g for _, g in eng.plan_history]
    # plan_history records transition points: one generation at the start,
    # one swap, monotone — the upgrade landed at a boundary, never mid-step
    assert gens == sorted(gens)
    assert gens[0] == g0 and gens[-1] > g0
    assert len(gens) == len(set(gens)) == 2


def test_windowed_arch_serving():
    cfg = reduced(get_arch("mixtral-8x22b"))  # SWA ring caches
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    eng = ServingEngine(model, params, slots=2, max_len=64)
    # prompt + generation longer than the (reduced, 8) window: ring must wrap
    r = eng.add_request(list(np.arange(1, 13)), max_new_tokens=12)
    eng.run_to_completion(max_steps=64)
    assert r.done and len(r.generated) == 12


def test_decode_donates_its_cache_where_kernels_compile(small_lm, monkeypatch):
    """Where the kernels compile for the chip (``ops.interpret_mode`` false;
    the ref backend here, so nothing compiles for a TPU), each decode step
    donates the engine's cache: every leaf of the pre-step cache is deleted
    after the step, the engine serves from the returned cache, and the
    tokens equal an engine that does not donate (interpret mode)."""
    from repro.kernels import ops

    cfg, model, params = small_lm
    prompts = [[5, 6, 7, 8], [9, 10, 11]]

    def serve():
        eng = ServingEngine(model, params, slots=2, max_len=24)
        reqs = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        held = []
        while eng.active:
            held.append(jax.tree_util.tree_leaves(eng.cache))
            eng.step()
        return [r.generated for r in reqs], held

    kept, kept_held = serve()
    assert not any(leaf.is_deleted() for leaves in kept_held for leaf in leaves)
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    donated, held = serve()
    assert donated == kept
    assert len(held) == 5
    assert all(leaf.is_deleted() for leaves in held for leaf in leaves)


def test_serving_reads_every_attention_cache_in_place(small_lm):
    """The engine's decode step through the Pallas kernels (interpret mode)
    reads each attention layer's cache in place from the stack: the
    provider counts every layer in place and none sliced."""
    from repro.kernels import ops

    cfg, model, params = small_lm
    provider = ops.ScheduleProvider()
    with ops.use_backend("pallas"):
        eng = ServingEngine(model, params, slots=2, max_len=16, provider=provider)
        r = eng.add_request([1, 2, 3], max_new_tokens=3)
        eng.run_to_completion()
    assert r.done and len(r.generated) == 3
    assert provider.stats()["kv_cache"] == {"in_place": cfg.n_layers, "sliced": 0}
