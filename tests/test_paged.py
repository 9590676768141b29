"""Paged KV cache: page-table bookkeeping, continuous-batching semantics,
and numerical equivalence with the contiguous (slot) engine."""
import jax
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.kernels.ops import use_backend
from repro.models import build_model
from repro.serving import (
    PagedServingEngine,
    PagesExhausted,
    PageTable,
    ServingEngine,
    SlotsFull,
)


@pytest.fixture(scope="module")
def small_lm():
    cfg = reduced(get_arch("minitron-4b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


# ---------------------------------------------------------------------------
# PageTable (pure bookkeeping, no jax)
# ---------------------------------------------------------------------------


def test_pagetable_alloc_accounting_and_determinism():
    t = PageTable(9, 4)  # 8 usable pages of 4 slots
    assert t.usable_pages == 8 and t.capacity_tokens == 32
    assert t.pages_for(0) == 0 and t.pages_for(1) == 1
    assert t.pages_for(4) == 1 and t.pages_for(5) == 2
    assert t.ensure(1, 6) == [1, 2]    # lowest-numbered free pages first
    assert t.ensure(1, 6) == []        # already covered: no-op
    assert t.ensure(1, 9) == [3]       # grows by exactly the shortfall
    assert t.ensure(2, 3) == [4]
    assert t.used_pages == 4 and t.free_pages == 4
    assert t.holders() == [1, 2]
    assert t.held_tokens(1) == 12


def test_pagetable_exhaustion_is_atomic():
    t = PageTable(4, 2)  # 3 usable pages
    t.ensure(1, 4)       # takes 2
    with pytest.raises(PagesExhausted):
        t.ensure(2, 6)   # needs 3, only 1 free
    assert t.free_pages == 1        # nothing was allocated
    assert 2 not in t.holders()     # the failed uid holds nothing
    t.ensure(2, 2)                  # the remaining page still works
    with pytest.raises(PagesExhausted):
        t.ensure(1, 6)              # growth failure keeps existing pages
    assert t.pages(1) == [1, 2]


def test_pagetable_release_reuses_lowest_first():
    t = PageTable(5, 2)
    t.ensure(1, 2)
    t.ensure(2, 2)
    t.ensure(3, 2)
    assert t.release(2) == 1
    assert t.release(2) == 0        # double release is a no-op
    assert t.ensure(4, 2) == [2]    # freed page is the lowest available
    assert t.releases == 1 and t.allocs == 4


def test_flat_rows_maps_overflow_to_trash_page():
    t = PageTable(6, 4)
    t.ensure(7, 6)  # pages [1, 2]
    rows = t.flat_rows(7, 16)
    assert list(rows[:4]) == [4, 5, 6, 7]       # page 1
    assert list(rows[4:8]) == [8, 9, 10, 11]    # page 2
    assert list(rows[8:]) == [0] * 8            # beyond allocation: trash
    assert list(t.flat_rows(99, 4)) == [0] * 4  # unknown uid: all trash


def test_fragmentation_gauge_and_defrag():
    t = PageTable(9, 2)
    for uid in range(1, 5):
        t.ensure(uid, 4)  # pages 1..8 across 4 uids
    for uid in (1, 3):
        t.release(uid)    # free list {1,2,5,6}: two runs of two
    assert t.fragmentation() == pytest.approx(0.5)
    before = {uid: t.flat_rows(uid, 4).copy() for uid in (2, 4)}
    moves = t.defrag()
    assert moves and all(src > dst for src, dst in moves)
    assert t.fragmentation() == 0.0  # free space is one contiguous block
    assert sorted(p for uid in t.holders() for p in t.pages(uid)) == [1, 2, 3, 4]
    for uid in (2, 4):  # per-request page ORDER preserved: rows stay aligned
        assert len(t.flat_rows(uid, 4)) == len(before[uid])


# ---------------------------------------------------------------------------
# Engine: admission, exact token counts, pool pressure
# ---------------------------------------------------------------------------


def _engine(model, params, **kw):
    kw.setdefault("decode_batch", 2)
    kw.setdefault("max_ctx", 32)
    kw.setdefault("page_size", 4)
    kw.setdefault("chunk", 8)
    return PagedServingEngine(model, params, **kw)


def test_rejects_oversize_and_admission_cap(small_lm):
    cfg, model, params = small_lm
    eng = _engine(model, params, admit_cap=2)
    with pytest.raises(ValueError, match="max_ctx"):
        eng.add_request(list(range(1, 30)), max_new_tokens=8)
    eng.add_request([1, 2, 3], max_new_tokens=2)
    eng.add_request([4, 5], max_new_tokens=2)
    with pytest.raises(SlotsFull):
        eng.add_request([6], max_new_tokens=1)
    assert not eng.free_slots
    eng.run_to_completion()
    assert not eng.active and eng.table.used_pages == 0


def test_request_larger_than_pool_rejected(small_lm):
    cfg, model, params = small_lm
    eng = _engine(model, params, pool_pages=3)  # 2 usable pages = 8 tokens
    with pytest.raises(ValueError, match="pages"):
        eng.add_request(list(range(1, 10)), max_new_tokens=4)
    assert not eng.active


def test_max_new_tokens_exact_and_chunked_prefill_progress(small_lm):
    """mnt=N yields exactly N tokens; a prompt longer than ``chunk``
    prefills across several steps without blocking the other lane."""
    cfg, model, params = small_lm
    eng = _engine(model, params, chunk=4)
    long = eng.add_request(list(range(1, 14)), max_new_tokens=3)   # 4 chunks
    short = eng.add_request([7, 8], max_new_tokens=3)
    eng.step()
    assert eng._off[long.uid] == 4          # one chunk of progress
    assert short.generated                  # short prompt already emitted
    eng.run_to_completion()
    assert long.done and len(long.generated) == 3
    assert short.done and len(short.generated) == 3
    assert eng.prefill_true_tokens == eng.prefill_padded_tokens  # no padding


def test_oversubscribed_pool_preempts_and_still_completes(small_lm):
    """More concurrent footprint than the pool holds: the engine evicts
    youngest decoders (recompute-on-resume) and every request still
    finishes with its exact token count."""
    cfg, model, params = small_lm
    eng = _engine(model, params, decode_batch=4, page_size=2,
                  pool_pages=13, chunk=8)  # 24 usable tokens for 4 lanes
    reqs = [eng.add_request([i + 1] * 5, max_new_tokens=6) for i in range(4)]
    eng.run_to_completion(max_steps=256)
    assert all(r.done and len(r.generated) == 6 for r in reqs)
    assert eng.preemptions > 0
    assert eng.table.used_pages == 0


def test_admission_gate_holds_fifo_until_pages_free(small_lm):
    """The watermark gate: a request whose prompt cannot fit on top of
    worst-case decode growth stays queued — and later arrivals never jump
    it (FIFO)."""
    cfg, model, params = small_lm
    eng = _engine(model, params, decode_batch=3, page_size=2,
                  pool_pages=8, chunk=16)  # 14 usable tokens
    a = eng.add_request([1] * 10, max_new_tokens=2)
    b = eng.add_request([2] * 10, max_new_tokens=2)   # cannot fit beside a
    c = eng.add_request([3, 4], max_new_tokens=2)     # could fit, but FIFO
    plan = eng.planned_work()
    assert plan["admits"] == 1
    eng.step()
    laned = [r.uid for r in eng.lanes if r is not None]
    assert laned == [a.uid]
    assert [r.uid for r in eng.waiting] == [b.uid, c.uid]
    eng.run_to_completion(max_steps=256)
    assert a.done and b.done and c.done


def test_partial_chunk_advances_under_page_pressure(small_lm):
    """When free pages cannot hold a whole chunk the schedule shrinks the
    chunk instead of stalling the prefill queue behind it."""
    cfg, model, params = small_lm
    eng = _engine(model, params, decode_batch=2, page_size=2,
                  pool_pages=10, chunk=8)  # 18 usable tokens
    a = eng.add_request([1] * 16, max_new_tokens=2)
    eng.step()                  # full first chunk: 8 tokens = 4 pages
    assert eng._off[a.uid] == 8
    eng.table.ensure(777, 8)    # external pressure: grab 4 of 5 free pages
    plan = eng.planned_work()
    assert plan["chunk_lens"] == [2]  # (4 held + 1 free) * 2 - 8 = 2 tokens
    eng.step()
    assert eng._off[a.uid] == 10      # partial progress, no stall
    eng.table.release(777)
    eng.run_to_completion(max_steps=256)
    assert a.done and len(a.generated) == 2


# ---------------------------------------------------------------------------
# Numerical equivalence with the contiguous cache
# ---------------------------------------------------------------------------


def _prompts(cfg, lens=(3, 11, 18, 6)):
    rng = np.random.default_rng(5)
    return [[int(t) for t in rng.integers(1, cfg.vocab_size, size=n)]
            for n in lens]


def _run_paged(model, params, prompts, *, fragment=False, mnt=5, **kw):
    kw.setdefault("decode_batch", len(prompts))
    kw.setdefault("max_ctx", 32)
    kw.setdefault("page_size", 4)
    kw.setdefault("chunk", 8)
    eng = PagedServingEngine(model, params, record_logits=True, **kw)
    if fragment:  # shred the free list before any real allocation
        for i in range(12):
            eng.table.ensure(900 + i, kw["page_size"])
        for i in range(0, 12, 2):
            eng.table.release(900 + i)
        assert eng.table.fragmentation() > 0.0
    reqs = [eng.add_request(p, max_new_tokens=mnt) for p in prompts]
    eng.run_to_completion(max_steps=512)
    assert all(r.done for r in reqs)
    return reqs, eng


def _check_paged_matches_slot(model, params, prompts):
    paged_reqs, _ = _run_paged(model, params, prompts)

    slot = ServingEngine(model, params, slots=len(prompts), max_len=32,
                         prefill_buckets=False)
    slot_reqs = [slot.add_request(p, max_new_tokens=5) for p in prompts]
    while slot.active:
        slot.step()
    for pr, sr in zip(paged_reqs, slot_reqs):
        assert pr.generated == sr.generated


def test_paged_matches_slot_bit_exact_global_attention(small_lm):
    """G-only arch: pages + gather/scatter + chunked prefill change nothing
    — token streams match the slot engine's exact (unbucketed) prefill."""
    cfg, model, params = small_lm
    _check_paged_matches_slot(model, params, _prompts(cfg))


def test_paged_matches_slot_on_pallas_kernels(small_lm):
    """The same equivalence through the Pallas kernels (interpret mode):
    chunked prefill, one-shot prefill and decode all read each layer's
    weights in place from the stacks."""
    cfg, model, params = small_lm
    with use_backend("pallas"):
        _check_paged_matches_slot(model, params, _prompts(cfg, lens=(3, 11)))


def test_fragmented_pool_is_bit_exact_vs_contiguous(small_lm):
    """Scattered pages vs a fresh pool: identical tokens AND identical
    final-chunk logits, bitwise — the dense gather makes layout invisible."""
    cfg, model, params = small_lm
    prompts = _prompts(cfg)
    contig_reqs, contig = _run_paged(model, params, prompts)
    frag_reqs, frag = _run_paged(model, params, prompts, fragment=True)
    for cr, fr in zip(contig_reqs, frag_reqs):
        assert cr.generated == fr.generated
        assert np.array_equal(contig.chunk_logits[cr.uid],
                              frag.chunk_logits[fr.uid])


@pytest.mark.parametrize("arch", ["mixtral-8x22b", "recurrentgemma-2b"])
def test_paged_matches_slot_windowed_and_recurrent(arch):
    """Ring caches and recurrent state stay dense lane strips in the paged
    engine; chunked prefill is exact at every split, so generations match
    the slot engine (logit-level fp reordering tolerated via one decode
    step's allclose, tokens compared exactly)."""
    cfg = reduced(get_arch(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    prompts = _prompts(cfg, lens=(4, 13, 9))  # crosses the reduced window
    paged_reqs, _ = _run_paged(model, params, prompts, page_size=4, chunk=6)

    slot = ServingEngine(model, params, slots=len(prompts), max_len=32,
                         prefill_buckets=False)
    slot_reqs = [slot.add_request(p, max_new_tokens=5) for p in prompts]
    while slot.active:
        slot.step()
    for pr, sr in zip(paged_reqs, slot_reqs):
        assert pr.generated == sr.generated


@pytest.mark.parametrize("arch", ["mixtral-8x22b", "recurrentgemma-2b"])
def test_paged_matches_slot_on_pallas_windowed_and_recurrent(arch):
    """Through the Pallas kernels (interpret mode), where the decode step
    reads each attention layer's cache in place from the stacks it carries
    (the paged engine's gathered dense view, the slot engine's cache):
    ring caches past the window and recurrent layers give the same tokens
    in the paged engine as in the slot engine."""
    cfg = reduced(get_arch(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    prompts = _prompts(cfg, lens=(4, 13))  # crosses the reduced window
    with use_backend("pallas"):
        paged_reqs, _ = _run_paged(model, params, prompts, page_size=4,
                                   chunk=6, mnt=4)
        slot = ServingEngine(model, params, slots=len(prompts), max_len=32,
                             prefill_buckets=False)
        slot_reqs = [slot.add_request(p, max_new_tokens=4) for p in prompts]
        while slot.active:
            slot.step()
    for pr, sr in zip(paged_reqs, slot_reqs):
        assert pr.generated == sr.generated and len(pr.generated) == 4


def test_live_defrag_is_bit_exact(small_lm):
    """A defrag forced mid-generation moves live KV pages and changes
    nothing observable: tokens and final-chunk logits match a run that
    never defragmented, and the engine counts the compaction."""
    cfg, model, params = small_lm
    prompts = _prompts(cfg)
    base_reqs, base = _run_paged(model, params, prompts)
    assert base.defrags == 0                      # no threshold: never fires

    eng = PagedServingEngine(model, params, decode_batch=len(prompts),
                             max_ctx=32, page_size=4, chunk=8,
                             defrag_threshold=0.05, record_logits=True)
    # dummies shred the free list so real allocations land scattered
    for i in range(12):
        eng.table.ensure(900 + i, 4)
    for i in range(0, 12, 2):
        eng.table.release(900 + i)
    reqs = [eng.add_request(p, max_new_tokens=5) for p in prompts]
    for _ in range(3):
        eng.step()                                # real KV rows now exist
    # releasing the interleaved dummies mid-run re-shreds the free list:
    # the next step boundary must defrag and relocate LIVE pages
    for i in range(1, 12, 2):
        eng.table.release(900 + i)
    assert eng.table.fragmentation() > 0.05
    eng.run_to_completion(max_steps=512)
    assert eng.defrags >= 1
    assert all(r.done for r in reqs)
    for br, r in zip(base_reqs, reqs):
        assert br.generated == r.generated
        assert np.array_equal(base.chunk_logits[br.uid],
                              eng.chunk_logits[r.uid])
