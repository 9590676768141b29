"""The slot engine's own spans: each prefill and decode step is one span
that ends after the host read of its tokens, with its parts as children in
order; each request is an async span keyed by its uid; a call that
compiled is marked; and tracing changes no token."""
import itertools

import jax
import pytest

from repro.configs import get_arch, reduced
from repro.models import build_model
from repro.obs import ANNOTATION_TRACER, NULL_TRACER, Tracer
from repro.serving import ServingEngine

PROMPTS = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 10], [11, 12, 13, 14], [15]]
PREFILL_PARTS = ["prefill.prepare", "prefill.dispatch", "prefill.read",
                 "prefill.splice"]
STEP_PARTS = ["decode_step.prepare", "decode_step.dispatch",
              "decode_step.read", "decode_step.commit"]


@pytest.fixture(scope="module")
def small_lm():
    cfg = reduced(get_arch("minitron-4b"))
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _ticks():
    """A clock that moves on at every reading, so that every order is strict."""
    n = itertools.count()
    return lambda: float(next(n))


class _Stamped(list):
    """A request's token list that reads the clock at every append."""

    def __init__(self, tokens, clock, stamps):
        super().__init__(tokens)
        self.clock, self.stamps = clock, stamps

    def append(self, tok):
        self.stamps.append(self.clock())
        super().append(tok)


def _serve(model, params, tracer=None, *, stamps=None, max_new_tokens=4):
    eng = ServingEngine(model, params, slots=2, max_len=32)
    if tracer is not None:
        eng.tracer = tracer
    pending, reqs = list(PROMPTS), []
    while pending or eng.active:
        while pending and eng.free_slots:
            r = eng.add_request(pending.pop(0), max_new_tokens=max_new_tokens)
            if stamps is not None:
                r.generated = _Stamped(r.generated, tracer.now, stamps)
            reqs.append(r)
        eng.step()
    return eng, reqs


def _children(spans, i):
    return [s for s in spans if s.parent == i]


def test_each_call_is_one_span_with_its_parts_ending_after_the_read(small_lm):
    tracer, stamps = Tracer(clock=_ticks()), []
    eng, reqs = _serve(*small_lm, tracer, stamps=stamps)
    spans = tracer.spans
    prefills = [i for i, s in enumerate(spans) if s.name == "prefill"]
    steps = [i for i, s in enumerate(spans) if s.name == "decode_step"]
    assert len(prefills) == len(PROMPTS) == eng._uid
    assert len(steps) == eng._steps > 0
    for i in prefills:
        p = spans[i]
        assert p.parent is None and set(p.attrs) >= {"uid", "true_len", "bucket", "slot"}
        assert [c.name for c in _children(spans, i)] == PREFILL_PARTS
    for i in steps:
        s = spans[i]
        assert s.parent is None
        assert s.attrs["active"] == len(s.attrs["uids"]) and s.attrs["step"] >= 1
        assert [c.name for c in _children(spans, i)] == STEP_PARTS
    for i in prefills + steps:
        kids = _children(spans, i)
        assert spans[i].t0 < kids[0].t0 and kids[-1].t1 < spans[i].t1
        assert all(a.t0 < a.t1 < b.t0 for a, b in zip(kids, kids[1:]))
    # Every decode token is appended after its step's host read and before
    # the step's span ends.
    assert len(stamps) == sum(len(r.generated) - 1 for r in reqs) > 0
    for t in stamps:
        (i,) = [i for i in steps if spans[i].t0 < t < spans[i].t1]
        read = _children(spans, i)[2]
        assert read.t1 < t


def test_request_spans_pair_up_by_uid(small_lm):
    tracer = Tracer(clock=_ticks())
    eng, reqs = _serve(*small_lm, tracer)
    requests = {s.id: s for s in tracer.spans if s.cat == "request"}
    assert sorted(requests) == sorted(str(r.uid) for r in reqs)
    prefill = {s.attrs["uid"]: s for s in tracer.spans if s.name == "prefill"}
    for r in reqs:
        span = requests[str(r.uid)]
        assert span.name == "request" and span.attrs["tokens"] == len(r.generated)
        assert prefill[r.uid].t0 < span.t0 < prefill[r.uid].t1
        # it closes in the commit of the last step that held it
        last = max((i for i, s in enumerate(tracer.spans)
                    if s.name == "decode_step" and r.uid in s.attrs["uids"]),
                   key=lambda i: tracer.spans[i].t0)
        commit = _children(tracer.spans, last)[3]
        assert commit.t0 < span.t1 < commit.t1
    assert eng._admitted_at == {}


def test_a_request_finished_by_its_prefill_closes_its_span_there(small_lm):
    tracer = Tracer(clock=_ticks())
    eng = ServingEngine(*small_lm, slots=1, max_len=32)
    eng.tracer = tracer
    r = eng.add_request([1, 2, 3], max_new_tokens=1)
    assert r.done and not eng.active
    (p,) = [i for i, s in enumerate(tracer.spans) if s.name == "prefill"]
    assert [c.name for c in _children(tracer.spans, p)] == PREFILL_PARTS[:3]
    (req,) = [s for s in tracer.spans if s.cat == "request"]
    assert req.id == str(r.uid) and tracer.spans[p].t1 < req.t1


def test_compiled_marks_the_first_call_of_each_shape(small_lm):
    tracer = Tracer(clock=_ticks())
    eng = ServingEngine(*small_lm, slots=2, max_len=32)
    eng.tracer = tracer
    eng.add_request([1, 2, 3], max_new_tokens=8)       # bucket 4: compiles
    eng.add_request([4, 5, 6, 7], max_new_tokens=8)    # bucket 4 again
    eng.step()
    eng.step()
    eng.run_to_completion()
    eng.add_request([1] * 5, max_new_tokens=2)         # bucket 8: compiles
    spans = [s for s in tracer.spans if s.name in ("prefill", "decode_step")]
    marked = [(s.name, s.attrs.get("bucket")) for s in spans
              if s.attrs.get("compiled")]
    assert marked == [("prefill", 4), ("decode_step", None), ("prefill", 8)]


@pytest.mark.parametrize("clock", ["ticks", "wall"])
def test_tracing_changes_no_token(small_lm, clock):
    """On the wall clock the spans are profiler annotations as well."""
    _, off = _serve(*small_lm)
    tracer = Tracer(clock=_ticks()) if clock == "ticks" else Tracer()
    assert tracer._annotate == (clock == "wall")
    _, on = _serve(*small_lm, tracer)
    assert [r.generated for r in on] == [r.generated for r in off]
    assert tracer.spans


@pytest.mark.parametrize("bound", ["default", "null", "virtual_clock"])
def test_untimed_engines_record_nothing(small_lm, bound):
    """The default (profiler annotations alone), the no-op tracer, and a
    fleet's tracer with compute spans off (its replica records the spans on
    the virtual clock) record no engine span."""
    eng = ServingEngine(*small_lm, slots=2, max_len=32)
    tracer = eng.tracer
    assert tracer is ANNOTATION_TRACER and not tracer.enabled
    if bound == "null":
        tracer = eng.tracer = NULL_TRACER
    if bound == "virtual_clock":
        tracer = eng.tracer = Tracer(clock=lambda: 0.0)
        eng.trace_compute = False
    eng.add_request([1, 2, 3], max_new_tokens=3)
    eng.run_to_completion()
    assert tracer.spans == [] and tracer.events == []
    assert eng._admitted_at == {} and eng._uid == 1
