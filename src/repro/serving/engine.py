"""Batched serving engine: slot-based continuous batching.

The engine owns a fixed number of decode *slots* (the serving batch) and a
single batched cache whose ``t`` vector tracks a per-slot decode position —
sequences at different lengths decode together in one ``decode_step`` call.
New requests are prefilled (batch=1) into a free slot by splicing that
slot's rows of every cache leaf; finished sequences (EOS / max-tokens) free
their slot immediately, keeping the decode batch dense.

Two serving-cost refinements live here:

* **Execution plans** — when constructed with a plan-capable
  :class:`~repro.kernels.ops.ScheduleProvider`, the engine pre-resolves its
  kernel set into an :class:`~repro.core.resolution.ExecutionPlan`
  (:func:`plan_serving`) and checks the resolution pipeline's generation
  *between* decode steps: when background tuning publishes an upgrade, the
  engine re-plans and re-traces at the step boundary — never mid-step — so
  schedules published to a live registry reach a running server without a
  restart.  ``plan_history`` records the (step, generation) transition
  points; ``replans`` counts swaps.
* **Prefill buckets** — prompts are padded (right, causal-safe) to
  power-of-two length buckets so the prefill trace count is O(log max_len)
  instead of one per distinct prompt length.  The model is told the true
  length (``true_len``) so logits, cache positions, the rows a ring (SWA)
  cache keeps and the tokens routed to experts are exact.  Bucketing is
  enabled only where padding is provably inert: attention-only stacks (a
  recurrent scan would fold pad steps into its state); everything else
  falls back to exact-length prefill.

This is the TPU-idiomatic shape of continuous batching for fixed-size
caches; ring buffers (windowed layers) and recurrent states come from the
model substrate unchanged.

**Tracing.**  With a :class:`~repro.obs.Tracer` bound to ``tracer`` (and
``trace_compute`` left on), each engine call is one span that ends after
the host read of its tokens, so it covers the device's work:
``prefill`` (children ``prefill.prepare`` — padded tokens and their upload,
``prefill.dispatch`` — until the jitted call returns, ``prefill.read`` —
the argmax and its transfer to the host, ``prefill.splice`` — dispatch of
the cache splice) and ``decode_step`` (``decode_step.replan`` when a plan
is swapped, ``.prepare``, ``.dispatch``, ``.read``, ``.commit`` — tokens
appended and slots freed).  Each request is an async span (category
``request``, id ``uid``) from admission to finish.  A call that grew a
jitted function's cache (a compile) is marked ``compiled=True`` on its
span, so a slow step in the trace can be told from a recompile.  The
default, :data:`~repro.obs.ANNOTATION_TRACER`, records nothing and enters
each region as a ``jax.profiler`` annotation named ``engine.<span>``
(``engine.decode_step.read`` …), so a JAX profile taken around the engine
holds its regions on the profiler's clock, and without a profile costs
under a microsecond a region; with it, or with
:data:`~repro.obs.NULL_TRACER`, the path does the same device work,
transfers and syncs as with none.

The slot engine is what the on-chip benchmark (``bench/``) serves.
:class:`~repro.serving.paged.PagedServingEngine` adds iteration-level
admission, a paged KV pool and chunked (padding-free) prefill; it runs
on the CPU in tests and is not measured on a chip.  The slot engine is
also the reference semantics for the paged engine's equivalence tests and
the path for families the paged engine does not cover (audio
encoder-decoder, vision-prefixed prompts).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.resolution import ExecutionPlan, plan_serving
from repro.kernels import ops
from repro.models.build import Model
from repro.obs import ANNOTATION_TRACER, NULL_TRACER


class SlotsFull(RuntimeError):
    """Raised by :meth:`ServingEngine.add_request` when every decode slot is
    occupied — the engine-level backpressure signal (routers queue or shed on
    it instead of probing for a ``None`` return)."""


@dataclasses.dataclass
class Request:
    uid: int
    prompt: list[int]
    max_new_tokens: int
    eos_id: int | None = None
    generated: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # speculative decode (paged engine only; the slot engine ignores both):
    speculative: bool = False
    request_class: str = ""


def prefill_bucket_lengths(cap: int) -> list[int]:
    """The prefill pad lengths up to ``cap``: powers of two, then ``cap``."""
    out, b = [], 1
    while b < cap:
        out.append(b)
        b *= 2
    return out + [cap]


class ServingEngine:
    def __init__(self, model: Model, params: Any, *, slots: int, max_len: int,
                 extras: dict | None = None, provider=None,
                 plan: ExecutionPlan | None = None,
                 prefill_buckets: bool = True):
        self.model = model
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.extras = {k: jnp.asarray(v) for k, v in (extras or {}).items()}
        self.cache = model.init_cache(slots, max_len)
        self.active: dict[int, Request] = {}
        self.last_logits = None   # (slots, vocab) from the latest decode step
        self._uid = 0
        self._admitted_at: dict[int, float] = {}   # uid -> span start, while tracing

        cfg = model.cfg
        self.prefill_buckets = (prefill_buckets and cfg.family != "audio"
                                and "R" not in cfg.layer_kinds)
        self._prefill_lengths: set[int] = set()  # distinct padded lengths traced
        # padding-waste ledger: true prompt tokens vs padded tokens computed
        # (the paged engine's chunked prefill holds these equal)
        self.prefill_true_tokens = 0
        self.prefill_padded_tokens = 0

        # Observability: the owner (fleet / launch driver) rebinds these
        # after construction.  trace_compute gates the engine's own spans —
        # fleets disable it (their tracer runs on the virtual clock, where a
        # jitted call is zero-width, and the replica records the spans).
        self.tracer = ANNOTATION_TRACER
        self.trace_track = "engine"
        self.trace_compute = True

        # Execution plan: pre-resolve the decode batch + prefill buckets.
        self.provider = provider
        self.plan = plan
        self.replans = 0
        # (step, plan generation) at each plan *transition* (first step and
        # every swap) — bounded by the number of re-plans, not the number of
        # decode steps, so a long-lived server never accumulates history.
        self.plan_history: list[tuple[int, int]] = []
        self._steps = 0
        if provider is not None and getattr(provider, "pipeline", None) is not None:
            if self.plan is None:
                self.plan = plan_serving(
                    cfg, provider.pipeline, slots=slots, max_len=max_len,
                    prefill_lengths=self._bucket_lengths())
            provider.plan = self.plan
        self._make_fns()

    # -- tracing --------------------------------------------------------------
    def _make_fns(self) -> None:
        """(Re)build the jitted entry points.

        Called at init and after every re-plan: schedules are resolved at
        trace time, so a plan swap must drop stale traces to take effect.

        Where the kernels compile for the chip, the decode step donates its
        cache: the step writes the new rows into the donated buffers in
        place (``lm.decode_step``) and returns them as the new cache, so the
        engine holds one cache, not two, and copies none of it.  The
        pre-step cache is deleted by the call: :meth:`step` replaces
        ``self.cache`` with the result, and nothing may keep a reference to
        the old one.  In interpret mode (no TPU) the cache is not donated:
        the benchmark's planted "state unchanged" fault
        (``bench/tests/test_bench_run.py``) hands the pre-step cache back to
        the engine, which a donated call would have deleted.
        """
        model, provider, max_len = self.model, self.provider, self.max_len

        def prefill_fn(params, batch, true_len):
            return model.prefill(params, batch, max_len=max_len,
                                 true_len=true_len, provider=provider)

        def decode_fn(params, cache, toks):
            return model.decode_step(params, cache, toks, provider=provider)

        self._prefill = jax.jit(prefill_fn)
        self._decode = jax.jit(
            decode_fn, donate_argnums=() if ops.interpret_mode() else (1,))

    # -- prefill buckets -------------------------------------------------------
    def _pad_len(self, n: int) -> int:
        """Power-of-two bucket for a prompt of n tokens, at most max_len (n
        itself when bucketing is off)."""
        if not self.prefill_buckets or n >= self.max_len:
            return n
        b = 1
        while b < n:
            b *= 2
        return min(b, self.max_len)

    def _bucket_lengths(self) -> list[int]:
        """Every pad length prefill can be traced at (for plan coverage)."""
        if not self.prefill_buckets:
            return []
        return prefill_bucket_lengths(self.max_len)

    @property
    def prefill_trace_count(self) -> int:
        """Distinct prefill shapes traced so far (bounded by the buckets)."""
        return len(self._prefill_lengths)

    def bucket_for(self, prompt_len: int) -> int:
        """The prefill bucket a prompt of this length pads to (routers and
        demand trackers key on it)."""
        return self._pad_len(prompt_len)

    # -- admission accessors ---------------------------------------------------
    @property
    def free_slots(self) -> int:
        """Decode slots currently available for admission."""
        return self.slots - len(self.active)

    def utilization(self) -> float:
        """Fraction of decode slots occupied (0.0 idle .. 1.0 full)."""
        return len(self.active) / self.slots

    # -- capacity gauges (comparable with the paged engine's) ------------------
    def kv_used_tokens(self) -> int:
        """Cache positions actually holding tokens across active slots."""
        return sum(len(r.prompt) + len(r.generated) - 1
                   for r in self.active.values())

    def kv_capacity_tokens(self) -> int:
        """Every slot reserves max_len rows whether used or not — the
        stranded-capacity denominator."""
        return self.slots * self.max_len

    # -- request admission ---------------------------------------------------
    def add_request(self, prompt: list[int], max_new_tokens: int = 16,
                    eos_id: int | None = None) -> Request:
        """Admit a request into a free slot.

        Raises :class:`SlotsFull` when the batch is full and ``ValueError``
        for a prompt the cache cannot hold.  A request the prefill already
        finishes — ``max_new_tokens <= 0``, or the prefill token is EOS — is
        returned ``done`` without ever occupying a slot.
        """
        n = len(prompt)
        if n > self.max_len:
            raise ValueError(
                f"prompt length {n} exceeds max_len {self.max_len}")
        free = [s for s in range(self.slots) if s not in self.active]
        if not free:
            raise SlotsFull(f"all {self.slots} decode slots are occupied")
        slot = free[0]
        self._uid += 1
        req = Request(self._uid, list(prompt), max_new_tokens, eos_id)
        pad = self._pad_len(n)
        self._prefill_lengths.add(pad)
        self.prefill_true_tokens += n
        self.prefill_padded_tokens += pad
        tracer, track = self._span_tracer(), self.trace_track
        with tracer.span("prefill", track, uid=req.uid, true_len=n,
                         bucket=pad, slot=slot) as span:
            if tracer.enabled:
                self._admitted_at[req.uid] = tracer.now()
            with tracer.span("prefill.prepare", track):
                toks = req.prompt + [0] * (pad - n)
                batch = {"tokens": jnp.asarray([toks], jnp.int32)}
                for k, v in self.extras.items():
                    batch[k] = v[None] if v.ndim == 2 else v  # (1, ..., D) stub inputs
                true_len = jnp.asarray(n, jnp.int32)
            with tracer.span("prefill.dispatch", track):
                logits, cache1 = self._call(tracer, span, self._prefill,
                                            self.params, batch, true_len)
            with tracer.span("prefill.read", track):
                # np.asarray forces the single host transfer here;
                # int(jnp.argmax(...)) would add a second device sync.
                tok = int(np.asarray(jnp.argmax(logits[0])))
            req.generated.append(tok)
            if max_new_tokens <= 0 or (eos_id is not None and tok == eos_id) or \
                    len(req.generated) >= max_new_tokens:
                # The prefill token is the whole response: the slot stays
                # free (its cache rows are overwritten by the next admission).
                req.done = True
            else:
                with tracer.span("prefill.splice", track):
                    self.cache = jax.tree_util.tree_map(
                        lambda full, one: _splice_slot(full, one, slot),
                        self.cache, cache1)
                self.active[slot] = req
        if req.done:
            self._finished(tracer, req)
        return req

    def _span_tracer(self):
        """Where the engine's own spans go: the bound tracer when it times
        real work, else nowhere."""
        return self.tracer if self.trace_compute else NULL_TRACER

    def _call(self, tracer, span, fn, *args):
        """Call a jitted entry point.  While tracing, a call that grew the
        function's cache (a compile, or a load from the persistent cache)
        marks ``span`` ``compiled=True``."""
        if not tracer.enabled:
            return fn(*args)
        before = fn._cache_size()
        out = fn(*args)
        if fn._cache_size() != before:
            span.set(compiled=True)
        return out

    def _finished(self, tracer, req: Request) -> None:
        """Close the request's async span, if its admission was traced."""
        t0 = self._admitted_at.pop(req.uid, None)
        if t0 is not None and tracer.enabled:
            tracer.add_async_span("request", self.trace_track, t0, tracer.now(),
                                  cat="request", id=req.uid,
                                  tokens=len(req.generated))

    # -- decode ----------------------------------------------------------------
    def _maybe_replan(self) -> None:
        """Swap in a fresh plan when background tuning moved the generation.

        Only ever called at a step boundary: a plan (and its traces) is
        immutable for the duration of one decode step.
        """
        if not self._plan_stale():
            return
        self.plan = self.plan.refresh(self.provider.pipeline)
        self.provider.plan = self.plan
        self.replans += 1
        self._make_fns()
        if self.tracer.enabled:
            self.tracer.event("replan", self.trace_track,
                              generation=self.plan.generation,
                              replans=self.replans)

    def _plan_stale(self) -> bool:
        return (self.plan is not None and self.provider is not None
                and self.provider.pipeline.generation() != self.plan.generation)

    def refresh_plan(self) -> bool:
        """Adopt any newer published schedule generation *now* — the same
        boundary check :meth:`step` performs, without decoding a token.
        Returns True when the plan was swapped."""
        before = self.replans
        self._maybe_replan()
        return self.replans != before

    def step(self) -> list[Request]:
        """One batched decode step for all active slots; returns finished."""
        if not self.active:
            self._maybe_replan()
            return []
        tracer, track = self._span_tracer(), self.trace_track
        self._steps += 1
        attrs = {}
        if tracer.enabled:
            attrs = {"step": self._steps, "active": len(self.active),
                     "uids": [r.uid for r in self.active.values()]}
        with tracer.span("decode_step", track, **attrs) as span:
            if self._plan_stale():
                with tracer.span("decode_step.replan", track):
                    self._maybe_replan()
            if self.plan is not None and (
                    not self.plan_history
                    or self.plan_history[-1][1] != self.plan.generation):
                self.plan_history.append((self._steps, self.plan.generation))
            with tracer.span("decode_step.prepare", track):
                toks = np.zeros(self.slots, np.int32)
                for slot, req in self.active.items():
                    toks[slot] = req.generated[-1]
                toks = jnp.asarray(toks)
            with tracer.span("decode_step.dispatch", track):
                logits, self.cache = self._call(tracer, span, self._decode,
                                                self.params, self.cache, toks)
                self.last_logits = logits
            with tracer.span("decode_step.read", track):
                nxt = np.asarray(jnp.argmax(logits, axis=-1))
            with tracer.span("decode_step.commit", track):
                finished = []
                for slot, req in list(self.active.items()):
                    tok = int(nxt[slot])
                    req.generated.append(tok)
                    if (req.eos_id is not None and tok == req.eos_id) or \
                            len(req.generated) >= req.max_new_tokens:
                        req.done = True
                        finished.append(req)
                        del self.active[slot]
                        self._finished(tracer, req)
        return finished

    def run_to_completion(self, max_steps: int = 512) -> None:
        for _ in range(max_steps):
            if not self.active:
                break
            self.step()


def _splice_slot(full: jax.Array, one: jax.Array, slot: int) -> jax.Array:
    """Write the batch=1 cache leaf `one` into row `slot` of the batched
    leaf `full` (the batch axis is wherever their shapes differ)."""
    for ax in range(one.ndim):
        if full.shape[ax] != one.shape[ax]:
            idx = [slice(None)] * one.ndim
            idx[ax] = slice(slot, slot + 1)
            return full.at[tuple(idx)].set(one.astype(full.dtype))
    # identical shapes: single-slot engine — the whole leaf is this slot's
    return one.astype(full.dtype)
