"""Public kernel ops: schedule-aware, backend-dispatching wrappers.

Models call these instead of raw jnp so tuned schedules (native or
transfer-tuned) plumb into execution as a first-class feature:

* ``backend="ref"``    — pure-jnp oracle path (XLA).  Default on CPU/this
  container; also the dry-run path, so `.lower()` sees the same sub-
  quadratic structure the Pallas kernels have (chunked attention).
* ``backend="pallas"`` — the Pallas kernels, realizing the resolved
  :class:`ConcreteSchedule` as BlockSpecs.  On a TPU backend they compile
  with the provider's target VMEM budget; on any other backend they run in
  interpret mode (functionally exact, used by the tests).
  :func:`interpret_mode` reports which, and ``serve.py`` prints it.

Schedule resolution is the :class:`~repro.core.resolution.ResolutionPipeline`
(service → static map → default) behind a :class:`ScheduleProvider` facade.
When an :class:`~repro.core.resolution.ExecutionPlan` is active (serving),
the pre-resolved plan is consulted first — a lock-free dict hit — and only
unplanned instances walk the pipeline (whose memo cache makes the steady
state a dict hit as well).

The per-op hot path is kept cheap: the backend probe runs once per process,
and kernel instances are interned so repeated calls with the same shapes
reuse one validated :class:`KernelInstance` (and its cached workload key)
instead of rebuilding it.
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import Mapping, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import resolution
from repro.core.resolution import ExecutionPlan, ResolutionPipeline
from repro.core.schedule import ConcreteSchedule, Schedule
from repro.core.workload import KernelInstance
from repro.kernels import decode_attention as _da
from repro.kernels import flash_attention as _fa
from repro.kernels import matmul as _mm
from repro.kernels import ref
from repro.kernels import rglru_scan as _rg
from repro.kernels import rwkv6_scan as _rw

_state = threading.local()


def _default_backend() -> str:
    return getattr(_state, "backend", "ref")


def set_backend(backend: str) -> None:
    assert backend in ("ref", "pallas")
    _state.backend = backend


@contextlib.contextmanager
def use_backend(backend: str):
    prev = _default_backend()
    set_backend(backend)
    try:
        yield
    finally:
        set_backend(prev)


class ScheduleProvider:
    """Resolves the schedule for each kernel instance the model emits.

    A thin facade over a :class:`ResolutionPipeline` plus an optional active
    :class:`ExecutionPlan`:

    * ``plan`` (when set) answers first — pre-resolved dict hit;
    * the pipeline walks service → static map → default on plan misses and
      memoizes per ``(workload, mode, target, generation)``.

    Construct either from the legacy pieces (``schedule_map`` and/or
    ``service``) or from an explicit ``pipeline``.  Invalid entries (e.g. a
    transferred schedule that does not concretize strictly) fall through to
    the next stage — execution never fails on a bad DB.

    Per-tier lookup counts (``exact``/``transfer``/``static``/``default``)
    live in the pipeline and are thread-safe; a service answer of the
    untuned-default tier is *not* a hit.  ``hits``/``misses`` remain as
    derived compatibility properties.
    """

    def __init__(self, schedule_map: Mapping[str, Schedule] | None = None,
                 mode: str = "strict", service=None, *,
                 pipeline: ResolutionPipeline | None = None,
                 plan: ExecutionPlan | None = None, target=None):
        if pipeline is None:
            pipeline = ResolutionPipeline.build(
                schedule_map=schedule_map, service=service, mode=mode,
                target=target)
        self.pipeline = pipeline
        self.plan = plan
        self._lock = threading.Lock()
        # Plan answers bucketed by tier (a default-tier plan entry is still
        # an untuned kernel — it must not masquerade as a hit), plus misses
        # (instances the plan does not cover, served by the pipeline) so
        # coverage gaps are observable.
        self._plan_served = {t: 0 for t in resolution.TIERS}
        self._plan_misses = 0
        # Pallas matmul weight reads, counted at trace time: "in_place" adds
        # a stack's depth for each traced call on a LayerRef (the call runs
        # once per layer of the scan), "plain" adds 1 for a plain array
        # (inside a layer scan, a slice of the stack copied out first).
        # The ragged expert GEMMs' expert stacks are counted apart.
        self._weight_reads = {"in_place": 0, "plain": 0}
        self._expert_reads = {"in_place": 0, "plain": 0}
        # Pallas decode-attention cache reads, counted the same way:
        # "in_place" adds a stack's depth for a layer-indexed cache, "sliced"
        # adds 1 for a plain per-layer cache.
        self._kv_reads = {"in_place": 0, "sliced": 0}

    @property
    def mode(self) -> str:
        return self.pipeline.mode

    @property
    def service(self):
        return self.pipeline.service

    @property
    def schedule_map(self) -> dict[str, Schedule]:
        return self.pipeline.schedule_map

    def get(self, instance: KernelInstance) -> ConcreteSchedule:
        plan = self.plan
        if plan is not None:
            r = plan.lookup(instance)
            if r is not None:
                with self._lock:
                    self._plan_served[r.tier] += 1
                return r.concrete
            with self._lock:
                self._plan_misses += 1
        return self.pipeline.resolve(instance).concrete

    def count_weight_read(self, in_place: bool, layers: int) -> None:
        with self._lock:
            self._weight_reads["in_place" if in_place else "plain"] += layers

    def count_expert_read(self, in_place: bool, layers: int) -> None:
        with self._lock:
            self._expert_reads["in_place" if in_place else "plain"] += layers

    def count_kv_read(self, in_place: bool, layers: int) -> None:
        with self._lock:
            self._kv_reads["in_place" if in_place else "sliced"] += layers

    @property
    def vmem_limit_bytes(self) -> int:
        """The target chip's VMEM budget: the legality rule sizes blocks
        against it and every kernel hands it to Mosaic."""
        return self.pipeline.spec.vmem_capacity

    # -- telemetry ------------------------------------------------------------
    @property
    def plan_hits(self) -> int:
        """Total resolutions the active plan answered (any tier)."""
        with self._lock:
            return sum(self._plan_served.values())

    def stats(self) -> dict:
        out = self.pipeline.stats()
        with self._lock:
            out["plan_served"] = dict(self._plan_served)
            out["plan_misses"] = self._plan_misses
            out["matmul_weights"] = dict(self._weight_reads)
            out["expert_weights"] = dict(self._expert_reads)
            out["kv_cache"] = dict(self._kv_reads)
        out["plan_hits"] = sum(out["plan_served"].values())
        out["plan_entries"] = len(self.plan) if self.plan is not None else 0
        out["plan_generation"] = (self.plan.generation
                                  if self.plan is not None else None)
        return out

    # Legacy counters: tuned-tier resolutions count as hits, untuned as
    # misses (regardless of whether the plan or the pipeline served them).
    @property
    def hits(self) -> int:
        s = self.stats()
        return sum(s["plan_served"][t] + s[f"served_{t}"]
                   for t in ("exact", "transfer", "static"))

    @property
    def misses(self) -> int:
        s = self.stats()
        return s["plan_served"]["default"] + s["served_default"]


_DEFAULT_PROVIDER = ScheduleProvider()


def set_default_provider(provider: ScheduleProvider | None) -> ScheduleProvider:
    """Install the provider kernels use when no explicit one is passed.

    Returns the previous default so callers can restore it.  ``None``
    reinstalls an empty (all-defaults) provider."""
    global _DEFAULT_PROVIDER
    prev = _DEFAULT_PROVIDER
    _DEFAULT_PROVIDER = provider if provider is not None else ScheduleProvider()
    return prev


def _resolve(provider: ScheduleProvider | None) -> ScheduleProvider:
    return provider if provider is not None else _DEFAULT_PROVIDER


# ---------------------------------------------------------------------------
# Per-op hot-path helpers: interned instances, hoisted backend probe
# ---------------------------------------------------------------------------

_DTYPE_STR: dict = {}


def _dtype_str(dt) -> str:
    s = _DTYPE_STR.get(dt)
    if s is None:
        s = _DTYPE_STR[dt] = str(dt)
    return s


@functools.lru_cache(maxsize=8192)
def _interned(class_id: str, dtype: str,
              params: tuple[tuple[str, int], ...]) -> KernelInstance:
    return KernelInstance(class_id=class_id, params=params, dtype=dtype)


def _instance(class_id: str, dtype, **params: int) -> KernelInstance:
    """Interned KernelInstance.make: validation + workload key amortized."""
    return _interned(class_id, _dtype_str(dtype),
                     tuple(sorted((k, int(v)) for k, v in params.items())))


@functools.cache
def interpret_mode() -> bool:
    """Whether the ``pallas`` backend runs its kernels in interpret mode:
    exactly when JAX's default backend is not a TPU.  The probe is
    process-wide and stable, so it runs once."""
    return jax.default_backend() != "tpu"


def _kernel_kw(provider: ScheduleProvider) -> dict:
    return {"interpret": interpret_mode(),
            "vmem_limit_bytes": provider.vmem_limit_bytes}


# ---------------------------------------------------------------------------
# matmul family
# ---------------------------------------------------------------------------


class LayerRef(NamedTuple):
    """Layer ``index`` of a stacked array ``stack`` (a weight (L, K, N), an
    expert stack (L, E, K, N), a KV cache (L, B, KV, T, hd)), as a layer
    scan hands it to :func:`matmul`, :func:`moe_gemm` or
    :func:`decode_attention`: the Pallas kernel reads the layer's blocks in
    place from the stack, where a slice ``stack[index]`` would be copied out
    first."""
    stack: jax.Array
    index: jax.Array


def matmul(x: jax.Array, w: jax.Array | LayerRef, *, class_id: str = "matmul",
           bias: jax.Array | None = None, residual: jax.Array | None = None,
           softcap: float = 0.0, provider: ScheduleProvider | None = None,
           backend: str | None = None) -> jax.Array:
    """x: (..., K) @ w: (K, N) with fused epilogue. GLU classes emit N//2.

    ``w`` may be a :class:`LayerRef`; the schedule is keyed on (M, N, K)
    alone, whichever layer is read."""
    backend = backend or _default_backend()
    in_place = isinstance(w, LayerRef)
    if backend == "ref":
        if in_place:
            w = jax.lax.dynamic_index_in_dim(w.stack, w.index, keepdims=False)
        return ref.matmul(x, w, class_id, bias=bias, residual=residual, softcap=softcap)
    stack, layer = (w.stack, w.index) if in_place else (w, 0)
    *lead, k = x.shape
    n = stack.shape[-1]
    m = 1
    for s in lead:
        m *= s
    x2 = x.reshape(m, k)
    res2 = residual.reshape(m, -1) if residual is not None else None
    inst = _instance(class_id, x.dtype, M=m, N=n, K=k)
    provider = _resolve(provider)
    provider.count_weight_read(in_place, stack.shape[0] if in_place else 1)
    y = _mm.matmul(x2, stack, provider.get(inst), layer=layer,
                   class_id=class_id, bias=bias, residual=res2,
                   softcap=softcap, **_kernel_kw(provider))
    return y.reshape(*lead, y.shape[-1])


def moe_gemm(x: jax.Array, w: jax.Array | LayerRef, *,
             group_sizes: jax.Array | None = None, class_id: str = "moe_gemm",
             provider: ScheduleProvider | None = None,
             backend: str | None = None) -> jax.Array:
    """Expert GEMM, in one of two layouts.

    * ``group_sizes`` given (ragged, the dropless dispatch): x (M, K) holds the
      routed rows sorted by expert, ``group_sizes`` (E,) counts each
      expert's rows, and row i is multiplied by its expert's (K, N) weight.
      Rows past the counted ones are no expert's: the ref backend gives
      zeros there, the Pallas kernel leaves a tile that no group reaches
      unwritten, so the caller masks them.  ``w`` is (E, K, N) or a
      :class:`LayerRef` to a stack (L, E, K, N), read in place.  The schedule is keyed on the routed rows: (M, N, K, E).
    * otherwise (the capacity dispatch, on a mesh): x (E, M, K) @ w
      (E, K, N), every expert's M rows, keyed on M·E."""
    backend = backend or _default_backend()
    in_place = isinstance(w, LayerRef)
    if group_sizes is not None:
        stack, layer = (w.stack, w.index) if in_place else (w, 0)
        if backend == "ref":
            ws = (jax.lax.dynamic_index_in_dim(stack, layer, keepdims=False)
                  if in_place else stack)
            y = jax.lax.ragged_dot(x, ws, group_sizes.astype(jnp.int32),
                                   preferred_element_type=jnp.float32)
            return ref.apply_epilogue(y, class_id).astype(x.dtype)
        m, k = x.shape
        e, n = stack.shape[-3], stack.shape[-1]
        inst = _instance(class_id, x.dtype, M=m, N=n, K=k, E=e)
        provider = _resolve(provider)
        provider.count_expert_read(in_place, stack.shape[0] if in_place else 1)
        return _mm.ragged_matmul(x, stack, group_sizes, provider.get(inst),
                                 layer=layer, class_id=class_id,
                                 **_kernel_kw(provider))
    if backend == "ref":
        return jax.vmap(lambda a, b: ref.matmul(a, b, class_id))(x, w)
    e, m, k = x.shape
    n = w.shape[2]
    inst = _instance(class_id, x.dtype, M=m * e, N=n, K=k, E=e)
    provider = _resolve(provider)
    return _mm.grouped_matmul(x, w, provider.get(inst), class_id=class_id,
                              **_kernel_kw(provider))


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    class_id: str = "flash_attention_causal",
                    causal: bool = True, window: int = 0, softcap: float = 0.0,
                    q_offset: int = 0, provider: ScheduleProvider | None = None,
                    backend: str | None = None, chunk: int = 1024) -> jax.Array:
    """q: (B,Hq,Sq,D); k/v: (B,Hkv,Skv,D) — GQA-aware flash attention."""
    backend = backend or _default_backend()
    if backend == "ref":
        return ref.chunked_attention(q, k, v, causal=causal, window=window,
                                     softcap=softcap, q_offset=q_offset, chunk=chunk)
    b, hq, sq, d = q.shape
    inst = _instance(class_id, q.dtype, Q=sq, KV=k.shape[2], H=hq, D=d, B=b,
                     window=window)
    provider = _resolve(provider)
    return _fa.flash_attention(q, k, v, provider.get(inst), class_id=class_id,
                               causal=causal, window=window, softcap=softcap,
                               q_offset=q_offset, **_kernel_kw(provider))


def decode_attention(q: jax.Array, k: jax.Array | LayerRef,
                     v: jax.Array | LayerRef, pos: jax.Array, *,
                     softcap: float = 0.0,
                     provider: ScheduleProvider | None = None,
                     backend: str | None = None) -> jax.Array:
    """One query token per slot against its cache: q (B, Hq, 1, hd); k and v
    (B, KV, T, hd), or :class:`LayerRef` s (one index) to stacks
    (L, B, KV, T, hd), which the Pallas kernel reads in place.  Slot b
    attends cache rows ``< min(pos[b] + 1, T)``: the causal mask of a full
    cache, the written slots of a ring cache no longer than its window.
    Float32 scores and sums.  The kernel's block comes from the shape: no
    schedule is resolved."""
    backend = backend or _default_backend()
    in_place = isinstance(k, LayerRef)
    if backend == "ref":
        if in_place:
            k = jax.lax.dynamic_index_in_dim(k.stack, k.index, keepdims=False)
            v = jax.lax.dynamic_index_in_dim(v.stack, v.index, keepdims=False)
        return ref.decode_attention(q, k, v, pos, softcap)
    ks, vs, layer = (k.stack, v.stack, k.index) if in_place else (k, v, 0)
    b, hq, _, d = q.shape
    hkv, t = ks.shape[-3], ks.shape[-2]
    provider = _resolve(provider)
    provider.count_kv_read(in_place, ks.shape[0] if in_place else 1)
    o = _da.decode_attention(q.reshape(b, hkv, hq // hkv, d), ks, vs,
                             jnp.minimum(pos + 1, t), layer=layer,
                             softcap=softcap, **_kernel_kw(provider))
    return o.reshape(b, hq, 1, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# recurrent scans
# ---------------------------------------------------------------------------


def rwkv6(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array, u: jax.Array,
          state: jax.Array, *, provider: ScheduleProvider | None = None,
          backend: str | None = None) -> tuple[jax.Array, jax.Array]:
    backend = backend or _default_backend()
    if backend == "ref":
        return ref.rwkv6_scan(r, k, v, w, u, state)
    b, h, t, d = r.shape
    inst = _instance("rwkv6_scan", r.dtype, T=t, C=h * d, D=d, B=b)
    provider = _resolve(provider)
    return _rw.rwkv6_scan(r, k, v, w, u, state, provider.get(inst),
                          **_kernel_kw(provider))


def rglru(x: jax.Array, a: jax.Array, state: jax.Array, *,
          provider: ScheduleProvider | None = None,
          backend: str | None = None) -> tuple[jax.Array, jax.Array]:
    backend = backend or _default_backend()
    if backend == "ref":
        return ref.rglru_scan(x, a, state)
    b, t, c = x.shape
    inst = _instance("rglru_scan", x.dtype, T=t, C=c, B=b)
    provider = _resolve(provider)
    return _rg.rglru_scan(x, a, state, provider.get(inst),
                          **_kernel_kw(provider))
