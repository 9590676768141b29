"""Set-up, measured window and record of one benchmark run.

A cell (one ``workloads`` entry of ``BENCHMARK.json``) names a
configuration, ``bench/configs/<config>.json``, and a traffic mix,
``bench/traffic/<traffic>.json``.  The configuration file names its
architecture's module, ``bench/models/<reference_module>.py``
(``arch_module``).  The harness builds what the repo's serving entry
(``repro.launch.serve``) builds: the model, the slot ``ServingEngine`` with a
``ScheduleProvider`` over a ``TuningService``, under
``use_backend("pallas")``.  Set-up, in order:

1. the weights, on the device from ``--seed`` (``bench/weights.py``, in the
   module's layout);
2. the donor tuned with the cost model from the configuration's fixed
   tuning seed into a fresh registry (as ``chip_smoke.tune_and_plan``);
3. the served plan resolved through the ``TuningService`` and its transfer
   jobs drained;
4. every prefill bucket the mix's prompt lengths reach, the decode step,
   and the cache splice into every slot, run once (compiled, or loaded
   from JAX's persistent cache).

The window then drives ``engine.add_request`` and ``engine.step`` from the
mix (``bench/traffic.py``) for ``seconds`` and records, on the host clock,
when each request came due, was admitted and got each token.  Both calls
end in a host read of the argmax, so each span ends when the device is
done.  Per-layer numbers come from those spans, from the engine's counters,
and, in a traced run, from the profiler trace of the end of the window.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import re
import shutil
import sys
import time
from collections import deque
from types import ModuleType

import traffic as traffic_mod

BENCH = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(BENCH), "src")      # the program under test
ROOT = os.path.dirname(BENCH)      # where BENCHMARK.json and its data files are
CACHE = os.path.join(BENCH, ".cache")
TRACE_SECONDS = 10.0     # a traced run profiles this much of the window's end
TRIALS_PER_KERNEL = 16   # cost-model trials the donor's tuning spends per kernel
MODULE_NAME = re.compile(r"[A-Za-z0-9_]{1,64}")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict | None
    arch: ModuleType      # bench/models/<reference_module>.py

    @property
    def model(self) -> dict:
        return self.config["model"]

    @property
    def deployment(self) -> dict:
        return self.config["deployment"]


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> tuple[Cell, dict]:
    """The cell ``name`` of ``BENCHMARK.json`` and the whole spec."""
    root = ROOT
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    bench = os.path.join(root, "bench")
    limits = os.path.join(bench, "limits", f"{name}.json")
    config = _json(os.path.join(root, conf["file"]))
    cell = Cell(name=name, chips=w["chips"], config=config,
                traffic=traffic_mod.load(os.path.join(bench, "traffic",
                                                      f"{w['traffic']}.json")),
                limits=_json(limits) if os.path.exists(limits) else None,
                arch=arch_module(config, conf["file"]))
    return cell, spec


def arch_module(config: dict, file: str) -> ModuleType:
    """The module ``bench/models/<name>.py`` that the configuration file
    ``file`` names under ``reference_module``; a missing key or a name with
    no such module is an error that names the file.

    The module holds what is specific to one architecture's equations:
    ``program_config(conf)``, the program's ``ArchConfig`` for the file,
    checked against its ``model`` block; ``layout(m)``, the weights' leaf
    shapes and draws in the program's parameter tree (``weights.py``);
    ``ref_config(m)``, the reference's hashable settings, refusing what it
    does not compute; ``final_hidden(weights, cfg, tokens, quant=None)``,
    the plain float32 pass to the final norm, with its fp8 control
    (``reference.gaps`` reads the head); and ``dims(m)``, the operations and
    bytes the metrics read as ``rec["dims"]``."""
    name = config.get("reference_module")
    if name is None:
        raise ValueError(f"{file}: no 'reference_module' names the "
                         f"architecture's module in bench/models/")
    models = os.path.join(ROOT, "bench", "models")
    path = os.path.join(models, f"{name}.py")
    if not (isinstance(name, str) and MODULE_NAME.fullmatch(name)
            and os.path.isfile(path)):
        known = sorted(f[:-3] for f in os.listdir(models) if f.endswith(".py"))
        raise ValueError(f"{file}: unknown reference_module {name!r}; "
                         f"bench/models/ has {known}")
    spec = importlib.util.spec_from_file_location(f"bench_model_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod       # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(spec: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that ``cell`` reports."""
    return [m for m in spec[kind] if cell in m.get("workloads", [cell])]


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------


def tune(conf: dict, cfg, target: str, registry_dir: str):
    """Tune the donor into a fresh registry, resolve the served plan through
    a TuningService and drain its jobs.  Returns (provider, service, info)."""
    from repro.configs.base import get_arch
    from repro.core.autoscheduler import tune_model
    from repro.core.resolution import ResolutionPipeline, plan_serving
    from repro.kernels.ops import ScheduleProvider
    from repro.service import ScheduleRegistry, TuningService
    from repro.serving.engine import prefill_bucket_lengths

    t = conf["tuning"]
    d = t["donor"]
    dcfg = dataclasses.replace(get_arch(d["arch"]), **d["overrides"])
    shutil.rmtree(registry_dir, ignore_errors=True)
    uses = plan_serving(dcfg, ResolutionPipeline.build(target=target),
                        slots=d["slots"], max_len=d["max_len"],
                        prefill_lengths=prefill_bucket_lengths(d["max_len"])).uses
    tuned = tune_model(uses, dcfg.name, seed=t["seed"], target=target,
                       total_trials=TRIALS_PER_KERNEL * len(uses))
    registry = ScheduleRegistry(registry_dir)
    registry.publish(tuned.records)
    service = TuningService(registry, model_id=f"serve/{cfg.name}",
                            seed=t["seed"], max_workers=0, target=target)
    provider = ScheduleProvider(service=service)
    dep = conf["deployment"]
    plan = plan_serving(cfg, provider.pipeline, slots=dep["slots"],
                        max_len=dep["max_len"],
                        prefill_lengths=prefill_bucket_lengths(dep["max_len"]))
    drained = service.drain()
    return provider, service, {"donor": d["arch"], "donor_kernels": len(uses),
                               "donor_records": len(tuned.records),
                               "plan_entries": len(plan), "jobs_drained": drained}


def warm(engine, spec: dict) -> list[int]:
    """Run every prefill bucket the mix can reach, the cache splice into
    every slot and the decode step once.  Returns the buckets."""
    lo, hi = traffic_mod.length_range(spec)
    buckets = sorted({engine.bucket_for(n) for n in range(lo, hi + 1)})
    lengths = [min(max(b, lo), hi) for b in buckets]
    for _ in range(engine.slots):
        engine.add_request([1] * lengths[0], max_new_tokens=2)
    engine.step()
    for n in lengths[1:]:
        engine.add_request([1] * n, max_new_tokens=2)
        engine.step()
    if engine.active:
        raise RuntimeError("warm-up left requests in the engine")
    return buckets


@dataclasses.dataclass
class Setup:
    weights: object
    engine: object
    provider: object
    service: object
    dims: object          # the architecture module's dims(m)
    info: dict
    spans: dict
    prev_provider: object = None

    def close(self) -> None:
        """Stop the tuning service and give the kernels back the default
        provider they had before the set-up."""
        from repro.kernels.ops import set_default_provider

        self.service.close()
        set_default_provider(self.prev_provider)


def build(cell: Cell, seed: int, target: str) -> Setup:
    """Steps 1-4 of the set-up (see the module docstring)."""
    import jax

    import weights as weights_mod
    from repro.kernels.ops import set_default_provider, use_backend
    from repro.models.build import build_model
    from repro.serving import ServingEngine

    spans = {}
    cfg = cell.arch.program_config(cell.config)
    model = build_model(cfg)
    t = time.monotonic()
    params = jax.block_until_ready(weights_mod.make(cell.arch, cell.model, seed))
    spans["weights_s"] = time.monotonic() - t
    t = time.monotonic()
    provider, service, info = tune(cell.config, cfg, target,
                                   os.path.join(CACHE, "registry", cell.name))
    spans["tune_s"] = time.monotonic() - t
    prev_provider = set_default_provider(provider)
    dep = cell.deployment
    if dep["engine"] != "slot":
        raise ValueError(f"engine {dep['engine']!r} is not one the harness drives")
    engine = ServingEngine(model, params, slots=dep["slots"],
                           max_len=dep["max_len"], provider=provider)
    t = time.monotonic()
    with use_backend("pallas"):
        info["buckets"] = warm(engine, cell.traffic)
    spans["compile_s"] = time.monotonic() - t
    info["plan_tiers"] = engine.plan.tier_counts()
    info["plan_entries"] = len(engine.plan)
    return Setup(params, engine, provider, service, cell.arch.dims(cell.model),
                 info, spans, prev_provider)


# ---------------------------------------------------------------------------
# The measured window
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Served:
    """One request as the window saw it (seconds from the window's start)."""
    prompt: list[int]
    max_new_tokens: int
    due: float | None
    admit: float | None = None
    first: float | None = None
    last: float | None = None
    tokens: int = 0
    prefills_seen: int = 0
    generated: list[int] | None = None


class _Tracer:
    """Profiles the window's last ``TRACE_SECONDS`` when asked to.  The
    profiler is stopped, and its trace written, once the window has closed,
    so that writing it stalls no request."""

    def __init__(self, on: bool, seconds: float, out_dir: str):
        self.on = on
        self.start = seconds - min(seconds, TRACE_SECONDS)
        self.out_dir = out_dir
        self.active = False
        self.done = False
        self._window = None

    def tick(self, now: float) -> None:
        import jax

        if not self.on or self.done or self.active or now < self.start:
            return
        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()
        self.active = True

    def finish(self) -> None:
        import jax

        if self.active:
            self._window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.active, self.done = False, True

    def annotate(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name) if self.active else \
            contextlib.nullcontext()


def run_window(setup: Setup, reqs: list, *, loop: str, seconds: float,
               trace: bool = False, trace_dir: str = "") -> dict:
    """Serve ``reqs`` for ``seconds``; returns the window's record."""
    from repro.kernels.ops import use_backend

    engine, dims = setup.engine, setup.dims
    served = [Served(r.prompt, r.max_new_tokens, r.due_s) for r in reqs]
    admitted: list[Served] = []
    tracer = _Tracer(trace, seconds, trace_dir)
    clock = time.monotonic
    live: dict[int, Served] = {}
    queue = deque(served)
    closed = loop == "closed"
    gaps, gap_prefill, steps, step_s, active_sum = [], [], 0, 0.0, 0
    traced = {"prefill_flops": 0, "decode_flops": 0, "decode_bytes": 0,
              "prefills": 0, "steps": 0}
    prefills = 0
    pad0 = (engine.prefill_true_tokens, engine.prefill_padded_tokens)
    replans0 = engine.replans
    finished: list[Served] = []
    t0 = clock()
    with use_backend("pallas"):
        while True:
            now = clock() - t0
            tracer.tick(now)
            if now >= seconds:
                break
            while engine.free_slots and queue and (closed or queue[0].due <= now):
                s = queue.popleft()
                if closed:     # the stream repeats if the window outlasts it
                    queue.append(Served(s.prompt, s.max_new_tokens, None))
                s.admit = clock() - t0
                admitted.append(s)
                with tracer.annotate("bench.admit"):
                    req = engine.add_request(s.prompt, s.max_new_tokens)
                s.first = s.last = clock() - t0
                s.tokens, s.generated = 1, req.generated
                prefills += 1
                s.prefills_seen = prefills
                if tracer.active:
                    traced["prefills"] += 1
                    traced["prefill_flops"] += dims.prefill_flops(len(s.prompt))
                if req.done:
                    finished.append(s)
                else:
                    live[req.uid] = s
                now = s.first
                if now >= seconds:
                    break
            if now >= seconds:
                continue
            if engine.active:
                attended = [len(r.prompt) + len(r.generated)
                            for r in engine.active.values()]
                ts = clock()
                with tracer.annotate("bench.step"):
                    done = engine.step()
                te = clock()
                steps += 1
                step_s += te - ts
                active_sum += len(attended)
                if tracer.active:
                    traced["steps"] += 1
                    traced["decode_flops"] += dims.decode_flops(attended)
                    traced["decode_bytes"] += dims.decode_bytes(attended)
                t = te - t0
                for s in live.values():
                    gaps.append(t - s.last)
                    gap_prefill.append(prefills != s.prefills_seen)
                    s.last, s.prefills_seen = t, prefills
                    s.tokens += 1
                for req in done:
                    finished.append(live.pop(req.uid))
            elif not closed:
                nxt = min(queue[0].due, seconds) if queue else seconds
                wait = nxt - (clock() - t0)
                if wait > 0:
                    with tracer.annotate("bench.sleep"):
                        time.sleep(wait)
    window_s = clock() - t0
    tracer.finish()
    return {
        "loop": loop, "start": t0, "window_s": window_s,
        "served": served, "admitted": admitted, "finished": finished,
        "gaps": gaps,
        "gap_prefill": gap_prefill, "steps": steps, "step_s": step_s,
        "active_sum": active_sum, "slots": engine.slots,
        "tokens": sum(s.tokens for s in admitted),
        "late": [s.admit - s.due for s in admitted if s.due is not None],
        "prefill_true": engine.prefill_true_tokens - pad0[0],
        "prefill_padded": engine.prefill_padded_tokens - pad0[1],
        "replans": engine.replans - replans0, "traced": traced,
    }


def percentile(xs: list[float], q: float) -> float | None:
    """q-th percentile (0..100, linear interpolation) as the repo's
    ``repro.obs.metrics.percentile`` computes it; None when empty."""
    import numpy as np

    if len(xs) == 0:
        return None
    return float(xs[0]) if len(xs) == 1 else float(np.percentile(xs, q))


def ttfts(rec: dict) -> list[float]:
    """Due time to first token of every request due in the window; one
    still waiting when the window closed counts at its wait so far."""
    out = []
    for s in rec["served"]:
        if s.due is None or s.due > rec["window_s"]:
            continue
        out.append((s.first if s.first is not None else rec["window_s"]) - s.due)
    return out
