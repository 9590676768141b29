#!/usr/bin/env python3
"""The serving engine's own spans and the kernel classes in a traced run's
profile, beside what ``trace_reduce.py`` takes from it.

The slot engine enters each of its regions as a ``jax.profiler``
annotation named ``engine.<span>`` (``engine.decode_step`` and its children
``engine.decode_step.prepare`` / ``.dispatch`` / ``.read`` / ``.commit``,
``engine.prefill`` and ``engine.prefill.prepare`` / ``.dispatch`` /
``.read`` / ``.splice``), so the trace holds them on the profiler's clock,
nested inside the harness's ``bench.*`` spans.  Every Pallas kernel is
named by its class (``matmul_bias_gelu``, ``flash_attention_causal`` …) on
the chip's ``XLA Ops`` line.  From those, within ``bench.window``:

* ``host_ms[<call>]``: for ``decode_step`` and ``prefill``, the mean of
  each span less its ``<call>.read`` child, in ms -- the host's part of the
  call, without the wait for the device and the transfer of the tokens;
  None where the trace holds no such pair (an engine that enters none);
* ``op_module_s``: each op's self time keyed by the XLA module execution
  that holds it, so that one kernel class is told apart in
  ``jit_prefill_fn`` and ``jit_decode_fn``;
* ``idle_spans``: the device's idle time split across the host spans it
  overlaps, each part under the innermost span, engine or harness, open
  then (``trace_reduce``'s ``idle_gaps`` names each whole gap by the
  harness span at its midpoint).

``of(rec)`` finds the trace the run wrote and reduces it once for all the
metrics that read it.

    python3 bench/engine_trace.py FILE.xplane.pb   # print op_module_s, idle_spans
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import sys
from collections import defaultdict

import harness
import trace_reduce as tr

PREFIXES = (tr.PREFIX, "engine.")
CALLS = ("decode_step", "prefill")
NO_MODULE = "(none)"
OTHER = "host.other"


def _pieces(intervals: list[tuple[str, float, float]]) -> list[tuple[str, float, float]]:
    """The time that ``intervals`` cover, cut into disjoint pieces in time
    order, each named by the innermost interval open there.  The intervals
    nest, as one thread's spans or one chip's module executions do; where
    two do not, the one that started later counts as the inner."""
    out: list[tuple[str, float, float]] = []
    stack: list[tuple[str, float, float]] = []     # open, innermost last
    t = float("-inf")                              # where the next piece starts

    def close(until: float) -> None:
        nonlocal t
        while stack and stack[-1][2] <= until:
            name, _, b = stack.pop()
            if b > t:
                out.append((name, t, b))
                t = b

    for name, a, b in sorted(intervals, key=lambda e: (e[1], -e[2])):
        close(a)
        if stack and a > t:
            out.append((stack[-1][0], t, a))
        t = a
        stack.append((name, a, b))
    close(float("inf"))
    return out


def _name_at(pieces: list[tuple[str, float, float]], points: list[float],
             default: str) -> list[str]:
    """For each point, the name of the piece that covers it, else
    ``default``."""
    ends = [b for _, _, b in pieces]
    out = []
    for t in points:
        i = bisect.bisect_left(ends, t)
        out.append(pieces[i][0] if i < len(pieces) and pieces[i][1] <= t
                   else default)
    return out


def _overlaps(pieces: list[tuple[str, float, float]],
              gaps: list[tuple[float, float]], default: str) -> dict[str, float]:
    """The gaps' time (the gaps in time order, apart) split across the
    pieces each overlaps, by name; what no piece covers under ``default``."""
    out: dict[str, float] = defaultdict(float)
    i = 0
    for a, b in gaps:
        while i < len(pieces) and pieces[i][2] <= a:
            i += 1
        covered = 0.0
        j = i
        while j < len(pieces) and pieces[j][1] < b:
            name, pa, pb = pieces[j]
            covered += min(b, pb) - max(a, pa)
            out[name] += min(b, pb) - max(a, pa)
            j += 1
        if b - a > covered:
            out[default] += b - a - covered
    return out


def _host_ms(spans: list[tuple[str, float, float]], call: str) -> float | None:
    """Mean of each ``engine.<call>`` span less the ``engine.<call>.read``
    inside it, in ms.  One thread enters them, so the calls do not
    overlap."""
    calls = sorted((a, b) for n, a, b in spans if n == f"engine.{call}")
    starts = [a for a, _ in calls]
    read: dict[int, float] = {}
    for n, a, b in spans:
        if n == f"engine.{call}.read":
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and b <= calls[i][1]:
                read[i] = b - a
    host = [calls[i][1] - calls[i][0] - r for i, r in read.items()]
    return 1e-6 * sum(host) / len(host) if host else None


def reduce_events(host: list[tuple[str, float, float]],
                  devices: dict[str, dict[str, list[tuple[str, float, float]]]]
                  ) -> dict:
    """The reduction over plain ``(name, start_ns, end_ns)`` events, as
    ``trace_reduce.reduce_events`` takes them, ``host`` with the engine's
    spans as well as the harness's."""
    windows = [(a, b) for n, a, b in host if n == tr.WINDOW]
    if not windows:
        raise ValueError(f"no {tr.WINDOW!r} span in the trace")
    w0, w1 = windows[0]
    spans = [(n, a, b) for n, a, b in host if n != tr.WINDOW]
    inside = [(n, a, b) for n, a, b in spans if w0 <= a and b <= w1]
    nested = _pieces(spans)
    op_module_ns: dict[str, dict[str, float]] = defaultdict(lambda: defaultdict(float))
    idle_ns: dict[str, float] = defaultdict(float)
    for lines in devices.values():
        modules = [(tr._module_name(n), *c) for n, a, b in lines.get(tr.MODULES, [])
                   if (c := tr._clip(a, b, w0, w1)) is not None]
        ops = [(tr._op_name(n), *c) for n, a, b in lines.get(tr.OPS, [])
               if (c := tr._clip(a, b, w0, w1)) is not None]
        # _self_times gives the ops in this order, each at its self time
        ordered = sorted(ops, key=lambda e: (e[1], -e[2]))
        held = _name_at(_pieces(modules), [(a + b) / 2 for _, a, b in ordered],
                        NO_MODULE)
        for (name, t), module in zip(tr._self_times(ops), held):
            op_module_ns[module][name] += t
        merged = tr._union([(a, b) for _, a, b in ops])
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        for name, t in _overlaps(nested, gaps, OTHER).items():
            idle_ns[name] += t
    chips = max(len(devices), 1)
    s = 1e-9
    return {
        "window_s": (w1 - w0) * s,
        "host_ms": {c: _host_ms(inside, c) for c in CALLS},
        "op_module_s": {m: {k: v * s / chips for k, v in ops.items()}
                        for m, ops in op_module_ns.items()},
        "idle_spans": [[k, v * s / chips] for k, v in
                       sorted(idle_ns.items(), key=lambda kv: -kv[1])],
    }


def load_events(path: str):
    """The harness's and the engine's host spans and the device events of
    an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host, devices = [], {}
    for plane in data.planes:
        if plane.name == tr.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIXES):
                        host.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
        elif tr.DEVICE_PLANE.match(plane.name):
            devices[plane.name] = {
                line.name: [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
                for line in plane.lines if line.name in (tr.MODULES, tr.OPS)}
    return host, devices


def reduce(path: str) -> dict:
    return reduce_events(*load_events(path))


def of(rec: dict) -> dict | None:
    """The reduction of the trace this run wrote, kept in ``rec`` for the
    next metric; None in a run that was not traced.  ``bench/run.py``
    reduced the newest ``.xplane.pb`` of the cell's trace directory under
    ``harness.CACHE``; the harness empties that directory before it
    profiles, so the run's trace is the newest there whose window is the
    one ``rec["trace"]`` holds."""
    if "engine_trace" not in rec:
        rec["engine_trace"] = None
        if rec.get("trace"):
            paths = glob.glob(os.path.join(harness.CACHE, "trace", "*", "**",
                                           "*.xplane.pb"), recursive=True)
            for path in sorted(paths, key=os.path.getmtime, reverse=True):
                try:
                    r = reduce(path)
                except ValueError:       # a trace with no window
                    continue
                if r["window_s"] == rec["trace"]["window_s"]:
                    rec["engine_trace"] = r
                    break
    return rec["engine_trace"]


if __name__ == "__main__":
    r = reduce(sys.argv[1])
    print(json.dumps({k: r[k] for k in ("host_ms", "op_module_s", "idle_spans")},
                     indent=1))
