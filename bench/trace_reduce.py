"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
device time per XLA module, the operations that took most time, and the
idle gaps named by what the host was doing.

Planes and lines, as the TPU profiler writes them: one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Modules`` holds one event per
execution of a compiled program (named ``<module>(<id>)``, e.g.
``jit_decode_fn(123)``) and whose line ``XLA Ops`` holds one event per
operation, a loop's body ops nested inside the loop's own event (an op
is counted at its self time); the host plane ``/host:CPU`` holds the harness's own
``jax.profiler.TraceAnnotation`` spans, whose names start with ``bench.``.
The span named ``bench.window`` marks the traced window; every figure is
clipped to it.  All times are nanoseconds on the profiler's one clock.
"""
from __future__ import annotations

import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
WINDOW = "bench.window"
PREFIX = "bench."
MODULES, OPS = "XLA Modules", "XLA Ops"
TOP = 10


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: float, b: float, lo: float, hi: float):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def _module_name(event_name: str) -> str:
    return event_name.split("(", 1)[0]


def _op_name(event_name: str) -> str:
    """``fusion.12`` and ``fusion.7`` are one kind of operation.  The TPU
    names an op by its whole HLO text, ``%fusion.12 = bf16[...] ...``: the
    name is what stands before `` = ``."""
    name = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", name) or name


def _self_times(ops: list[tuple[str, float, float]]) -> list[tuple[str, float]]:
    """Each op's time less the time of the ops nested inside it: the TPU's
    op line holds a loop (``while``) and, inside its interval, the ops of
    its body."""
    out: list[list] = []
    stack: list[int] = []      # indices into out of the ops still open
    for name, a, b in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and out[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= out[stack[-1]][2]:
            out[stack[-1]][3] -= b - a
        out.append([name, a, b, b - a])
        stack.append(len(out) - 1)
    return [(name, t) for name, _, _, t in out]


def reduce_events(host: list[tuple[str, float, float]],
                  devices: dict[str, dict[str, list[tuple[str, float, float]]]]
                  ) -> dict:
    """The reduction itself, over plain ``(name, start_ns, end_ns)`` events:
    ``host`` holds the harness's spans, ``devices[plane][line]`` each chip's
    module and op events."""
    windows = [(a, b) for n, a, b in host if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    w0, w1 = windows[0]
    spans = sorted(((n, a, b) for n, a, b in host if n != WINDOW),
                   key=lambda s: s[1])
    module_ns: dict[str, float] = defaultdict(float)
    module_n: dict[str, int] = defaultdict(int)
    op_ns: dict[str, float] = defaultdict(float)
    gap_ns: dict[str, float] = defaultdict(float)
    busy = []
    for lines in devices.values():
        for name, a, b in lines.get(MODULES, []):
            if (c := _clip(a, b, w0, w1)) is not None:
                module_ns[_module_name(name)] += c[1] - c[0]
                module_n[_module_name(name)] += 1
        ops = []
        for name, a, b in lines.get(OPS, []):
            if (c := _clip(a, b, w0, w1)) is not None:
                ops.append((_op_name(name), *c))
        for name, t in _self_times(ops):
            op_ns[name] += t
        merged = _union([(a, b) for _, a, b in ops])
        busy.append(sum(b - a for a, b in merged))
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        for (a, b), name in zip(gaps, _host_activity(spans, gaps)):
            gap_ns[name] += b - a
    chips = max(len(devices), 1)
    s = 1e-9
    return {
        "window_s": (w1 - w0) * s,
        "busy_s": sum(busy) / chips * s,
        "chips": len(devices),
        "module_s": {k: v * s for k, v in module_ns.items()},
        "module_n": dict(module_n),
        "device_ops": [[k, v * s / chips] for k, v in
                       sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[k, v * s / chips] for k, v in
                      sorted(gap_ns.items(), key=lambda kv: -kv[1])[:TOP]],
    }


def _host_activity(spans: list[tuple[str, float, float]],
                   gaps: list[tuple[float, float]]) -> list[str]:
    """For each gap, in time order, the harness span that covers its
    midpoint.  The harness's spans (other than the window) do not overlap,
    so one sweep over both sorted lists finds them."""
    out, i = [], 0
    for a, b in gaps:
        t = (a + b) / 2
        while i < len(spans) and spans[i][2] < t:
            i += 1
        out.append(spans[i][0] if i < len(spans) and spans[i][1] <= t
                   else "host.other")
    return out


def load_events(path: str):
    """Read the host spans and device events of an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host, devices = [], {}
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        host.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
        elif DEVICE_PLANE.match(plane.name):
            devices[plane.name] = {
                line.name: [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
                for line in plane.lines if line.name in (MODULES, OPS)}
    return host, devices


def reduce(path: str) -> dict:
    return reduce_events(*load_events(path))
