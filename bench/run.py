#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one chip: the cell's configuration is served through the slot
engine with the transfer-tuned Pallas kernels (``harness.py``), the mix
(``traffic.py``) is offered for ``--seconds`` seconds, and the result is
checked against the plain float32 reference (``check.py``).  The last line
of standard output is one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, with ``--trace 1``, ``breakdown``;
its last key, ``checks``, holds each number compared beside its limit, and
the last lines of standard error repeat them.  ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics, each read
by ``bench/metrics/<name>.py`` from the run's record.

The run exits non-zero, with no result, unless JAX's first device is a TPU
whose ``device_kind`` is in ``counts.PEAKS`` and it sees as many chips as
the cell asks for.  JAX's persistent compilation cache lives in
``bench/.cache/jax`` inside the checkout, so only a checkout's first run
compiles.
"""
from __future__ import annotations

import time

_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import check  # noqa: E402
import counts  # noqa: E402
import harness  # noqa: E402
import traffic  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def fail(msg: str) -> None:
    print(f"bench: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def configure_jax() -> str:
    """Keep the compile cache and the TPU runtime's logs inside the
    checkout; call before JAX is imported."""
    os.environ["TPU_LOG_DIR"] = "disabled"
    path = os.path.join(harness.CACHE, "jax")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def require_tpu(chips: int) -> dict:
    """The device block; exits unless JAX's first device is a TPU in the
    peaks table and there are enough of them."""
    import jax

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        fail(f"no TPU found: JAX's first device is {d.platform!r} ({d.device_kind})")
    if d.device_kind not in counts.PEAKS:
        fail(f"device kind {d.device_kind!r} is not in the peaks table")
    if len(devices) < chips:
        fail(f"the cell asks for {chips} chips, JAX sees {len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def reader(name: str):
    """``read(record)`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Compiles:
    """Programs built (compiled, or loaded from the persistent cache), with
    the monotonic time each was done: none may fall inside the window."""

    def __init__(self):
        import jax

        self.at: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.at.append(time.monotonic())

    def between(self, a: float, b: float) -> int:
        return sum(a <= t <= b for t in self.at)


def emit_stderr(device: dict, **payload) -> None:
    print(json.dumps({"device": device, **payload}), file=sys.stderr, flush=True)


def main(argv=None, *, device: dict | None = None) -> dict:
    """One run.  ``device`` stands in for the look for a chip (tests)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        fail("--seed must be >= 0 and --seconds > 0")
    cell, spec = harness.load_cell(args.workload)
    cache_dir = configure_jax()
    import jax

    dev = device if device is not None else require_tpu(cell.chips)
    peaks = counts.peaks_for(dev["kind"])
    if harness.SRC not in sys.path:
        sys.path.insert(0, harness.SRC)
    from repro.launch.compile_cache import enable_compile_cache
    from repro.targets import target_for_device

    enable_compile_cache()
    compiles = Compiles()
    setup = harness.build(cell, args.seed, target_for_device(dev["kind"]).name)
    reqs = traffic.generate(cell.traffic, seconds=args.seconds, seed=args.seed,
                            vocab=cell.model["vocab_size"])
    trace_dir = os.path.join(harness.CACHE, "trace", cell.name)
    rec = harness.run_window(setup, reqs, loop=cell.traffic["loop"],
                             seconds=args.seconds, trace=bool(args.trace),
                             trace_dir=trace_dir)
    in_window = compiles.between(rec["start"], rec["start"] + rec["window_s"])
    compiles.close()
    stats = jax.devices()[0].memory_stats() or {}
    dev_block = dict(dev, memory_peak_bytes=int(stats.get("peak_bytes_in_use", 0)))
    rec.update(setup_s=rec["start"] - _START, spans=setup.spans,
               plan_tiers=setup.info["plan_tiers"], dims=setup.dims,
               peaks=peaks, trace=None)
    if args.trace:
        xplane = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                                  recursive=True))
        if not xplane:
            fail(f"the traced run left no trace under {trace_dir}")
        import trace_reduce

        rec["trace"] = trace_reduce.reduce(xplane[-1])
        dev_block.update(busy_s=rec["trace"]["busy_s"],
                         window_s=rec["trace"]["window_s"])
    emit_stderr(dev, phase="window", cell=cell.name, seed=args.seed,
                setup_s=rec["setup_s"], **setup.spans, **setup.info,
                window_s=rec["window_s"], due=len(harness.ttfts(rec)),
                admitted=len(rec["admitted"]), finished=len(rec["finished"]),
                tokens=rec["tokens"], steps=rec["steps"], replans=rec["replans"],
                compiles_in_window=in_window, compile_cache=cache_dir,
                late_mean_s=(sum(rec["late"]) / len(rec["late"])
                             if rec["late"] else None),
                late_max_s=max(rec["late"], default=None),
                gaps=len(rec["gaps"]), traced=rec["traced"],
                gap_prefill_share=(sum(rec["gap_prefill"]) / len(rec["gap_prefill"])
                                   if rec["gap_prefill"] else None))

    # Free the program's state before the reference runs beside the weights.
    finished, weights = rec["finished"], setup.weights
    setup.close()
    del setup
    gc.collect()
    read = check.readings(weights, cell.arch, cell.model,
                          check.sample(finished, args.seed))
    ok, failed, checks = check.decide(read, cell.limits)

    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in harness.cell_metrics(spec, cell.name, kind):
        value = reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": ok,
              "attempted": len(harness.ttfts(rec)) if rec["loop"] == "open"
              else len(rec["admitted"]),
              "failed": failed,
              "metrics": metrics, "device": dev_block}
    if rec["trace"] is not None:
        result["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                               "idle_gaps": rec["trace"]["idle_gaps"]}
    result["checks"] = checks
    emit_stderr(dev, phase="check", sampled_requests=read["requests"],
                sampled_tokens=read["tokens"], correct=ok)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
