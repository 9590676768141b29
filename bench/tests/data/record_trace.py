"""Record the small profiler trace that ``test_bench_trace_reduce.py`` reads,
or list the planes, lines and first events of any trace.

    python bench/tests/data/record_trace.py OUT_DIR      # record (TPU or CPU)
    python bench/tests/data/record_trace.py --dump FILE  # list a .xplane.pb

The recording holds the harness's span names around a few device programs:
``bench.window`` over all, then ``bench.step`` around two runs of a jitted
matmul, ``bench.sleep`` around 20 ms of host sleep, and ``bench.admit``
around one run of a Pallas kernel.
"""
from __future__ import annotations

import glob
import os
import sys
import time


def record(out_dir: str) -> str:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def double(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    step = jax.jit(lambda a, b: jnp.tanh(a @ b))
    kernel = jax.jit(lambda x: pl.pallas_call(
        double, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=jax.default_backend() != "tpu")(x))
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    x = jnp.ones((512, 512), jnp.float32)
    step(a, a).block_until_ready()
    kernel(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.step"):
                step(a, a).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.02)
        with jax.profiler.TraceAnnotation("bench.admit"):
            kernel(x).block_until_ready()
    jax.profiler.stop_trace()
    return sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]


def dump(path: str, per_line: int = 12) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r} events={len(events)}")
            for e in events[:per_line]:
                print(f"    {e.name!r} start_ns={e.start_ns} dur_ns={e.duration_ns}")


if __name__ == "__main__":
    if sys.argv[1] == "--dump":
        dump(sys.argv[2], int(sys.argv[3]) if len(sys.argv) > 3 else 12)
    else:
        path = record(sys.argv[1])
        print(path, os.path.getsize(path))
        dump(path, 1000)
