"""Tuning-search accounting: one module per paper table/figure.

Every time these suites print is a *cost-model microsecond* (a kernel's
modelled runtime) or a *virtual search second* (the simulated measurement
harness); see DESIGN.md for the two clocks.  None is a device time: the
on-chip benchmark is ``bench/run.py`` over ``BENCHMARK.json``'s cells.

Prints ``name,us_per_call,derived`` CSV (the harness contract) and writes
structured JSON under benchmarks/results/.
"""
from __future__ import annotations

import sys
import time

from benchmarks import common


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        bench_full_tuning,
        bench_gemm_transfer,
        bench_headline,
        bench_heuristic,
        bench_kernel_matrix,
        bench_pool,
        bench_resnet,
        bench_runner_cache,
        bench_seqlen,
        bench_targets,
    )

    suites = [
        ("Fig.1 full auto-scheduling", bench_full_tuning),
        ("§4.1 GEMM cross-transfer", bench_gemm_transfer),
        ("Fig.4 per-kernel transfer matrix", bench_kernel_matrix),
        ("Fig.5/Table 4 headline", bench_headline),
        ("Tables 2/3 donor heuristic", bench_heuristic),
        ("Fig.7 sequence-length transfer", bench_seqlen),
        ("Fig.8 mixed pool", bench_pool),
        ("§4.3 ResNet18 from ResNet50 (paper's own models)", bench_resnet),
        ("MeasureRunner cached/pruned backends", bench_runner_cache),
        ("§5.3 server-vs-edge multi-target", bench_targets),
    ]
    only = sys.argv[1] if len(sys.argv) > 1 else None
    t0 = time.monotonic()
    for title, mod in suites:
        if only and only not in mod.__name__:
            continue
        print(f"\n# === {title} ===", flush=True)
        t = time.monotonic()
        common.emit(mod.run())
        print(f"# ({mod.__name__} took {time.monotonic() - t:.1f}s host wall time)",
              flush=True)
    print(f"\n# total host wall time: {time.monotonic() - t0:.1f}s")


if __name__ == "__main__":
    main()
