#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, many seeds in one process.

    python bench/calibrate.py --workload <cell> --seeds 11,12,... \\
        --control-seeds 11,12,13 --seconds 20

For each seed: the seed's weights, a window of the cell's own traffic at its
own load through the same engine (one set-up for all seeds), then the
sample that a benchmark run compares (``check.py``).  Prints the widest gap
of the served tokens (the program's reading) and, on the control seeds, the
widest gap of the tokens the fp8 reference puts first (the control's
reading), each with what ``check.decide`` makes of it under the cell's
limit, if it has one.  The limit in ``bench/limits/<cell>.json`` lies
between the largest program reading and the smallest control reading.  Not
part of a benchmark run.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import check  # noqa: E402
import harness  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402
import weights  # noqa: E402


def main(argv=None, *, device: dict | None = None) -> list[dict]:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    cell, _ = harness.load_cell(args.workload)
    run.configure_jax()
    import jax

    dev = device if device is not None else run.require_tpu(cell.chips)
    if harness.SRC not in sys.path:
        sys.path.insert(0, harness.SRC)
    from repro.launch.compile_cache import enable_compile_cache
    from repro.targets import target_for_device

    enable_compile_cache()
    setup = harness.build(cell, seeds[0], target_for_device(dev["kind"]).name)
    out = []
    for i, seed in enumerate(seeds):
        if i:
            setup.engine.params = setup.weights = None
            gc.collect()
            setup.weights = setup.engine.params = jax.block_until_ready(
                weights.make(cell.arch, cell.model, seed))
        reqs = traffic.generate(cell.traffic, seconds=args.seconds, seed=seed,
                                vocab=cell.model["vocab_size"])
        rec = harness.run_window(setup, reqs, loop=cell.traffic["loop"],
                                 seconds=args.seconds)
        read = check.readings(setup.weights, cell.arch, cell.model,
                              check.sample(rec["finished"], seed),
                              control=seed in control)
        ctrl = read.pop("control", None)
        row = {"device": dev, "cell": cell.name, "seed": seed,
               "finished": len(rec["finished"]), **read,
               "correct": check.decide(read, cell.limits)[0]}
        if ctrl is not None:
            row["control_max_logit_gap"] = ctrl["max_logit_gap"]
            row["control_correct"] = check.decide(ctrl, cell.limits)[0]
        print(json.dumps(row), flush=True)
        out.append(row)
        from repro.kernels.ops import use_backend

        with use_backend("pallas"):
            while setup.engine.active:
                setup.engine.step()
    setup.close()
    return out


if __name__ == "__main__":
    main()
