#!/usr/bin/env python3
"""Find an open-loop cell's knee: serve its mix at several fixed rates in
one process (one set-up) and report, per rate, the tails and whether a
backlog grew.

    python bench/sweep.py --workload <cell> --rates 2,3,4,5 --seconds 20

Not part of a benchmark run: the cell's rate is fixed in its traffic file
from one such sweep.  A rate is sustained when no backlog grows (the
requests still waiting at the window's end stay near none, and the TTFT p90
of the window's second half is not far above that of its first) and the
TTFT p90, overall and in the second half, stays within the mix's latency
limit; the cell's rate is about four fifths of the highest such rate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell, _ = harness.load_cell(args.workload)
    run.configure_jax()
    dev = run.require_tpu(cell.chips)
    sys.path.insert(0, harness.SRC)
    from repro.launch.compile_cache import enable_compile_cache
    from repro.kernels.ops import use_backend
    from repro.targets import target_for_device

    enable_compile_cache()
    setup = harness.build(cell, args.seed, target_for_device(dev["kind"]).name)
    for rate in (float(r) for r in args.rates.split(",")):
        spec = dict(cell.traffic, rate_per_s=rate)
        reqs = traffic.generate(spec, seconds=args.seconds, seed=args.seed,
                                vocab=cell.model["vocab_size"])
        rec = harness.run_window(setup, reqs, loop="open", seconds=args.seconds)
        ttft = harness.ttfts(rec)
        half = args.seconds / 2
        first = [t for t, s in zip(ttft, rec["served"]) if s.due < half]
        second = [t for t, s in zip(ttft, rec["served"]) if s.due >= half]
        p = harness.percentile
        p90, p90_second = p(ttft, 90) * 1e3, (p(second, 90) or 0) * 1e3
        sustained = (len(ttft) - len(rec["admitted"]) <= 1
                     and p90_second <= 2 * max((p(first, 90) or 0) * 1e3, 1.0)
                     and max(p90, p90_second) <= cell.traffic["ttft_limit_ms"])
        print(json.dumps({
            "sustained": sustained, "ttft_limit_ms": cell.traffic["ttft_limit_ms"],
            "device": dev, "cell": cell.name, "rate_per_s": rate,
            "due": len(ttft), "admitted": len(rec["admitted"]),
            "waiting_at_end": len(ttft) - len(rec["admitted"]),
            "finished": len(rec["finished"]),
            "ttft_p50_ms": p(ttft, 50) * 1e3, "ttft_p90_ms": p(ttft, 90) * 1e3,
            "ttft_p90_first_half_ms": (p(first, 90) or 0) * 1e3,
            "ttft_p90_second_half_ms": (p(second, 90) or 0) * 1e3,
            "itl_p50_ms": p(rec["gaps"], 50) * 1e3,
            "itl_p99_ms": p(rec["gaps"], 99) * 1e3,
            "output_tok_s": rec["tokens"] / rec["window_s"],
            "decode_step_ms": 1e3 * rec["step_s"] / max(rec["steps"], 1),
            "slot_occupancy": rec["active_sum"] / max(rec["steps"] * rec["slots"], 1),
            "gap_prefill_share": sum(rec["gap_prefill"]) / max(len(rec["gaps"]), 1),
        }), flush=True)
        with use_backend("pallas"):     # drain before the next rate
            while setup.engine.active:
                setup.engine.step()
    setup.close()


if __name__ == "__main__":
    main()
