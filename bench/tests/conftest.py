"""The tiny spec (``bench_tiny.py``) stands each cell of the real benchmark
for the tiny cell of its loop, by the table ``bench_tiny.AS_TINY``.  A cell
the table does not name yet is given the tiny cell of its own traffic's
loop here, so that a cell added with new files only finds its stand-in."""
import json
import os

import bench_tiny


def _loop_cells() -> dict[str, str]:
    with open(os.path.join(bench_tiny.REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    out = {}
    for w in spec["workloads"]:
        with open(os.path.join(bench_tiny.BENCH, "traffic", f"{w['traffic']}.json")) as f:
            loop = json.load(f)["loop"]
        out[w["name"]] = {"open": "tiny.chat", "closed": "tiny.decode"}[loop]
    return out


for _cell, _tiny in _loop_cells().items():
    bench_tiny.AS_TINY.setdefault(_cell, _tiny)
