"""Seeded request streams, generated from a traffic file.

One general generator reads every mix in ``bench/traffic/<mix>.json``:

* ``loop``: ``"open"`` (independent users: requests come due on a Poisson
  schedule at ``rate_per_s`` whether or not earlier ones have finished) or
  ``"closed"`` (every decode slot is refilled as soon as its request ends;
  ``requests`` is the size of the stream, which repeats if it runs out).
* ``ttft_limit_ms`` (open loop): the limit on the TTFT p90 under which
  ``sweep.py`` counts a rate as sustained; a run does not read it.
* ``prompt_len`` / ``output_len``: ``{"dist": "lognormal", "median", "sigma",
  "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``, clipped to
  ``[min, max]``.

The run's ``--seed`` does not change the work: every seed serves the same
(prompt, output) lengths with the same arrival times, in the same order, and
draws its own prompt tokens.  So runs on different seeds differ in the
numbers they compute, not in how much there is to do or in which request
meets which: a tail read over a few dozen requests does not swing with the
order they come in.  The Poisson arrivals follow the repo's fleet traffic
generator (exponential gaps); an open-loop stream holds
``round(rate * seconds)`` requests, scaled so that the last is due just
before the window closes.
"""
from __future__ import annotations

import dataclasses
import json

import numpy as np

SIZES_SEED = 0     # draws every mix's lengths, arrivals and their order


@dataclasses.dataclass
class Request:
    prompt: list[int]
    max_new_tokens: int
    due_s: float | None = None    # seconds after the window opens; None: closed loop


def load(path: str) -> dict:
    with open(path) as f:
        spec = json.load(f)
    if spec["loop"] not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be 'open' or 'closed'")
    return spec


def draw_lengths(rng: np.random.Generator, dist: dict, n: int) -> np.ndarray:
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        x = rng.lognormal(np.log(dist["median"]), dist["sigma"], size=n)
        x = np.rint(x)
    elif dist["dist"] == "uniform":
        x = rng.integers(lo, hi + 1, size=n)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


def stream_size(spec: dict, seconds: float) -> int:
    if spec["loop"] == "open":
        return max(1, int(round(spec["rate_per_s"] * seconds)))
    return int(spec["requests"])


def generate(spec: dict, *, seconds: float, seed: int, vocab: int) -> list[Request]:
    """The run's requests, in the order they come due (open loop) or are
    admitted (closed loop)."""
    n = stream_size(spec, seconds)
    fixed = np.random.default_rng(SIZES_SEED)
    prompt_lens = draw_lengths(fixed, spec["prompt_len"], n)
    output_lens = draw_lengths(fixed, spec["output_len"], n)
    rng = np.random.default_rng(seed)
    due = [None] * n
    if spec["loop"] == "open":
        gaps = fixed.exponential(1.0 / spec["rate_per_s"], size=n)
        times = np.cumsum(gaps) * (seconds * (1 - 0.5 / n) / gaps.sum())
        due = [float(t) for t in times]
    return [Request([int(t) for t in rng.integers(1, vocab, size=int(p))],
                    int(o), due[i])
            for i, (p, o) in enumerate(zip(prompt_lens, output_lens))]


def length_range(spec: dict) -> tuple[int, int]:
    """The shortest and longest prompt the mix can send."""
    return int(spec["prompt_len"]["min"]), int(spec["prompt_len"]["max"])
