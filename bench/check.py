"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests the engine finished is
drawn from the seed, the longest among them, until it holds
``SAMPLE_TOKENS`` served tokens or the reference would run over
``SAMPLE_POSITIONS`` positions (long prompts with short answers reach the
second first).  The plain reference (the configuration's
``bench/models/<name>.py`` with ``reference.py``) runs once over each prompt
followed by its served tokens, and the number compared is the widest gap,
over every served token of the sample, by which the served token's logit
lies below the reference's best logit at that position.  Greedy decoding
serves the program's own best token, so a sound program reads a gap of
rounding size: its bf16 logits put first a token that the float32 reference
has within rounding of its best.
"""
from __future__ import annotations

import functools

import numpy as np

import reference

SAMPLE_TOKENS = 2048
SAMPLE_POSITIONS = 65536


def sample(finished: list, seed: int) -> list:
    """The longest finished request, then others in an order drawn from
    ``seed``, until ``SAMPLE_TOKENS`` served tokens or ``SAMPLE_POSITIONS``
    positions are in."""
    if not finished:
        return []
    longest = max(finished, key=lambda s: (len(s.prompt) + len(s.generated),
                                           len(s.generated)))
    rest = [s for s in finished if s is not longest]
    rng = np.random.default_rng([seed, 1])
    picked = [longest]
    n, pos = len(longest.generated), len(longest.prompt) + len(longest.generated)
    for i in rng.permutation(len(rest)):
        if n >= SAMPLE_TOKENS or pos >= SAMPLE_POSITIONS:
            break
        picked.append(rest[i])
        n += len(rest[i].generated)
        pos += len(rest[i].prompt) + len(rest[i].generated)
    return picked


def readings(weights, arch, model: dict, picked: list, *,
             control: bool = False) -> dict:
    """Widest gap of the served tokens over the picked requests, through the
    pass of the architecture module ``arch`` (``bench/models/<name>.py``).
    With ``control``, ``read["control"]`` holds the same readings for the
    tokens the fp8 reference puts first, in the program's place, for
    ``decide``."""
    hidden = functools.partial(arch.final_hidden, weights, arch.ref_config(model))
    served, ctrl, n = [], [], 0
    for s in picked:
        g = reference.gaps(weights, hidden, s.prompt, s.generated, control=control)
        served.append(float(np.max(g["served"])))
        n += len(g["served"])
        if control:
            ctrl.append(float(np.max(g["control"])))
    out = {"max_logit_gap": max(served) if served else float("nan"),
           "per_request": served, "requests": len(picked), "tokens": n}
    if control:
        out["control"] = {"max_logit_gap": max(ctrl) if ctrl else float("nan"),
                          "per_request": ctrl, "requests": len(picked), "tokens": n}
    return out


def decide(read: dict, limits: dict | None) -> tuple[bool, int, dict]:
    """``correct``, the number of sampled requests that failed, and the
    numbers compared, each with its limit.  Without a limit nothing can be
    decided, and the run is not correct."""
    value = read["max_logit_gap"]
    limit = limits["max_logit_gap"]["limit"] if limits is not None else None
    checks = {"max_logit_gap": {"value": value, "limit": limit}}
    if limit is None:
        return False, read["requests"], checks
    failed = sum(not (g <= limit) for g in read["per_request"])
    ok = bool(read["tokens"] > 0 and np.isfinite(value) and failed == 0)
    return ok, failed, checks
