"""The seeded traffic generator: deterministic per seed, inside its clips,
the same work on every seed in the same order."""
from collections import Counter

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts bench/ on the path)
import traffic

CHAT = {"loop": "open", "rate_per_s": 4.0,
        "prompt_len": {"dist": "lognormal", "median": 256, "sigma": 0.6,
                       "min": 32, "max": 768},
        "output_len": {"dist": "lognormal", "median": 96, "sigma": 0.6,
                       "min": 16, "max": 256}}
DECODE = {"loop": "closed", "requests": 64,
          "prompt_len": {"dist": "uniform", "min": 32, "max": 128},
          "output_len": {"dist": "lognormal", "median": 512, "sigma": 0.3,
                         "min": 256, "max": 896}}
BIG_SEED = 2**31 + 12345


def _gen(spec, seed, seconds=45.0):
    return traffic.generate(spec, seconds=seconds, seed=seed, vocab=1000)


@pytest.mark.parametrize("spec", [CHAT, DECODE], ids=["open", "closed"])
def test_same_seed_same_requests(spec):
    a, b = _gen(spec, BIG_SEED), _gen(spec, BIG_SEED)
    assert [(r.prompt, r.max_new_tokens, r.due_s) for r in a] == \
           [(r.prompt, r.max_new_tokens, r.due_s) for r in b]


@pytest.mark.parametrize("spec", [CHAT, DECODE], ids=["open", "closed"])
def test_seed_changes_the_draw(spec):
    a, b = _gen(spec, 7), _gen(spec, BIG_SEED)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert [(len(r.prompt), r.max_new_tokens, r.due_s) for r in a] == \
           [(len(r.prompt), r.max_new_tokens, r.due_s) for r in b]


@pytest.mark.parametrize("spec", [CHAT, DECODE], ids=["open", "closed"])
def test_every_seed_serves_the_same_work(spec):
    a, b = _gen(spec, 7), _gen(spec, BIG_SEED)
    assert Counter((len(r.prompt), r.max_new_tokens) for r in a) == \
        Counter((len(r.prompt), r.max_new_tokens) for r in b)


@pytest.mark.parametrize("spec", [CHAT, DECODE], ids=["open", "closed"])
def test_lengths_inside_their_clips(spec):
    reqs = _gen(spec, 3)
    p, o = spec["prompt_len"], spec["output_len"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in reqs)
    assert all(o["min"] <= r.max_new_tokens <= o["max"] for r in reqs)
    assert all(1 <= t < 1000 for r in reqs for t in r.prompt)


def test_lognormal_median_and_clip_reached():
    x = traffic.draw_lengths(np.random.default_rng(0), CHAT["prompt_len"], 20000)
    assert abs(np.median(x) - 256) < 10
    assert x.min() == 32 and x.max() == 768


def test_open_loop_due_times_fill_the_window():
    reqs = _gen(CHAT, 5, seconds=45.0)
    due = [r.due_s for r in reqs]
    assert len(reqs) == 180                       # 4 req/s * 45 s
    assert due == sorted(due) and 0 < due[0] and due[-1] < 45.0
    assert due[-1] == pytest.approx(45.0 * (1 - 0.5 / 180))
    gaps = np.diff([0.0] + due)
    assert gaps.mean() == pytest.approx(45.0 / 180, rel=0.01)
    # the gaps are one multiset for every seed
    other = np.diff([0.0] + [r.due_s for r in _gen(CHAT, BIG_SEED, 45.0)])
    assert sorted(gaps) == pytest.approx(sorted(other))


def test_closed_loop_has_no_due_times():
    reqs = _gen(DECODE, 5)
    assert len(reqs) == 64 and all(r.due_s is None for r in reqs)


def test_length_range_and_unknown_distribution():
    assert traffic.length_range(CHAT) == (32, 768)
    with pytest.raises(ValueError, match="unknown length distribution"):
        traffic.draw_lengths(np.random.default_rng(0),
                             {"dist": "zipf", "min": 1, "max": 2}, 4)
