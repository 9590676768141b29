"""The sample that the reference reads: the longest finished request first,
the rest in an order drawn from the seed, until the served tokens or the
positions the reference would run over reach their budget."""
import types

import pytest

import bench_tiny  # noqa: F401  (puts bench/ on the path)
import check


def _req(prompt: int, served: int):
    return types.SimpleNamespace(prompt=[1] * prompt, generated=[2] * served)


FINISHED = [_req(10, 5), _req(100, 50), _req(20, 3), _req(30, 30), _req(40, 8)]


def test_the_longest_comes_first_and_the_draw_repeats_per_seed():
    a, b = check.sample(FINISHED, 7), check.sample(FINISHED, 7)
    assert a[0] is FINISHED[1]
    assert [id(x) for x in a] == [id(x) for x in b]
    assert len(a) == len(FINISHED)             # everything fits the budgets
    assert check.sample([], 7) == []


@pytest.mark.parametrize("tokens,positions", [(60, 10**6), (10**6, 160)],
                         ids=["served_tokens", "positions"])
def test_the_sample_stops_at_either_budget(monkeypatch, tokens, positions):
    monkeypatch.setattr(check, "SAMPLE_TOKENS", tokens)
    monkeypatch.setattr(check, "SAMPLE_POSITIONS", positions)
    picked = check.sample(FINISHED, 7)
    assert 2 <= len(picked) < len(FINISHED)
    served = sum(len(s.generated) for s in picked)
    pos = sum(len(s.prompt) + len(s.generated) for s in picked)
    assert served >= tokens or pos >= positions
    # one request fewer would not have reached either budget
    assert served - len(picked[-1].generated) < tokens
    assert pos - len(picked[-1].prompt) - len(picked[-1].generated) < positions
