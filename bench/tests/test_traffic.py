"""The traffic generator: determinism under a seed, the same work for every
seed, and its wall-clock schedule."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench.lib.traffic import (exponential_gaps, lognormal_quantiles,
                               make_arrivals)

BENCH = Path(__file__).resolve().parents[1]
MIX = json.loads((BENCH / "traffic" / "chat.mxint8.json").read_text())


def test_same_seed_same_requests():
    big = 2 ** 31 + 987654321          # seeds past 32 signed bits
    a = make_arrivals(MIX, 1000, big, 60.0)
    b = make_arrivals(MIX, 1000, big, 60.0)
    assert [x.due_s for x in a] == [x.due_s for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [x.max_new for x in a] == [x.max_new for x in b]


def test_seeds_reorder_the_same_sizes():
    n = 200
    lens = lognormal_quantiles(MIX["prompt"], n)
    assert lens.min() >= MIX["prompt"]["min"]
    assert lens.max() <= MIX["prompt"]["max"]
    # the unclipped mid-quantiles have the stated mean, to a few percent
    assert np.mean(lognormal_quantiles(
        dict(MIX["prompt"], min=0, max=10 ** 9), 20000)) == \
        pytest.approx(MIX["prompt"]["mean"], rel=0.03)
    # two seeds offer the same schedule: when each request is due, its
    # prompt and output lengths; only the prompts' tokens differ
    spans = [15.0, 51.0, 60.0]
    a = make_arrivals(MIX, 1000, 1, spans, rate_per_s=1.0)
    b = make_arrivals(MIX, 1000, 2, spans, rate_per_s=1.0)
    assert [x.due_s for x in a] == [x.due_s for x in b]
    assert [len(x.prompt) for x in a] == [len(x.prompt) for x in b]
    assert [x.max_new for x in a] == [x.max_new for x in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # the window holds round(rate * T) requests, its gaps the exponential's
    # mid-quantiles in a fixed order
    win = [x for x in a if 15.0 <= x.due_s < 66.0]
    assert len(win) == 51
    gaps = np.diff([x.due_s for x in win] + [66.0])
    assert np.allclose(np.sort(gaps), np.sort(exponential_gaps(1.0, 51)
                                              * 51.0 / exponential_gaps(
                                                  1.0, 51).sum()))


def test_wall_clock_schedule():
    rate, horizon = 2.0, 300.0
    a = make_arrivals(MIX, 1000, 5, horizon, rate_per_s=rate)
    due = np.array([x.due_s for x in a])
    assert due[0] == 0.0 and np.all(np.diff(due) > 0)
    assert due[-1] < horizon
    # a Poisson process at `rate`: rate * horizon arrivals, filling it
    assert len(a) == rate * horizon
    assert np.diff(due).mean() == pytest.approx(1 / rate, rel=0.01)
    gaps = exponential_gaps(rate, 1000)
    assert gaps.mean() == pytest.approx(1 / rate, rel=0.01)
    assert all(1 <= x.prompt.min() and x.prompt.max() < 1000 for x in a)
