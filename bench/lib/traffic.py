"""The one traffic generator: an open loop on the wall clock.

A traffic mix is a data file (``bench/traffic/<mix>.json``) of parameters;
this module turns it and a seed into the list of requests a run offers, each
with the wall-clock offset at which it is due. A run's clock is cut into
spans (the ramp, the measured window, the drain). A span of ``T`` seconds
holds ``round(rate_per_s * T)`` requests, whose lengths are the lognormal's
quantiles at ``(i + 0.5) / n`` and whose gaps are the exponential's (a
Poisson process at ``rate_per_s``), scaled to fill the span exactly, each
shuffled by its own stream of ``SCHEDULE_SEED``. The schedule (when each
request is due, its prompt length and its output length) is so the same
for every ``--seed``; the seed draws the prompts' tokens. At a few dozen
requests a window, the first-token tail depends on where the long prompts
fall among the bursts: with the order drawn from the run's seed, seeds
differed far more than two runs of one seed.

Keys of a mix file:

* ``rate_per_s``: Poisson arrival rate;
* ``ramp_s``: arrivals before the measured window opens, so that it opens
  at steady state;
* ``prompt`` / ``output``: ``{"mean", "sigma", "min", "max"}`` of a
  lognormal (``mean`` of the unclipped distribution, ``sigma`` of its log)
  and the clip applied to each drawn length;
* ``rung``: the format the operator pins for this traffic;
* ``drain_limit_s``: how long past the window's close the run keeps
  serving for the window's requests to produce their first tokens;
* ``trace_s``: the window's last seconds that a ``--trace 1`` run traces;
* ``check``: how many served tokens, from at most how many requests, the
  correctness check compares (``bench/lib/cell.py``).
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List, Optional, Sequence, Union

import numpy as np

FAR = 2 ** 62           # an arrival tick no generate() loop reaches
SCHEDULE_SEED = 20260412


@dataclasses.dataclass
class Arrival:
    rid: int
    due_s: float             # offset from the origin of the run's clock
    prompt: np.ndarray       # (S,) int32 token ids
    max_new: int


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2 ** 64, stream])


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the lognormal's mid-quantiles, clipped."""
    sigma = float(spec["sigma"])
    mu = math.log(float(spec["mean"])) - sigma * sigma / 2
    z = np.array([statistics.NormalDist().inv_cdf((i + 0.5) / n)
                  for i in range(n)])
    lens = np.rint(np.exp(mu + sigma * z)).astype(np.int64)
    return np.clip(lens, int(spec["min"]), int(spec["max"]))


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps at the exponential's mid-quantiles."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def make_arrivals(mix: dict, vocab: int, seed: int,
                  spans: Union[float, Sequence[float]],
                  rate_per_s: Optional[float] = None) -> List[Arrival]:
    """The requests due in the spans laid end to end from 0, ordered by due
    time; each span of ``T`` seconds holds ``max(1, round(rate * T))``.
    Only the prompts' tokens depend on ``seed``."""
    rate = float(rate_per_s if rate_per_s is not None else mix["rate_per_s"])
    spans = [float(spans)] if np.ndim(spans) == 0 else [float(t)
                                                         for t in spans]
    toks = _rng(seed, 3)
    out: List[Arrival] = []
    start = 0.0
    for j, span in enumerate(spans):
        n = max(1, int(round(rate * span)))
        gaps = exponential_gaps(rate, n)
        gaps *= span / gaps.sum()
        _rng(SCHEDULE_SEED, 4 * j).shuffle(gaps)
        due = start + np.cumsum(gaps) - gaps[0]
        plens = lognormal_quantiles(mix["prompt"], n)
        _rng(SCHEDULE_SEED, 4 * j + 1).shuffle(plens)
        olens = lognormal_quantiles(mix["output"], n)
        _rng(SCHEDULE_SEED, 4 * j + 2).shuffle(olens)
        for i in range(n):
            out.append(Arrival(
                rid=len(out), due_s=float(due[i]),
                prompt=toks.integers(1, vocab, int(plens[i])).astype(
                    np.int32),
                max_new=int(olens[i])))
        start += span
    return out
