"""Percentiles and request-level metric arithmetic.

A percentile is the nearest rank: the ``ceil(p/100 * n)``-th smallest value.
It is always one of the samples, so a missing request, counted as ``inf``,
sets the tail when more than ``100 - p`` percent of requests are missing.
"""
from __future__ import annotations

import math
from typing import Iterable, List, Optional


def percentile(values: Iterable[float], p: float) -> Optional[float]:
    vals = sorted(values)
    if not vals:
        return None
    rank = max(1, math.ceil(p / 100.0 * len(vals)))
    return vals[rank - 1]


def ttft_samples(due_s: List[float], first_s: List[Optional[float]],
                 failed: List[bool]) -> List[float]:
    """Seconds from due to first token, one per request due in the window.

    A request that failed or never produced a first token misses every
    limit: it counts as ``inf``, not as a dropped sample.
    """
    out = []
    for due, first, bad in zip(due_s, first_s, failed):
        out.append(math.inf if bad or first is None else first - due)
    return out


def gaps_in_window(stamps: List[float], t0: float, t1: float) -> List[float]:
    """Gaps between consecutive token stamps whose later token fell in
    ``[t0, t1)``."""
    return [b - a for a, b in zip(stamps, stamps[1:]) if t0 <= b < t1]
