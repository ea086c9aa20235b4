"""Metric arithmetic: percentiles, missing and failed requests."""
import math

from bench.lib.stats import gaps_in_window, percentile, ttft_samples


def test_nearest_rank_percentile():
    v = list(range(1, 101))                  # 1..100
    assert percentile(v, 90) == 90
    assert percentile(v, 95) == 95
    assert percentile(v[::-1], 50) == 50
    assert percentile([7.0], 90) == 7.0
    assert percentile([], 90) is None


def test_missing_and_failed_requests_count_as_misses():
    due = [0.0, 1.0, 2.0, 3.0]
    first = [0.5, None, 2.25, 3.1]
    failed = [False, False, True, False]
    s = ttft_samples(due, first, failed)
    assert s[0] == 0.5 and math.isinf(s[1]) and math.isinf(s[2])
    assert abs(s[3] - 0.1) < 1e-12
    # two misses of four: the 90th percentile is a miss, not dropped
    assert math.isinf(percentile(s, 90))
    assert percentile(s, 50) == 0.5


def test_gaps_in_window():
    stamps = [0.0, 1.0, 1.5, 3.0, 4.0]
    assert gaps_in_window(stamps, 1.0, 3.5) == [1.0, 0.5, 1.5]
    assert gaps_in_window(stamps, 5.0, 6.0) == []
