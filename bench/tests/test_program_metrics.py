"""The readers of the engine's own stamps (``admit_wait_p90_ms``,
``host_gap_ms``, ``mixed_step_ms``) on a hand-built run, against values
worked out by hand."""
import math
import types

import numpy as np

from bench.lib import spec
from bench.lib.cell import RunData
from bench.lib.driver import Pacer, Window
from bench.lib.traffic import Arrival


def tick(kind, dispatched=None, fetched=None, step=None):
    return {"kind": kind, "dispatched_s": dispatched, "fetched_s": fetched,
            "step_s": step}


def make_run(ticks, requests, dues, trace_ticks=(1, 6)):
    """A run whose window opens at 1 s and closes at 5 s, the last 1 s of
    it traced: requests due in [1, 4) are the ones the queue readers
    select."""
    arrivals = [Arrival(rid=i, due_s=d, prompt=np.ones(4, np.int32),
                        max_new=2) for i, d in enumerate(dues)]
    pacer = Pacer(arrivals, requests, Window(1.0, 5.0, 5.0, trace_s=1.0),
                  compiles=lambda: 0)
    pacer.trace_ticks = list(trace_ticks)
    return RunData(cell={}, shapes=None, slots=2, chunk=8, page=8, bits=8,
                   peaks=None, arrivals=arrivals, requests=requests,
                   pacer=pacer, tick_trace=ticks, phases={})


def req(arrival_s=None, admitted_s=None):
    return types.SimpleNamespace(arrival_s=arrival_s, admitted_s=admitted_s)


TICKS = [
    tick("mixed", 0.0, 0.30, 0.29),          # before the traced range
    tick("chunk", 1.00),                     # 1: a chunk alone, no fetch
    tick("mixed", 1.01, 1.30, 0.28),         # 2
    tick("decode", 1.305, 1.35, 0.044),      # 3: gap 5 ms after tick 2
    tick("idle"),                            # 4
    tick("mixed", 2.00, 2.27, 0.26),         # 5
    tick("decode", 2.28, 2.32, 0.04),        # 6: after the traced range
]


def test_host_gap_reads_consecutive_fetch_to_dispatch():
    # traced ticks 1..5: the pairs (2, 3) only; (1, 2) has no fetch,
    # (3, 4) no dispatch, (4, 5) no fetch
    v = spec.reader("host_gap_ms")(make_run(TICKS, [], []))
    assert math.isclose(v, 5.0)
    # with tick 6 traced too, (5, 6) adds 10 ms; nearest-rank median of
    # (5, 10) is 5
    v = spec.reader("host_gap_ms")(make_run(TICKS, [], [], (1, 7)))
    assert math.isclose(v, 5.0)
    v = spec.reader("host_gap_ms")(make_run(TICKS, [], [], (2, 7)))
    assert math.isclose(v, 5.0)
    v = spec.reader("host_gap_ms")(make_run(TICKS, [], [], (5, 7)))
    assert math.isclose(v, 10.0)


def test_mixed_step_reads_the_median_mixed_step():
    # traced mixed ticks 2 and 5: 280 and 260 ms, nearest-rank median 260
    v = spec.reader("mixed_step_ms")(make_run(TICKS, [], []))
    assert math.isclose(v, 260.0)
    v = spec.reader("mixed_step_ms")(make_run(TICKS, [], [], (0, 3)))
    assert math.isclose(v, 280.0)


def test_mixed_step_is_none_with_no_mixed_tick_traced():
    assert spec.reader("mixed_step_ms")(
        make_run(TICKS, [], [], (3, 5))) is None


def test_readers_of_ticks_are_none_outside_a_traced_range():
    for name in ("host_gap_ms", "mixed_step_ms"):
        assert spec.reader(name)(make_run(TICKS, [], [], (1, None))) is None


def test_readers_of_ticks_are_none_without_the_stamps():
    old = [{"prefill_tokens": 8, "decode": 1} for _ in TICKS]
    for name in ("host_gap_ms", "mixed_step_ms"):
        assert spec.reader(name)(make_run(old, [], [])) is None


def test_admit_wait_reads_the_p90_over_the_window_before_the_trace():
    # due in [1, 4): requests 1..10; waits 10, 20, ..., 100 ms; the
    # nearest-rank p90 of ten is the 9th, 90 ms
    dues = [0.5] + [1.0 + 0.25 * k for k in range(10)] + [4.5]
    reqs = [req(0.0, 5.0)] + [req(2.0, 2.0 + 0.01 * (k + 1))
                              for k in range(10)] + [req(6.0, 9.0)]
    v = spec.reader("admit_wait_p90_ms")(make_run([], reqs, dues))
    assert math.isclose(v, 90.0)


def test_admit_wait_counts_a_request_never_admitted_as_inf():
    dues = [1.0, 1.5, 2.0]
    reqs = [req(1.0, 1.01), req(1.5, None), req(None, None)]
    v = spec.reader("admit_wait_p90_ms")(make_run([], reqs, dues))
    assert v == math.inf
    reqs = [req(1.0, 1.01)] * 9 + [req(1.5, None)]
    v = spec.reader("admit_wait_p90_ms")(make_run([], reqs, [1.0] * 10))
    assert math.isclose(v, 10.0)


def test_admit_wait_is_none_from_an_engine_without_the_stamp():
    reqs = [types.SimpleNamespace(arrival_s=1.0)]
    assert spec.reader("admit_wait_p90_ms")(
        make_run([], reqs, [1.0])) is None
