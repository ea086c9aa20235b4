"""The serving driver's hook on a fake clock: releases, idle sleep, window
close, stamps."""
import types

import numpy as np

from bench.lib import driver
from bench.lib.driver import Pacer, StampedTokens, Window
from bench.lib.traffic import FAR, Arrival


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def make(dues, window, clock):
    arr = [Arrival(rid=i, due_s=d, prompt=np.ones(4, np.int32), max_new=2)
           for i, d in enumerate(dues)]
    reqs = [types.SimpleNamespace(arrival_tick=FAR, done=False,
                                  out_tokens=StampedTokens(clock))
            for _ in arr]
    return arr, reqs, Pacer(arr, reqs, window, compiles=lambda: 0,
                            clock=clock)


def test_releases_due_requests_and_maps_ticks(monkeypatch):
    clock = Clock()
    arr, reqs, p = make([0.0, 0.5, 2.0], Window(1.0, 3.0, 5.0), clock)
    monkeypatch.setattr(driver.time, "sleep",
                        lambda s: setattr(clock, "t", clock.t + s))
    assert p.preempted is False            # tick 0 at t=100: rid 0 due
    assert reqs[0].arrival_tick == 0 and reqs[1].arrival_tick == FAR
    clock.t = 100.6
    assert p.preempted is False            # tick 1: rid 1 comes due
    assert reqs[1].arrival_tick == 0
    assert abs(p.lateness_s()[1] - 0.1) < 1e-9
    assert p.tick_of(100.3) == 0 and p.tick_of(100.7) == 1


def test_sleeps_until_next_due_when_idle(monkeypatch):
    clock = Clock()
    arr, reqs, p = make([0.0, 2.0], Window(1.0, 3.0, 5.0), clock)
    slept = []

    def sleep(s):
        slept.append(s)
        clock.t += s
    monkeypatch.setattr(driver.time, "sleep", sleep)
    p.preempted
    reqs[0].done = True                    # nothing in flight any more
    clock.t = 100.5
    assert p.preempted is False
    assert slept == [1.5] and reqs[1].arrival_tick == 0
    assert p.lateness_s()[1] == 0.0


def test_close_waits_for_first_tokens_of_the_window():
    clock = Clock()
    arr, reqs, p = make([0.0, 1.5, 2.5, 3.5], Window(1.0, 3.0, 5.0), clock)
    p.preempted
    clock.t = 103.2                         # past the close
    assert p.preempted is False             # rids 1, 2 have no token yet
    assert p.compiles_at_close == 0
    reqs[1].out_tokens.append(7)
    reqs[2].done = True                     # failed: ends, counts as a miss
    clock.t = 103.3
    assert p.preempted is True
    assert reqs[1].out_tokens.stamps == [103.2]


def test_drain_limit_ends_the_run():
    clock = Clock()
    arr, reqs, p = make([0.0, 1.5], Window(1.0, 3.0, 5.0), clock)
    p.preempted
    clock.t = 108.1
    assert p.preempted is True


def test_stamped_tokens_extend():
    clock = Clock()
    st = StampedTokens(clock)
    st.append(1)
    clock.t = 101.0
    st.extend([2, 3])
    assert list(st) == [1, 2, 3] and st.stamps == [100.0, 101.0, 101.0]
