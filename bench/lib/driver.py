"""The serving driver: an open loop on the wall clock around ``generate``.

``ElasticEngine.generate`` takes a list of requests and gates them only by
``Request.arrival_tick``. Two public hooks carry the driver:

* ``guard.preempted`` is read once at each tick boundary. ``Pacer`` stands
  in for the guard: at each read it records the boundary's wall time,
  releases every request that has come due (``arrival_tick`` from far away
  to 0), sleeps until the next one is due when nothing is in flight (so
  idle ticks do not spin), starts and stops the profiler at the traced
  window's boundaries, and reports preemption once the run is over, which
  makes ``generate`` return (no snapshot directory is given).
* the engine only ``append``s to or ``extend``s ``Request.out_tokens``;
  ``StampedTokens`` stamps each token as it reaches the host.

Requests not yet due get a far ``arrival_tick``; every time is on one
monotonic clock (``time.perf_counter``). The hook's own work shows in a
profiler trace as the host span ``bench.hook``, its idle sleep as
``bench.idle_wait``.
"""
from __future__ import annotations

import bisect
import dataclasses
import time
from typing import Callable, List, Optional

from bench.lib.traffic import FAR, Arrival


class StampedTokens(list):
    """``out_tokens`` that stamps the host time of every token it takes."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        super().__init__()
        self.stamps: List[float] = []
        self._clock = clock

    def append(self, tok):
        self.stamps.append(self._clock())
        super().append(tok)

    def extend(self, toks):
        toks = list(toks)
        self.stamps.extend([self._clock()] * len(toks))
        super().extend(toks)


@dataclasses.dataclass
class Window:
    """Run clock, seconds from the origin (the first tick boundary)."""
    open_s: float
    close_s: float
    drain_limit_s: float
    trace_s: float = 0.0     # >0: trace the last trace_s of the window


class Pacer:
    """The guard ``generate`` reads at every tick boundary (module doc)."""

    def __init__(self, arrivals: List[Arrival], requests: list,
                 window: Window, *, compiles: Callable[[], int],
                 tracer=None, clock: Callable[[], float] = time.perf_counter):
        self.arrivals, self.requests, self.window = arrivals, requests, window
        self.clock, self._compiles, self.tracer = clock, compiles, tracer
        self.origin: Optional[float] = None
        self.boundaries: List[float] = []     # wall time of tick k's start
        self.released_at: List[Optional[float]] = [None] * len(arrivals)
        self._next = 0
        self._live: List[int] = []
        self.compiles_at_open: Optional[int] = None
        self.compiles_at_close: Optional[int] = None
        self.trace_ticks: Optional[List[int]] = None   # [first, end)
        self.trace_host: Optional[List[float]] = None  # [start, stop] abs
        self.closed_tick: Optional[int] = None

    # ---- clock helpers ----------------------------------------------------
    def due_abs(self, i: int) -> float:
        return self.origin + self.arrivals[i].due_s

    def in_window(self, i: int) -> bool:
        return self.window.open_s <= self.arrivals[i].due_s \
            < self.window.close_s

    def tick_of(self, t: float) -> int:
        """The tick during which host time ``t`` fell."""
        return bisect.bisect_right(self.boundaries, t) - 1

    # ---- the hook ---------------------------------------------------------
    def _release_due(self, now: float) -> None:
        while self._next < len(self.arrivals) and \
                self.due_abs(self._next) <= now:
            self.requests[self._next].arrival_tick = 0
            self.released_at[self._next] = now
            self._live.append(self._next)
            self._next += 1

    def _window_done(self, now: float) -> bool:
        """Past the close, every request due in the window has its first
        token or has ended (or the drain limit is reached)."""
        if now >= self.origin + self.window.close_s \
                + self.window.drain_limit_s:
            return True
        return all(self.requests[i].out_tokens or self.requests[i].done
                   for i in range(len(self.arrivals)) if self.in_window(i))

    @property
    def preempted(self) -> bool:
        from jax.profiler import TraceAnnotation
        with TraceAnnotation("bench.hook"):
            return self._boundary()

    def _boundary(self) -> bool:
        now = self.clock()
        if self.origin is None:
            self.origin = now
        w = self.window
        self._release_due(now)
        if self.compiles_at_open is None and now >= self.origin + w.open_s:
            self.compiles_at_open = self._compiles()
        if self.tracer is not None and w.trace_s > 0:
            self._trace_edges(now)
        if now >= self.origin + w.close_s:
            if self.compiles_at_close is None:
                self.compiles_at_close = self._compiles()
                self.closed_tick = len(self.boundaries)
            if self._window_done(now):
                return True
        self._live = [i for i in self._live if not self.requests[i].done]
        if not self._live:
            # nothing in flight: sleep until the next request is due
            if self._next >= len(self.arrivals):
                return now >= self.origin + w.close_s
            wait = self.due_abs(self._next) - self.clock()
            if wait > 0:
                from jax.profiler import TraceAnnotation
                with TraceAnnotation("bench.idle_wait"):
                    time.sleep(wait)
            self._release_due(self.clock())
        self.boundaries.append(self.clock())
        return False

    def _trace_edges(self, now: float) -> None:
        w = self.window
        start = self.origin + w.close_s - w.trace_s
        if self.trace_ticks is None and now >= start:
            self.tracer.start()
            self.trace_ticks = [len(self.boundaries), None]
            self.trace_host = [self.clock(), None]
        elif self.trace_ticks is not None and self.trace_ticks[1] is None \
                and now >= self.origin + w.close_s:
            self.trace_host[1] = self.clock()
            self.trace_ticks[1] = len(self.boundaries)
            self.tracer.stop()

    # ---- what the run saw -----------------------------------------------
    def lateness_s(self) -> List[float]:
        """How late each released request was released after it was due."""
        return [self.released_at[i] - self.due_abs(i)
                for i in range(len(self.arrivals))
                if self.released_at[i] is not None]


def make_requests(arrivals: List[Arrival], request_cls,
                  clock: Callable[[], float] = time.perf_counter):
    """One engine ``Request`` per arrival, not yet due."""
    return [request_cls(rid=a.rid, prompt=a.prompt, max_new=a.max_new,
                        out_tokens=StampedTokens(clock), arrival_tick=FAR)
            for a in arrivals]
