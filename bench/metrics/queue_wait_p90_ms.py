"""Scheduler: 90th percentile of the wait from due to admission.

A request's admission time is the wall time of the tick boundary at which
the engine admitted it (``Request.admitted_tick``, mapped by the driver's
hook). Over the requests due in the window before the traced part began
(the profiler's stop stalls the loop at the window's close); one never
admitted counts as ``inf``.
"""
import math

from bench.lib.stats import percentile


def read(run):
    p = run.pacer
    end = p.window.close_s - p.window.trace_s
    waits = []
    for i, a in enumerate(run.arrivals):
        if not p.window.open_s <= a.due_s < end:
            continue
        k = run.requests[i].admitted_tick
        waits.append(math.inf if k is None or k >= len(p.boundaries)
                     else max(0.0, p.boundaries[k] - p.due_abs(i)))
    v = percentile(waits, 90)
    return None if v is None else v * 1e3
