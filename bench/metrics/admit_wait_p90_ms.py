"""Scheduler: 90th percentile of the engine's own queue wait.

``Request.admitted_s - Request.arrival_s``: both are stamped by the engine
on its own clock, from the tick at which the request became visible to it
to the tick at which admission claimed it. The driver's release lateness
(it releases requests only at tick boundaries) is left out, which is what
``queue_wait_p90_ms``, due to admission, adds on top. Over the requests
that metric reads: due in the window before the traced part began. One
never admitted counts as ``inf``. An engine that stamps no admission time
gives no reading.
"""
import math

from bench.lib.stats import percentile


def read(run):
    if not run.requests or not hasattr(run.requests[0], "admitted_s"):
        return None
    p = run.pacer
    end = p.window.close_s - p.window.trace_s
    waits = []
    for i, a in enumerate(run.arrivals):
        if not p.window.open_s <= a.due_s < end:
            continue
        r = run.requests[i]
        waits.append(math.inf if r.admitted_s is None or r.arrival_s is None
                     else r.admitted_s - r.arrival_s)
    v = percentile(waits, 90)
    return None if v is None else v * 1e3
