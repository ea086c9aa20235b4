"""Scheduler: median host time between one tick's last fetch and the next
tick's first step dispatch.

From the engine's ``tick_trace`` stamps: ``dispatched_s[k + 1] -
fetched_s[k]`` over the traced ticks ``k`` where ``k`` fetched and ``k +
1`` dispatched a step. In that stretch the host has read the step's
results and not yet sent the next step, so the device's queue is empty
(sweeps, admission, staging, retiring, the preemption guard). Median by
nearest rank. An engine without the stamps gives no reading.
"""
from bench.lib.stats import percentile


def read(run):
    tr = run.pacer.trace_ticks
    if tr is None or tr[1] is None:
        return None
    ticks = run.tick_trace[tr[0]:tr[1]]
    gaps = [b["dispatched_s"] - a["fetched_s"]
            for a, b in zip(ticks, ticks[1:])
            if a.get("fetched_s") is not None
            and b.get("dispatched_s") is not None]
    v = percentile(gaps, 50)
    return None if v is None else v * 1e3
