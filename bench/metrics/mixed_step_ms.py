"""Model step: median latency of the mixed step, as the host sees it.

``step_s`` of the engine's ``tick_trace``: from the mixed step's dispatch
to the end of the first fetch after it (the logit guard's), over the
traced ticks of kind ``mixed``, median by nearest rank. It holds the
device's time for the step and the host's wait for it. No reading where no
mixed tick was traced, or from an engine without the stamps.
"""
from bench.lib.stats import percentile


def read(run):
    tr = run.pacer.trace_ticks
    if tr is None or tr[1] is None:
        return None
    steps = [e["step_s"] for e in run.tick_trace[tr[0]:tr[1]]
             if e.get("kind") == "mixed" and e.get("step_s") is not None]
    v = percentile(steps, 50)
    return None if v is None else v * 1e3
