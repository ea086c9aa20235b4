"""Device: share of the traced window in which no operation ran.

``1 - union(device op intervals) / window``, averaged over the chips.
"""
from bench.lib.trace import busy_ns


def read(run):
    t = run.trace
    if t is None:
        return None
    span = t.window[1] - t.window[0]
    busy = [busy_ns(evs, t.window) for evs in t.ops.values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / span)
