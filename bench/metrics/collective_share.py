"""Device: share of the traced window in which a chip runs a collective.

Per chip of the cell, the union of the intervals of its collective ops
(all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all,
and the start and done of asynchronous ones; ``trace.is_collective``)
inside the traced window, over the window; averaged over the cell's chips.
The base is the window, not the busy time: on a tensor-parallel mesh this
is the time the chips spend exchanging partial sums. No reading where no
collective ran, as on one chip.
"""
from bench.lib.trace import busy_ns, is_collective


def read(run):
    t = run.trace
    if t is None or not t.ops:
        return None
    span = t.window[1] - t.window[0]
    ns = [busy_ns([e for e in evs if is_collective(e.text)], t.window)
          for evs in t.ops.values()]
    if not any(ns):
        return None
    return 100.0 * sum(ns) / len(ns) / span
