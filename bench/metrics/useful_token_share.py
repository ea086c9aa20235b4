"""Scheduler: real query tokens over the positions the executables computed.

Over the traced ticks: live decode rows plus each chunk's real tokens, over
``rows x padded width`` summed over the ticks' executables. A mixed tick
computes every slot at the chunk's padded width, so this share is what the
padding wastes. Source: the engine's ``tick_trace`` and the driver's
request records.
"""


def read(run):
    w = run.traced
    if not w or not w["positions"]:
        return None
    return 100.0 * w["real_tokens"] / w["positions"]
