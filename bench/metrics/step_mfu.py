"""Model step: useful model operations over the traced window's peak.

Useful operations count real tokens only (``bench/lib/work.tick_totals``):
the projections, causal attention over each row's real context, and the
lm_head where logits are consumed. Over the traced window's wall time and
the chip's bf16 peak. Padding, scheduling gaps and host time all lower it.
"""


def read(run):
    w, t = run.traced, run.trace
    if not w or t is None or not w["model_flops"]:
        return None
    window_s = (t.window[1] - t.window[0]) / 1e9
    return 100.0 * w["model_flops"] / (window_s * run.peaks["flops_bf16"])
