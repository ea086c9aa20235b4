"""Collectives: the tensor-parallel exchange's roofline share.

The least time of the traced ticks' exchanges over the time the chips
spend in collectives: per chip, the union of its collective ops
(``trace.is_collective``) inside the traced window, averaged over the
cell's chips.

The yardstick is the algorithm's bytes, from the configuration's shapes
and the ticks ``bench/lib/ticks.rebuild`` gives (the M ``gemm_roofline``
counts). A tick's step all-reduces ``M x d_model`` bf16 activations twice
a layer (after wo and after w_down) and once for the embedding lookup,
and all-gathers the float32 logits of the rows it samples from (every
slot of a decode or mixed tick, the one row of a chunk that runs alone).
On ``n`` chips an all-reduce moves ``2(n-1)/n`` of its bytes out of each
chip and an all-gather ``(n-1)/n``, at the chip's inter-chip bandwidth:
1,600 Gbit/s a TPU v5e (Google Cloud documentation, "TPU v5e"), taken as
what a chip can send over all its links at once, which can only shorten
the least time. No reading on one chip, or where no collective ran.
"""
from bench.lib.ticks import rebuild
from bench.lib.trace import busy_ns, is_collective
from bench.lib.work import ACT_BYTES

ICI_BYTES_PER_S = {"TPU v5 lite": 1600e9 / 8}
LOGIT_BYTES = 4


def payload(s, kind: str, m: int, slots: int):
    """(all-reduced, all-gathered) bytes of one tick's step, whole: what
    each all-reduce sums and what the logits' all-gather leaves on every
    chip."""
    if kind == "idle":
        return 0, 0
    rows = 1 if kind == "chunk" else slots
    return ((2 * s.n_layers + 1) * m * s.d_model * ACT_BYTES,
            rows * s.vocab * LOGIT_BYTES)


def read(run):
    t, tp = run.trace, run.shapes.tp
    tr = run.pacer.trace_ticks
    if tp <= 1 or t is None or not t.ops or tr is None or tr[1] is None:
        return None
    ticks, _ = rebuild(run.tick_trace, run.requests, run.pacer.tick_of,
                       run.slots, run.chunk, tr[0], tr[1])
    per_chip = 0.0
    for tick in ticks:
        reduced, gathered = payload(run.shapes, tick.kind, tick.m,
                                    run.slots)
        per_chip += (2 * (tp - 1) / tp * reduced
                     + (tp - 1) / tp * gathered)
    ns = [busy_ns([e for e in evs if is_collective(e.text)], t.window)
          for evs in t.ops.values()]
    if not any(ns) or not per_chip:
        return None
    import jax
    bw = ici_bytes_per_s(jax.devices()[0].device_kind)
    return 100.0 * per_chip / bw / (sum(ns) / len(ns) / 1e9)


def ici_bytes_per_s(kind: str) -> float:
    """A chip's inter-chip bandwidth; a kind not listed is an error."""
    if kind not in ICI_BYTES_PER_S:
        raise ValueError(f"no published inter-chip bandwidth for device "
                         f"kind {kind!r}; known: {sorted(ICI_BYTES_PER_S)}")
    return ICI_BYTES_PER_S[kind]
