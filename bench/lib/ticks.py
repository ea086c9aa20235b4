"""Which rows each scheduler tick computed, rebuilt from what the run saw.

The engine's ``tick_trace`` says what kind of executable a tick ran and how
wide it was; the driver knows each request's prompt, the tick it was
admitted in (``Request.admitted_tick``) and the host time of each of its
tokens. Under chunked admission a request's chunks run in consecutive
ticks from its admission, one a tick, and its token ``j >= 1`` comes from a
decode row that fed position ``len(prompt) + j - 1``. ``rebuild`` checks
the rows against ``tick_trace`` and counts the ticks where they disagree.
"""
from __future__ import annotations

import math
from collections import defaultdict
from typing import List, Tuple

from bench.lib.work import TickWork


def bucket(take: int, chunk: int) -> int:
    """The engine's padded width of a final chunk of ``take`` tokens."""
    b = 8
    while b < take:
        b *= 2
    return min(b, chunk)


def rebuild(tick_trace: List[dict], requests: list, tick_of, slots: int,
            chunk: int, k0: int, k1: int) -> Tuple[List[TickWork], int]:
    """(work of ticks ``[k0, k1)``, number of ticks that disagree)."""
    fills, decodes = defaultdict(list), defaultdict(list)
    for r in requests:
        if r.admitted_tick is None:
            continue
        plen = len(r.prompt)
        n = math.ceil(plen / chunk)
        for j in range(n):
            t = r.admitted_tick + j
            if k0 <= t < k1:
                fills[t].append((j * chunk, min(chunk, plen - j * chunk),
                                 j == n - 1))
        for j, st in enumerate(getattr(r.out_tokens, "stamps", [])[1:],
                               start=1):
            t = tick_of(st)
            if k0 <= t < k1:
                decodes[t].append((plen + j - 1, 1, True))
    out, bad = [], 0
    for k in range(k0, min(k1, len(tick_trace))):
        e = tick_trace[k]
        if e["decode"] and e["prefill_chunks"]:
            kind, m = "mixed", slots * e["prefill_tokens"]
        elif e["decode"]:
            kind, m = "decode", slots
        elif e["prefill_chunks"]:
            kind, m = "chunk", e["prefill_tokens"]
        else:
            kind, m = "idle", 0
        f, d = fills[k], decodes[k]
        want = [bucket(take, chunk) if final else chunk
                for _, take, final in f]
        if want != ([e["prefill_tokens"]] if e["prefill_chunks"] else []) \
                or len(d) != e["decode_rows"]:
            bad += 1
        out.append(TickWork(kind=kind, m=m, rows=f + d))
    return out, bad
