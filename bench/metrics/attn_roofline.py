"""Kernels: paged-attention roofline share.

The least time of the decode and multi-query paged-attention kernel calls
in the traced ticks (each call's query tokens against the keys and the KV
pages its live rows must read, ``bench/lib/work.attn_work``) over the
device time of both kernels' events in the trace.

The trace names a kernel's op by its HLO instruction, not by the kernel
function (``_paged_attn_kernel``, ``_paged_attn_mq_kernel``), so a call is
found by its signature: a Mosaic call whose operands are the
scalar-prefetched int32 page table and lengths, then the queries
``[B, Hkv, G, D]`` and the key and value pools ``[P, page, Hkv*D]``, and
whose result has the queries' rank.
"""
from bench.lib.trace import custom_call_types, kernel_ns


def is_attn(text: str) -> bool:
    sig = custom_call_types(text)
    if sig is None:
        return False
    out, ins = sig
    if len(out) != 1 or len(ins) < 5:
        return False
    scalars, (qt, q), (kt, k), (vt, v) = ins[:-3], *ins[-3:]
    return (all(dt == "s32" for dt, _ in scalars) and len(q) == 4
            and len(out[0][1]) == 4 and out[0][1][:2] == q[:2]
            and len(k) == 3 and k == v and kt == vt
            and k[2] == q[1] * q[3])


def read(run):
    w, t = run.traced, run.trace
    if not w or t is None or not w["attn_least_s"]:
        return None
    ns = sum(kernel_ns(evs, is_attn, t.window) for evs in t.ops.values())
    if not ns:
        return None
    return 100.0 * w["attn_least_s"] / (ns / 1e9)
