"""Kernels: dequant-GEMM roofline share.

The least time of every dequant-GEMM call in the traced ticks (the larger
of ``2MKN`` over the bf16 peak and codes + scales + activations over HBM
bandwidth, ``bench/lib/work.gemm_work``) over the device time of the GEMM
kernels' events in the trace.

The trace names a kernel's op by its HLO instruction, not by the kernel
function (``_mm_kernel``, ``_mm4_kernel``), so a GEMM is found by its
signature: a Mosaic call ``f32[M,N] <- (float[M,K], s8|u8[K,N or N/2],
s8|u8[K/bs,N])``.
"""
from bench.lib.trace import custom_call_types, kernel_ns

CODES = ("s8", "u8")


def is_gemm(text: str) -> bool:
    sig = custom_call_types(text)
    if sig is None:
        return False
    out, ins = sig
    if len(out) != 1 or out[0][0] != "f32" or len(out[0][1]) != 2 \
            or len(ins) != 3:
        return False
    m, n = out[0][1]
    (xt, x), (ct, c), (st, sc) = ins
    return (xt[0] not in "su" and ct in CODES and st in CODES
            and len(x) == len(c) == len(sc) == 2
            and x[0] == m and c[0] == x[1] and sc[1] == n
            and c[1] in (n, n // 2))

def read(run):
    w, t = run.traced, run.trace
    if not w or t is None or not w["gemm_least_s"]:
        return None
    ns = sum(kernel_ns(evs, is_gemm, t.window) for evs in t.ops.values())
    if not ns:
        return None
    return 100.0 * w["gemm_least_s"] / (ns / 1e9)
