"""Kernel microbenchmarks: wall-clock of the jit'd MX ops on this host +
bytes accounting (the HBM-traffic contract the TPU kernels are built to).

CPU wall-clock is not TPU performance; it validates that the fused paths do
less work than the unfused ones and provides the us_per_call CSV row format.

Extras:
  --smoke     fast CI gate: asserts the qmatmul dispatch layer really routes
              to the Pallas kernels (trace-time counters) and matches the
              dense reference — a silent regression to the densify fallback
              fails the build.
  --autotune  sweep tile candidates for the serving GEMM shapes and register
              the winners in the dispatch tile cache (per (shape, fmt)).
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np       # noqa: E402

from repro.core import get_format                             # noqa: E402
from repro.core.mx import (dequantize, quantize,              # noqa: E402
                           quantize_dequantize)
from repro.core.slice_scale import slice_and_scale            # noqa: E402
from repro.kernels import dispatch, ops                       # noqa: E402
from repro.kernels import paged_attention as pattn            # noqa: E402
from repro.serve.packed_params import pack_leaf_int4          # noqa: E402


def timeit(fn, *args, n=20):
    fn(*args)  # compile
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def _leaf_for(w, fmt):
    t = quantize(w, fmt, axis=0)
    if fmt.kind == "int" and fmt.bits == 4:
        return pack_leaf_int4(t)
    return t


# =============================================================================
# qmatmul tile autotuning — winners cached per (shape, fmt) in the dispatch
# tile table so subsequent traces pick them up automatically.
# =============================================================================
def _tile_candidates(m, k, n, fmt, kind):
    bs = fmt.block_size
    n_eff = n // 2 if kind == "int4" else n
    cands = []
    for tm in (8, 32, 128):
        for tn in (64, 128, 256):
            for tk in (bs, 4 * bs, 8 * bs):
                if tm <= max(m, 8) * 4 and tn <= max(n_eff, 64) * 2 \
                        and tk <= max(k, bs) * 2:
                    cands.append((tm, tn, tk))
    base = dispatch.select_tiles(m, k, n, fmt, kind)
    return [base] + [c for c in cands if c != base]


def autotune_qmatmul(m, k, n, fmt_name, *, n_iter=5, verbose=False):
    """Sweep tile candidates for one (M, K, N, fmt) qmatmul; register the
    winner via ``dispatch.register_tiles``. Returns (tiles, us_per_call)."""
    fmt = get_format(fmt_name, 32)
    int4 = fmt.kind == "int" and fmt.bits == 4
    kind = "int4" if int4 else "mx"
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    leaf = _leaf_for(w, fmt)

    best, best_us = None, float("inf")
    for tiles in _tile_candidates(m, k, n, fmt, kind):
        fn = jax.jit(lambda xx, tiles=tiles: dispatch.qmatmul(
            xx, leaf, mode="pallas", tiles=tiles))
        try:
            us = timeit(fn, x, n=n_iter)
        except Exception:          # tile combo the kernel rejects: skip
            continue
        if verbose:
            print(f"#   {fmt_name} ({m},{k},{n}) tiles={tiles}: {us:.1f}us")
        if us < best_us:
            best, best_us = tiles, us
    if best is None:
        raise RuntimeError(
            f"autotune: every tile candidate failed for "
            f"{fmt_name} ({m},{k},{n}) — run one candidate outside the "
            "sweep to see the kernel error")
    dispatch.register_tiles(m, k, n, fmt_name, best, kind,
                            block_size=fmt.block_size)
    return best, best_us


def run_autotune(verbose=True):
    shapes = [(8, 1024, 4096), (8, 4096, 1024), (64, 1024, 1024)]
    rows = []
    for fmt_name in ("mxint8", "mxint4"):
        for (m, k, n) in shapes:
            tiles, us = autotune_qmatmul(m, k, n, fmt_name, verbose=verbose)
            rows.append((f"autotune_{fmt_name}_{m}x{k}x{n}", us,
                         f"tm{tiles[0]}_tn{tiles[1]}_tk{tiles[2]}"))
    return rows


# =============================================================================
# --smoke: the dispatch layer must actually hit the Pallas kernels
# =============================================================================
def smoke():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 128)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(128, 160)).astype(np.float32))
    for fmt_name, counter in (("mxint8", "pallas"), ("mxfp8", "pallas"),
                              ("mxint4", "pallas_int4")):
        fmt = get_format(fmt_name, 32)
        leaf = _leaf_for(w, fmt)
        t = quantize(w, fmt, axis=0)
        want = np.asarray(x @ dequantize(t, jnp.float32))
        dispatch.reset_stats()
        got = np.asarray(dispatch.qmatmul(x, leaf, mode="pallas"))
        st = dispatch.stats()
        assert st[counter] >= 1 and st["densify"] == 0, (
            f"{fmt_name}: dispatch regressed to the fallback: {st}")
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
        ref = np.asarray(dispatch.qmatmul(x, leaf, mode="densify"))
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-4)
        print(f"smoke {fmt_name}: pallas path live, parity ok ({st})")

    # Paged decode attention: the gather-free kernel must be the path that
    # actually traces under mode="pallas", and must match the gather +
    # masked-softmax fallback on the same pool/block-table.
    b, mp, ps, hkv, g, d = 2, 4, 8, 2, 2, 16
    n_pages = b * mp + 1
    q = jnp.asarray(rng.normal(size=(b, 1, hkv * g, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pages, ps, hkv, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, ps, hkv, d)), jnp.float32)
    bt = np.zeros((b, mp), np.int32)
    perm = rng.permutation(np.arange(1, n_pages))
    lens = [9, 24]
    for i, n in enumerate(lens):
        k = -(-n // ps)
        bt[i, :k] = perm[i * mp:i * mp + k]
    bt = jnp.asarray(bt)
    cl = jnp.asarray(lens, jnp.int32)
    pattn.reset_stats()
    got = np.asarray(pattn.paged_decode_attention(q, kp, vp, bt, cl,
                                                  mode="pallas"))
    st = pattn.stats()
    assert st["pallas"] >= 1 and st["fallback"] == 0, (
        f"paged attention regressed to the gather fallback: {st}")
    ref = np.asarray(pattn.paged_decode_attention(q, kp, vp, bt, cl,
                                                  mode="fallback"))
    assert pattn.stats()["fallback"] >= 1
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    print(f"smoke paged_attention: pallas path live, parity ok ({st})")

    # Multi-query paged attention (the mixed prefill+decode tick): the MQ
    # kernel must be the traced path under mode="pallas" and must match the
    # gather + masked-softmax fallback on ragged query spans — one chunk row
    # straddling a page boundary, one decode row.
    c = 8
    qm = jnp.asarray(rng.normal(size=(b, c, hkv * g, d)), jnp.float32)
    qo = jnp.asarray([7, 23], jnp.int32)     # row 0: chunk at cursor 7
    ql = jnp.asarray([8, 1], jnp.int32)      # row 1: plain decode
    pattn.reset_stats()
    got = np.asarray(pattn.paged_mixed_attention(qm, kp, vp, bt, qo, ql,
                                                 mode="pallas"))
    st = pattn.stats()
    assert st["pallas_mq"] >= 1 and st["fallback_mq"] == 0, (
        f"mixed paged attention regressed to the gather fallback: {st}")
    ref = np.asarray(pattn.paged_mixed_attention(qm, kp, vp, bt, qo, ql,
                                                 mode="fallback"))
    assert pattn.stats()["fallback_mq"] >= 1
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    print(f"smoke paged_attention_mq: pallas path live, parity ok ({st})")
    print("smoke: OK")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast dispatch-layer gate (CI)")
    ap.add_argument("--autotune", action="store_true",
                    help="sweep qmatmul tiles for the serving shapes")
    args = ap.parse_args()

    if args.smoke:
        smoke()
        return

    rng = np.random.default_rng(0)
    shape = (1024, 4096)
    w = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(64, 1024)).astype(np.float32))
    fmt8 = get_format("mxint8", 32)
    fmt4 = get_format("mxint4", 32)

    rows = []

    f_quant = jax.jit(lambda v: quantize(v, fmt8, axis=0).codes)
    rows.append(("core_quantize_mxint8", timeit(f_quant, w),
                 f"{np.prod(shape)} elems"))

    f_fq = jax.jit(lambda v: quantize_dequantize(v, fmt8, axis=0))
    rows.append(("core_fake_quant_mxint8", timeit(f_fq, w), "fused"))

    t8 = quantize(w, fmt8, axis=0)
    f_ss = jax.jit(lambda t: slice_and_scale(t, fmt4).codes)
    rows.append(("core_ss_8to4", timeit(f_ss, t8), "packed-domain"))

    f_deq_mm = jax.jit(lambda xx, t: xx @ dequantize(t, jnp.float32))
    rows.append(("xla_dequant_matmul_int8", timeit(f_deq_mm, x, t8),
                 "XLA fused"))

    # dispatch layer: fused Pallas vs densify fallback on the same leaf
    f_disp_p = jax.jit(lambda xx: dispatch.qmatmul(x=xx, leaf=t8,
                                                   mode="pallas"))
    rows.append(("dispatch_qmatmul_pallas", timeit(f_disp_p, x, n=3),
                 "interpret on cpu"))
    f_disp_d = jax.jit(lambda xx: dispatch.qmatmul(x=xx, leaf=t8,
                                                   mode="densify"))
    rows.append(("dispatch_qmatmul_densify", timeit(f_disp_d, x),
                 "XLA fallback"))

    # Pallas kernels (interpret mode on CPU — correctness-path timing only)
    codes, scales = ops.to_weight_layout(t8)
    rows.append(("pallas_mx_matmul_interp",
                 timeit(lambda: ops.mx_matmul(x, codes, scales, fmt8,
                                              interpret=True), n=3),
                 "interpret=True"))
    rows.append(("pallas_fake_quant_interp",
                 timeit(lambda: ops.fake_quant(w, fmt8, axis=0,
                                               interpret=True), n=3),
                 "interpret=True"))

    if args.autotune:
        rows.extend(run_autotune())

    # bytes accounting: serving weight-read sizes per format
    n_el = int(np.prod(shape))
    for bits, name in ((16, "bf16"), (8, "mxint8"), (4, "mxint4_packed")):
        b = n_el * bits // 8 + (n_el // 32 if bits < 16 else 0)
        rows.append((f"weight_bytes_{name}", 0.0, f"{b}"))

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
