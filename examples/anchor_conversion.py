"""Anchor-format conversion walkthrough (§3.3/§3.4 numerics, visible).

Shows the Slice-and-Scale mechanics on real tensors: scales match direct
quantization EXACTLY, element codes differ by at most 1 ulp, and the packed
checkpoint sizes step down 8 -> 4 -> 2 bits.
"""
import sys

import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "src")

from repro.core import (dequantize, get_format, quantize,  # noqa: E402
                        slice_and_scale)
from repro.core.packed import pack_np  # noqa: E402


def main():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(256, 1024)).astype(np.float32) * 0.02)

    print("=== SSMXINT: 8-bit anchor -> {6,4,3,2} bits ===")
    hi = quantize(w, get_format("mxint8", 32), axis=-1)
    print(f"anchor mxint8: codes int8 {hi.codes.shape}, "
          f"scales int8 {hi.scale_exp.shape}")
    for b in (6, 4, 3, 2):
        lo_fmt = get_format(f"mxint{b}", 32)
        ss = slice_and_scale(hi, lo_fmt)
        direct = quantize(w, lo_fmt, axis=-1)
        scale_eq = bool(jnp.all(ss.scale_exp == direct.scale_exp))
        code_diff = int(jnp.max(jnp.abs(ss.codes.astype(jnp.int32)
                                        - direct.codes.astype(jnp.int32))))
        mse_ss = float(jnp.mean((w - dequantize(ss)) ** 2))
        mse_dr = float(jnp.mean((w - dequantize(direct)) ** 2))
        packed, _ = pack_np(np.asarray(ss.codes), b)
        print(f"mxint{b}: scales==direct: {scale_eq}  "
              f"max|code diff|: {code_diff}  "
              f"mse ss/direct: {mse_ss / mse_dr:.3f}  "
              f"packed: {packed.nbytes / 1024:.0f} kB")

    print("\n=== SSMXFP: e4m3 anchor -> e3m3, e3m2, e2m2, e2m1 ===")
    hif = quantize(w, get_format("mxfp8", 32), axis=-1)
    for b in (7, 6, 5, 4):
        lo_fmt = get_format(f"mxfp{b}", 32)
        ss = slice_and_scale(hif, lo_fmt)
        direct = quantize(w, lo_fmt, axis=-1)
        scale_eq = bool(jnp.all(ss.scale_exp == direct.scale_exp))
        mse_ss = float(jnp.mean((w - dequantize(ss)) ** 2))
        mse_dr = float(jnp.mean((w - dequantize(direct)) ** 2))
        print(f"mxfp{b} (e{lo_fmt.ebits}m{lo_fmt.mbits}): "
              f"scales==direct: {scale_eq}  "
              f"mse ss/direct: {mse_ss / mse_dr:.3f}")

    print("\nSS never touches FP32 master weights: MXINT is an integer "
          "shift-round on packed codes; MXFP re-rounds element values. "
          "Scales are exactly the direct-quantization scales (Eq. 4/6).")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
