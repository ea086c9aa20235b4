"""Appendix C (Figs 19-20): tensor-level reconstruction MSE.

Exact reproduction of the paper's protocol: average layer-wise MSE on 100
random tensors of shape (1, 1024); direct MXINT/MXFP quantization vs
Slice-and-Scale conversion from the 8-bit anchor. Two sweeps: bit precision
at block size 64, and block size at 4-bit precision.
"""
import time

import jax.numpy as jnp
import numpy as np

from repro.core import (dequantize, get_format, quantize, slice_and_scale)


def mse_direct(v, fmt):
    return float(jnp.mean((v - dequantize(quantize(v, fmt))) ** 2))


def mse_ss(v, high, low):
    t = slice_and_scale(quantize(v, high), low)
    return float(jnp.mean((v - dequantize(t)) ** 2))


def run(n_tensors=100, dim=1024, seed=0):
    rng = np.random.default_rng(seed)
    v = jnp.asarray(rng.normal(size=(n_tensors, dim)).astype(np.float32))
    rows = []

    # Sweep 1: bit precision at block size 64 (Figs 19/20 left)
    for kind, bits_list, anchor_b in (("int", range(2, 9), 8),
                                      ("fp", range(4, 9), 8)):
        hi = get_format(f"mx{kind}{anchor_b}", 64)
        for b in bits_list:
            lo = get_format(f"mx{kind}{b}", 64)
            rows.append({
                "sweep": "bits@bs64", "kind": kind, "bits": b,
                "block_size": 64,
                "mse_direct": mse_direct(v, lo),
                "mse_ss": mse_ss(v, hi, lo) if b < anchor_b else
                mse_direct(v, lo),
            })

    # Sweep 2: block size at 4-bit (Figs 19/20 right)
    for kind in ("int", "fp"):
        for bs in (16, 32, 64, 128, 256):
            hi = get_format(f"mx{kind}8", bs)
            lo = get_format(f"mx{kind}4", bs)
            rows.append({
                "sweep": "bs@4bit", "kind": kind, "bits": 4,
                "block_size": bs,
                "mse_direct": mse_direct(v, lo),
                "mse_ss": mse_ss(v, hi, lo),
            })
    return rows


def main(csv=True):
    t0 = time.time()
    rows = run()
    us = (time.time() - t0) * 1e6 / len(rows)
    worst_ratio = max(r["mse_ss"] / max(r["mse_direct"], 1e-30)
                      for r in rows)
    if csv:
        print("# appc_ss_mse: direct vs slice-and-scale reconstruction MSE")
        print("sweep,kind,bits,block_size,mse_direct,mse_ss,ratio")
        for r in rows:
            print(f'{r["sweep"]},{r["kind"]},{r["bits"]},{r["block_size"]},'
                  f'{r["mse_direct"]:.3e},{r["mse_ss"]:.3e},'
                  f'{r["mse_ss"] / max(r["mse_direct"], 1e-30):.3f}')
    print(f"appc_ss_mse,{us:.0f},worst_ss_over_direct={worst_ratio:.3f}")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
