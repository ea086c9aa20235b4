"""MX gradient compression with error feedback for cross-pod data parallelism.

The paper's machinery applied to the distributed layer: cross-pod gradient
reduction is the dominant collective at multi-pod scale (slow inter-pod
links). We quantize pod-local gradients to MXINT8 blocks (+E8M0 scales),
all-gather the *packed* representation across the pod axis (4x fewer bytes
than an f32 psum ring), dequantize and sum locally, and keep the quantization
residual as error feedback so the compression bias vanishes over steps
(EF-SGD style).

Composition rule: with compression ON, params/optimizer shard FSDP over
`data` only and replicate across `pod` — pod-local grads exist, the
compressed all-gather is the only cross-pod traffic. (Without compression,
fsdp spans (pod, data) and GSPMD emits f32 reduce-scatters across pods.)
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.formats import MXFormat, get_format
from repro.core.mx import MXTensor, dequantize, quantize

PAD = 128   # flatten-pad multiple (>= block size, lane aligned)


def _flatten_pad(g: jax.Array, bs: int) -> Tuple[jax.Array, int]:
    flat = g.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    pad = (-n) % max(bs, PAD)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(1, -1), n


def ef_compress_leaf(g: jax.Array, err: jax.Array, fmt: MXFormat):
    """(grad, error_state) -> (MXTensor, new_error_state).

    err has g's shape; the quantization residual accumulates there.
    """
    corrected = g.astype(jnp.float32) + err.astype(jnp.float32)
    flat, n = _flatten_pad(corrected, fmt.block_size)
    t = quantize(flat, fmt, axis=-1)
    deq = dequantize(t).reshape(-1)[:n].reshape(g.shape)
    new_err = corrected - deq
    return t, new_err.astype(err.dtype)


def ef_decompress_sum(gathered_codes, gathered_scales, fmt: MXFormat,
                      shape, n: int):
    """Sum dequantized per-pod contributions: codes (npod, 1, L)."""
    t = MXTensor(codes=gathered_codes, scale_exp=gathered_scales,
                 fmt=fmt, block_axis=gathered_codes.ndim - 1)
    deq = dequantize(t)                     # (npod, 1, L)
    s = jnp.sum(deq, axis=0).reshape(-1)[:n].reshape(shape)
    return s


def init_error_state(grads_or_params):
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), grads_or_params)


def compressed_pod_allreduce(grads, err_state, fmt_name: str = "mxint8",
                             axis_name: str = "pod", mean: bool = True):
    """Inside shard_map(manual over `axis_name`): EF-compress + all-gather +
    local dequant-sum. Returns (reduced_grads, new_err_state)."""
    fmt = get_format(fmt_name)
    npod = jax.lax.psum(1, axis_name)

    def one(g, err):
        t, new_err = ef_compress_leaf(g, err, fmt)
        codes = jax.lax.all_gather(t.codes, axis_name)        # (npod, 1, L)
        scales = jax.lax.all_gather(t.scale_exp, axis_name)
        flatn = g.size
        s = ef_decompress_sum(codes, scales, fmt, g.shape, flatn)
        if mean:
            s = s / npod
        return s.astype(g.dtype), new_err

    flat_g, tdef = jax.tree_util.tree_flatten(grads)
    flat_e = jax.tree_util.tree_leaves(err_state)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    red = jax.tree_util.tree_unflatten(tdef, [o[0] for o in out])
    new_err = jax.tree_util.tree_unflatten(tdef, [o[1] for o in out])
    return red, new_err


def compressed_bytes(params, fmt_name: str = "mxint8") -> int:
    """Cross-pod bytes per step with compression (vs 4 bytes/param f32)."""
    fmt = get_format(fmt_name)
    total = 0
    for p in jax.tree_util.tree_leaves(params):
        n = p.size
        npad = n + ((-n) % max(fmt.block_size, PAD))
        total += npad * fmt.bits // 8 + npad // fmt.block_size
    return total
