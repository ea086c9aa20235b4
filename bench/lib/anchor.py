"""The program's MXINT8 anchor, made on the device from the seed.

One jitted call draws every layer's float32 leaves (``bench/lib/weights``)
inside a ``lax.map`` over the layers, quantizes each projection straight to
the anchor format with the program's own ``quantize`` and keeps the other
leaves in bfloat16, the type they are served in. The float32 tree is never
held whole, and the call is one program, which the persistent compilation
cache keeps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.lib.weights import base_key, global_weights, layer_shapes, \
    layer_weights


def _path(name: str) -> str:
    """Leaf name -> the program's parameter path (one layer per group)."""
    if name in ("embed", "lm_head", "final_norm"):
        return f"['{name}']"
    return "['blocks'][0]" + "".join(f"['{p}']" for p in name.split("."))


def make_anchor_on_device(m: dict, seed: int, fmt_name: str = "mxint8",
                          block_size: int = 32):
    """The program's ``AnchorModel`` of the seed's weights."""
    from repro.core import get_format
    from repro.core.anchor import AnchorModel
    from repro.core.mx import MXTensor, quantize

    fmt = get_format(fmt_name, block_size)
    kinds = {n: k for n, (_, k) in layer_shapes(m).items()}

    @jax.jit
    def build(key):
        def one(layer):
            out = {}
            for name, w in layer_weights(key, m, layer).items():
                if kinds[name] in ("proj", "out"):
                    t = quantize(w, fmt, axis=0)
                    out[name] = (t.codes, t.scale_exp)
                else:
                    out[name] = w.astype(jnp.bfloat16)
            return out

        stacked = jax.lax.map(one, jnp.arange(m["n_layers"]))
        glob = {k: v.astype(jnp.bfloat16)
                for k, v in global_weights(key, m).items()}
        return stacked, glob

    stacked, glob = build(base_key(seed))
    quantized, raw = {}, {}
    for name, v in stacked.items():
        if isinstance(v, tuple):
            quantized[_path(name)] = MXTensor(codes=v[0], scale_exp=v[1],
                                              fmt=fmt, block_axis=1)
        else:
            raw[_path(name)] = v
    for name, v in glob.items():
        raw[_path(name)] = v
    return AnchorModel(quantized=quantized, raw=raw, fmt_name=fmt.name)
