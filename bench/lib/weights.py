"""Random weights from the seed, one layer at a time, in float32.

The benchmark serves random weights: they are enough for speed and for the
comparison with the plain reference. Both the program's anchor
(``bench/lib/anchor.py``) and the reference (``bench/lib/reference.py``)
draw them from here, so the two see the same model, and neither ever holds
the float32 tree whole: a layer's leaves come from one jitted call with the
layer index traced, and every leaf has its own key, folded from the seed,
its name and its layer.

Scales follow common initialisation: projections N(0, 0.02), output
projections N(0, 0.02 / sqrt(n_layers)), biases N(0, 0.02), norm gains
1 + N(0, 0.1) (random, so that a gain applied to the wrong axis shows).
Leaf names are the model's: ``attn.wq`` ... ``mlp.w_down``.
"""
from __future__ import annotations

import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

STD = 0.02
GAIN_STD = 0.1


def base_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (64 bits are kept)."""
    s = seed % 2 ** 64
    return jax.random.wrap_key_data(
        jnp.array([s >> 32, s & 0xFFFFFFFF], dtype=jnp.uint32),
        impl="threefry2x32")


def _key(key, name: str, layer=None):
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return k if layer is None else jax.random.fold_in(k, layer)


def layer_shapes(m: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, kind) of one layer; kind is proj | out | bias | gain."""
    d, h, kv, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["head_dim"], m["d_ff"])
    out = {"mixer_norm": ((d,), "gain"), "ffn_norm": ((d,), "gain"),
           "attn.wq": ((d, h * hd), "proj"), "attn.wk": ((d, kv * hd), "proj"),
           "attn.wv": ((d, kv * hd), "proj"), "attn.wo": ((h * hd, d), "out")}
    if m["qkv_bias"]:
        out.update({"attn.bq": ((h * hd,), "bias"),
                    "attn.bk": ((kv * hd,), "bias"),
                    "attn.bv": ((kv * hd,), "bias")})
    if m["qk_norm"]:
        out.update({"attn.q_norm": ((hd,), "gain"),
                    "attn.k_norm": ((hd,), "gain")})
    if m["act"] == "swiglu":
        out["mlp.w_gate"] = ((d, f), "proj")
    out["mlp.w_up"] = ((d, f), "proj")
    out["mlp.w_down"] = ((f, d), "out")
    if m["mlp_bias"]:
        out.update({"mlp.b_up": ((f,), "bias"), "mlp.b_down": ((d,), "bias")})
    return out


def scaled(z, kind: str, n_layers: int):
    """A standard normal draw ``z`` at the scale of a leaf of ``kind``."""
    if kind == "gain":
        return 1.0 + GAIN_STD * z
    if kind == "out":
        return (STD / n_layers ** 0.5) * z
    return STD * z


def _draw(key, shape, kind, n_layers):
    return scaled(jax.random.normal(key, shape, jnp.float32), kind, n_layers)


def layer_weights(key, m: dict, layer) -> Dict[str, jax.Array]:
    """One layer's float32 leaves (``layer`` may be traced)."""
    return {name: _draw(_key(key, name, layer), shape, kind, m["n_layers"])
            for name, (shape, kind) in layer_shapes(m).items()}


def global_shapes(m: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, kind) of the embedding (V, d), the final norm gain
    (d,) and, unless the embeddings are tied, the lm_head (d, V)."""
    d, v = m["d_model"], m["vocab"]
    out = {"embed": ((v, d), "proj"), "final_norm": ((d,), "gain")}
    if not m["tie_embeddings"]:
        out["lm_head"] = ((d, v), "proj")
    return out


def global_normal(key, m: dict, name: str) -> jax.Array:
    """The standard normal draw behind global leaf ``name``."""
    return jax.random.normal(_key(key, name), global_shapes(m)[name][0],
                             jnp.float32)


def global_weight(key, m: dict, name: str) -> jax.Array:
    """One float32 global leaf; each has its own key, so one can be drawn
    without the others."""
    return scaled(global_normal(key, m, name), global_shapes(m)[name][1],
                  m["n_layers"])


def global_weights(key, m: dict) -> Dict[str, jax.Array]:
    """Every float32 global leaf (``global_shapes``)."""
    return {name: global_weight(key, m, name) for name in global_shapes(m)}
