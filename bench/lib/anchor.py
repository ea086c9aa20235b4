"""The program's MXINT8 anchor, made on the device from the seed.

One jitted call draws every layer's float32 leaves (``bench/lib/weights``)
inside a ``lax.map`` over the layers, quantizes each projection straight to
the anchor format with the program's own ``quantize`` and keeps the other
leaves in bfloat16, the type they are served in. The float32 tree is never
held whole, and the call is one program, which the persistent compilation
cache keeps.

On a ``(1, tp)`` mesh the call's outputs are sharded as the engine lays
out the tree it serves (``packed_param_shardings``: codes and scales split
along the axis tensor parallelism splits, the embedding and lm_head along
the vocabulary), and each layer is constrained to that layout inside the
map, so no chip ever holds a whole stacked leaf and the engine's
``device_put`` finds every leaf already in place. The draw is threefry,
which partitions: the values are the unsharded draw's, bit for bit.
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


def _assemble(stacked: dict, glob: dict, fmt):
    """The ``AnchorModel`` of ``build``'s outputs."""
    from repro.core.anchor import AnchorModel
    from repro.core.mx import MXTensor
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


def _engine_layout(api, template, mesh, stacked, glob, fmt):
    """Shardings of ``build``'s outputs (same structure): those the engine
    of ``api`` and ``template`` gives each leaf of the tree it serves at the
    anchor's rung."""
    from repro.core.mx import MXTensor
    from repro.serve.packed_params import make_packed_params, \
        packed_param_shardings

    served = jax.eval_shape(
        lambda a: make_packed_params(a, template, target_fmt=fmt.name),
        _assemble(stacked, glob, fmt))
    is_mx = lambda x: isinstance(x, MXTensor)
    by_path = {jax.tree_util.keystr(p): s for p, s in
               jax.tree_util.tree_flatten_with_path(
                   packed_param_shardings(served, api.param_axes(), mesh),
                   is_leaf=is_mx)[0]}

    def one(name, v):
        s = by_path[_path(name)]
        return (s.codes, s.scale_exp) if isinstance(v, tuple) else s

    return ({n: one(n, v) for n, v in stacked.items()},
            {n: one(n, v) for n, v in glob.items()})


def _per_layer(s):
    """A stacked leaf's sharding without its leading layer axis."""
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(s.mesh, PartitionSpec(*tuple(s.spec)[1:]))


def _builder(m: dict, fmt, layers=None):
    """The jitted call's function; ``layers`` constrains each layer's
    leaves to shardings (``_per_layer``) inside the map."""
    from repro.core.mx import quantize
    kinds = {n: k for n, (_, k) in layer_shapes(m).items()}

    def build(key):
        def one(layer):
            out = {}
            for name, w in layer_weights(key, m, layer).items():
                if kinds[name] in ("proj", "out"):
                    t = quantize(w, fmt, axis=0)
                    out[name] = (t.codes, t.scale_exp)
                else:
                    out[name] = w.astype(jnp.bfloat16)
            if layers is not None:
                out = jax.lax.with_sharding_constraint(out, layers)
            return out

        stacked = jax.lax.map(one, jnp.arange(m["n_layers"]))
        glob = {k: v.astype(jnp.bfloat16)
                for k, v in global_weights(key, m).items()}
        return stacked, glob
    return build


def make_anchor_on_device(m: dict, seed: int, fmt_name: str = "mxint8",
                          block_size: int = 32, mesh=None, api=None,
                          template=None):
    """The program's ``AnchorModel`` of the seed's weights, sharded on
    ``mesh`` (a ``(1, tp)`` ``("data", "model")`` mesh) as the engine built
    from ``api`` and ``template`` serves it, or on the default device
    without a mesh."""
    from repro.core import get_format

    fmt = get_format(fmt_name, block_size)
    key = base_key(seed)
    build = _builder(m, fmt)
    if mesh is None:
        stacked, glob = jax.jit(build)(key)
    else:
        out = _engine_layout(api, template, mesh,
                             *jax.eval_shape(build, key), fmt)
        build = _builder(m, fmt, jax.tree_util.tree_map(_per_layer, out[0]))
        stacked, glob = jax.jit(build, out_shardings=out)(key)
    return _assemble(stacked, glob, fmt)
