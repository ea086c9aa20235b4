"""Packed-MX serving parameters: packed leaves all the way to the GEMM.

The elastic-inference performance claim: decode is HBM-bound on weight reads,
so serving from MX codes (int8, or nibble-packed int4) cuts the memory
roofline term by 2x/4x vs bf16 dense weights. These containers keep the
*packed* representation as the on-device params pytree, and two serving
contracts realize the claim:

  fused (default on TPU)  — ``make_packed_serve_step(api, fused=True)``
    passes the packed tree straight into the model; every projection routes
    its leaf through ``repro.kernels.dispatch.qmatmul``, the fused Pallas
    dequant-GEMM (interpret-mode off TPU), so the only weight HBM traffic is
    the packed codes + scales streamed tile-by-tile into VMEM.

  densify-inside-jit      — the XLA fallback: leaves are dequantized inside
    the jitted step and XLA fuses the dequant into the consuming matmuls.
    Numerically identical (same codes); the reference for parity tests.

MXINT4 leaves use the split-N nibble layout (``PackedInt4Leaf`` with
``layout="splitn"``): byte column j holds output column j in the low nibble
and column j + N/2 in the high nibble, which is exactly what
``mx_matmul_int4_pallas`` streams.

The layout conventions these containers rely on (scan-stale metadata,
moved-last scales, split-N vs split-K) are documented in
docs/serving_internals.md.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.anchor import AnchorModel
from repro.core.formats import get_format
from repro.core.mx import MXTensor, decode_elements, dequantize
from repro.core.packed import (pack_int4_jnp, pack_int4_splitn_jnp,
                               unpack_int4_jnp, unpack_int4_splitn_jnp)
from repro.core.qat import QATConfig


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("packed", "scale_exp"),
                   meta_fields=("shape", "block_axis", "fmt_name", "layout"))
@dataclasses.dataclass
class PackedInt4Leaf:
    packed: jax.Array            # uint8 nibble pairs, codes.size / 2
    scale_exp: jax.Array
    shape: tuple                 # original codes shape
    block_axis: int
    fmt_name: str
    # "splitn": codes shape with the last (output) axis halved; byte col j =
    #   output cols (j, j + N/2) — the fused int4 GEMM kernel's layout.
    # "splitk": legacy — block axis moved last, adjacent nibble pairs along
    #   it; densify-only (no fused kernel reads it).
    layout: str = "splitn"


def pack_leaf_int4(t: MXTensor, layout: str = "splitn") -> PackedInt4Leaf:
    assert t.fmt.kind == "int" and t.fmt.bits == 4
    # split-N needs the last axis to be the GEMM output dim (block axis is
    # the contraction) and even; otherwise fall back to the split-K layout.
    if layout == "splitn" and (
            t.block_axis % t.codes.ndim == t.codes.ndim - 1
            or t.codes.shape[-1] % 2 != 0):
        layout = "splitk"
    if layout == "splitn":
        packed = pack_int4_splitn_jnp(t.codes)
    else:
        packed = pack_int4_jnp(jnp.moveaxis(t.codes, t.block_axis, -1))
    return PackedInt4Leaf(packed=packed,
                          scale_exp=t.scale_exp,
                          shape=tuple(t.codes.shape),
                          block_axis=t.block_axis,
                          fmt_name=t.fmt.name,
                          layout=layout)


def leaf_block_size(p: PackedInt4Leaf) -> int:
    """The block size the leaf was actually packed at, from its shapes.

    K sits at ndim-2 for split-N (last dim is N/2) and, nibble-paired, at
    the last dim for split-K; scale_exp's last dim is K/bs either way. Never
    trust the format registry default here — anchors quantize at arbitrary
    block sizes.
    """
    k = p.packed.shape[-2] if p.layout == "splitn" \
        else p.packed.shape[-1] * 2
    return k // p.scale_exp.shape[-1]


def leaf_as_mx(p: PackedInt4Leaf, block_size: Optional[int] = None,
               block_axis: Optional[int] = None) -> MXTensor:
    """Unpack a PackedInt4Leaf back to an MXTensor view (int8 codes).

    ``block_axis`` overrides the stored metadata — leaves sliced out of a
    scan keep stale static axes; the serving convention is ndim-2.
    ``block_size=None`` derives it from the leaf's own shapes.
    """
    ax = p.block_axis if block_axis is None else block_axis
    bs = leaf_block_size(p) if block_size is None else block_size
    if p.layout == "splitn":
        codes = unpack_int4_splitn_jnp(p.packed)
    else:
        codes = jnp.moveaxis(unpack_int4_jnp(p.packed), -1, ax)
    return MXTensor(codes=codes, scale_exp=p.scale_exp,
                    fmt=get_format(p.fmt_name, bs), block_axis=ax)


def unpack_leaf_int4(p: PackedInt4Leaf, block_size: int,
                     dtype=jnp.bfloat16) -> jax.Array:
    return dequantize(leaf_as_mx(p, block_size), dtype=dtype)


def densify_leaf(leaf, block_size: Optional[int], dtype,
                 serving_axis: bool = False) -> jax.Array:
    """One packed container -> dense weight; non-containers pass through.

    ``serving_axis=True`` re-derives the contraction axis as ndim-2 (the
    serving convention — leaves sliced out of a scan keep stale static
    ``block_axis``/``shape`` metadata). ``block_size=None`` derives the int4
    block size from the leaf's own shapes. This is THE densify
    implementation; both the qmatmul fallback and ``QuantCtx.dense`` route
    here so the convention can't diverge between them.
    """
    if isinstance(leaf, MXTensor):
        ax = max(leaf.codes.ndim - 2, 0) if serving_axis else leaf.block_axis
        t = MXTensor(codes=leaf.codes, scale_exp=leaf.scale_exp,
                     fmt=leaf.fmt, block_axis=ax)
        return dequantize(t, dtype=dtype)
    if isinstance(leaf, PackedInt4Leaf):
        ax = max(leaf.packed.ndim - 2, 0) if serving_axis else None
        return dequantize(leaf_as_mx(leaf, block_size, block_axis=ax),
                          dtype=dtype)
    return leaf


def anchor_block_size(anchor: AnchorModel) -> int:
    """The block size the anchor was actually quantized at."""
    for t in anchor.quantized.values():
        return t.fmt.block_size
    return get_format(anchor.fmt_name).block_size


def make_packed_params(anchor: AnchorModel, template, *,
                       target_bits: int = 8, target_fmt: str | None = None,
                       dtype=jnp.bfloat16):
    """Params pytree whose quantized leaves are packed MX containers.

    ``target_fmt`` names any same-kind format at or below the anchor's
    precision: the anchor is Slice-and-Scaled to it (packed domain, no FP32
    round-trip) and the result kept as MXTensor leaves — except 4-bit MXINT,
    which is additionally nibble-packed (``PackedInt4Leaf``, 2 codes/byte).
    Legacy ``target_bits`` (8 = anchor as-is, 4 = mxint4) is honored when
    ``target_fmt`` is None.
    """
    from repro.core.anchor import convert
    bs = anchor_block_size(anchor)
    if target_fmt is None:
        target_fmt = anchor.fmt_name if target_bits == 8 else "mxint4"
    fmt_t = get_format(target_fmt, bs)
    model = anchor if fmt_t.name == anchor.fmt_name \
        else convert(anchor, fmt_t)
    pack4 = fmt_t.kind == "int" and fmt_t.bits == 4

    leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    out = []
    for pth, leaf in leaves:
        k = jax.tree_util.keystr(pth)
        if k in model.quantized:
            t = model.quantized[k]
            out.append(pack_leaf_int4(t) if pack4 else t)
        else:
            w = model.raw[k]
            out.append(w.astype(dtype)
                       if jnp.issubdtype(w.dtype, jnp.floating) else w)
    return jax.tree_util.tree_unflatten(treedef, out)


def densify_params(packed_params, block_size: int = 32,
                   dtype=jnp.bfloat16):
    """Inside-jit: packed leaves -> dense weights (fuses into consumers)."""
    return jax.tree_util.tree_map(
        lambda leaf: densify_leaf(leaf, block_size, dtype),
        packed_params,
        is_leaf=lambda x: isinstance(x, (MXTensor, PackedInt4Leaf)))


def repack_splitn_for_tp(packed_params, shardings, tp: int):
    """Re-nibble split-N int4 leaves whose output (N) axis is sharded.

    Split-N byte column ``j`` pairs output columns ``(j, j + N/2)`` — a
    GLOBAL interleave. Contiguously sharding the packed array hands each
    shard bytes whose nibbles decode to a permuted, non-contiguous column
    set, while the row-parallel consumer downstream (wo / w_down) shards
    its contraction rows contiguously — half the per-head / per-ff-block
    contributions would pair wrong under ``shard_map``. Repack so each
    shard's contiguous slice is a self-contained split-N layout of its own
    ``N/tp`` columns: the local unpack then yields exactly the columns the
    local step function expects, and the fused int4 kernel still reads a
    valid split-N tile (its dims come from the local shapes).

    Column-sharded leaves are detected from ``shardings`` (the tree
    ``packed_param_shardings`` built): a ``PackedInt4Leaf`` whose packed
    spec carries a mesh axis on the last dim. Split-K leaves and k-sharded
    split-N leaves (row-parallel) slice cleanly and pass through.
    """
    def fix(leaf, shd):
        if not (isinstance(leaf, PackedInt4Leaf) and leaf.layout == "splitn"
                and tp > 1):
            return leaf
        spec = shd.packed.spec
        last = spec[-1] if len(spec) == leaf.packed.ndim else None
        if last is None:
            return leaf
        # shard count along the byte-column axis — size-1 mesh axes (e.g.
        # 'data' on a (1, tp) serving mesh) never split it, so standard
        # split-N nibbling is already correct for those leaves.
        mesh_shape = shd.packed.mesh.shape
        n_shards = 1
        for nm in (last if isinstance(last, tuple) else (last,)):
            n_shards *= int(mesh_shape[nm])
        if n_shards <= 1:
            return leaf
        codes = unpack_int4_splitn_jnp(leaf.packed)
        n = codes.shape[-1]
        if n % (2 * n_shards):
            raise ValueError(
                f"cannot repack split-N leaf with N={n} over "
                f"{n_shards} shards")
        n_loc = n // n_shards
        packed = jnp.concatenate(
            [pack_int4_splitn_jnp(codes[..., s * n_loc:(s + 1) * n_loc])
             for s in range(n_shards)], axis=-1)
        return dataclasses.replace(leaf, packed=packed)

    is_c = lambda x: isinstance(x, (MXTensor, PackedInt4Leaf))
    return jax.tree_util.tree_map(fix, packed_params, shardings,
                                  is_leaf=is_c)


def packed_param_shardings(packed_abstract, axes_tree, mesh, rules=None):
    """NamedShardings for a packed-params pytree.

    Codes/packed arrays shard with the dense weight's logical axes (the
    packed dim reuses the block axis' mapping when divisibility allows);
    scale tensors follow the moved-last layout; raw leaves use their axes.

    These placements are what the tensor-parallel serving path
    (``ElasticEngine(mesh=...)``) feeds to ``jax.device_put`` before
    wrapping the step functions in ``shard_map`` — see
    docs/serving_internals.md §11 "Tensor-parallel serving".
    """
    from jax.sharding import NamedSharding
    from repro.sharding.rules import spec_for_axes

    is_ax = lambda x: isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)

    flat_a = {jax.tree_util.keystr(p): a for p, a in
              jax.tree_util.tree_flatten_with_path(
                  axes_tree, is_leaf=is_ax)[0]}

    def container(path_str, leaf):
        axes = flat_a[path_str]
        if isinstance(leaf, MXTensor):
            ax = leaf.block_axis
            moved = tuple(a for i, a in enumerate(axes) if i != ax) + \
                (axes[ax],)
            return MXTensor(
                codes=NamedSharding(mesh, spec_for_axes(
                    leaf.codes.shape, axes, mesh, rules)),
                scale_exp=NamedSharding(mesh, spec_for_axes(
                    leaf.scale_exp.shape, moved, mesh, rules)),
                fmt=leaf.fmt, block_axis=leaf.block_axis)
        if isinstance(leaf, PackedInt4Leaf):
            ax = leaf.block_axis
            moved_axes = tuple(a for i, a in enumerate(axes) if i != ax) + \
                (axes[ax],)
            # split-N keeps the dense axis order (last dim halved);
            # split-K moves the block axis last (nibble-paired).
            packed_axes = axes if leaf.layout == "splitn" else moved_axes
            return PackedInt4Leaf(
                packed=NamedSharding(mesh, spec_for_axes(
                    leaf.packed.shape, packed_axes, mesh, rules)),
                scale_exp=NamedSharding(mesh, spec_for_axes(
                    leaf.scale_exp.shape, moved_axes, mesh, rules)),
                shape=leaf.shape, block_axis=ax, fmt_name=leaf.fmt_name,
                layout=leaf.layout)
        return NamedSharding(mesh, spec_for_axes(leaf.shape, axes, mesh,
                                                 rules))

    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        packed_abstract,
        is_leaf=lambda x: isinstance(x, (MXTensor, PackedInt4Leaf)))
    return jax.tree_util.tree_unflatten(
        treedef, [container(jax.tree_util.keystr(p), l)
                  for p, l in leaves])


def make_packed_fn(api, fn, block_size: int = 32):
    """Wrap a ``fn(params, *rest)`` entry point to take packed params.

    Densification runs *inside* the (to-be-jitted) call, so the resident /
    HBM-streamed weights are the packed bytes and the dequant fuses into the
    consuming matmuls. This is the XLA fallback contract; the fused contract
    (``fused=True`` below) skips densification entirely. The wrapper keeps
    ``fn``'s name, so the step compiles as ``jit_<name>``.
    """
    @functools.wraps(fn)
    def wrapped(packed_params, *rest):
        params = densify_params(packed_params, block_size,
                                api.cfg.compute_dtype)
        return fn(params, *rest)
    return wrapped


def _fused_api(api, block_size: int, attn_impl: str = "gather"):
    """A ModelApi clone whose serving entry points run packed leaves through
    the fused Pallas dequant-GEMM dispatch (``kernels.dispatch.qmatmul``),
    with the paged decode-attention path (``attn_impl``) baked in."""
    if api.with_qmm is None:
        raise ValueError(
            f"model family {api.cfg.family!r} has no qmm hook; use the "
            "densify path (fused=False)")
    from repro.kernels.dispatch import make_qmm
    qmm = make_qmm(block_size=block_size, mode="pallas")
    if api.with_serving is not None:
        return api.with_serving(qmm=qmm, attn_impl=attn_impl)
    if attn_impl != "gather":
        raise ValueError(
            f"model family {api.cfg.family!r} cannot rebuild its serving "
            f"entry points with attn_impl={attn_impl!r} (no with_serving)")
    return api.with_qmm(qmm)


def _attn_api(api, attn_impl: str):
    """``api`` rebuilt (if needed) so serve_step uses ``attn_impl``."""
    if api.attn_impl == attn_impl:
        return api
    if api.with_serving is None:
        raise ValueError(
            f"model family {api.cfg.family!r} cannot rebuild its serving "
            f"entry points with attn_impl={attn_impl!r} (no with_serving)")
    return api.with_serving(attn_impl=attn_impl)


def make_packed_serve_step(api, block_size: int = 32, *,
                           fused: bool = False, attn_impl: str = "gather"):
    """serve_step over packed params (the roofline-optimized decode path).

    ``fused=True`` returns a step where each projection calls the Pallas
    dequant-GEMM on its packed leaf (interpret-mode off TPU); ``fused=False``
    keeps the XLA densify-inside-jit contract. Both take the same packed
    pytree and produce the same logits (same codes). ``attn_impl`` picks the
    paged decode-attention read path — the gather-free block-table kernel
    (``"paged_kernel"``) vs gather + masked softmax (``"gather"``) — and is
    orthogonal to the weight contract: any (fused, attn_impl) pairing is a
    valid serving configuration with identical token streams.
    """
    if fused:
        return _fused_api(api, block_size, attn_impl).serve_step
    api = _attn_api(api, attn_impl)
    return make_packed_fn(api, api.serve_step, block_size)


def make_packed_mixed_step(api, block_size: int = 32, *,
                           fused: bool = False, attn_impl: str = "gather"):
    """Unified mixed prefill+decode tick over packed params.

    ``(packed_params, batch{tokens (B,C), q_len (B,)}, cache, cache_len)
    -> (logits (B,V), cache)`` — the single-executable scheduler tick
    subsuming serve_step + prefill_chunk (``ModelApi.mixed_step``): decode
    rows carry 1 real token, the mid-prefill row its chunk, each at its own
    ``cache_len`` cursor. Contracts mirror ``make_packed_serve_step``:
    fused Pallas dequant-GEMM vs XLA densify-inside-jit on the weight side,
    and ``attn_impl`` picking the ragged multi-query paged read path — the
    gather-free MQ block-table kernel (``"paged_kernel"``) vs gather +
    masked softmax (``"gather"``). Any (fused, attn_impl) pairing yields
    identical token streams.
    """
    if fused:
        return _fused_api(api, block_size, attn_impl).mixed_step
    api = _attn_api(api, attn_impl)
    return make_packed_fn(api, api.mixed_step, block_size)


def make_packed_verify_step(api, block_size: int = 32, *,
                            fused: bool = False, attn_impl: str = "gather"):
    """Speculative verify tick over packed params.

    ``(packed_params, batch{tokens (B,C), q_len (B,)}, cache, cache_len)
    -> (logits (B,C,V), cache)`` — ``ModelApi.verify_step``, the
    all-positions sibling of ``mixed_step``: one executable scores a
    k-token draft burst per decode row under the verify format so the
    engine can accept the longest greedy-matching prefix and rewind the
    rest (docs/serving_internals.md §9 "Speculative decoding"). Weight and
    attention contracts mirror ``make_packed_mixed_step`` — fused Pallas
    dequant-GEMM vs XLA densify-inside-jit, and the ragged multi-query
    paged read path (``"paged_kernel"`` | ``"gather"``). Any
    (fused, attn_impl) pairing yields identical token streams.
    """
    if fused:
        return _fused_api(api, block_size, attn_impl).verify_step
    api = _attn_api(api, attn_impl)
    return make_packed_fn(api, api.verify_step, block_size)


def make_packed_prefill_slot(api, block_size: int = 32, *,
                             fused: bool = False):
    """Single-slot prefill-insert over packed params (see ModelApi).

    This is the *monolithic* admission path: the whole prompt in one call.
    The chunked counterpart is ``make_packed_prefill_chunk`` below; the
    engine's admission state machine that drives both is documented in
    docs/serving_internals.md ("Admission & scheduling").
    """
    if fused:
        return _fused_api(api, block_size).prefill_slot
    return make_packed_fn(api, api.prefill_slot, block_size)


def make_packed_prefill_chunk(api, block_size: int = 32, *,
                              fused: bool = False):
    """Single-slot *chunked* prefill over packed params.

    ``(packed_params, batch{tokens (1,C), lengths}, cache, slot, start_pos)
    -> (logits (V,), cache, new_len)`` — one prompt chunk at cursor
    ``start_pos``. The engine calls it once per tick so a long admission
    never stalls running slots for more than one chunk; it compiles once
    per chunk *bucket* (C is the fixed chunk size, or a pow2 bucket of the
    final remainder), not once per cursor — ``start_pos`` is traced.
    Contracts mirror ``make_packed_prefill_slot``: fused Pallas dequant-GEMM
    vs XLA densify-inside-jit, same packed tree, same logits.
    """
    if fused:
        return _fused_api(api, block_size).prefill_chunk_slot
    return make_packed_fn(api, api.prefill_chunk_slot, block_size)


def weight_stream_bytes(params) -> int:
    """Device bytes one decode tick must stream for the weight pytree.

    For packed trees this counts codes + scales at their stored width (uint8
    nibble-pairs for PackedInt4Leaf), i.e. the roofline weight-read term.
    """
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(params))


def weight_stream_bytes_local(params) -> int:
    """Per-chip weight-stream bytes for a (possibly sharded) weight pytree.

    Uses each leaf's actual sharding to size the LOCAL shard — on a
    ``(1, n_model)`` mesh this is ~``weight_stream_bytes / n_model`` (exactly,
    up to replicated bias/norm leaves), which is the number the per-chip
    roofline cost model must be seeded with. Falls back to the global size
    for uncommitted/unsharded leaves.
    """
    total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None:
            shape = sharding.shard_shape(leaf.shape)
            n = 1
            for d in shape:
                n *= d
        else:
            n = leaf.size
        total += n * leaf.dtype.itemsize
    return total
