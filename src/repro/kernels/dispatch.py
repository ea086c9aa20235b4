"""Quantized-matmul dispatch: packed MX weight leaves straight to the MXU.

``qmatmul(x, leaf)`` is the serving hot loop's GEMM entry point. It accepts
the packed containers the weight caches hold — ``MXTensor`` (int8/uint8
element codes + E8M0 scales) and split-N ``PackedInt4Leaf`` (nibble pairs) —
and routes them to the fused Pallas dequant-GEMM kernels in ``mx_matmul.py``
without ever materializing a dense weight in HBM:

  mode "pallas"   ``mx_matmul_pallas`` / ``mx_matmul_int4_pallas``; on TPU
                  these lower to Mosaic, elsewhere they run interpret-mode
                  (the test/CI correctness path).
  mode "densify"  XLA fallback: dequantize the leaf at its point of use and
                  issue a plain dot (XLA fuses the dequant into the GEMM).
  mode "auto"     "pallas" on TPU, "densify" elsewhere.

The wrapper owns everything the raw kernels refuse to deal with: arbitrary
``(M, K, N)`` via zero padding to tile multiples (zero codes dequantize to
exactly 0 in every MX format, so padding never perturbs the result), the
int4 kernel's ``half_n % tn == 0`` constraint (both packed halves are padded
and the two output column ranges re-spliced), and tile-size selection — a
static table refined by autotuned entries registered per ``(shape, fmt)``
from ``benchmarks/kernels_bench.py``.

Fallback conditions (leaf not 2D after scan slicing, legacy split-K int4
layout, K not a block-size multiple) take the densify path under mode
"auto"; under an explicit mode "pallas" a 2D leaf the kernels cannot take
raises instead, so a fused engine never serves a densified GEMM unseen.
``stats()`` counts which path each traced call took so benchmarks and CI
can assert the fused kernels are actually live.

Layout conventions this layer depends on — scan-stale leaf metadata
(contraction dim re-derived as ndim-2), moved-last ``(N, K/bs)`` scales
(the kernels consume the transpose), split-N vs split-K nibble packing —
are documented in docs/serving_internals.md §§1-3.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.formats import MXFormat, get_format
from repro.core.mx import MXTensor
from repro.kernels import mx_matmul as _mm

# ---------------------------------------------------------------------------
# Mode resolution + trace-time accounting
# ---------------------------------------------------------------------------
MODES = ("auto", "pallas", "densify")

_stats: Dict[str, int] = {"pallas": 0, "pallas_int4": 0, "densify": 0}


def stats() -> Dict[str, int]:
    """Trace-time counts of which execution path qmatmul dispatched to."""
    return dict(_stats)


def reset_stats() -> None:
    for k in _stats:
        _stats[k] = 0


def default_mode() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "densify"


def resolve_mode(mode: Optional[str]) -> str:
    if mode is None or mode == "auto":
        return default_mode()
    if mode not in ("pallas", "densify"):
        raise ValueError(f"unknown qmatmul mode {mode!r}; one of {MODES}")
    return mode


def _interpret() -> bool:
    # Mosaic only lowers on TPU; everywhere else the kernel body runs in the
    # Pallas interpreter (exactly as written — the CI correctness contract).
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Tile selection: static table + autotune-registered cache
# ---------------------------------------------------------------------------
# (m, k, n, block_size, fmt_name, kind) -> (tm, tn, tk); kind is "mx" or
# "int4" (for "int4" the tn entry tiles half_n = n // 2, matching the kernel
# grid). The key is EXACTLY the shapes the kernel is traced with — under
# shard_map these are the per-shard LOCAL dims (a tensor-parallel projection
# sees n / n_model, or k / n_model for row-parallel), so autotune for a
# meshed engine must register the local shapes, and entries tuned at global
# shapes simply miss (heuristic fallback) instead of mis-tiling the shard.
# block_size is part of the key: a tk tuned for one block size need not
# divide another's scale blocking (kp // bs would truncate — silently wrong
# scales), so entries never apply across block sizes.
_TILE_CACHE: Dict[Tuple[int, int, int, int, str, str],
                  Tuple[int, int, int]] = {}

# Hard ceilings keeping one (TM,TK)+(TK,TN) operand pair comfortably in VMEM.
# A dim no larger than its _WHOLE cap may instead be taken as one whole tile.
_TM_CAP, _TN_CAP, _TK_CAP = 128, 256, 512
_TN_WHOLE_CAP, _TK_WHOLE_CAP = 1024, 1024
_LANE, _SUBLANE = 128, 8


def register_tiles(m: int, k: int, n: int, fmt_name: str,
                   tiles: Tuple[int, int, int], kind: str = "mx",
                   block_size: int = 32) -> None:
    """Pin (tm, tn, tk) for an exact (M, K, N, fmt@block_size) — autotune
    results land here (``benchmarks/kernels_bench.py::autotune_qmatmul``).
    (M, K, N) are the shapes the kernel is traced with: per-shard local
    dims under a mesh, global dims on one device."""
    _TILE_CACHE[(m, k, n, block_size, fmt_name, kind)] = tuple(tiles)


def tile_cache() -> Dict:
    return dict(_TILE_CACHE)


# Weights laid out ahead of time, by their padded (K, N, block_size, fmt,
# kind): the (tn, tk) they were padded for (``pin_layout``). The heuristic
# re-derived from a padded width need not be that tile: qwen2-72b's d_ff
# share 7392 is padded to 7424 for tn 256, and 7424, a lane multiple, would
# be read at tn 128.
_LAID_OUT: Dict[Tuple[int, int, int, str, str], Tuple[int, int]] = {}


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _best_tile(dim: int, unit: int, cap: int) -> int:
    """Largest multiple of ``unit`` <= cap that minimizes padded size."""
    best, best_pad = unit, _round_up(max(dim, 1), unit)
    t = unit
    while t <= cap:
        pad = _round_up(max(dim, 1), t)
        if pad < best_pad or (pad == best_pad and t > best):
            best, best_pad = t, pad
        t += unit
    return best


def _k_unit(bs: int) -> int:
    """Smallest split-K tile Mosaic takes: a lane multiple (the (tm, tk)
    activation block) whose scale block (tk/bs, tn) is a sublane multiple."""
    return math.lcm(_LANE, _SUBLANE * bs)


def tiles_aligned(m: int, k: int, n: int, tiles: Tuple[int, int, int],
                  bs: int, kind: str = "mx") -> bool:
    """Whether ``(tm, tn, tk)`` meets the Mosaic block rules for this call.

    tm is a sublane multiple. tk is a multiple of ``_k_unit(bs)`` or the
    whole K. tn is a lane multiple, or (unpacked kind only) the whole N —
    the int4 kernel's scale block spans both packed halves, so a whole
    half-N tile is never a whole scale dim there.
    """
    tm, tn, tk = tiles
    n_eff = n // 2 if kind == "int4" else n
    tn_ok = tn > 0 and (tn % _LANE == 0 or (kind != "int4" and tn == n_eff))
    tk_ok = tk > 0 and (tk % _k_unit(bs) == 0 or tk == _round_up(k, bs))
    return tm > 0 and tm % _SUBLANE == 0 and tn_ok and tk_ok


def weight_tiles(k: int, n: int, fmt: MXFormat,
                 kind: str = "mx") -> Tuple[int, int]:
    """(tn, tk) of the heuristic: the tiles a weight of (K, N) is read
    with when no autotuned entry applies. They do not depend on M, so they
    are also the rule a weight is laid out by ahead of time
    (``weight_layout``).

    tn: a lane multiple, or the whole N when it is small and not
    lane-aligned (unpacked kind only); among lane multiples up to the cap,
    the one that pads N least. tk: a ``_k_unit(bs)`` multiple that pads K
    least, or the whole K when that is small and would otherwise pad.
    A shape ``pin_layout`` recorded is read with the tiles it was padded
    for.
    """
    bs = fmt.block_size
    pinned = _LAID_OUT.get((k, n, bs, fmt.name, kind))
    if pinned is not None:
        return pinned
    n_eff = n // 2 if kind == "int4" else n
    if n_eff % _LANE and kind != "int4" and n_eff <= _TN_WHOLE_CAP:
        tn = n_eff
    else:
        tn = _LANE if n_eff % _LANE == 0 else _best_tile(n_eff, _LANE,
                                                         _TN_CAP)
    unit = _k_unit(bs)
    kw = _round_up(k, bs)
    tk = _best_tile(kw, unit, max(unit, _TK_CAP))
    if kw <= _TK_WHOLE_CAP and _round_up(kw, tk) > kw:
        tk = kw                   # one whole-K tile beats padding K
    return tn, tk


def weight_layout(k: int, n: int, fmt: MXFormat,
                  kind: str = "mx") -> Tuple[int, int]:
    """(K, N) a weight of (k, n) is padded to with zero codes so that the
    tiles ``weight_tiles`` picks for (k, n) divide it (``pin_layout`` then
    makes them the tiles the padded weight is read with): the wrapper
    below pads no weight operand inside a step. For ``kind="int4"``, N
    counts unpacked columns (twice the split-N byte columns). A width that
    already needs no pad is returned as it is.
    """
    tn, tk = weight_tiles(k, n, fmt, kind)
    kp = _round_up(_round_up(k, fmt.block_size), tk)
    np_ = 2 * _round_up(n // 2, tn) if kind == "int4" else _round_up(n, tn)
    return kp, np_


def pin_layout(k: int, n: int, kp: int, np_: int, fmt: MXFormat,
               kind: str = "mx") -> Tuple[int, int]:
    """Record that a weight of (k, n) is served padded to (kp, np_): from
    now on (kp, np_) is read with the tiles it was padded for, those of
    (k, n), where they divide it. Returns the (tn, tk) it is read with."""
    tn, tk = weight_tiles(k, n, fmt, kind)
    n_eff = np_ // 2 if kind == "int4" else np_
    if (kp, np_) != (k, n) and n_eff % tn == 0 and kp % tk == 0:
        _LAID_OUT[(kp, np_, fmt.block_size, fmt.name, kind)] = (tn, tk)
    return weight_tiles(kp, np_, fmt, kind)


def select_tiles(m: int, k: int, n: int, fmt: MXFormat,
                 kind: str = "mx") -> Tuple[int, int, int]:
    """(tm, tn, tk) for an (M, K, N) qmatmul at ``fmt``.

    Autotuned entries win; otherwise tm is the sublane multiple that pads M
    least and (tn, tk) come from ``weight_tiles``, which minimize zero
    padding subject to VMEM-friendly caps and the Mosaic block rules of
    ``tiles_aligned``: lane multiples for N, or the whole N when it is small
    and not lane-aligned; ``_k_unit(bs)`` multiples for K, or the whole K
    when it is small — so both the activation block and the scale block
    tile.

    Where a weight's N or K is not a multiple of its tile, the wrapper pads
    the weight's codes and scales on every call, and a pad cannot fuse into
    the Mosaic call: a copy of the weight each step. So a weight that is
    served sharded is laid out once, when it is packed, at
    ``weight_layout``'s widths (``serve.packed_params.layout_tp_shards``;
    docs/serving_internals.md §11), and ``pin_layout`` makes the tiles it
    was padded for the ones it is read with. An autotuned entry for such a
    shape must divide the laid-out widths too, or it brings the per-call
    pad back.

    ``(m, k, n)`` are whatever shapes this trace actually sees — per-shard
    local dims inside shard_map — and the lookup keys on them plus
    ``fmt.block_size``, so a cached entry can never pick tiles that don't
    divide the shapes (or scale blocking) of the call at hand. A registered
    entry that nonetheless violates the alignment rules (stale
    hand-registration) is ignored, not applied.
    """
    bs = fmt.block_size
    key = (m, k, n, bs, fmt.name, kind)
    if key in _TILE_CACHE and tiles_aligned(m, k, n, _TILE_CACHE[key], bs,
                                            kind):
        return _TILE_CACHE[key]
    tn, tk = weight_tiles(k, n, fmt, kind)
    return _best_tile(m, _SUBLANE, _TM_CAP), tn, tk


# ---------------------------------------------------------------------------
# Padded kernel wrappers
# ---------------------------------------------------------------------------
def _pad_to(a: jax.Array, axis: int, size: int) -> jax.Array:
    pad = size - a.shape[axis]
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def qmatmul_mx(x2: jax.Array, codes: jax.Array, scales_kn: jax.Array,
               fmt: MXFormat, *, tiles: Optional[Tuple] = None) -> jax.Array:
    """x2 (M, K) @ dequant(codes (K, N), scales (K/bs, N)) -> (M, N) f32.

    Pads every dim to the selected tile multiples (zero codes contribute
    exactly 0) and slices the result back — arbitrary shapes welcome.
    """
    m, k = x2.shape
    n = codes.shape[1]
    bs = fmt.block_size
    tm, tn, tk = tiles or select_tiles(m, k, n, fmt, kind="mx")
    mp, kp, np_ = _round_up(m, tm), _round_up(k, tk), _round_up(n, tn)
    x2 = _pad_to(_pad_to(x2, 0, mp), 1, kp)
    codes = _pad_to(_pad_to(codes, 0, kp), 1, np_)
    scales = _pad_to(_pad_to(scales_kn, 0, kp // bs), 1, np_)
    _stats["pallas"] += 1
    out = _mm.mx_matmul_pallas(x2, codes, scales, fmt, tm=tm, tn=tn, tk=tk,
                               interpret=_interpret())
    return out[:m, :n]


def qmatmul_int4(x2: jax.Array, packed: jax.Array, scales_kn: jax.Array,
                 fmt: MXFormat, *, tiles: Optional[Tuple] = None) -> jax.Array:
    """x2 (M, K) @ dequant(split-N int4 (K, N/2), scales (K/bs, N)) -> (M, N).

    The raw kernel requires ``half_n % tn == 0``; here both nibble halves are
    zero-padded to the tile multiple (scales split and re-packed to match the
    padded column layout) and the two true output ranges re-spliced, so odd
    tile-unfriendly N just works.
    """
    m, k = x2.shape
    half_n = packed.shape[1]
    n = half_n * 2
    bs = fmt.block_size
    tm, tn, tk = tiles or select_tiles(m, k, n, fmt, kind="int4")
    mp, kp = _round_up(m, tm), _round_up(k, tk)
    hp = _round_up(half_n, tn)
    x2 = _pad_to(_pad_to(x2, 0, mp), 1, kp)
    packed = _pad_to(_pad_to(packed, 0, kp), 1, hp)
    scales = jnp.concatenate([_pad_to(scales_kn[:, :half_n], 1, hp),
                              _pad_to(scales_kn[:, half_n:], 1, hp)], axis=1)
    scales = _pad_to(scales, 0, kp // bs)
    _stats["pallas_int4"] += 1
    out = _mm.mx_matmul_int4_pallas(x2, packed, scales, fmt,
                                    tm=tm, tn=tn, tk=tk,
                                    interpret=_interpret())
    return jnp.concatenate([out[:m, :half_n], out[:m, hp:hp + half_n]],
                           axis=1)


# ---------------------------------------------------------------------------
# Leaf-level dispatch
# ---------------------------------------------------------------------------
def _check_serving_layout(leaf) -> None:
    """Reject 2D MXTensor leaves whose scales aren't in the serving layout.

    The contract is codes (K, N) with scale_exp (N, K/bs) — what
    ``quantize(w, fmt, axis=0)`` and scan-sliced serving trees produce. A
    leaf quantized along the wrong axis has scale_exp (K, N/bs), which for
    non-square weights is caught here LOUDLY (both the fused kernel and the
    serving-axis densify fallback would silently misread it). Square K == N
    is inherently shape-ambiguous; callers own the convention there.
    """
    if isinstance(leaf, MXTensor) and leaf.codes.ndim == 2:
        k, n = leaf.codes.shape
        bs = leaf.fmt.block_size
        want = (n, k // bs)
        if k % bs == 0 and tuple(leaf.scale_exp.shape) != want:
            raise ValueError(
                f"MXTensor leaf violates the serving layout: codes "
                f"{(k, n)} expect scale_exp {want}, got "
                f"{tuple(leaf.scale_exp.shape)} — was it quantized along "
                "the wrong axis?")


def _fused_supported(leaf) -> bool:
    from repro.serve.packed_params import PackedInt4Leaf
    if isinstance(leaf, MXTensor):
        return leaf.codes.ndim == 2 and leaf.codes.shape[0] % \
            leaf.fmt.block_size == 0
    if isinstance(leaf, PackedInt4Leaf):
        # legacy split-K nibble layout has no fused kernel — densify it
        return leaf.layout == "splitn" and leaf.packed.ndim == 2
    return False


def _codes_2d(leaf) -> Optional[jax.Array]:
    """The leaf's 2D code array (codes or nibble-packed), else None."""
    arr = getattr(leaf, "codes", getattr(leaf, "packed", None))
    return arr if arr is not None and arr.ndim == 2 else None


def qmatmul(x: jax.Array, leaf, *, mode: Optional[str] = None,
            block_size: int = 32, tiles: Optional[Tuple] = None,
            out_dtype=None) -> jax.Array:
    """y = x @ dequant(leaf), never materializing the dense weight in HBM.

    x (..., K); leaf is an MXTensor with codes (K, N) / scales (N, K/bs)
    (the serving convention: contraction dim = ndim-2, scales in the
    moved-last blocked layout) or a split-N PackedInt4Leaf with packed
    (K, N/2). Block sizes are carried by the leaves themselves
    (``block_size`` is kept for API stability only). Returns (..., N) in
    ``out_dtype`` (default: x.dtype).
    """
    out_dtype = out_dtype or x.dtype
    _check_serving_layout(leaf)
    supported = _fused_supported(leaf)
    if mode == "pallas" and not supported and _codes_2d(leaf) is not None:
        raise ValueError(
            f"qmatmul(mode='pallas'): {type(leaf).__name__} leaf "
            f"{tuple(_codes_2d(leaf).shape)} "
            f"(layout {getattr(leaf, 'layout', 'unpacked')}) cannot take "
            "the fused kernel; serve it with mode='densify' (fused=False)")
    use_pallas = resolve_mode(mode) == "pallas" and supported
    if not use_pallas:
        from repro.serve.packed_params import densify_leaf
        _stats["densify"] += 1
        w = densify_leaf(leaf, None, out_dtype, serving_axis=True)
        return jax.lax.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())),
                                   preferred_element_type=out_dtype)

    from repro.serve.packed_params import PackedInt4Leaf, leaf_block_size
    k = x.shape[-1]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, k)
    if isinstance(leaf, MXTensor):
        fmt = leaf.fmt
        out = qmatmul_mx(x2, leaf.codes, leaf.scale_exp.T, fmt, tiles=tiles)
    else:
        assert isinstance(leaf, PackedInt4Leaf)
        # block size from the leaf's own shapes, not the registry default
        fmt = get_format(leaf.fmt_name, leaf_block_size(leaf))
        out = qmatmul_int4(x2, leaf.packed, leaf.scale_exp.T, fmt,
                           tiles=tiles)
    return out.reshape(*lead, out.shape[-1]).astype(out_dtype)


def make_qmm(block_size: int = 32, mode: Optional[str] = None) -> Callable:
    """A ``QuantCtx.qmm`` hook: (x, leaf, name) -> y at a fixed mode.

    The mode is resolved once, at construction — engines build one jitted
    executable per hook, so the fused/densify choice is baked into the trace
    (no stale-jit-cache hazards from flipping a global).
    """
    resolved = resolve_mode(mode)

    def qmm(x, leaf, name=None):
        del name
        return qmatmul(x, leaf, mode=resolved, block_size=block_size)

    return qmm
