"""qmatmul dispatch layer: fused Pallas (interpret) vs the dense reference.

The serving contract under test: packed weight leaves (MXTensor, split-N
PackedInt4Leaf) go straight into the fused dequant-GEMM with shape padding,
and the result matches x @ dequantize(leaf) within fp32 tolerance for every
serving format — including split-N int4 whose half_n doesn't divide the
tile size.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import get_format
from repro.core.mx import dequantize, quantize
from repro.kernels import dispatch
from repro.serve.packed_params import PackedInt4Leaf, pack_leaf_int4

FORMATS = ["mxint8", "mxfp8", "mxint6", "mxint4"]
# (M, K, N): deliberately tile-hostile — M < 8, N not a multiple of the
# lane tile, K needing padding to the tk multiple.
SHAPES = [(3, 96, 80), (8, 128, 130), (16, 64, 256), (5, 160, 48)]


def _rand(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape)
                       .astype(np.float32))


def _leaf(w, fmt):
    t = quantize(w, fmt, axis=0)
    if fmt.kind == "int" and fmt.bits == 4:
        return t, pack_leaf_int4(t)
    return t, t


@pytest.mark.parametrize("name", FORMATS)
@pytest.mark.parametrize("mnk", SHAPES)
def test_qmatmul_pallas_matches_dense_reference(name, mnk):
    m, k, n = mnk
    fmt = get_format(name, 32)
    x = _rand((m, k), seed=1)
    w = _rand((k, n), seed=2)
    t, leaf = _leaf(w, fmt)
    want = np.asarray(x @ dequantize(t, jnp.float32))
    got = np.asarray(dispatch.qmatmul(x, leaf, mode="pallas"))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("name", ["mxint8", "mxint4"])
def test_qmatmul_densify_matches_pallas(name):
    fmt = get_format(name, 32)
    x = _rand((4, 64), seed=3)
    w = _rand((64, 96), seed=4)
    t, leaf = _leaf(w, fmt)
    a = np.asarray(dispatch.qmatmul(x, leaf, mode="pallas"))
    b = np.asarray(dispatch.qmatmul(x, leaf, mode="densify"))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n", [130, 258, 82])
def test_qmatmul_int4_splitn_padded_n_regression(n):
    """The raw int4 kernel requires half_n % tn == 0; the dispatch wrapper
    pads both nibble halves and re-splices the output, so odd / non-tile
    half widths (65, 129, 41) must come out exact."""
    fmt = get_format("mxint4", 32)
    k = 64
    x = _rand((6, k), seed=5)
    w = _rand((k, n), seed=6)
    t = quantize(w, fmt, axis=0)
    leaf = pack_leaf_int4(t)
    assert leaf.layout == "splitn"
    assert leaf.packed.shape == (k, n // 2)
    want = np.asarray(x @ dequantize(t, jnp.float32))
    got = np.asarray(dispatch.qmatmul(x, leaf, mode="pallas"))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_qmatmul_batched_x_and_dtype():
    """x may carry leading dims (B, S, K) and a non-f32 dtype; output shape
    and dtype follow x."""
    fmt = get_format("mxint8", 32)
    x = _rand((2, 3, 64), seed=7).astype(jnp.bfloat16)
    w = _rand((64, 48), seed=8)
    t, leaf = _leaf(w, fmt)
    got = dispatch.qmatmul(x, leaf, mode="pallas")
    assert got.shape == (2, 3, 48) and got.dtype == jnp.bfloat16
    want = x.astype(jnp.float32).reshape(-1, 64) @ dequantize(t, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(got, np.float32).reshape(-1, 48), np.asarray(want),
        rtol=2e-2, atol=2e-2)


def test_qmatmul_legacy_splitk_leaf_falls_back():
    """Split-K nibble layout has no fused kernel: mode "auto" densifies it
    (and stays correct) rather than feed the kernel a wrong layout, while an
    explicit mode "pallas" refuses it loudly instead of densifying unseen."""
    fmt = get_format("mxint4", 32)
    x = _rand((4, 64), seed=9)
    w = _rand((64, 96), seed=10)
    t = quantize(w, fmt, axis=0)
    leaf = pack_leaf_int4(t, layout="splitk")
    want = np.asarray(x @ dequantize(t, jnp.float32))
    dispatch.reset_stats()
    with pytest.raises(ValueError, match="cannot take the fused kernel"):
        dispatch.qmatmul(x, leaf, mode="pallas")
    got = np.asarray(dispatch.qmatmul(x, leaf, mode="auto"))
    st = dispatch.stats()
    assert st["densify"] == 1 and st["pallas_int4"] == 0
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_dispatch_counters_see_pallas_hits():
    """The CI smoke contract: pallas mode increments the kernel counters
    (this is what keeps the dispatch from silently regressing to the
    fallback)."""
    fmt8 = get_format("mxint8", 32)
    fmt4 = get_format("mxint4", 32)
    x = _rand((4, 64), seed=11)
    w = _rand((64, 64), seed=12)
    _, leaf8 = _leaf(w, fmt8)
    _, leaf4 = _leaf(w, fmt4)
    dispatch.reset_stats()
    dispatch.qmatmul(x, leaf8, mode="pallas")
    dispatch.qmatmul(x, leaf4, mode="pallas")
    dispatch.qmatmul(x, leaf8, mode="densify")
    st = dispatch.stats()
    assert st["pallas"] == 1 and st["pallas_int4"] == 1 \
        and st["densify"] == 1


def test_tile_registration_overrides_table():
    fmt = get_format("mxint8", 32)
    base = dispatch.select_tiles(7, 64, 96, fmt)
    # a Mosaic-legal entry the heuristic would not pick (N padded to a lane
    # tile instead of taken whole)
    dispatch.register_tiles(7, 64, 96, "mxint8", (8, 128, 64))
    try:
        assert base != (8, 128, 64)
        assert dispatch.select_tiles(7, 64, 96, fmt) == (8, 128, 64)
        # registered tiles actually run (and stay correct)
        x = _rand((7, 64), seed=13)
        w = _rand((64, 96), seed=14)
        t, leaf = _leaf(w, fmt)
        got = dispatch.qmatmul(x, leaf, mode="pallas")
        want = x @ dequantize(t, jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)
    finally:
        dispatch._TILE_CACHE.pop((7, 64, 96, 32, "mxint8", "mx"), None)
    assert dispatch.select_tiles(7, 64, 96, fmt) == base


def test_tile_cache_keys_on_block_size():
    """Regression (tensor-parallel serving PR): the tile cache must key on
    block_size. An entry tuned at bs=64 applied to a bs=96 call with the
    same (M, K, N) can give a tk that doesn't divide the scale blocking —
    ``kp // bs`` truncates and the kernel reads wrong scales — so
    cross-block-size hits must be misses."""
    fmt64 = get_format("mxint8", 64)
    fmt32 = get_format("mxint8", 32)
    dispatch.register_tiles(7, 192, 96, "mxint8", (16, 128, 192),
                            block_size=64)
    try:
        assert dispatch.select_tiles(7, 192, 96, fmt64) == (16, 128, 192)
        # same (m, k, n), different block size: the bs=64 entry must NOT
        # apply — the key includes block_size, so the bs=32 lookup falls
        # back to the heuristic, whose tk always divides its own blocking.
        t32 = dispatch.select_tiles(7, 192, 96, fmt32)
        assert t32 != (16, 128, 192)
        assert t32[2] % 32 == 0
    finally:
        dispatch._TILE_CACHE.pop((7, 192, 96, 64, "mxint8", "mx"), None)


def test_tile_cache_local_shard_shapes_hit_globals_miss():
    """Regression (tensor-parallel serving PR): under shard_map the kernel
    traces with per-shard LOCAL shapes. An entry registered at the local
    shape must hit; the global-shape entry must miss (heuristic fallback)
    rather than hand the shard tiles that don't divide it."""
    fmt = get_format("mxint8", 32)
    n_global, tp = 256, 2
    n_local = n_global // tp
    # global-shape registration with tiles that would NOT divide the local
    # shard (tn=256 > n_local): must not leak into the local-shape lookup
    dispatch.register_tiles(8, 64, n_global, "mxint8", (8, 256, 64))
    dispatch.register_tiles(8, 64, n_local, "mxint8", (16, 128, 64))
    try:
        assert dispatch.select_tiles(8, 64, n_local, fmt) == (16, 128, 64)
        assert dispatch.select_tiles(8, 64, n_global, fmt) == (8, 256, 64)
        # the registered local tiles actually run on a local-shaped GEMM
        x = _rand((8, 64), seed=18)
        w = _rand((64, n_local), seed=19)
        t, leaf = _leaf(w, fmt)
        got = dispatch.qmatmul(x, leaf, mode="pallas")
        want = x @ dequantize(t, jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)
    finally:
        dispatch._TILE_CACHE.pop((8, 64, n_global, 32, "mxint8", "mx"), None)
        dispatch._TILE_CACHE.pop((8, 64, n_local, 32, "mxint8", "mx"), None)


def test_tile_cache_ignores_misaligned_entries():
    """A hand-registered entry violating the kernel's alignment rules
    (tm not a sublane multiple / tk not a block-size multiple) is ignored
    — heuristic fallback — never applied to corrupt the scale padding."""
    fmt = get_format("mxint8", 32)
    dispatch.register_tiles(16, 64, 96, "mxint8", (7, 48, 48))  # bad tm+tk
    try:
        tm, tn, tk = dispatch.select_tiles(16, 64, 96, fmt)
        assert (tm, tn, tk) != (7, 48, 48)
        assert tm % 8 == 0 and tk % fmt.block_size == 0
    finally:
        dispatch._TILE_CACHE.pop((16, 64, 96, 32, "mxint8", "mx"), None)


def test_select_tiles_divide_padded_dims():
    for (m, k, n) in [(1, 32, 8), (300, 544, 1000), (8, 96, 130)]:
        for name in ("mxint8", "mxint4"):
            fmt = get_format(name, 32)
            kind = "int4" if name == "mxint4" else "mx"
            tm, tn, tk = dispatch.select_tiles(m, k, n, fmt, kind)
            assert tm % 8 == 0 and tk % fmt.block_size == 0
            n_eff = n // 2 if kind == "int4" else n
            # padding to the tile multiple must stay bounded
            assert -(-m // tm) * tm < m + tm
            assert -(-n_eff // tn) * tn < n_eff + tn
            assert -(-k // tk) * tk < k + tk


@pytest.mark.parametrize("bs", [16, 32, 64])
@pytest.mark.parametrize("name", ["mxint8", "mxint4"])
@pytest.mark.parametrize("mkn", [(8, 576, 576), (8, 576, 192), (8, 1536, 576),
                                 (8, 2560, 9728), (8, 9728, 2560),
                                 (1, 64, 96), (300, 544, 1000), (8, 96, 130)])
def test_select_tiles_meet_mosaic_block_rules(mkn, name, bs):
    """The (8, 128) tiling rule, stated on its own: the (tm, tk) activation
    block and (tk, tn) weight block tile as lane multiples or whole dims, and
    the (tk/bs, tn) scale block's row count is a sublane multiple unless tk
    is the whole K. Real widths (K=576) used to get tk=288 here."""
    m, k, n = mkn
    fmt = get_format(name, bs)
    kind = "int4" if name == "mxint4" else "mx"
    tm, tn, tk = dispatch.select_tiles(m, k, n, fmt, kind)
    n_eff = n // 2 if kind == "int4" else n
    k_whole = -(-k // bs) * bs
    assert tm % 8 == 0
    assert tn % 128 == 0 or (kind == "mx" and tn == n_eff)
    assert tk == k_whole or (tk % 128 == 0 and (tk // bs) % 8 == 0)
    assert tk % bs == 0


def test_misaligned_registered_tiles_are_ignored():
    """Entries that break the lane/sublane rules (tn=48, tk=32 at K=64) are
    skipped — the heuristic's aligned tiles apply instead."""
    fmt = get_format("mxint8", 32)
    base = dispatch.select_tiles(8, 64, 96, fmt)
    dispatch.register_tiles(8, 64, 96, "mxint8", (8, 48, 32))
    try:
        assert dispatch.select_tiles(8, 64, 96, fmt) == base
    finally:
        dispatch._TILE_CACHE.pop((8, 64, 96, 32, "mxint8", "mx"), None)


def test_mode_resolution():
    assert dispatch.resolve_mode("pallas") == "pallas"
    assert dispatch.resolve_mode("densify") == "densify"
    assert dispatch.resolve_mode(None) in ("pallas", "densify")
    assert dispatch.resolve_mode("auto") == dispatch.default_mode()
    with pytest.raises(ValueError):
        dispatch.resolve_mode("nope")


def test_qmatmul_rejects_wrong_axis_leaf():
    """A non-square MXTensor quantized along the wrong axis (scales
    (K, N/bs) instead of (N, K/bs)) must fail loudly, not return garbage."""
    fmt = get_format("mxint8", 32)
    x = _rand((4, 64), seed=16)
    w = _rand((64, 96), seed=17)
    t_bad = quantize(w, fmt, axis=-1)       # blocks along N: wrong for serving
    with pytest.raises(ValueError, match="serving layout"):
        dispatch.qmatmul(x, t_bad, mode="pallas")
    with pytest.raises(ValueError, match="serving layout"):
        dispatch.qmatmul(x, t_bad, mode="densify")


@pytest.mark.parametrize("bs", [16, 64])
def test_qmatmul_nondefault_block_size(bs):
    """Block sizes ride on the leaves (MXTensor.fmt / PackedInt4Leaf shapes),
    never the registry default — parity must hold at 16 and 64."""
    k, n = 128, 96
    x = _rand((4, k), seed=18)
    w = _rand((k, n), seed=19)
    for name in ("mxint8", "mxint4"):
        fmt = get_format(name, bs)
        t = quantize(w, fmt, axis=0)
        leaf = pack_leaf_int4(t) if name == "mxint4" else t
        want = np.asarray(x @ dequantize(t, jnp.float32))
        for mode in ("pallas", "densify"):
            got = np.asarray(dispatch.qmatmul(x, leaf, mode=mode))
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4,
                                       err_msg=f"{name} bs={bs} {mode}")


def test_splitn_leaf_densify_roundtrip():
    """Split-N packing is lossless: densified leaf == dequantized tensor."""
    from repro.serve.packed_params import unpack_leaf_int4
    fmt = get_format("mxint4", 32)
    w = _rand((64, 130), seed=15)
    t = quantize(w, fmt, axis=0)
    leaf = pack_leaf_int4(t)
    np.testing.assert_array_equal(
        np.asarray(unpack_leaf_int4(leaf, 32, jnp.float32)),
        np.asarray(dequantize(t, jnp.float32)))
