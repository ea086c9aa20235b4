"""Compile the serving kernels for a described TPU v5e, without a chip.

Interpret-mode tests run the kernel bodies as written; they cannot see what
Mosaic (the TPU kernel compiler) refuses — block shapes off the (8, 128)
tiling, in-kernel reshapes of tiled values, VMEM overuse. These tests lower
and compile each main-path kernel for one chip of a described ``v5e:2x2``
topology at published widths, so such a refusal fails here and not on the
chip.

The topology is described inside a module-scope fixture, never while a
module is imported: only one process at a time may load the TPU library,
and each test worker imports every test file. Keep these tests in this one
file so that only the worker given this file loads it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.formats import get_format
from repro.core.mx import quantize
from repro.kernels import dispatch
from repro.kernels import paged_attention as pa
from repro.serve.packed_params import pack_leaf_int4

# (K, N) projections: smollm-135m (d_model 576, kv width 3*64, d_ff 1536),
# qwen3-4b (d_model 2560, q width 32*128, d_ff 9728), and one chip's shard
# of qwen2-72b on a (1, 4) mesh (d_model 8192, 16 q and 2 kv heads of 128,
# its d_ff share 29568 / 4 = 7392 as laid out at pack time, 7424).
GEMMS = {
    "smollm-qo": (576, 576), "smollm-kv": (576, 192),
    "smollm-up": (576, 1536), "smollm-down": (1536, 576),
    "qwen3-q": (2560, 4096), "qwen3-up": (2560, 9728),
    "qwen3-down": (9728, 2560),
    "qwen2tp4-q": (8192, 2048), "qwen2tp4-kv": (8192, 256),
    "qwen2tp4-o": (2048, 8192), "qwen2tp4-up": (8192, 7424),
    "qwen2tp4-down": (7424, 8192),
}
DECODE_M = 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Steer the dispatch wrappers to the Mosaic lowering: off a TPU backend
    they pick the Pallas interpreter, which would compile no kernel."""
    monkeypatch.setattr(dispatch, "_interpret", lambda: False)


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("gemm", sorted(GEMMS))
@pytest.mark.parametrize("fmt_name", ["mxint8", "mxfp8", "mxint6", "mxint4"])
def test_dequant_gemm_compiles_for_v5e(one_chip, mosaic, gemm, fmt_name):
    """The fused dequant-GEMM at the tiles select_tiles picks (split-N
    packed kernel for mxint4), padding included."""
    k, n = GEMMS[gemm]
    fmt = get_format(fmt_name, 32)
    w = jax.ShapeDtypeStruct((k, n), jnp.float32)
    leaf = jax.eval_shape(lambda a: quantize(a, fmt, axis=0), w)
    x = _sds(one_chip, (DECODE_M, k), jnp.bfloat16)
    if fmt_name == "mxint4":
        leaf = jax.eval_shape(
            lambda a: pack_leaf_int4(quantize(a, fmt, axis=0)), w)
        assert leaf.layout == "splitn"
        _compile(lambda x, p, s: dispatch.qmatmul_int4(x, p, s.T, fmt), x,
                 _sds(one_chip, leaf.packed.shape, leaf.packed.dtype),
                 _sds(one_chip, leaf.scale_exp.shape, leaf.scale_exp.dtype))
    else:
        _compile(lambda x, c, s: dispatch.qmatmul_mx(x, c, s.T, fmt), x,
                 _sds(one_chip, leaf.codes.shape, leaf.codes.dtype),
                 _sds(one_chip, leaf.scale_exp.shape, leaf.scale_exp.dtype))


@pytest.mark.parametrize("m", [DECODE_M, 1024])
@pytest.mark.parametrize("fmt_name", ["mxint8", "mxint6"])
def test_pinned_layout_gemm_compiles_for_v5e(one_chip, mosaic, monkeypatch,
                                             m, fmt_name):
    """qwen2-72b's w_gate share at tp 4 as laid out (8192 x 7424), read at
    the tn it was padded for (256, ``dispatch.pin_layout``), at a decode
    and at a mixed tick's M."""
    monkeypatch.setattr(dispatch, "_LAID_OUT", {})
    fmt = get_format(fmt_name, 32)
    assert dispatch.pin_layout(8192, 7392, 8192, 7424, fmt) == (256, 512)
    w = jax.ShapeDtypeStruct((8192, 7424), jnp.float32)
    leaf = jax.eval_shape(lambda a: quantize(a, fmt, axis=0), w)
    hlo = _compile(lambda x, c, s: dispatch.qmatmul_mx(x, c, s.T, fmt),
                   _sds(one_chip, (m, 8192), jnp.bfloat16),
                   _sds(one_chip, leaf.codes.shape, leaf.codes.dtype),
                   _sds(one_chip, leaf.scale_exp.shape,
                        leaf.scale_exp.dtype)).as_text()
    assert "s8[8192,7424]" in hlo and "pad(" not in hlo


# (H, Hkv, D): smollm-135m, qwen3-4b, and the per-chip shares of qwen3-4b
# and of qwen2-72b (a GQA group of 8) on a (1, 4) tensor-parallel mesh.
HEADS = {"smollm": (9, 3, 64), "qwen3": (32, 8, 128), "qwen3-tp4": (8, 2, 128),
         "qwen2-tp4": (16, 2, 128)}
B, PAGES, PS, MAX_PAGES, CHUNK = 8, 64, 16, 33, 16


@pytest.mark.parametrize("window", [None, 64])
@pytest.mark.parametrize("heads", sorted(HEADS))
def test_paged_decode_kernel_compiles_for_v5e(one_chip, heads, window):
    h, hkv, d = HEADS[heads]
    pool = _sds(one_chip, (PAGES, PS, hkv * d), jnp.bfloat16)
    _compile(lambda q, k, v, bt, cl: pa.paged_attention_pallas(
                 q, k, v, bt, cl, window=window),
             _sds(one_chip, (B, h, d), jnp.bfloat16), pool, pool,
             _sds(one_chip, (B, MAX_PAGES), jnp.int32),
             _sds(one_chip, (B,), jnp.int32))


@pytest.mark.parametrize("window", [None, 64])
@pytest.mark.parametrize("heads", sorted(HEADS))
def test_paged_multi_query_kernel_compiles_for_v5e(one_chip, heads, window):
    h, hkv, d = HEADS[heads]
    pool = _sds(one_chip, (PAGES, PS, hkv * d), jnp.bfloat16)
    i32 = lambda *s: _sds(one_chip, s, jnp.int32)
    _compile(lambda q, k, v, bt, qo, ql: pa.paged_attention_pallas_mq(
                 q, k, v, bt, qo, ql, window=window),
             _sds(one_chip, (B, CHUNK, h, d), jnp.bfloat16), pool, pool,
             i32(B, MAX_PAGES), i32(B), i32(B))


@pytest.mark.parametrize("window", [None, 64])
@pytest.mark.parametrize("heads", ["smollm", "qwen3", "qwen2-tp4"])
def test_paged_multi_query_narrow_fold_compiles_for_v5e(one_chip, heads,
                                                         window):
    """At the serving chunk of 64 lanes both branches of the narrow fold
    lower: NARROW_LANES * G query rows (24 at smollm-135m's G 3, 32 at
    qwen3-4b's G 4, 64 at qwen2-72b's G 8) and the whole block of 64 * G."""
    h, hkv, d = HEADS[heads]
    c = 64
    assert c > pa.NARROW_LANES
    pool = _sds(one_chip, (PAGES, PS, hkv * d), jnp.bfloat16)
    i32 = lambda *s: _sds(one_chip, s, jnp.int32)
    _compile(lambda q, k, v, bt, qo, ql: pa.paged_attention_pallas_mq(
                 q, k, v, bt, qo, ql, window=window),
             _sds(one_chip, (B, c, h, d), jnp.bfloat16), pool, pool,
             i32(B, MAX_PAGES), i32(B), i32(B))
