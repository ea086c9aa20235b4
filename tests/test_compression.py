"""MX gradient compression with error feedback: unbiasedness over steps,
bytes accounting, and shard_map wiring on a 1-device pod mesh."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.train.compression import (compressed_bytes, ef_compress_leaf,
                                     compressed_pod_allreduce,
                                     init_error_state)
from jax import shard_map
from repro.core.formats import get_format
from repro.core.mx import dequantize
from repro.launch.mesh import make_mesh


def test_ef_compress_roundtrip_error_bounded():
    fmt = get_format("mxint8", 32)
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(67, 33)), jnp.float32)  # awkward shape
    err = jnp.zeros_like(g)
    t, new_err = ef_compress_leaf(g, err, fmt)
    flat = dequantize(t).reshape(-1)[:g.size].reshape(g.shape)
    # int8 blocks: relative error small
    assert float(jnp.max(jnp.abs(flat - g))) < 0.02 * float(jnp.max(jnp.abs(g)))
    np.testing.assert_allclose(np.asarray(new_err), np.asarray(g - flat),
                               atol=1e-7)


def test_error_feedback_removes_bias():
    """Accumulated EF-compressed updates converge to accumulated true grads."""
    fmt = get_format("mxint4", 32)   # coarse: bias obvious without EF
    rng = np.random.default_rng(1)
    g_const = jnp.asarray(rng.normal(size=(128,)), jnp.float32) * 0.01

    err = jnp.zeros_like(g_const)
    acc_ef = jnp.zeros_like(g_const)
    acc_noef = jnp.zeros_like(g_const)
    for _ in range(50):
        t, err = ef_compress_leaf(g_const, err, fmt)
        acc_ef = acc_ef + dequantize(t).reshape(-1)[:128]
        t2, _ = ef_compress_leaf(g_const, jnp.zeros_like(err), fmt)
        acc_noef = acc_noef + dequantize(t2).reshape(-1)[:128]
    true = g_const * 50
    err_ef = float(jnp.linalg.norm(acc_ef - true) / jnp.linalg.norm(true))
    err_noef = float(jnp.linalg.norm(acc_noef - true) / jnp.linalg.norm(true))
    assert err_ef < 0.05
    assert err_ef < err_noef * 0.5 or err_noef < 1e-6


def test_compressed_bytes_accounting():
    params = {"a": jnp.zeros((1000, 100)), "b": jnp.zeros((999,))}
    b8 = compressed_bytes(params, "mxint8")
    f32 = (1000 * 100 + 999) * 4
    assert b8 < f32 * 0.27   # ~4x compression minus scale overhead


def test_pod_allreduce_shard_map_single_device():
    """Wire through shard_map on a pod-axis mesh of size 1 (CPU container);
    numerics = identity reduce + error feedback."""
    mesh = make_mesh((1,), ("pod",))
    grads = {"w": jnp.asarray(np.random.default_rng(2).normal(size=(64, 32)),
                              jnp.float32)}
    err = init_error_state(grads)

    from jax.sharding import PartitionSpec as P

    fn = shard_map(
        functools.partial(compressed_pod_allreduce, fmt_name="mxint8"),
        mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        check_vma=False)
    red, new_err = jax.jit(fn)(grads, err)
    # npod=1: reduced grad == dequant(quant(g)) and err == residual
    assert red["w"].shape == grads["w"].shape
    np.testing.assert_allclose(np.asarray(red["w"]), np.asarray(grads["w"]),
                               atol=0.05)
    np.testing.assert_allclose(
        np.asarray(grads["w"] - red["w"]), np.asarray(new_err["w"]),
        atol=1e-6)
