"""The plain reference against the program, at a tiny size on the CPU.

The reference imports nothing of the program; these tests are where the
two meet: the same MX codes, the same anchor, the same forward pass in
float32.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib.anchor import make_anchor_on_device
from bench.lib.reference import Reference, rung_weight
from bench.lib.weights import base_key, global_weights, layer_shapes, \
    layer_weights

SWIGLU = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
              d_ff=128, vocab=512, act="swiglu", norm_eps=1e-5,
              rope_theta=1e6, qk_norm=True, qkv_bias=False, mlp_bias=False,
              tie_embeddings=False)
GELU = dict(SWIGLU, act="gelu", qk_norm=False, qkv_bias=True, mlp_bias=True,
            rope_theta=1e5, n_kv_heads=1, tie_embeddings=True,
            sliding_window=24)


def program_params(m, seed, dtype=jnp.float32):
    """The program's parameter tree of the seed's float32 weights."""
    key = base_key(seed)
    per = [layer_weights(key, m, i) for i in range(m["n_layers"])]
    block = {}
    for name in layer_shapes(m):
        node = block
        parts = name.split(".")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = jnp.stack([w[name] for w in per]).astype(dtype)
    g = global_weights(key, m)
    return dict(g, blocks=[block])


@pytest.mark.parametrize("bits", [8, 4])
def test_mx_codes_match_the_program(bits):
    from repro.core import get_format
    from repro.core.mx import dequantize, quantize
    from repro.core.slice_scale import slice_and_scale
    w = 0.02 * jax.random.normal(jax.random.PRNGKey(3), (256, 96))
    t = quantize(w, get_format("mxint8", 32), axis=0)
    if bits != 8:
        t = slice_and_scale(t, get_format(f"mxint{bits}", 32))
    np.testing.assert_array_equal(np.asarray(rung_weight(w, 8, bits, 32)),
                                  np.asarray(dequantize(t)))


def test_anchor_matches_make_anchor():
    from repro.core import get_format, make_anchor
    from repro.core.qat import QATConfig
    seed = 2 ** 33 + 5
    mine = make_anchor_on_device(GELU, seed)
    ref = make_anchor(program_params(GELU, seed), QATConfig(),
                      get_format("mxint8", 32))
    assert sorted(mine.quantized) == sorted(ref.quantized)
    assert sorted(mine.raw) == sorted(ref.raw)
    for k, t in ref.quantized.items():
        np.testing.assert_array_equal(mine.quantized[k].codes, t.codes)
        np.testing.assert_array_equal(mine.quantized[k].scale_exp,
                                      t.scale_exp)
        assert mine.quantized[k].block_axis == t.block_axis
    # the same draws; the jitted maker may fuse ``1 + 0.1 z`` differently
    # from the eager one, so a value may round to the next bf16 ulp
    for k, w in ref.raw.items():
        assert mine.raw[k].dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(mine.raw[k], np.float32),
                                   np.asarray(w, np.float32), rtol=2 ** -8,
                                   atol=1e-6)


@pytest.mark.parametrize("m", [SWIGLU, GELU], ids=["swiglu", "gelu"])
def test_forward_matches_the_program_in_float32(m):
    """Reference logits against the program's own prefill, both float32,
    on the anchor's weights: the layer equations agree."""
    from repro.core.anchor import materialize
    from repro.models import get_model
    from repro.models.common import ModelConfig
    seed = 11
    cfg = ModelConfig(name="t", family="dense", compute_dtype=jnp.float32,
                      **m)
    api = get_model(cfg, None)
    anchor = make_anchor_on_device(m, seed)
    params = materialize(anchor, jax.eval_shape(api.init_params,
                                                jax.random.PRNGKey(0)),
                         dtype=jnp.float32)
    toks = np.random.default_rng(0).integers(1, m["vocab"], 40) \
        .astype(np.int32)
    ref = Reference(m, seed, anchor_bits=8, bits=8)
    h = ref.hidden([toks])[0]
    lm_head, fnorm = ref.head_weights()
    want = np.asarray(ref._logits(h, lm_head, fnorm))
    for t in (1, 17, 40):
        cache = api.init_cache(1, 64, jnp.float32)
        got, _, _ = jax.jit(api.prefill)(params, {"tokens": toks[None, :t]},
                                         cache)
        np.testing.assert_allclose(np.asarray(got[0]), want[t - 1],
                                   rtol=2e-4, atol=2e-5)


def _old_global(key, m):
    """The global leaves as they were first drawn: all three at once,
    eagerly, in float32."""
    from bench.lib.weights import GAIN_STD, STD, _key
    d, v = m["d_model"], m["vocab"]
    out = {"embed": STD * jax.random.normal(_key(key, "embed"), (v, d)),
           "final_norm": 1.0 + GAIN_STD * jax.random.normal(
               _key(key, "final_norm"), (d,))}
    if not m["tie_embeddings"]:
        out["lm_head"] = STD * jax.random.normal(_key(key, "lm_head"), (d, v))
    return out


@pytest.mark.parametrize("m", [SWIGLU, GELU], ids=["untied", "tied"])
def test_global_leaves_one_at_a_time_keep_their_values(m, monkeypatch):
    """Drawn one leaf at a time, and rounded in the draw's own buffer, the
    global leaves hold the values they always held, so ``logit_gap``
    readings do not move; the head draws only the leaf it uses."""
    from bench.lib import reference
    from bench.lib.reference import bf16
    seed = 2 ** 31 + 77
    old = _old_global(base_key(seed), m)
    new = global_weights(base_key(seed), m)
    assert list(new) == list(old)
    for k in old:
        np.testing.assert_array_equal(np.asarray(new[k]), np.asarray(old[k]))
    ref = Reference(m, seed, anchor_bits=8, bits=8)
    drawn = []
    real = reference.global_normal
    monkeypatch.setattr(reference, "global_normal",
                        lambda key, mm, name: drawn.append(name)
                        or real(key, mm, name))
    head, fnorm = ref.head_weights()
    want = old["embed"].T if m["tie_embeddings"] else old["lm_head"]
    np.testing.assert_array_equal(np.asarray(head), np.asarray(bf16(want)))
    np.testing.assert_array_equal(np.asarray(fnorm),
                                  np.asarray(bf16(old["final_norm"])))
    assert drawn == ["embed" if m["tie_embeddings"] else "lm_head",
                     "final_norm"]
    np.testing.assert_array_equal(np.asarray(ref._global("embed")),
                                  np.asarray(bf16(old["embed"])))


def test_anchor_on_a_mesh_is_the_unsharded_anchor():
    """Made on a (1, 2) mesh, each leaf is split as the engine serves it
    and holds the unsharded anchor's values bit for bit."""
    from repro.launch.mesh import make_mesh
    from repro.models import get_model
    from repro.models.common import ModelConfig
    assert len(jax.devices()) >= 2      # the root conftest pins 2 on the CPU
    m = dict(SWIGLU, qkv_bias=True, d_model=128, head_dim=32, d_ff=256)
    seed = 2 ** 32 + 9
    one = make_anchor_on_device(m, seed)
    mesh = make_mesh((1, 2), ("data", "model"))
    api = get_model(ModelConfig(name="small", family="dense", **m), None)
    template = jax.eval_shape(api.init_params, jax.random.PRNGKey(0))
    two = make_anchor_on_device(m, seed, mesh=mesh, api=api,
                                template=template)
    assert sorted(two.quantized) == sorted(one.quantized)
    assert sorted(two.raw) == sorted(one.raw)
    split = {"wq": 2, "wk": 2, "wv": 2, "w_gate": 2, "w_up": 2,
             "wo": 1, "w_down": 1}          # the axis tensor parallelism cuts
    for k, t in one.quantized.items():
        u = two.quantized[k]
        np.testing.assert_array_equal(u.codes, t.codes)
        np.testing.assert_array_equal(u.scale_exp, t.scale_exp)
        assert u.block_axis == t.block_axis and u.fmt == t.fmt
        ax = split[k.split("'")[-2]]
        assert u.codes.sharding.spec[ax] == "model"
        for sh in u.codes.addressable_shards:
            assert sh.data.shape[ax] == t.codes.shape[ax] // 2
    for k, w in one.raw.items():
        np.testing.assert_array_equal(np.asarray(two.raw[k], np.float32),
                                      np.asarray(w, np.float32))
        assert two.raw[k].dtype == w.dtype
    # the embedding and the lm_head split along the vocabulary
    assert two.raw["['embed']"].sharding.spec[0] == "model"
    assert two.raw["['lm_head']"].sharding.spec[1] == "model"
