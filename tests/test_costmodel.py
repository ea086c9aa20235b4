"""Validate the analytic roofline cost model against compiled HLO counts.

Strategy: build small *unrolled* configs (python-loop layers, no remat, no
inner scans: seq_chunk >= seq), compile train/prefill/decode on 1 device, and
compare ``cost_analysis()['flops']`` with the analytic prediction. The
analytic model must land within a modest band — it feeds §Roofline.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.shapes import ShapeSpec
from repro.launch import costmodel as cm
from repro.models import get_model
from repro.models.common import ModelConfig


def _tiny_dense():
    return ModelConfig(
        name="tiny", family="dense", n_layers=2, d_model=128, n_heads=4,
        n_kv_heads=2, d_ff=256, vocab=512, compute_dtype=jnp.float32,
        seq_chunk=4096, remat=False, unroll=True, flash_vjp=False)


def _compiled_flops(fn, *args):
    c = jax.jit(fn).lower(*args).compile()
    return float((c.cost_analysis() or {}).get("flops", 0.0))


def test_prefill_flops_close():
    cfg = _tiny_dense()
    api = get_model(cfg, None)
    shape = ShapeSpec("t", seq_len=128, global_batch=2, kind="prefill")
    params = jax.eval_shape(api.init_params, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: api.init_cache(2, 128))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 128), jnp.int32)}
    got = _compiled_flops(api.prefill, params, batch, cache)
    want = cm.flops_prefill(cfg, shape)["total"]
    assert 0.6 < got / want < 1.7, (got, want)


def test_train_flops_close():
    cfg = _tiny_dense()
    api = get_model(cfg, None)
    shape = ShapeSpec("t", seq_len=128, global_batch=2, kind="train")
    params = jax.eval_shape(api.init_params, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 128), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, 128), jnp.int32)}

    def loss_and_grad(p, b):
        return jax.value_and_grad(
            lambda pp: api.train_loss(pp, b, None)[0])(p)

    got = _compiled_flops(loss_and_grad, params, batch)
    # analytic model includes remat (x8); this config has remat off (x6)
    want = cm.flops_train(cfg, shape)["total"] * 6.0 / 8.0
    assert 0.5 < got / want < 1.8, (got, want)


def test_decode_flops_close():
    cfg = _tiny_dense()
    api = get_model(cfg, None)
    shape = ShapeSpec("t", seq_len=256, global_batch=4, kind="decode")
    params = jax.eval_shape(api.init_params, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: api.init_cache(4, 256))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 1), jnp.int32)}
    lens = jax.ShapeDtypeStruct((4,), jnp.int32)
    got = _compiled_flops(api.serve_step, params, batch, cache, lens)
    want = cm.flops_decode(cfg, shape)["total"]
    assert 0.4 < got / want < 2.0, (got, want)


def test_param_counts_match_init():
    """Analytic total_params == actual init param count (matmuls+embeds)."""
    from repro.configs import get_config
    for arch in ["qwen3-4b", "smollm-135m", "mixtral-8x7b"]:
        cfg = get_config(arch)
        api = get_model(cfg, None)
        shapes = jax.eval_shape(api.init_params, jax.random.PRNGKey(0))
        actual = sum(int(np.prod(s.shape))
                     for s in jax.tree_util.tree_leaves(shapes))
        pred = cm.total_params(cfg)
        # analytic skips norms/biases/small vectors — within 2%
        assert 0.98 < pred / actual < 1.02, (arch, pred, actual)


def test_known_param_magnitudes():
    """Sanity: headline param counts are in the right ballpark."""
    from repro.configs import get_config
    assert 6.5e9 < cm.total_params(get_config("llava-next-mistral-7b")) < 8e9
    assert 65e9 < cm.total_params(get_config("qwen2-72b")) < 80e9
    assert 1.2e11 < cm.total_params(get_config("mixtral-8x22b")) < 1.5e11
    assert 3.3e11 < cm.total_params(get_config("jamba-1.5-large-398b")) < 4.6e11
    assert 1.1e8 < cm.total_params(get_config("smollm-135m")) < 1.7e8
    assert 6e9 < cm.total_params(get_config("rwkv6-7b")) < 9e9


def test_roofline_terms_reasonable():
    from repro.configs import get_config
    from repro.configs.shapes import SHAPES
    mesh = cm.MeshDesc(pod=1, data=16, model=16)
    r = cm.roofline(get_config("qwen2-72b"), SHAPES["train_4k"], mesh)
    assert r["t_compute"] > 0 and r["t_memory"] > 0 and r["t_collective"] > 0
    assert r["dominant"] in ("compute", "memory", "collective")
    assert 0 < r["useful_ratio"] <= 1.2
    # decode must be memory-bound at bf16 weights
    r2 = cm.roofline(get_config("qwen2-72b"), SHAPES["decode_32k"], mesh)
    assert r2["dominant"] in ("memory", "collective")
    # int4 weights strictly shrink decode memory; on a weights-dominated
    # cell (SWA-bounded cache, batch 1) the cut approaches 8x
    r4 = cm.roofline(get_config("qwen2-72b"), SHAPES["decode_32k"], mesh,
                     weight_bits_decode=4)
    assert r4["t_memory"] < r2["t_memory"]
    m16 = cm.roofline(get_config("mixtral-8x7b"), SHAPES["long_500k"], mesh,
                      weight_bits_decode=16)
    m4 = cm.roofline(get_config("mixtral-8x7b"), SHAPES["long_500k"], mesh,
                     weight_bits_decode=4)
    assert m4["t_memory"] < m16["t_memory"] * 0.5   # rest is the KV band


# ---- serving roofline terms vs a real engine (the cost-model seed) ---------
# serve_* terms feed serve.slo.CostModel.from_roofline; the contract is that
# they agree with what the packed-weight engine MEASURES: weight_stream_bytes
# over each cached serving tree, and the attn_read_bytes counter a decode
# wave accumulates, per format x {dense, paged}.

def _serve_engine(**kw):
    from repro.configs import get_reduced
    from repro.core import make_anchor
    from repro.core.qat import QATConfig
    from repro.models import get_model as _gm
    from repro.serve.engine import ElasticEngine
    cfg = get_reduced("smollm-135m")
    api = _gm(cfg, None)
    params = api.init_params(jax.random.PRNGKey(0))
    anchor = make_anchor(params, QATConfig(
        formats=("mxint4", "mxint8"), anchor="mxint8", block_size=32))
    eng = ElasticEngine(api, anchor, batch_slots=2, max_len=48,
                        param_template=params, **kw)
    return cfg, eng


def test_serve_weight_stream_bytes_matches_packed_trees():
    """Analytic per-tick weight stream vs the real packed containers, per
    format (bf16 = the dense pseudo-format). No generate needed — the
    bytes are a property of the cached tree. Norm vectors are the only
    thing the analytic term drops, so the band is tight."""
    cfg, eng = _serve_engine()
    for fmt in ("mxint4", "mxint8", "bf16"):
        eng.weights_for(fmt)
    measured = eng.stats["weight_bytes"]
    for fmt in ("mxint4", "mxint8", "bf16"):
        analytic = cm.serve_weight_stream_bytes(cfg, fmt, block_size=32)
        assert analytic == pytest.approx(measured[fmt], rel=0.02), \
            (fmt, analytic, measured[fmt])
    assert measured["mxint4"] < measured["mxint8"] < measured["bf16"]


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_serve_attn_bytes_match_engine_counters(layout):
    """Analytic attention bytes/row/tick vs the engine's own accounting
    over a real decode wave, under the gather read path (span == the
    whole logical view for every batch row): the counter must equal
    decode_ticks * slots * span exactly, and the byte multiplier must be
    the same K+V-at-compute-dtype constant on both sides."""
    import numpy as np
    from repro.serve.engine import Request
    kw = {"kv_layout": layout}
    if layout == "paged":
        kw.update(kv_page_size=8, attn_impl="gather")
    cfg, eng = _serve_engine(**kw)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab, 8).astype(np.int32),
                    max_new=3) for i in range(3)]
    eng.generate(reqs, fmt_override="mxint8")
    decode_ticks = sum(t["decode"] for t in eng.tick_trace)
    assert decode_ticks > 0
    span = cm.serve_attn_read_span(cfg, 48, layout, kv_page_size=8)
    st = eng.stats
    assert st["attn_tokens_read"] == decode_ticks * eng.slots * span
    assert st["attn_read_bytes"] == pytest.approx(
        st["attn_tokens_read"] * cm.serve_attn_bytes_per_row(cfg, 1))


def test_serve_roofline_terms_scale_with_mesh():
    """Satellite regression: the serving roofline is PER CHIP. A tensor-
    parallel engine streams 1/n_model of the weight bytes and 1/n_model of
    every KV token's bytes per chip, so seeding the cost model from the
    unsharded terms would predict tick times n_model x too slow. Both
    terms must divide exactly by the mesh's 'model' axis size."""
    from repro.configs import get_reduced
    cfg = get_reduced("smollm-135m")
    fmts = ("mxint4", "mxint8", "bf16")
    base = cm.serve_roofline_terms(cfg, fmts, max_len=48)
    tp2 = cm.serve_roofline_terms(cfg, fmts, max_len=48, n_model=2)
    for f in fmts:
        assert tp2[f]["weight_bytes"] == \
            pytest.approx(base[f]["weight_bytes"] / 2)
        assert tp2[f]["attn_bytes_per_row"] == \
            pytest.approx(base[f]["attn_bytes_per_row"] / 2)
    with pytest.raises(ValueError):
        cm.serve_roofline_terms(cfg, fmts, max_len=48, n_model=0)


def test_costmodel_from_roofline_per_chip_seed():
    """CostModel.from_roofline(n_model=2) must seed per-chip byte terms —
    halved predictions at the same per-chip HBM bandwidth."""
    from repro.configs import get_reduced
    from repro.serve.slo import CostModel
    cfg = get_reduced("smollm-135m")
    c1 = CostModel.from_roofline(cfg, ("mxint8",), max_len=48,
                                 hbm_bytes_per_s=1e9)
    c2 = CostModel.from_roofline(cfg, ("mxint8",), max_len=48, n_model=2,
                                 hbm_bytes_per_s=1e9)
    p1 = c1.raw_predict_s("mxint8", rows=2)
    p2 = c2.raw_predict_s("mxint8", rows=2)
    assert p1 is not None and p2 is not None
    assert p2 == pytest.approx(p1 / 2)


def test_meshed_engine_seeds_per_chip_bytes():
    """A meshed engine's cost-model seed and stats must report the per-chip
    weight stream (~1/2 the global bytes at tp=2; replicated norm vectors
    keep it from being exact)."""
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 host devices (root conftest provides them)")
    from repro.launch.mesh import make_debug_mesh
    from repro.serve.policy import FormatPolicy
    from repro.serve.slo import CostModel
    cfg, eng1 = _serve_engine()
    _, eng2 = _serve_engine(
        mesh=make_debug_mesh(1, 2),
        policy=FormatPolicy("mxint8", cost=CostModel(hbm_bytes_per_s=1e9)))
    for fmt in ("mxint8", "bf16"):
        eng1.weights_for(fmt)
        eng2.weights_for(fmt)
        g = eng1.stats["weight_bytes"][fmt]
        local = eng2.stats["weight_bytes_per_chip"][fmt]
        assert 0.5 <= local / g < 0.56, (fmt, local, g)
        # the cost model was seeded with the per-chip number
        cost = eng2.policy.cost
        assert cost.terms[fmt].base_s == pytest.approx(
            local / cost.hbm_bytes_per_s)
