"""Tensor-parallel elastic serving: a (1, 2) CPU mesh must be BIT-IDENTICAL
to the single-device engine.

The root conftest pins 2 CPU host devices (``XLA_FLAGS``) before jax loads,
so every test here runs on a real two-device platform. The house invariant
extends over the mesh axis: for any serving configuration, the token
streams of ``ElasticEngine(mesh=(1,2))`` equal the single-device engine's
exactly — greedy and seeded sampling both — because the sharded math is
arithmetically identical (per-kv-head attention is exactly parallel; the
only reductions that reorder are the two psums per layer, whose operands
are the same partial sums the single-device dot products produce).

Fast tier: {densify} x {dense, paged} x {mxint8, mxint4} x {greedy,
seeded}. The @slow matrix adds fused Pallas (interpret), the gather-free
paged kernel, the mixed scheduler, speculative decoding, and bf16.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import make_anchor
from repro.core.qat import QATConfig
from repro.launch.mesh import make_debug_mesh
from repro.models import get_model
from repro.serve.engine import ElasticEngine, Request

QAT = QATConfig(formats=("mxint4", "mxint8"), anchor="mxint8", block_size=32)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs 2 host devices (root conftest pins them)")


@pytest.fixture(scope="module")
def setup():
    cfg = get_reduced("smollm-135m")
    api = get_model(cfg, None)
    params = api.init_params(jax.random.PRNGKey(0))
    anchor = make_anchor(params, QAT)
    return cfg, api, params, anchor


def _reqs(cfg, n=3, plen=8, max_new=6):
    rng = np.random.default_rng(0)
    return [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab, plen).astype(np.int32),
                    max_new=max_new) for i in range(n)]


def _streams(setup, mesh, fmt, greedy, **kw):
    cfg, api, params, anchor = setup
    eng = ElasticEngine(api, anchor, batch_slots=2, max_len=48,
                        param_template=params, seed=0, mesh=mesh,
                        temperature=0.9, top_p=0.95, **kw)
    out = eng.generate(_reqs(cfg), greedy=greedy, fmt_override=fmt)
    return [r.out_tokens for r in out], eng


def _assert_identical(setup, fmt, greedy, **kw):
    single, _ = _streams(setup, None, fmt, greedy, **kw)
    meshed, eng = _streams(setup, make_debug_mesh(1, 2), fmt, greedy, **kw)
    assert single == meshed, (fmt, greedy, kw, single, meshed)
    assert all(len(t) > 0 for t in single)
    return eng


# ---- fast tier: densify contract, both layouts, both sampling modes -------
@pytest.mark.parametrize("fmt", ["mxint8", "mxint4"])
@pytest.mark.parametrize("greedy", [True, False])
def test_mesh_bit_identity_dense(setup, fmt, greedy):
    _assert_identical(setup, fmt, greedy, fused=False)


@pytest.mark.parametrize("greedy", [True, False])
def test_mesh_bit_identity_paged(setup, greedy):
    eng = _assert_identical(setup, "mxint8", greedy, fused=False,
                            kv_layout="paged", kv_page_size=8)
    # sharded pools change nothing about the host-side page bookkeeping:
    # every page allocated over the wave came back
    st = eng.stats
    assert st["kv_pages_alloc"] > 0
    assert st["kv_pages_alloc"] == st["kv_pages_freed"]
    assert st["mesh"] == "1x2"


# ---- slow tier: the full contract matrix ----------------------------------
@pytest.mark.slow
@pytest.mark.parametrize("fmt", ["mxint8", "mxint4", "bf16"])
@pytest.mark.parametrize("greedy", [True, False])
@pytest.mark.parametrize("kw", [
    dict(fused=False, prefill_chunk=8, scheduler="mixed"),
    dict(fused=False, kv_layout="paged", kv_page_size=8, prefill_chunk=8,
         scheduler="mixed"),
], ids=["mixed-dense", "mixed-paged"])
def test_mesh_bit_identity_mixed(setup, fmt, greedy, kw):
    _assert_identical(setup, fmt, greedy, **kw)


@pytest.mark.slow
def test_mesh_bit_identity_fused(setup):
    """Fused Pallas dequant-GEMM (interpret mode on CPU) inside shard_map:
    the kernels see shard-local shapes (the tile-cache satellite) and the
    streams still match the single-device fused engine."""
    _assert_identical(setup, "mxint8", True, fused=True)


@pytest.mark.slow
def test_mesh_bit_identity_paged_kernel(setup):
    _assert_identical(setup, "mxint8", True, fused=False,
                      kv_layout="paged", kv_page_size=8,
                      attn_impl="paged_kernel")


@pytest.mark.slow
def test_mesh_bit_identity_speculative(setup):
    from repro.serve.policy import SpecConfig
    _assert_identical(setup, "mxint8", True, fused=False,
                      speculative=SpecConfig(draft_fmt="mxint4", k=2))


# ---- split-N repack (the nibble-interleave bug) ----------------------------
def test_repack_splitn_local_shards_decode_contiguous_columns(setup):
    """Split-N byte column j packs output columns (j, j + N/2) — a global
    interleave. Without the per-shard repack, a column-sharded mxint4 leaf
    decodes to a PERMUTED column set on each chip while wo/w_down shard
    their contraction rows contiguously, silently mispairing half the
    head / ff-block contributions (logits were off by ~0.2, not ulps).
    Every local shard must densify to exactly its contiguous submatrix."""
    from repro.serve.packed_params import PackedInt4Leaf, densify_leaf
    cfg, api, params, anchor = setup
    eng = ElasticEngine(api, anchor, batch_slots=2, max_len=48,
                        param_template=params, fused=False,
                        mesh=make_debug_mesh(1, 2))
    ref = ElasticEngine(api, anchor, batch_slots=2, max_len=48,
                        param_template=params, fused=False)
    w = eng.weights_for("mxint4")
    wr = ref.weights_for("mxint4")
    for name, axis in (("wq", 1), ("wo", 0)):   # column- and row-parallel
        leaf, rleaf = (t["blocks"][0]["attn"][name] for t in (w, wr))
        want = np.asarray(densify_leaf(rleaf, 32, jnp.float32,
                                       serving_axis=True))[0]
        got = np.concatenate(
            [np.asarray(densify_leaf(
                PackedInt4Leaf(
                    packed=jnp.asarray(ps.data)[0],
                    scale_exp=jnp.asarray(
                        leaf.scale_exp.addressable_shards[s].data)[0],
                    shape=leaf.shape, block_axis=leaf.block_axis,
                    fmt_name=leaf.fmt_name, layout=leaf.layout),
                32, jnp.float32, serving_axis=True))
             for s, ps in enumerate(leaf.packed.addressable_shards)],
            axis=axis)
        assert np.array_equal(want, got), name


# ---- per-chip accounting ---------------------------------------------------
def test_mesh_weight_bytes_per_chip_halved(setup):
    """Each chip streams ~1/2 of the packed tree at tp=2 (replicated norm
    vectors keep it just above exactly half)."""
    _, eng = _streams(setup, make_debug_mesh(1, 2), "mxint8", True,
                      fused=False)
    st = eng.stats
    ratio = st["weight_bytes_per_chip"]["mxint8"] / \
        st["weight_bytes"]["mxint8"]
    assert 0.5 <= ratio < 0.56, ratio


# ---- snapshot/resume mesh fingerprint --------------------------------------
def test_snapshot_on_mesh_refuses_single_device_resume(setup, tmp_path):
    """A snapshot taken on a mesh holds sharded-layout state; resuming on a
    single-device engine must fail loudly, naming the mesh field."""
    cfg, api, params, anchor = setup
    from repro.runtime.fault import FaultInjector, PreemptionGuard
    meshed = ElasticEngine(api, anchor, batch_slots=2, max_len=48,
                           param_template=params, seed=0, fused=False,
                           mesh=make_debug_mesh(1, 2),
                           fault_injector=FaultInjector(preempt_at=2))
    meshed.generate(_reqs(cfg, max_new=8), greedy=True,
                    fmt_override="mxint8", guard=PreemptionGuard(),
                    snapshot_dir=str(tmp_path))
    assert meshed.last_snapshot is not None
    single = ElasticEngine(api, anchor, batch_slots=2, max_len=48,
                           param_template=params, seed=0, fused=False)
    with pytest.raises(ValueError, match="mesh"):
        single.resume(str(tmp_path))


def test_snapshot_resume_on_same_mesh(setup, tmp_path):
    """Same mesh shape on both sides: the resumed wave finishes with the
    exact streams of the uninterrupted meshed run."""
    cfg, api, params, anchor = setup
    from repro.runtime.fault import FaultInjector, PreemptionGuard

    def eng(**kw):
        return ElasticEngine(api, anchor, batch_slots=2, max_len=48,
                             param_template=params, seed=0, fused=False,
                             mesh=make_debug_mesh(1, 2), **kw)
    full = eng().generate(_reqs(cfg, max_new=8), greedy=True,
                          fmt_override="mxint8")
    want = [r.out_tokens for r in full]
    e1 = eng(fault_injector=FaultInjector(preempt_at=2))
    e1.generate(_reqs(cfg, max_new=8), greedy=True, fmt_override="mxint8",
                guard=PreemptionGuard(), snapshot_dir=str(tmp_path))
    assert e1.last_snapshot is not None
    out = eng().resume(str(tmp_path))
    assert [r.out_tokens for r in out] == want


# ---- construction guards ---------------------------------------------------
def test_mesh_guard_messages(setup):
    cfg, api, params, anchor = setup
    import dataclasses as dc
    from jax.sharding import Mesh

    def build(mesh, api=api):
        return ElasticEngine(api, anchor, batch_slots=2, max_len=48,
                             param_template=params, mesh=mesh)

    with pytest.raises(ValueError, match="'model'"):
        build(Mesh(np.array(jax.devices()[:2]).reshape(1, 2),
                   ("data", "x")))
    with pytest.raises(ValueError, match="replicas"):
        # data axis > 1: DP belongs to ReplicaSet, not the engine
        build(Mesh(np.array(jax.devices()[:2]).reshape(2, 1),
                   ("data", "model")))
    # indivisible dims must be rejected up front, not at trace time
    bad_api = get_model(dc.replace(cfg, vocab=cfg.vocab - 1), None)
    with pytest.raises(ValueError, match="divisible"):
        ElasticEngine(bad_api, anchor, batch_slots=2, max_len=48,
                      mesh=make_debug_mesh(1, 2))


# ---- data-parallel replicas -------------------------------------------------
def test_replica_set_partitions_and_matches(setup):
    """Two single-device replicas: every request's stream equals the one a
    lone engine produces for it (the partition decides WHERE, never WHAT)."""
    from repro.serve.replicas import ReplicaSet
    cfg, api, params, anchor = setup
    kw = dict(batch_slots=2, max_len=48, param_template=params, seed=0,
              fused=False)
    lone = ElasticEngine(api, anchor, **kw)
    want = {r.rid: r.out_tokens
            for r in lone.generate(_reqs(cfg, n=4), greedy=True,
                                   fmt_override="mxint8")}
    rs = ReplicaSet(api, anchor, n_replicas=2, **kw)
    got = rs.generate(_reqs(cfg, n=4), greedy=True, fmt_override="mxint8")
    assert {r.rid: r.out_tokens for r in got} == want
    assert rs.stats["tokens_out"] == lone.stats["tokens_out"]
    assert [rs.home(r.rid) for r in got] == [0, 1, 0, 1]


def test_replica_meshes_disjoint():
    from repro.serve.replicas import replica_meshes
    meshes = replica_meshes(2, 1)
    devs = [d for m in meshes for d in m.devices.flat]
    assert len(set(devs)) == 2
    with pytest.raises(ValueError, match="device"):
        replica_meshes(2, 2)   # 4 needed, 2 present


def test_prompt_logits_mesh_matches_single_device(setup):
    """The logits-level twin of the stream identity tests: the meshed
    engine's mixed step (psums inside shard_map) against one device."""
    cfg, api, params, anchor = setup
    kw = dict(batch_slots=2, max_len=48, param_template=params, fused=False,
              kv_layout="paged", kv_page_size=8, prefill_chunk=8,
              scheduler="mixed")
    prompt = _reqs(cfg, n=1, plen=19)[0].prompt
    single = ElasticEngine(api, anchor, **kw).prompt_logits(prompt, "mxint8")
    meshed = ElasticEngine(api, anchor, mesh=make_debug_mesh(1, 2),
                           **kw).prompt_logits(prompt, "mxint8")
    np.testing.assert_allclose(meshed, single, rtol=1e-5, atol=1e-5)
