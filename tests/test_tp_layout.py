"""Tensor-parallel shards laid out tile-aligned once, at pack time.

A Qwen2-style model (q/k/v biases, untied head, no q/k norm, a GQA group of
8) on the ``(1, 2)`` CPU mesh of the root conftest, at a d_ff whose
per-chip share (2112 / 2 = 1056) is above the kernels' whole-tile caps and
not a multiple of 128: before the pack-time layout, every step padded
w_gate's and w_up's N and w_down's K (codes and scales) on every call. Now
``ElasticEngine.weights_for`` pads each chip's share once, to 1280, and
the padded columns run through silu(0) * 0 into w_down's zero rows.

The engine's logits are compared with the benchmark's plain float32
reference (``bench/lib/reference.py``), which imports nothing of the
program, on the seed's random weights.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib.anchor import make_anchor_on_device
from bench.lib.reference import Reference
from repro.core import get_format
from repro.kernels import dispatch
from repro.launch.mesh import make_debug_mesh
from repro.models import get_model
from repro.models.common import ModelConfig
from repro.serve import engine as engine_mod
from repro.serve.engine import ElasticEngine, Request
from repro.serve.packed_params import (layout_tp_shards, make_packed_params,
                                       packed_param_shardings, tp_ff_width)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs 2 host devices (root conftest pins them)")

M = dict(n_layers=2, d_model=256, n_heads=16, n_kv_heads=2, head_dim=16,
         d_ff=2112, vocab=512, act="swiglu", norm_eps=1e-6, rope_theta=1e6,
         qk_norm=False, qkv_bias=True, mlp_bias=False, tie_embeddings=False)
SEED = 2 ** 31 + 11
SHARE, WIDTH = 1056, 1280      # each chip's d_ff share, and as laid out
MLP = "['blocks'][0]['mlp']"

# Float32 compute on both sides, from the same MX codes: the program and
# the reference differ only in float32 summation order (the psum of two
# partial products, the kernel's K tiles), which moves logits of size ~1
# by ~1e-6 (readings: 7e-7 to 1.1e-6). 1e-4 leaves a hundredfold margin
# and is still 40 times below one bfloat16 rounding of such a logit
# (2**-8 = 3.9e-3): a step computed in bfloat16 fails it.
LOGIT_TOL = 1e-4


def _api():
    cfg = ModelConfig(name="qwen2-tiny", family="dense",
                      compute_dtype=jnp.float32, **M)
    api = get_model(cfg, None)
    return api, jax.eval_shape(api.init_params, jax.random.PRNGKey(0))


def _engine(mesh, anchor=None, **kw):
    api, tmpl = _api()
    if anchor is None:
        anchor = make_anchor_on_device(M, SEED, mesh=mesh, api=api,
                                       template=tmpl)
    return ElasticEngine(api, anchor, batch_slots=2, max_len=64,
                         param_template=tmpl, kv_layout="paged",
                         kv_page_size=8, prefill_chunk=16,
                         attn_impl="paged_kernel", fused=True, seed=0,
                         mesh=mesh, **kw)


def _prompts(n, lo=9, hi=30):
    rng = np.random.default_rng(5)
    return [rng.integers(1, M["vocab"], int(rng.integers(lo, hi))
                         ).astype(np.int32) for _ in range(n)]


def _serve(eng, prompts, fmt="mxint8", max_new=6):
    reqs = [Request(rid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    eng.generate(reqs, greedy=True, fmt_override=fmt)
    return [list(r.out_tokens) for r in reqs]


def _ref_logits(prompt, served, bits=8):
    with jax.default_matmul_precision("highest"):
        ref = Reference(M, SEED, anchor_bits=8, bits=bits)
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        h = ref.hidden([seq])[0]
        head, norm = ref.head_weights()
        return np.asarray(ref._logits(h, head, norm), np.float64)


def _weight_pads(jaxpr):
    """``pad`` equations of a jaxpr, at any depth, whose operand is int8
    or uint8 (a weight's codes, nibbles or scale exponents)."""
    out = []
    for e in jaxpr.eqns:
        if e.primitive.name == "pad" and \
                e.invars[0].aval.dtype in (jnp.int8, jnp.uint8):
            out.append(e.invars[0].aval)
        for v in e.params.values():
            for j in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    out.extend(_weight_pads(inner))
    return out


@pytest.fixture
def step_jaxprs(monkeypatch):
    """The jaxpr of every meshed step the engine runs, as it traces them."""
    seen = []
    real = engine_mod._collective_bytes

    def spy(jaxpr):
        if hasattr(jaxpr, "jaxpr"):     # a step's own (closed) jaxpr, not
            seen.append(jaxpr.jaxpr)    # one of its sub-jaxprs
        return real(jaxpr)
    monkeypatch.setattr(engine_mod, "_collective_bytes", spy)
    return seen


def test_tp2_prefill_then_decode_logits_match_reference():
    mesh = make_debug_mesh(1, 2)
    api, tmpl = _api()
    anchor = make_anchor_on_device(M, SEED, mesh=mesh, api=api,
                                   template=tmpl)
    eng = _engine(mesh, anchor)
    w = eng.weights_for("mxint8")
    mlp = w["blocks"][0]["mlp"]
    assert mlp["w_gate"].codes.shape == (2, 256, 2 * WIDTH)
    assert mlp["w_down"].codes.shape == (2, 2 * WIDTH, 256)
    assert mlp["w_down"].scale_exp.shape == (2, 256, 2 * WIDTH // 32)
    # the anchor lived on the mesh: its unpadded MLP leaves were donated,
    # and the engine's anchor holds the laid-out ones
    assert anchor.quantized[MLP + "['w_up']"].codes.is_deleted()
    assert eng.anchor.quantized[MLP + "['w_up']"] is mlp["w_up"]
    logits = []
    real = eng._sample

    def sample(lg, greedy, slot=None):
        logits.append(np.asarray(lg, np.float64).reshape(-1, M["vocab"])[0])
        return real(lg, greedy, slot)
    eng._sample = sample
    prompt = _prompts(1, 21, 22)[0]
    served = _serve(eng, [prompt])[0]
    assert len(logits) == len(served) == 6      # one prefill, five decodes
    ref = _ref_logits(prompt, served)
    for j, lg in enumerate(logits):
        np.testing.assert_allclose(lg, ref[len(prompt) - 1 + j], rtol=0,
                                   atol=LOGIT_TOL)


def test_tp2_serves_the_tokens_of_one_device():
    """Request for request the one-device engine's greedy tokens, up to a
    tie that rounding decides: where a stream departs, the one-device
    engine's logits of the two tokens lie closer together than the two
    engines' logits lie apart (the rule ``bench/tests/test_harness.py``
    states for the row-parallel partials' rounding, ROADMAP C6)."""
    prompts = _prompts(4)
    one = _engine(None, make_anchor_on_device(M, SEED))
    tp2 = _engine(make_debug_mesh(1, 2))
    a, b = _serve(one, prompts, max_new=8), _serve(tp2, prompts, max_new=8)
    assert [len(t) for t in a] == [len(t) for t in b] == [8] * 4
    for p, x, y in zip(prompts, a, b):
        j = next((j for j, (u, v) in enumerate(zip(x, y)) if u != v), None)
        if j is None:
            continue
        prefix = np.concatenate([p, np.asarray(x[:j], np.int32)])
        l1 = one.prompt_logits(prefix, "mxint8")
        l2 = tp2.prompt_logits(prefix, "mxint8")
        assert abs(l1[x[j]] - l1[y[j]]) < np.max(np.abs(l1 - l2)) < LOGIT_TOL


@pytest.mark.parametrize("fmt", ["mxint8", "mxint4"])
def test_no_step_pads_a_weight(step_jaxprs, fmt):
    """No step pads a weight's codes or scales. At MXINT4 the tiny head
    widths here (a chip's wq has 8 heads of 16, its wk one) are not split-N
    tile widths and are still padded per call, as on one chip; only the
    d_ff share is laid out, so there no pad may touch an MLP leaf (dims
    1056, 528 nibble columns, 33 scale rows)."""
    eng = _engine(make_debug_mesh(1, 2))
    _serve(eng, _prompts(2, 20, 30), fmt=fmt, max_new=4)
    kinds = {e["kind"] for e in eng.tick_trace}
    assert {"mixed", "decode"} <= kinds, kinds
    assert len(step_jaxprs) >= 2
    mlp_dims = {SHARE, SHARE // 2, SHARE // 32}
    for j in step_jaxprs:
        pads = _weight_pads(j)
        if fmt == "mxint4":
            pads = [a for a in pads if mlp_dims & set(a.shape)]
        assert pads == []


def test_without_the_layout_every_step_pads_the_mlp(step_jaxprs,
                                                    monkeypatch):
    """The control of the test above: with each chip's share left at 1056,
    the same walk finds the per-call pads of w_gate's and w_up's N and
    w_down's K, codes and scales."""
    from repro.serve.packed_params import repack_splitn_for_tp
    monkeypatch.setattr(
        engine_mod, "layout_tp_shards",
        lambda w, shd, axes, tp, donate=False: (
            repack_splitn_for_tp(w, shd, tp), []))
    eng = _engine(make_debug_mesh(1, 2))
    _serve(eng, _prompts(1, 20, 21), max_new=3)
    pads = {tuple(a.shape) for j in step_jaxprs
            for a in _weight_pads(j)}
    assert {(256, SHARE), (SHARE, 256)} <= pads, pads


def test_int4_split_n_shards_agree_with_one_device():
    """At MXINT4 the column-parallel leaves are split-N nibbles, laid out
    per chip in the unpacked domain and re-nibbled per shard; the meshed
    engine's logits stay those of the one-device engine and of the
    reference at 4 bits."""
    tp2 = _engine(make_debug_mesh(1, 2))
    w = tp2.weights_for("mxint4")
    mlp = w["blocks"][0]["mlp"]
    assert mlp["w_gate"].packed.shape == (2, 256, WIDTH)   # 2 x WIDTH / 2
    assert mlp["w_down"].packed.shape == (2, 2 * WIDTH, 128)
    one = _engine(None, make_anchor_on_device(M, SEED))
    prompt = _prompts(1, 25, 26)[0]
    l1 = one.prompt_logits(prompt, "mxint4")
    l2 = tp2.prompt_logits(prompt, "mxint4")
    np.testing.assert_allclose(l2, l1, rtol=0, atol=LOGIT_TOL)
    ref = _ref_logits(prompt, [0], bits=4)[len(prompt) - 1]
    np.testing.assert_allclose(l2, ref, rtol=0, atol=LOGIT_TOL)


def test_the_kv_pool_is_made_sharded(monkeypatch):
    """On a mesh each chip allocates only its kv heads' share of the pool:
    ``generate`` never places a whole pool on one device to split it (at
    qwen2-72b's tp 4 the whole pool does not fit on the first chip beside
    its weights)."""
    from jax.sharding import PartitionSpec
    eng = _engine(make_debug_mesh(1, 2))
    pool = jax.eval_shape(eng._new_cache)["blocks"][0]["k_pages"]
    cache = eng._new_cache()
    k = cache["blocks"][0]["k_pages"]
    assert k.sharding.spec == PartitionSpec(None, None, None, "model")
    assert {s.data.shape for s in k.addressable_shards} == \
        {pool.shape[:3] + (pool.shape[3] // 2,)}
    placed = []
    real = jax.device_put

    def spy(x, *a, **kw):
        placed.extend(jax.tree_util.tree_leaves(x))
        return real(x, *a, **kw)
    monkeypatch.setattr(jax, "device_put", spy)
    _serve(eng, _prompts(2, 20, 30), max_new=3)
    assert placed and all(np.shape(l) != pool.shape for l in placed)


def test_each_non_final_chunk_is_waited_for(monkeypatch):
    """A prompt's non-final chunks that run alone return nothing the host
    fetches. The engine waits for each one's cache before it dispatches
    the next: chunk steps queued back to back would each hold an output
    pool at once (the KV cache is not donated)."""
    eng = _engine(make_debug_mesh(1, 2))
    caches, waited = [], []
    chunk = eng._packed_prefill_chunk

    def spy_chunk(*a):
        out = chunk(*a)
        caches.append(out[1])
        return out
    eng._packed_prefill_chunk = spy_chunk
    wait = jax.block_until_ready

    def spy_wait(x):
        waited.append(x)
        return wait(x)
    monkeypatch.setattr(jax, "block_until_ready", spy_wait)
    _serve(eng, _prompts(1, 40, 41), max_new=2)   # chunks at 0, 16, 32
    assert len(caches) == 3
    for c in caches[:-1]:
        assert any(w is c for w in waited)


@pytest.mark.parametrize("donate", [False, True])
def test_engines_sharing_a_mesh_sharded_anchor(donate):
    """Two engines built from one anchor that already lives on their mesh.
    With ``donate_anchor=False`` both serve the same tokens and the anchor
    stays whole. By default the first takes the anchor over (the layout
    deletes each leaf it pads), and the second refuses to build a rung,
    naming the cause, instead of failing on a deleted array."""
    mesh = make_debug_mesh(1, 2)
    api, tmpl = _api()
    anchor = make_anchor_on_device(M, SEED, mesh=mesh, api=api,
                                   template=tmpl)
    kw = {} if donate else {"donate_anchor": False}
    first, second = _engine(mesh, anchor, **kw), _engine(mesh, anchor, **kw)
    prompts = _prompts(2)
    tokens = _serve(first, prompts, max_new=4)
    leaves = jax.tree_util.tree_leaves(anchor.quantized)
    if donate:
        assert any(x.is_deleted() for x in leaves)
        with pytest.raises(ValueError, match="donate_anchor"):
            second.weights_for("mxint8")
    else:
        assert not any(x.is_deleted() for x in leaves)
        assert _serve(second, prompts, max_new=4) == tokens


def test_replicas_never_take_the_shared_anchor_over():
    """``ReplicaSet`` hands one anchor to every replica's engine, so none
    of them may delete its leaves; a lone replica may."""
    from repro.serve.replicas import ReplicaSet
    api, tmpl = _api()
    anchor = make_anchor_on_device(M, SEED)
    for n, want in [(2, False), (1, True)]:
        rs = ReplicaSet(api, anchor, n_replicas=n, batch_slots=2,
                        max_len=64, param_template=tmpl)
        assert [e.donate_anchor for e in rs.engines] == [want] * n


def test_exchange_bytes_count_the_collectives():
    """``tick_trace[k]["exchange_bytes"]``: per layer two all-reduces of
    the step's activations (float32 here), one for the embedding lookup,
    and the all-gather of the float32 logits of every row; 0 on one
    chip."""
    eng = _engine(make_debug_mesh(1, 2))
    _serve(eng, _prompts(2, 20, 30), max_new=4)
    d, v, layers = M["d_model"], M["vocab"], M["n_layers"]
    for e in eng.tick_trace:
        if e["kind"] == "decode":
            m, rows = eng.slots, eng.slots
        elif e["kind"] == "mixed":
            m, rows = eng.slots * e["prefill_tokens"], eng.slots
        elif e["kind"] == "chunk":
            m, rows = e["prefill_tokens"], 1
        else:
            assert e["exchange_bytes"] == 0
            continue
        assert e["exchange_bytes"] == (2 * layers + 1) * m * d * 4 \
            + rows * v * 4, e
    assert eng.stats["tp_exchange_bytes"] == \
        sum(e["exchange_bytes"] for e in eng.tick_trace)
    one = _engine(None, make_anchor_on_device(M, SEED))
    _serve(one, _prompts(1), max_new=2)
    assert {e["exchange_bytes"] for e in one.tick_trace} == {0}
    assert one.stats["tp_exchange_bytes"] == 0


# qwen3-4b's one-chip GEMM tiles as the parent commit chose them, before
# the heuristic was split into weight_tiles: (M, K, N, kind) -> tiles.
_QWEN3_TILES = {(m, k, n, kind): (min(m, 128), 128, 512)
                for m in (16, 64, 1024)
                for k, n in [(2560, 4096), (2560, 1024), (4096, 2560),
                             (2560, 9728), (9728, 2560)]
                for kind in ("mx", "int4")}
_QWEN3_TILES.update({(m, 2560, 151936, "mx"): (min(m, 128), 128, 512)
                     for m in (16, 64, 1024)})
_QWEN3_TILES.update({(m, 2560, 151936, "int4"): (min(m, 128), 256, 512)
                     for m in (16, 64, 1024)})


@pytest.mark.parametrize("key", sorted(_QWEN3_TILES))
def test_qwen3_4b_tiles_unchanged(key):
    m, k, n, kind = key
    fmt = get_format("mxint4" if kind == "int4" else "mxint8", 32)
    assert dispatch.select_tiles(m, k, n, fmt, kind) == _QWEN3_TILES[key]
    if n != 151936:     # the tied head stays bf16; the projections are
        # aligned, and laid out at their own widths
        assert dispatch.weight_layout(k, n, fmt, kind) == (k, n)


@pytest.mark.parametrize("tp", [1, 2, 4])
def test_qwen3_4b_shards_pack_as_before(tp):
    """qwen3-4b's widths: on one chip and at tp 2 every d_ff share is
    already tile-aligned, so the layout leaves the packed tree, byte for
    byte, as it was (the same leaf objects); at tp 4 the share 2432 would
    make w_down pad K to 2560 on every call, so it is laid out at 2560."""
    cfg = ModelConfig(name="qwen3-4b", family="dense", n_layers=2,
                      d_model=2560, n_heads=32, n_kv_heads=8, head_dim=128,
                      d_ff=9728, vocab=151936, qk_norm=True,
                      tie_embeddings=True)
    api = get_model(cfg, None)
    tmpl = jax.eval_shape(api.init_params, jax.random.PRNGKey(0))
    from repro.core.anchor import make_anchor
    from repro.core.qat import QATConfig
    qat = QATConfig(formats=("mxint4", "mxint8"), anchor="mxint8",
                    block_size=32)
    served = jax.eval_shape(
        lambda p: make_packed_params(make_anchor(p, qat), p), tmpl)
    share, width = tp_ff_width(served, api.param_axes(), tp)
    assert share == 9728 // tp
    assert width == (2560 if tp == 4 else share)


def test_aligned_shards_come_back_unchanged():
    """A tree whose shares need no pad is returned as the same leaves."""
    small = dict(M, d_ff=256)
    cfg = ModelConfig(name="aligned", family="dense",
                      compute_dtype=jnp.float32, **small)
    api = get_model(cfg, None)
    tmpl = jax.eval_shape(api.init_params, jax.random.PRNGKey(0))
    anchor = make_anchor_on_device(small, SEED)
    w = make_packed_params(anchor, tmpl, dtype=jnp.float32)
    mesh = make_debug_mesh(1, 2)
    shd = packed_param_shardings(w, api.param_axes(), mesh)
    out, laid = layout_tp_shards(w, shd, api.param_axes(), 2, donate=True)
    assert laid == []
    for a, b in zip(jax.tree_util.tree_leaves(w),
                    jax.tree_util.tree_leaves(out)):
        assert a is b and not a.is_deleted()


@pytest.mark.parametrize("bs", [16, 32, 64])
@pytest.mark.parametrize("kind", ["mx", "int4"])
@pytest.mark.parametrize("dim", [96, 576, 1056, 1536, 2432, 4864, 7392,
                                 7424, 9728])
def test_weight_layout_needs_no_pad_when_read(dim, kind, bs):
    """A weight laid out at ``weight_layout``'s widths is read with tiles
    that divide it, as N (K = 1024) and as K (N = 1024): no per-call pad."""
    fmt = get_format("mxint4" if kind == "int4" else "mxint8", bs)
    for k, n in [(1024, dim), (dim, 1024)]:
        if k % bs:
            continue
        kp, np_ = dispatch.weight_layout(k, n, fmt, kind)
        assert kp >= k and np_ >= n
        tn, tk = dispatch.weight_tiles(kp, np_, fmt, kind)
        n_eff = np_ // 2 if kind == "int4" else np_
        assert n_eff % tn == 0 and kp % tk == 0
        assert dispatch.weight_layout(kp, np_, fmt, kind) == (kp, np_)


@pytest.fixture
def fresh_pins(monkeypatch):
    """``dispatch``'s record of laid-out shapes, emptied for one test."""
    monkeypatch.setattr(dispatch, "_LAID_OUT", {})
    return dispatch._LAID_OUT


@pytest.mark.parametrize("m", [16, 64, 1024])
def test_a_laid_out_share_is_read_with_the_tiles_it_was_padded_for(
        fresh_pins, m):
    """qwen2-72b's d_ff share at tp 4: w_gate and w_up (K 8192, N 7392)
    are padded to 7424 for tn 256, and read at tn 256 (re-derived from
    7424, a lane multiple, the heuristic would say 128); w_down (K 7392)
    is padded to 7424 for tk 256 and read so. Other shapes keep the
    heuristic's tiles."""
    fmt = get_format("mxint8", 32)
    assert dispatch.weight_tiles(8192, 7424, fmt) == (128, 512)
    assert dispatch.pin_layout(8192, 7392, 8192, 7424, fmt) == (256, 512)
    assert dispatch.pin_layout(7392, 8192, 7424, 8192, fmt) == (128, 256)
    assert dispatch.select_tiles(m, 8192, 7424, fmt) == (min(m, 128), 256,
                                                         512)
    assert dispatch.select_tiles(m, 7424, 8192, fmt) == (min(m, 128), 128,
                                                         256)
    # another format, and an aligned shape, are not pinned
    assert dispatch.weight_tiles(8192, 7424, get_format("mxfp8", 32)) == \
        (128, 512)
    assert dispatch.weight_tiles(2560, 9728, fmt) == (128, 512)
    # an unpadded shape, or tiles that would not divide, pin nothing
    dispatch.pin_layout(2560, 9728, 2560, 9728, fmt)
    dispatch.pin_layout(8192, 7392, 8192, 7400, fmt)
    assert len(fresh_pins) == 2


def test_the_layout_pins_each_padded_share(fresh_pins):
    """A share that the heuristic pads for tn 256 (1184 -> 1280) is read at
    tn 256 after ``layout_tp_shards``, not at the 128 that 1280 alone
    would give; w_down's K share is read at the tk it was padded for."""
    small = dict(M, d_ff=2368)
    cfg = ModelConfig(name="pinned", family="dense",
                      compute_dtype=jnp.float32, **small)
    api = get_model(cfg, None)
    tmpl = jax.eval_shape(api.init_params, jax.random.PRNGKey(0))
    mesh = make_debug_mesh(1, 2)
    anchor = make_anchor_on_device(small, SEED)
    w = make_packed_params(anchor, tmpl, dtype=jnp.float32)
    shd = packed_param_shardings(w, api.param_axes(), mesh)
    assert tp_ff_width(w, api.param_axes(), 2) == (1184, 1280)
    out, laid = layout_tp_shards(w, shd, api.param_axes(), 2)
    assert sorted(p.rsplit("'", 2)[-2] for p in laid) == [
        "w_down", "w_gate", "w_up"]
    fmt = get_format("mxint8", 32)
    assert dispatch.weight_tiles(256, 1280, fmt) == (256, 256)
    assert dispatch.weight_tiles(1280, 256, fmt) == (128, 256)
    assert len(fresh_pins) == 2
    # tn partitions the output columns only: each column's sum over K is
    # the same, so the pinned reading equals the heuristic's bit for bit
    gate = out["blocks"][0]["mlp"]["w_gate"]
    codes, scales = gate.codes[0], jnp.moveaxis(gate.scale_exp[0], -1, 0)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 256), jnp.float32)
    y = dispatch.qmatmul_mx(x, codes, scales, gate.fmt)
    y128 = dispatch.qmatmul_mx(x, codes, scales, gate.fmt,
                               tiles=(16, 128, 256))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y128))
