"""Gather-free paged-attention decode kernel: an adversarial paged-KV net.

Four lines of defense around kernels/paged_attention.py:

1. **Parity** — the interpret-mode kernel vs the gather + masked-softmax
   reference (`decode_attention(paged_gather(...))`), swept over batch,
   GQA ratio, page size, ragged cache_len (zero, page-boundary, max) and
   sliding window.
2. **Adversarial poison** — every non-allocated page, the scratch page 0,
   and the garbage tail beyond each slot's write frontier are filled with
   NaN / ±1e9 and the output must be BIT-identical to the zero-filled run.
   Zero-filled garbage (all prior tests) is too kind: a masking bug that
   multiplies a dead position by 0 survives it; NaN does not (0*NaN=NaN).
   The same poison corrupting the *gather* reference proves the case has
   teeth — gather's safety depends on zeroed pools, the kernel's does not.
3. **Block-table round-trip property** — random disjoint page assignments
   written through the real write path (`paged_prefill_update` +
   `paged_decode_append`) must read back through the kernel identically to
   the dense cache layout (hypothesis when installed, seeded sweep always).
4. **Engine token identity** — `attn_impl="paged_kernel"` vs `"gather"`
   streams must match token for token (greedy + seeded sampling, mxint8 +
   bf16, fused + densify contracts). Heavyweight matrix cases are
   `@pytest.mark.slow` per pytest.ini; one acceptance pair stays tier-1.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:
    from _hypothesis_stub import hypothesis, st

from repro.configs import get_reduced
from repro.core import make_anchor
from repro.core.qat import QATConfig
from repro.kernels import paged_attention as pa
from repro.models import get_model
from repro.models.layers import (decode_attention, paged_decode_append,
                                 paged_gather, paged_prefill_update)
from repro.serve.engine import ElasticEngine, Request

QAT = QATConfig(formats=("mxint4", "mxint8"), anchor="mxint8", block_size=32)


# =============================================================================
# Fixtures: random pools with disjoint per-slot page assignments
# =============================================================================
def _pool_case(seed, b, mp, ps, hkv, g, d=16):
    """Random q/pools + a random DISJOINT block table (pages shuffled, page 0
    reserved scratch) — the layout invariant the engine maintains."""
    rng = np.random.default_rng(seed)
    h = hkv * g
    n_pages = b * mp + 1
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pages, ps, hkv * d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, ps, hkv * d)), jnp.float32)
    perm = rng.permutation(np.arange(1, n_pages))
    bt = np.zeros((b, mp), np.int32)
    return q, kp, vp, bt, perm


def _map_pages(bt, perm, lens, mp, ps):
    """Map each row's live pages (covering ``lens[i]`` tokens) from ``perm``;
    unmapped entries stay 0 (scratch), exactly like the engine free-list."""
    for i, n in enumerate(lens):
        k = -(-int(n) // ps)
        bt[i, :k] = perm[i * mp:i * mp + k]
    return jnp.asarray(bt)


def _gather_ref(q, kp, vp, bt, cl, window=None):
    return decode_attention(q, paged_gather(kp, bt, q.shape[-1]),
                            paged_gather(vp, bt, q.shape[-1]),
                            cl, window=window)


def _kernel(q, kp, vp, bt, cl, window=None):
    return pa.paged_decode_attention(q, kp, vp, bt, cl, window=window,
                                     mode="pallas")


# =============================================================================
# 1. Parity sweep (kernel in interpret mode vs gather reference)
# =============================================================================
@pytest.mark.parametrize("b", [1, 3])
@pytest.mark.parametrize("g", [1, 4])
@pytest.mark.parametrize("ps", [8, 16])
@pytest.mark.parametrize("window", [None, 10])
def test_kernel_matches_gather_reference(b, g, ps, window):
    """Ragged cache_len per row: 1 (minimum), a page boundary, and the full
    table (max) — every page-count the block-table walk can see."""
    mp = 4
    q, kp, vp, bt, perm = _pool_case(0, b, mp, ps, hkv=2, g=g)
    lens = [1, 2 * ps, mp * ps][:b]
    cl = jnp.asarray(lens, jnp.int32)
    bt = _map_pages(bt, perm, lens, mp, ps)
    got = _kernel(q, kp, vp, bt, cl, window=window)
    want = _gather_ref(q, kp, vp, bt, cl, window=window)
    assert got.shape == want.shape == q.shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-5, atol=1e-5)


def test_cache_len_zero_yields_zeros_not_nan():
    """No valid key exists at cache_len=0: the dense-math reference NaNs
    (softmax over an empty set); the kernel defines the row as exact zeros.
    The engine never emits the case (decode appends before attending), but
    the kernel must not poison a batch that contains such a row."""
    q, kp, vp, bt, perm = _pool_case(1, 3, 4, 8, hkv=2, g=2)
    lens = [0, 9, 32]
    cl = jnp.asarray(lens, jnp.int32)
    bt = _map_pages(bt, perm, lens, 4, 8)
    got = _kernel(q, kp, vp, bt, cl)
    assert bool(jnp.all(got[0] == 0))
    assert bool(jnp.all(jnp.isfinite(got)))
    want = _gather_ref(q, kp, vp, bt, cl)
    np.testing.assert_allclose(np.asarray(got[1:], np.float32),
                               np.asarray(want[1:], np.float32),
                               rtol=1e-5, atol=1e-5)


def test_kernel_under_jit_with_traced_cache_len():
    """serve_step jits the kernel with cache_len traced — the scalar-prefetch
    operands must accept tracers, and retracing must not be length-dependent."""
    q, kp, vp, bt, perm = _pool_case(2, 2, 4, 8, hkv=2, g=2)
    bt = _map_pages(bt, perm, [5, 17], 4, 8)
    f = jax.jit(lambda cl: _kernel(q, kp, vp, bt, cl))
    for lens in ([5, 17], [8, 32], [1, 9]):
        cl = jnp.asarray(lens, jnp.int32)
        np.testing.assert_allclose(
            np.asarray(f(cl), np.float32),
            np.asarray(_gather_ref(q, kp, vp, bt, cl), np.float32),
            rtol=1e-5, atol=1e-5)


# =============================================================================
# 2. Adversarial poison: garbage must never enter the reduction
# =============================================================================
def _poison(kp, vp, bt, lens, ps):
    """NaN/±1e9 in every byte the kernel must not read: unallocated pages,
    scratch page 0, and the tail beyond each row's frontier inside its own
    last live page. K always gets NaN (tests the score mask before the
    running max); V alternates NaN / ±1e9 per page (NaN tests the PV-product
    mask — a zeroed probability is NOT enough, 0*NaN=NaN — and ±1e9 tests
    that 'approximately masked' would still be loud)."""
    kp_p, vp_p = np.array(kp), np.array(vp)
    used = set(np.asarray(bt).flatten().tolist()) - {0}
    for pg in range(kp_p.shape[0]):
        if pg not in used:
            kp_p[pg] = np.nan
            vp_p[pg] = np.nan if pg % 2 == 0 else 1e9
    for i, n in enumerate(lens):
        n = int(n)
        pg, off = n // ps, n % ps
        row = np.asarray(bt)[i]
        if off and pg < row.size and row[pg] != 0:
            kp_p[row[pg], off:] = np.nan
            vp_p[row[pg], off:] = np.nan if i % 2 == 0 else -1e9
    return jnp.asarray(kp_p), jnp.asarray(vp_p)


@pytest.mark.parametrize("window", [None, 10])
def test_kernel_ignores_nan_poisoned_dead_pages(window):
    q, kp, vp, bt, perm = _pool_case(3, 3, 4, 8, hkv=2, g=2)
    lens = [1, 9, 24]
    cl = jnp.asarray(lens, jnp.int32)
    bt = _map_pages(bt, perm, lens, 4, 8)
    clean = _kernel(q, kp, vp, bt, cl, window=window)
    kp_p, vp_p = _poison(kp, vp, bt, lens, 8)
    dirty = _kernel(q, kp_p, vp_p, bt, cl, window=window)
    # BIT-identical, not allclose: the poisoned values must contribute
    # exactly nothing, not approximately nothing.
    assert np.array_equal(np.asarray(clean), np.asarray(dirty))
    assert bool(jnp.all(jnp.isfinite(dirty)))


def test_poison_corrupts_the_gather_reference():
    """The adversarial case must have teeth: the same poison NaNs the gather
    path (0 * NaN = NaN in its masked PV product), which is why gather
    depends on the engine's zero-filled-pool invariant and the kernel's
    in-kernel masking is the stronger contract."""
    q, kp, vp, bt, perm = _pool_case(4, 2, 4, 8, hkv=2, g=2)
    lens = [9, 24]
    cl = jnp.asarray(lens, jnp.int32)
    bt = _map_pages(bt, perm, lens, 4, 8)
    kp_p, vp_p = _poison(kp, vp, bt, lens, 8)
    ref = _gather_ref(q, kp_p, vp_p, bt, cl)
    assert not bool(jnp.all(jnp.isfinite(ref)))


def test_serve_step_logits_survive_poisoned_pool():
    """Model-level: a full paged serve_step (scan over layers, per-layer
    pools) with attn_impl='paged_kernel' must produce identical logits with
    every non-allocated page and scratch page 0 poisoned."""
    cfg = get_reduced("smollm-135m")
    api = get_model(cfg, None).with_serving(attn_impl="paged_kernel")
    params = api.init_params(jax.random.PRNGKey(0))
    cache = api.init_cache(2, 32, kv_layout="paged", page_size=8)
    bt = np.zeros((2, 4), np.int32)
    bt[0, :2] = [1, 2]
    bt[1, :2] = [5, 6]
    cache["block_table"] = jnp.asarray(bt)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (1, 9)), jnp.int32)
    _, cache, _ = jax.jit(api.prefill_slot)(
        params, {"tokens": toks}, cache, 0)
    _, cache, _ = jax.jit(api.prefill_slot)(
        params, {"tokens": toks[:, :5]}, cache, 1)
    step = jax.jit(api.serve_step)
    batch = {"tokens": jnp.asarray([[3], [4]], jnp.int32)}
    cache_len = jnp.asarray([9, 5], jnp.int32)
    logits, _ = step(params, batch, cache, cache_len)

    used = {1, 2, 5, 6}
    poisoned = dict(cache)
    poisoned["blocks"] = []
    for blk in cache["blocks"]:
        mask = np.asarray([pg not in used
                           for pg in range(blk["k_pages"].shape[1])])
        sel = jnp.asarray(mask)[None, :, None, None]
        poisoned["blocks"].append({
            "k_pages": jnp.where(sel, jnp.asarray(
                jnp.nan, blk["k_pages"].dtype), blk["k_pages"]),
            "v_pages": jnp.where(sel, jnp.asarray(
                jnp.nan, blk["v_pages"].dtype), blk["v_pages"])})
    logits_p, _ = step(params, batch, poisoned, cache_len)
    assert np.array_equal(np.asarray(logits), np.asarray(logits_p))
    assert bool(jnp.all(jnp.isfinite(logits_p)))


# =============================================================================
# 3. Block-table translation round-trip (real write path, property-style)
# =============================================================================
def _check_roundtrip(seed, ps, lens):
    """Writes through paged_prefill_update + paged_decode_append, reads
    through the kernel, and must match the dense cache layout exactly."""
    rng = np.random.default_rng(seed)
    b = len(lens)
    hkv, g, d = 2, 2, 16
    h = hkv * g
    mp = max(-(-(int(n) + 1) // ps) for n in lens)
    s_max = mp * ps
    n_pages = b * mp + 1
    perm = rng.permutation(np.arange(1, n_pages))
    bt = np.zeros((b, mp), np.int32)
    bt = _map_pages(bt, perm, [int(n) + 1 for n in lens], mp, ps)

    k_new = jnp.asarray(rng.normal(size=(b, s_max, hkv, d)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(b, s_max, hkv, d)), jnp.float32)
    k_tok = jnp.asarray(rng.normal(size=(b, 1, hkv, d)), jnp.float32)
    v_tok = jnp.asarray(rng.normal(size=(b, 1, hkv, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    cl = jnp.asarray(lens, jnp.int32)

    # paged: prefill-scatter the (padded) prompt, then append one token at
    # each row's cache_len — exactly what a decode tick does.
    kp = paged_prefill_update(jnp.zeros((n_pages, ps, hkv * d)), k_new, bt)
    vp = paged_prefill_update(jnp.zeros((n_pages, ps, hkv * d)), v_new, bt)
    kp = paged_decode_append(kp, k_tok, bt, cl)
    vp = paged_decode_append(vp, v_tok, bt, cl)

    # dense: same values, contiguous per-slot buffers.
    upd = jax.vmap(lambda c, t, n: jax.lax.dynamic_update_slice_in_dim(
        c, t, n, axis=0))
    kd = upd(k_new, k_tok, cl)
    vd = upd(v_new, v_tok, cl)

    got = _kernel(q, kp, vp, bt, cl + 1)
    want = decode_attention(q, kd, vd, cl + 1)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-5, atol=1e-5)


@hypothesis.given(seed=st.integers(0, 2**31 - 1),
                  ps=st.sampled_from([8, 16]),
                  lens=st.lists(st.integers(0, 40), min_size=1, max_size=4))
@hypothesis.settings(deadline=None, max_examples=25)
def test_block_table_roundtrip_property(seed, ps, lens):
    _check_roundtrip(seed, ps, lens)


@pytest.mark.parametrize("seed,ps,lens", [
    (0, 8, [0, 7, 8]),        # empty row, sub-page, exact page
    (1, 8, [15, 16, 17]),     # page-boundary straddle
    (2, 16, [5, 31, 40]),     # bigger pages, multi-page rows
    (3, 8, [39]),             # single slot near table max
])
def test_block_table_roundtrip_seeded(seed, ps, lens):
    """Always-run slice of the property above (hypothesis skips when the
    stub is active — see tests/_hypothesis_stub.py)."""
    _check_roundtrip(seed, ps, lens)


# =============================================================================
# 4. Engine-level token identity + knob validation
# =============================================================================
def _setup(arch="smollm-135m"):
    cfg = get_reduced(arch)
    api = get_model(cfg, None)
    params = api.init_params(jax.random.PRNGKey(0))
    anchor = make_anchor(params, QAT)
    return cfg, api, params, anchor


def _engine(api, anchor, params, **kw):
    kw.setdefault("batch_slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("kv_layout", "paged")
    kw.setdefault("kv_page_size", 8)
    return ElasticEngine(api, anchor, param_template=params, **kw)


def _reqs(cfg, n, max_new=5, plen=8, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab, plen)
                    .astype(np.int32), max_new=max_new) for i in range(n)]


@pytest.mark.parametrize("fused", [True, False])
def test_engine_identity_kernel_vs_gather(fused):
    """Acceptance gate (fast slice): greedy mxint8 streams identical across
    attn impls under both packed-serving contracts, with the path counters
    proving which attention implementation actually traced."""
    cfg, api, params, anchor = _setup()
    streams, reads = {}, {}
    for impl in ("gather", "paged_kernel"):
        pa.reset_stats()
        eng = _engine(api, anchor, params, fused=fused, attn_impl=impl)
        reqs = _reqs(cfg, 3, max_new=5, seed=7)
        eng.generate(reqs, fmt_override="mxint8")
        st_ = pa.stats()
        if impl == "paged_kernel":
            assert st_["pallas"] >= 1 and st_["fallback"] == 0, st_
        else:
            assert st_["fallback"] >= 1 and st_["pallas"] == 0, st_
        streams[impl] = [r.out_tokens for r in reqs]
        reads[impl] = eng.stats["attn_tokens_read"]
    assert streams["gather"] == streams["paged_kernel"]
    # the kernel's accounted reads cover live pages only — strictly fewer
    # tokens than gather's full-logical-view reads on this workload
    assert 0 < reads["paged_kernel"] < reads["gather"]


@pytest.mark.slow
@pytest.mark.parametrize("fmt", ["mxint8", "bf16"])
@pytest.mark.parametrize("fused", [True, False])
def test_engine_identity_matrix_greedy(fmt, fused):
    cfg, api, params, anchor = _setup()
    streams = {}
    for impl in ("gather", "paged_kernel"):
        eng = _engine(api, anchor, params, fused=fused, attn_impl=impl)
        reqs = _reqs(cfg, 4, max_new=6, seed=11)
        eng.generate(reqs, fmt_override=fmt)
        streams[impl] = [r.out_tokens for r in reqs]
    assert streams["gather"] == streams["paged_kernel"]


@pytest.mark.slow
@pytest.mark.parametrize("fmt", ["mxint8", "bf16"])
def test_engine_identity_seeded_sampling(fmt):
    """Sampling depends only on logits + per-slot RNG streams; identical
    streams across attn impls means the kernel's logits are close enough
    that every categorical draw lands on the same token."""
    cfg, api, params, anchor = _setup()
    streams = {}
    for impl in ("gather", "paged_kernel"):
        eng = _engine(api, anchor, params, attn_impl=impl, seed=3,
                      temperature=1.0, top_p=0.9)
        reqs = _reqs(cfg, 3, max_new=5, seed=13)
        eng.generate(reqs, greedy=False, fmt_override=fmt)
        streams[impl] = [r.out_tokens for r in reqs]
    assert streams["gather"] == streams["paged_kernel"]


@pytest.mark.slow
def test_engine_identity_sliding_window():
    """A windowed arch forces the in-kernel window mask through the engine:
    streams must still match the gather path token for token."""
    cfg, api, params, anchor = _setup()
    wcfg = dataclasses.replace(cfg, sliding_window=8)
    wapi = get_model(wcfg, None)
    streams = {}
    for impl in ("gather", "paged_kernel"):
        eng = _engine(wapi, anchor, params, attn_impl=impl)
        reqs = _reqs(wcfg, 3, max_new=8, plen=12, seed=5)
        eng.generate(reqs, fmt_override="mxint8")
        streams[impl] = [r.out_tokens for r in reqs]
    assert streams["gather"] == streams["paged_kernel"]


def test_attn_impl_validation():
    cfg, api, params, anchor = _setup()
    with pytest.raises(ValueError, match="requires kv_layout='paged'"):
        ElasticEngine(api, anchor, batch_slots=2, max_len=32,
                      param_template=params, kv_layout="dense",
                      attn_impl="paged_kernel")
    with pytest.raises(ValueError, match="unknown attn_impl"):
        ElasticEngine(api, anchor, batch_slots=2, max_len=32,
                      param_template=params, kv_layout="paged",
                      attn_impl="flash")
    with pytest.raises(ValueError, match="unknown attn_impl"):
        api.with_serving(attn_impl="bogus")
    with pytest.raises(ValueError, match="unknown paged-attention mode"):
        pa.resolve_mode("gathered")
