"""One mixed prefill+decode batch per tick: the unified-scheduler net.

Four lines of defense around the mixed tick (serve/engine.py "mixed"
scheduler + models' ``mixed_step`` + the multi-query kernel in
kernels/paged_attention.py):

1. **MQ kernel parity** — the multi-query Pallas kernel (interpret mode)
   vs the gather + masked-softmax reference (``mixed_attention(
   paged_gather(...))``), swept over ragged per-row query spans whose
   cursors sit AT, just past, and just before page boundaries
   (``q_offset % page_size in {0, 1, page_size-1}``), q-block tilings
   (``tq``), sliding window, and the q_len==1 collapse onto the
   single-query kernel (bit-identical — decode rows cost and compute
   exactly what they did before the refactor).
2. **Adversarial poison** — unallocated pages, scratch page 0, dead query
   lanes and the tail beyond each row's frontier are NaN / ±1e9; outputs
   must be BIT-identical to the zero-filled run. The per-lane causal mask
   makes this strictly harder than the single-query case: an executed page
   may be dead for SOME lanes only, so the running-max update must guard
   lanes whose max is still -inf (exp(-inf - -inf) = NaN).
3. **Scheduler identity** — token streams under ``scheduler="mixed"``
   (chunk rides the decode batch, ONE executable per tick) must match
   ``scheduler="sequential"`` (PR 4's chunk-then-decode, two executables)
   bit for bit across {fused, densify} x {dense, paged} x {gather,
   paged_kernel} x {greedy, seeded} x {mxint8, bf16}. Heavyweight matrix
   cases are ``@pytest.mark.slow`` per pytest.ini; an acceptance slice
   stays tier-1.
4. **Scheduler invariants** — exactly one executable per work tick
   (asserted from tick_trace ``execs``, with the sequential scheduler
   demonstrably running two), pool exhaustion mid-chunk under the mixed
   scheduler still releases-and-requeues without leaking pages, knob
   validation, and the ``mixed_step`` hook surviving ``with_qmm`` /
   ``with_serving`` chaining in either order.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import make_anchor
from repro.core.qat import QATConfig
from repro.kernels import paged_attention as pa
from repro.models import get_model
from repro.models.layers import mixed_attention, paged_gather
from repro.serve.engine import ElasticEngine, Request

QAT = QATConfig(formats=("mxint4", "mxint8"), anchor="mxint8", block_size=32)
PS = 8          # page size
CHUNK = 8       # prefill chunk (== one page, the paged-layout default)


# =============================================================================
# Fixtures
# =============================================================================
@pytest.fixture(scope="module")
def setup():
    cfg = get_reduced("smollm-135m")
    api = get_model(cfg, None)
    params = api.init_params(jax.random.PRNGKey(0))
    anchor = make_anchor(params, QAT)
    return cfg, api, params, anchor


def _mq_case(seed, rows, ps=PS, c=8, hkv=2, g=2, d=16):
    """Random q/pools + disjoint block table for a mixed batch. ``rows`` is
    a list of (q_offset, q_len); row i's live span is q_offset+q_len tokens
    (the chunk's KV is in the pool before attention runs, exactly as
    ``paged_mixed_update`` leaves it)."""
    rng = np.random.default_rng(seed)
    b, h = len(rows), hkv * g
    mp = max(-(-(qo + ql) // ps) for qo, ql in rows)
    n_pages = b * mp + 1
    q = jnp.asarray(rng.normal(size=(b, c, h, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pages, ps, hkv * d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, ps, hkv * d)), jnp.float32)
    perm = rng.permutation(np.arange(1, n_pages))
    bt = np.zeros((b, mp), np.int32)
    for i, (qo, ql) in enumerate(rows):
        k = -(-(qo + ql) // ps)
        bt[i, :k] = perm[i * mp:i * mp + k]
    qo = jnp.asarray([r[0] for r in rows], jnp.int32)
    ql = jnp.asarray([r[1] for r in rows], jnp.int32)
    return q, kp, vp, jnp.asarray(bt), qo, ql


def _mq_kernel(q, kp, vp, bt, qo, ql, window=None, tq=None):
    return pa.paged_mixed_attention(q, kp, vp, bt, qo, ql, window=window,
                                    mode="pallas", tq=tq)


def _mq_gather_ref(q, kp, vp, bt, qo, ql, window=None):
    return mixed_attention(q, paged_gather(kp, bt, q.shape[-1]),
                            paged_gather(vp, bt, q.shape[-1]),
                           qo, ql, window=window)


# The adversarial span set: cursors at a page boundary, one past it, and one
# before it; chunks that end on / straddle boundaries; a decode row; a
# zero-cursor first chunk.
BOUNDARY_ROWS = [(PS, CHUNK),          # cursor % ps == 0, chunk == one page
                 (PS + 1, CHUNK - 3),  # cursor % ps == 1
                 (PS - 1, CHUNK),      # cursor % ps == ps-1 (straddles)
                 (2 * PS - 3, 1),      # decode row mid-page
                 (0, CHUNK - 1)]       # first chunk from zero


# =============================================================================
# 1. MQ kernel parity
# =============================================================================
@pytest.mark.parametrize("window", [None, 10])
@pytest.mark.parametrize("tq", [None, 4, 2])
def test_mq_kernel_matches_gather_reference(window, tq):
    q, kp, vp, bt, qo, ql = _mq_case(0, BOUNDARY_ROWS)
    got = _mq_kernel(q, kp, vp, bt, qo, ql, window=window, tq=tq)
    want = _mq_gather_ref(q, kp, vp, bt, qo, ql, window=window)
    assert got.shape == want.shape == q.shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("window", [None, 12])
def test_mq_q_len_one_collapses_to_single_query_kernel(window):
    """A mixed batch of pure decode rows is the same page walk and online
    softmax as the single-query kernel, with pad lanes exact zeros. The
    match is ULP-scale, not bit-exact: the MQ contraction carries a q axis,
    and the backend may vectorize the two dot shapes differently (the
    engine-level identity tests below hold the contract that matters —
    identical token streams)."""
    rows = [(8, 1), (23, 1), (16, 1)]
    q, kp, vp, bt, qo, ql = _mq_case(1, rows, c=4)
    mq = np.asarray(_mq_kernel(q, kp, vp, bt, qo, ql, window=window))
    sq = pa.paged_decode_attention(q[:, :1], kp, vp, bt, qo + 1,
                                   window=window, mode="pallas")
    np.testing.assert_allclose(np.asarray(sq, np.float32),
                               mq[:, :1].astype(np.float32),
                               rtol=1e-6, atol=1e-6)
    assert np.all(mq[:, 1:] == 0)


def test_mq_kernel_under_jit_with_traced_spans():
    """The engine jits mixed_step with q_offset/q_len traced — the scalar-
    prefetch operands must accept tracers and retracing must not depend on
    the span values."""
    q, kp, vp, bt, qo, ql = _mq_case(2, BOUNDARY_ROWS)
    f = jax.jit(lambda o, n: _mq_kernel(q, kp, vp, bt, o, n))
    for rows in (BOUNDARY_ROWS, [(0, 8), (8, 8), (15, 1), (9, 2), (1, 1)]):
        o = jnp.asarray([r[0] for r in rows], jnp.int32)
        n = jnp.asarray([r[1] for r in rows], jnp.int32)
        np.testing.assert_allclose(
            np.asarray(f(o, n), np.float32),
            np.asarray(_mq_gather_ref(q, kp, vp, bt, o, n), np.float32),
            rtol=1e-5, atol=1e-5)


def test_pages_read_mq_collapses_to_pages_read():
    """The host-side cost mirror: a decode row (q_len=1 at offset L-1) must
    account exactly like the single-query walk for L live tokens."""
    for ps in (8, 16):
        for window in (None, 10, 64):
            for L in (1, 7, 8, 9, 31, 32, 40):
                assert pa.pages_read_mq(L - 1, 1, ps, window) == \
                    pa.pages_read(L, ps, window), (ps, window, L)


# The narrow fold: at a chunk wider than NARROW_LANES, rows with few live
# lanes fold only the first NARROW_LANES * G query rows of the block. Rows
# on both sides of the threshold, at q_len 0, and a whole-chunk row.
WIDE_C = 32
NARROW_ROWS = [(5, 0),       # no live lane
               (17, 1),      # decode row
               (8, 7),       # narrow, one lane short of the threshold
               (3, 8),       # narrow, exactly at the threshold
               (16, 9),      # one lane past it: the full fold
               (9, WIDE_C)]  # the whole chunk


@pytest.mark.parametrize("g", [2, 3])
@pytest.mark.parametrize("window", [None, 10])
@pytest.mark.parametrize("tq", [None, 16])
def test_mq_narrow_fold_matches_gather_reference(window, tq, g):
    q, kp, vp, bt, qo, ql = _mq_case(5, NARROW_ROWS, c=WIDE_C, g=g)
    got = _mq_kernel(q, kp, vp, bt, qo, ql, window=window, tq=tq)
    want = _mq_gather_ref(q, kp, vp, bt, qo, ql, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-5, atol=1e-5)
    for i, (_, ql_i) in enumerate(NARROW_ROWS):
        assert np.all(np.asarray(got)[i, ql_i:] == 0), i


def test_mq_rows_folded_follows_the_narrow_rule():
    """The host mirror: per q block, nothing without a live lane,
    NARROW_LANES * G rows up to NARROW_LANES live lanes, the whole block
    past that — so every live lane is folded and no row-count exceeds the
    padded ``c * g``."""
    n = pa.NARROW_LANES
    assert n == 8
    for g in (1, 3, 4):
        want = {0: 0, 1: n * g, 7: n * g, 8: n * g, 9: 32 * g, 32: 32 * g}
        for q_len, rows in want.items():
            assert pa.mq_rows_folded(q_len, 32, g) == rows, (q_len, g)
        # two q blocks of 16: each block applies the rule to its own lanes
        blocks = {0: 0, 8: n * g, 9: 16 * g, 16: 16 * g, 17: 16 * g + n * g,
                  24: 16 * g + n * g, 25: 32 * g, 32: 32 * g}
        for q_len, rows in blocks.items():
            assert pa.mq_rows_folded(q_len, 32, g, tq=16) == rows, (q_len, g)
        # a block no wider than NARROW_LANES always folds whole
        assert pa.mq_rows_folded(5, 8, g) == 8 * g
        assert pa.mq_rows_folded(5, 8, g, tq=4) == 8 * g
        assert pa.mq_rows_folded(0, 8, g, tq=4) == 0
        for c, tq in ((32, None), (32, 16), (64, None), (8, 4)):
            for q_len in range(c + 1):
                rows = pa.mq_rows_folded(q_len, c, g, tq=tq)
                assert q_len * g <= rows <= c * g, (c, tq, q_len)
    # the chat cell's full-width mixed tick: 15 decode rows + one chunk of 64
    # at qwen3-4b's G = 4
    share = (15 * pa.mq_rows_folded(1, 64, 4)
             + pa.mq_rows_folded(64, 64, 4)) / (16 * 64 * 4)
    assert share == pytest.approx((15 * 32 + 256) / (16 * 256))


# =============================================================================
# 2. Adversarial poison
# =============================================================================
def _poison_mq(kp, vp, bt, rows, ps):
    """NaN/±1e9 in every byte the MQ kernel must not read: unallocated pages
    (incl. scratch page 0) and the tail beyond each row's frontier
    (q_offset + q_len) inside its last live page."""
    kp_p, vp_p = np.array(kp), np.array(vp)
    used = set(np.asarray(bt).flatten().tolist()) - {0}
    for pg in range(kp_p.shape[0]):
        if pg not in used:
            kp_p[pg] = np.nan
            vp_p[pg] = np.nan if pg % 2 == 0 else 1e9
    for i, (qo, ql) in enumerate(rows):
        n = qo + ql
        pg, off = n // ps, n % ps
        row = np.asarray(bt)[i]
        if off and pg < row.size and row[pg] != 0:
            kp_p[row[pg], off:] = np.nan
            vp_p[row[pg], off:] = np.nan if i % 2 == 0 else -1e9
    return jnp.asarray(kp_p), jnp.asarray(vp_p)


@pytest.mark.parametrize("window", [None, 10])
def test_mq_kernel_ignores_poisoned_pool(window):
    q, kp, vp, bt, qo, ql = _mq_case(3, BOUNDARY_ROWS)
    clean = np.asarray(_mq_kernel(q, kp, vp, bt, qo, ql, window=window))
    kp_p, vp_p = _poison_mq(kp, vp, bt, BOUNDARY_ROWS, PS)
    dirty = np.asarray(_mq_kernel(q, kp_p, vp_p, bt, qo, ql, window=window))
    # BIT-identical, not allclose: poisoned values contribute exactly nothing
    assert np.array_equal(clean, dirty)
    assert np.all(np.isfinite(dirty))
    # dead query lanes (beyond each row's q_len) are exact zeros even with
    # the pool poisoned — the engine's sampler never sees them, but a NaN
    # there would poison the whole row through the output projection
    for i, (_, ql_i) in enumerate(BOUNDARY_ROWS):
        assert np.all(dirty[i, ql_i:] == 0), i


@pytest.mark.parametrize("window", [None, 10])
@pytest.mark.parametrize("tq", [None, 16])
def test_mq_narrow_fold_ignores_poisoned_pad_queries(window, tq):
    """NaN in the pad lanes of the queries (every lane at or past each
    row's q_len, in the narrow rows' folded extent and past it alike):
    live lanes stay BIT-identical to the clean run, dead lanes exact
    zeros."""
    q, kp, vp, bt, qo, ql = _mq_case(6, NARROW_ROWS, c=WIDE_C, g=3)
    clean = np.asarray(_mq_kernel(q, kp, vp, bt, qo, ql, window=window,
                                  tq=tq))
    q_p = np.array(q)
    for i, (_, ql_i) in enumerate(NARROW_ROWS):
        q_p[i, ql_i:] = np.nan
    dirty = np.asarray(_mq_kernel(jnp.asarray(q_p), kp, vp, bt, qo, ql,
                                  window=window, tq=tq))
    assert np.array_equal(clean, dirty)
    for i, (_, ql_i) in enumerate(NARROW_ROWS):
        assert np.all(dirty[i, ql_i:] == 0), i


def test_poison_corrupts_the_mq_gather_reference():
    """Teeth check: the same poison NaNs the gather path (0 * NaN = NaN in
    its masked PV product) — gather's safety still depends on the engine's
    zero-filled-pool invariant; the MQ kernel's does not."""
    q, kp, vp, bt, qo, ql = _mq_case(4, BOUNDARY_ROWS)
    kp_p, vp_p = _poison_mq(kp, vp, bt, BOUNDARY_ROWS, PS)
    ref = _mq_gather_ref(q, kp_p, vp_p, bt, qo, ql)
    assert not bool(jnp.all(jnp.isfinite(ref)))


def test_mixed_step_logits_survive_poisoned_pool():
    """Model-level: a full paged mixed_step (scan over layers, ragged
    q_len=[chunk, 1]) with attn_impl='paged_kernel' produces identical
    logits with every non-allocated page and scratch page 0 poisoned."""
    cfg = get_reduced("smollm-135m")
    api = get_model(cfg, None).with_serving(attn_impl="paged_kernel")
    params = api.init_params(jax.random.PRNGKey(0))
    cache = api.init_cache(2, 32, kv_layout="paged", page_size=PS)
    bt = np.zeros((2, 4), np.int32)
    bt[0, :2] = [1, 2]       # fill row: chunk [8:16) -> pages 1,2
    bt[1, :2] = [5, 6]       # decode row at position 9 -> pages 5,6
    cache["block_table"] = jnp.asarray(bt)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (1, 8)), jnp.int32)
    _, cache, _ = jax.jit(api.prefill_chunk_slot)(
        params, {"tokens": prompt, "lengths": jnp.asarray([16])}, cache, 0, 0)
    _, cache, _ = jax.jit(api.prefill_slot)(
        params, {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab, (1, 9)), jnp.int32)}, cache, 1)
    step = jax.jit(api.mixed_step)
    tok2d = np.zeros((2, 8), np.int32)
    tok2d[0] = np.asarray(rng.integers(0, cfg.vocab, 8))
    tok2d[1, 0] = 3
    batch = {"tokens": jnp.asarray(tok2d),
             "q_len": jnp.asarray([8, 1], jnp.int32)}
    cache_len = jnp.asarray([8, 9], jnp.int32)
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
# 3. Scheduler identity: mixed vs sequential, token for token
# =============================================================================
def _engine(api, anchor, params, **kw):
    kw.setdefault("batch_slots", 2)
    kw.setdefault("max_len", 48)
    return ElasticEngine(api, anchor, param_template=params, **kw)


def _reqs(cfg, n, max_new=5, plens=(8, 21, 13), seed=7):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, plens[i % len(plens)])
                    .astype(np.int32), max_new=max_new) for i in range(n)]


def _streams(api, anchor, params, cfg, scheduler, *, greedy=True,
             fmt="mxint8", n=4, **kw):
    eng = _engine(api, anchor, params, prefill_chunk=CHUNK,
                  scheduler=scheduler, **kw)
    reqs = _reqs(cfg, n)
    eng.generate(reqs, greedy=greedy, fmt_override=fmt)
    assert all(r.done for r in reqs)
    return [r.out_tokens for r in reqs], eng


@pytest.mark.parametrize("kv,fused,impl", [
    ("dense", False, "gather"),
    ("paged", True, "gather"),
    ("paged", True, "paged_kernel"),
])
def test_mixed_matches_sequential_greedy(setup, kv, fused, impl):
    """Acceptance gate (fast slice): greedy streams bit-identical mixed vs
    sequential, across KV layouts / serving contracts / attention impls —
    with the path counters proving the MQ kernel actually traced."""
    cfg, api, params, anchor = setup
    kw = dict(fused=fused)
    if kv == "paged":
        kw.update(kv_layout="paged", kv_page_size=PS, attn_impl=impl)
    seq, _ = _streams(api, anchor, params, cfg, "sequential", **kw)
    pa.reset_stats()
    mixed, eng = _streams(api, anchor, params, cfg, "mixed", **kw)
    assert seq == mixed
    if impl == "paged_kernel":
        st = pa.stats()
        assert st["pallas_mq"] >= 1 and st["fallback_mq"] == 0, st


@pytest.mark.slow
@pytest.mark.parametrize("fmt", ["mxint8", "bf16"])
@pytest.mark.parametrize("greedy", [True, False])
@pytest.mark.parametrize("kv,fused,impl", [
    ("dense", False, "gather"), ("dense", True, "gather"),
    ("paged", False, "gather"), ("paged", True, "gather"),
    ("paged", False, "paged_kernel"), ("paged", True, "paged_kernel"),
])
def test_mixed_matches_sequential_matrix(setup, fmt, greedy, kv, fused, impl):
    """The full acceptance matrix: {fused, densify} x {dense, paged} x
    {gather, paged_kernel} x {greedy, seeded} at mxint8 + bf16."""
    cfg, api, params, anchor = setup
    kw = dict(fused=fused)
    if kv == "paged":
        kw.update(kv_layout="paged", kv_page_size=PS, attn_impl=impl)
    if not greedy:
        kw.update(seed=3, temperature=1.0, top_p=0.9)
    seq, _ = _streams(api, anchor, params, cfg, "sequential", greedy=greedy,
                      fmt=fmt, **kw)
    mixed, _ = _streams(api, anchor, params, cfg, "mixed", greedy=greedy,
                        fmt=fmt, **kw)
    assert seq == mixed


def test_mixed_matches_monolithic(setup):
    """Transitivity anchor: mixed == sequential == monolithic — asserted
    directly so a joint drift in both chunked schedulers cannot hide."""
    cfg, api, params, anchor = setup
    eng = _engine(api, anchor, params)
    reqs = _reqs(cfg, 4)
    eng.generate(reqs, fmt_override="mxint8")
    mono = [r.out_tokens for r in reqs]
    mixed, _ = _streams(api, anchor, params, cfg, "mixed")
    assert mono == mixed


# =============================================================================
# 4. Scheduler invariants + knob validation
# =============================================================================
def test_exactly_one_executable_per_tick(setup):
    """THE refactor's claim, from the engine's own trace: under the mixed
    scheduler every work tick dispatches exactly one executable — including
    ticks that carry a prefill chunk AND a decode step — while the
    sequential scheduler demonstrably needs two for those ticks."""
    cfg, api, params, anchor = setup
    wl = lambda: _reqs(cfg, 3, plens=(30, 8, 8), seed=2)

    eng = _engine(api, anchor, params, prefill_chunk=CHUNK, scheduler="mixed")
    eng.generate(wl(), fmt_override="mxint8")
    assert eng.tick_trace, "mixed run recorded no ticks"
    coalesced = 0
    for t in eng.tick_trace:
        assert t["execs"] <= 1, t
        if t["prefill_chunks"] == 1 and t["decode"] == 1:
            coalesced += 1
            assert t["execs"] == 1
            assert t["decode_rows"] >= 1
    assert coalesced >= 1, "workload never coalesced a chunk into a decode"

    seq = _engine(api, anchor, params, prefill_chunk=CHUNK,
                  scheduler="sequential")
    seq.generate(wl(), fmt_override="mxint8")
    assert max(t["execs"] for t in seq.tick_trace) == 2
    # the per-tick work bound is unchanged by the refactor
    for t in eng.tick_trace:
        assert t["prefill_chunks"] <= 1 and t["prefill_tokens"] <= CHUNK


def test_exhaustion_mid_chunk_requeues_not_leaks_mixed(setup):
    """Pool exhaustion mid-chunk under the mixed scheduler: release the
    partial admission's pages, requeue, retry after a retire — streams match
    a roomy run and alloc == freed (no leak), exactly as sequential."""
    cfg, api, params, anchor = setup
    rng = np.random.default_rng(1)
    p0 = rng.integers(0, cfg.vocab, 6).astype(np.int32)
    p1 = rng.integers(0, cfg.vocab, 22).astype(np.int32)
    mk = lambda: [Request(rid=0, prompt=p0.copy(), max_new=8),
                  Request(rid=1, prompt=p1.copy(), max_new=3)]

    roomy = _engine(api, anchor, params, max_len=32, kv_layout="paged",
                    kv_page_size=PS, prefill_chunk=CHUNK, scheduler="mixed")
    ref = mk()
    roomy.generate(ref, fmt_override="mxint8")

    eng = _engine(api, anchor, params, max_len=32, kv_layout="paged",
                  kv_page_size=PS, prefill_chunk=CHUNK, scheduler="mixed",
                  kv_num_pages=5)
    reqs = mk()
    eng.generate(reqs, fmt_override="mxint8")
    st = eng.stats
    assert all(r.done for r in reqs)
    assert st["admission_requeues"] >= 1
    assert st["kv_pages_alloc"] == st["kv_pages_freed"]       # no leak
    assert [r.out_tokens for r in reqs] == [r.out_tokens for r in ref]


def test_mq_rows_folded_counts_the_narrow_fold(setup):
    """stats() accounts the MQ kernel's fold: at a chunk wider than
    NARROW_LANES the decode rows of each mixed tick fold NARROW_LANES
    lanes, so fewer rows fold than the block holds; with no MQ call
    (sequential scheduler, gather impl) both counts stay equal, at 0. The
    streams are the sequential scheduler's, token for token."""
    cfg, api, params, anchor = setup
    chunk = 4 * CHUNK
    assert chunk > pa.NARROW_LANES
    kw = dict(batch_slots=4, max_len=64, kv_layout="paged", kv_page_size=PS,
              prefill_chunk=chunk)
    reqs = lambda: _reqs(cfg, 5, max_new=4, plens=(40, 9, 21, 3), seed=3)

    def run(scheduler, impl):
        eng = _engine(api, anchor, params, scheduler=scheduler,
                      attn_impl=impl, **kw)
        rs = reqs()
        eng.generate(rs, fmt_override="mxint8")
        assert all(r.done for r in rs)
        return [r.out_tokens for r in rs], eng.stats

    mixed, st = run("mixed", "paged_kernel")
    g = cfg.n_heads // cfg.n_kv_heads
    assert 0 < st["mq_rows_folded"] < st["mq_rows_padded"], st
    assert st["mq_rows_padded"] % (4 * chunk * g) == 0
    for sched, impl in (("sequential", "paged_kernel"), ("mixed", "gather")):
        streams, st0 = run(sched, impl)
        assert st0["mq_rows_folded"] == st0["mq_rows_padded"] == 0
        assert streams == mixed


def test_scheduler_knob_validation(setup):
    cfg, api, params, anchor = setup
    with pytest.raises(ValueError, match="prefill_chunk"):
        _engine(api, anchor, params, scheduler="mixed")
    with pytest.raises(ValueError, match="unknown scheduler"):
        _engine(api, anchor, params, prefill_chunk=CHUNK,
                scheduler="interleaved")
    # auto resolution: chunked admission defaults to the unified tick,
    # monolithic stays sequential
    assert _engine(api, anchor, params,
                   prefill_chunk=CHUNK).scheduler == "mixed"
    assert _engine(api, anchor, params).scheduler == "sequential"


def test_mixed_step_survives_api_chaining(setup):
    """The small-fix regression: ``mixed_step`` must survive ``with_qmm`` /
    ``with_serving`` chaining in either order, keeping the chained
    attn_impl — and the three knobs (fused qmm x paged_kernel x mixed
    scheduler) must compose end-to-end against the all-default path."""
    cfg, api, params, anchor = setup
    from repro.kernels.dispatch import make_qmm
    qmm = make_qmm(block_size=32, mode="pallas")

    a = api.with_serving(attn_impl="paged_kernel").with_qmm(qmm)
    b = api.with_qmm(qmm).with_serving(attn_impl="paged_kernel")
    for chained in (a, b):
        assert chained.mixed_step is not None
        assert chained.attn_impl == "paged_kernel"

    # three-knob composition: every knob flipped at once vs none
    kw = dict(kv_layout="paged", kv_page_size=PS)
    base, _ = _streams(api, anchor, params, cfg, "sequential", n=3,
                       fused=False, attn_impl="gather", **kw)
    full, _ = _streams(api, anchor, params, cfg, "mixed", n=3,
                       fused=True, attn_impl="paged_kernel", **kw)
    assert base == full


def test_execs_per_tick_invariant_survives_speculation(setup):
    """tick_trace splits ``draft_execs``/``verify_execs`` out of ``execs``
    precisely so this file's one-executable-per-tick claim stays
    assertable when speculation is on: a tick's PLAIN executables are
    ``execs - draft_execs - verify_execs``, and under the mixed scheduler
    that difference never exceeds one (a speculative tick replaces the
    single decode executable with the draft burst + one verify)."""
    from repro.serve.policy import SpecConfig
    cfg, api, params, anchor = setup
    eng = _engine(api, anchor, params, prefill_chunk=CHUNK,
                  scheduler="mixed", max_len=64,
                  speculative=SpecConfig(draft_fmt="mxint4", k=4))
    eng.generate(_reqs(cfg, 3, plens=(30, 8, 8), seed=2),
                 fmt_override="mxint8")
    assert any(t["draft_execs"] for t in eng.tick_trace), "never drafted"
    for t in eng.tick_trace:
        plain = t["execs"] - t["draft_execs"] - t["verify_execs"]
        assert 0 <= plain <= 1, t
        # spec only ever replaces the pure-decode executable: chunk ticks
        # keep the coalesced single-exec shape with no draft burst
        if t["prefill_chunks"]:
            assert t["draft_execs"] == 0 and t["verify_execs"] == 0, t


@pytest.mark.parametrize("fmt", ["mxint8", "mxint4"])
def test_prompt_logits_agree_across_contracts(setup, fmt):
    """prompt_logits runs the engine's own mixed-step executable: the fused
    dequant-GEMM + MQ paged kernel and the densify + gather reference give
    the same next-token logits, and their argmax is the first token greedy
    generate() serves for that prompt (a prompt spanning several chunks)."""
    cfg, api, params, anchor = setup
    prompt = _reqs(cfg, 2, plens=(21,))[1].prompt
    kw = dict(kv_layout="paged", kv_page_size=PS, prefill_chunk=CHUNK,
              scheduler="mixed")
    fused = _engine(api, anchor, params, fused=True,
                    attn_impl="paged_kernel", **kw)
    ref = _engine(api, anchor, params, fused=False, attn_impl="gather", **kw)
    got = fused.prompt_logits(prompt, fmt)
    want = ref.prompt_logits(prompt, fmt)
    assert got.shape == (cfg.vocab,) and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    req = Request(rid=0, prompt=prompt, max_new=1)
    fused.generate([req], fmt_override=fmt)
    assert req.out_tokens == [int(np.argmax(got))]


def test_prompt_logits_needs_mixed_paged_engine(setup):
    cfg, api, params, anchor = setup
    eng = _engine(api, anchor, params, fused=False)
    with pytest.raises(ValueError, match="scheduler='mixed'"):
        eng.prompt_logits(np.arange(1, 9, dtype=np.int32), "mxint8")
