"""Pallas TPU kernel: gather-free paged decode attention over the block table.

``paged_gather`` (models/layers.py) made paged serving *correct* by
materializing each slot's logical KV view — (B, max_pages*page_size, Hkv, D)
per layer per tick — before the masked softmax, so attention-side HBM
traffic and scratch footprint still scaled with ``max_len`` rather than live
tokens. This kernel is the PagedAttention move (Kwon et al., SOSP 2023): it
consumes the page pools and the per-slot block table *directly*.

Grid = (slot, logical KV block). Each step translates logical block ``j`` →
physical page via the scalar-prefetched block table (the index map picks the
page, so only the pages a slot actually occupies are ever DMA'd into VMEM)
and folds one page into a flash-style running (max, sum-exp, acc) partial
softmax held in VMEM scratch. Steps past the live frontier revisit the last
live page — Pallas skips the DMA when the block index repeats — so per-slot
KV reads are ``ceil(cache_len/page_size)`` pages, not ``max_pages``.

Masking is IN-KERNEL and total: a position contributes iff
``pos < cache_len`` (and, with a sliding window, ``pos >= cache_len - W``).
Scores at dead positions are forced to -inf *before* the running max,
probabilities are re-zeroed after the exp, and V rows are zeroed before the
PV product — so garbage beyond the write frontier, scratch-page-0 contents,
and unallocated pages never enter the reduction, **even when they hold NaN
or ±1e9** (0 * NaN = NaN, which is why masking only the scores is not
enough; the adversarial poison tests in tests/test_paged_attention_kernel.py
hold this line). ``cache_len == 0`` rows produce exact zeros (the dense
reference NaNs there — no valid key exists; the engine never emits it since
decode always appends before attending).

GQA (``Hkv != H``) runs natively: queries fold to (Hkv, G, D) and every
reduction stays per-kv-head, matching ``decode_attention``.

The multi-query variant (``paged_attention_pallas_mq``) generalizes the
grid to (slot, q block, logical KV block) for the unified mixed
prefill+decode tick: each row carries a ragged span of ``q_len`` queries at
cursor ``q_offset`` (decode rows 1, the mid-prefill row a whole chunk), the
causal mask is per query lane (``pos <= q_offset + i``), and the same
clamped block-table walk bounds DMA to the pages each q block's live lanes
can see (``pages_read_mq``). It subsumes the single-query kernel
(``q_len == 1`` rows cost and compute identically) and retires the
gather-based chunked-prefill read path on TPU.

Dispatch (mirroring kernels/dispatch.py): ``paged_decode_attention`` is the
serving entry point. Mode "pallas" runs this kernel — Mosaic on TPU,
interpret-mode elsewhere (the test/CI correctness path); mode "fallback"
keeps the original gather + ``decode_attention`` pair; "auto" picks
"pallas" on TPU. Trace-time ``stats()`` counters let benchmarks and the
``kernels_bench.py --smoke`` CI gate assert which path is live.

Layout/placement conventions are documented in docs/serving_internals.md §5.

Tensor parallelism: every dimension here — Hkv, page size, page count — is
derived from the INPUT shapes, never from a model config, so under the
head-sharded serving mesh (docs §11) the kernels run unchanged on each
shard's local slice of the pools (kv-head axis split across chips) with the
REPLICATED block table and its global page ids. The grid covers local pages
only; no collective appears at this layer (attention is exactly per-kv-head
parallel — the psum lives in the wo projection above).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# ---------------------------------------------------------------------------
# Mode resolution + trace-time accounting (kernels/dispatch.py conventions)
# ---------------------------------------------------------------------------
MODES = ("auto", "pallas", "fallback")

_stats: Dict[str, int] = {"pallas": 0, "fallback": 0,
                          "pallas_mq": 0, "fallback_mq": 0}


def stats() -> Dict[str, int]:
    """Trace-time counts of which paged-attention path was dispatched."""
    return dict(_stats)


def reset_stats() -> None:
    for k in _stats:
        _stats[k] = 0


def default_mode() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "fallback"


def resolve_mode(mode: Optional[str]) -> str:
    if mode is None or mode == "auto":
        return default_mode()
    if mode not in ("pallas", "fallback"):
        raise ValueError(
            f"unknown paged-attention mode {mode!r}; one of {MODES}")
    return mode


def _interpret() -> bool:
    # Mosaic only lowers on TPU; everywhere else the kernel body runs in the
    # Pallas interpreter (exactly as written — the CI correctness contract).
    return jax.default_backend() != "tpu"


def pages_read(length: int, page_size: int,
               window: Optional[int] = None) -> int:
    """Distinct pages one slot's block-table walk DMAs for ``length`` live
    tokens — THE host-side mirror of ``kv_index``'s clamp arithmetic below
    (the engine's attention-read accounting must use this, never reimplement
    it, so the metric stays definitionally consistent with the kernel).
    Zero-length rows still fetch the clamped page 0 once."""
    pages = max(-(-length // page_size), 1)
    if window is not None:
        pages -= min(max((length - window) // page_size, 0), pages - 1)
    return pages


def pages_read_mq(q_offset: int, q_len: int, page_size: int,
                  window: Optional[int] = None) -> int:
    """Distinct pages the multi-query walk DMAs for one row whose ``q_len``
    queries sit at positions ``q_offset .. q_offset + q_len - 1`` — the
    host-side mirror of the MQ ``kv_index`` clamp below (single q block).
    The highest query attends up to ``q_offset + q_len`` positions; the
    lowest query's window lower-bounds the walk. ``q_len == 1`` collapses
    to ``pages_read(q_offset + 1, ...)`` — decode rows in a mixed batch
    cost exactly what they cost in the single-query kernel."""
    last = max(-(-(q_offset + q_len) // page_size) - 1, 0)
    first = 0
    if window is not None:
        first = min(max((q_offset + 1 - window) // page_size, 0), last)
    return last - first + 1


# ---------------------------------------------------------------------------
# The kernels
# ---------------------------------------------------------------------------
# Mosaic-facing layout: one layer's pool is (P, ps, Hkv*D), so a page block
# (ps, Hkv*D) tiles on its last two dims and kv head h is the static lane
# slice [h*D, (h+1)*D). Queries arrive head-grouped as (Hkv, rows, D) and
# every product is a plain 2-D dot per kv head — no in-kernel reshape of a
# tiled value, which Mosaic refuses. Running (max, sum-exp) are (rows, 1)
# columns per kv head.
def _init_scratch(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _fold_page(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, mask, vvalid, *,
               hkv: int, d: int):
    """Fold one page into every kv head's flash partial softmax.

    ``mask`` (rows, ps) says which (query row, key) pairs are live,
    ``vvalid`` (ps, 1) which V rows may enter the PV product. Masking is
    total: scores are -inf'd BEFORE the max (dead positions may hold NaN —
    poisoned / recycled pages — and NaN propagates through jnp.maximum), p
    is re-zeroed after the exp, and V rows are zeroed too (0 * NaN = NaN in
    the PV product) — this triple is what the NaN-poison tests pin down.
    ``alpha`` keeps the ``m == -inf`` guard: a row that has not seen a
    valid position yet would otherwise NaN on exp(-inf - -inf).
    """
    scale = 1.0 / (d ** 0.5)
    for h in range(hkv):
        cols = slice(h * d, (h + 1) * d)
        q = q_ref[0, h].astype(jnp.float32)                  # (rows, D)
        k = k_ref[0, :, cols].astype(jnp.float32)            # (ps, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask, s, -jnp.inf)
        m_prev = m_ref[h]                                    # (rows, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(jnp.where(m_new > -jnp.inf, m_prev - m_new, 0.0))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        v = jnp.where(vvalid, v_ref[0, :, cols].astype(jnp.float32), 0.0)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[h] = m_new


def _finalize(o_ref, l_ref, acc_ref, *, hkv: int):
    for h in range(hkv):
        l = l_ref[h]
        out = acc_ref[h] / jnp.maximum(l, 1e-30)
        # rows that saw no valid position (cache_len == 0, dead lanes) -> 0
        o_ref[0, h] = jnp.where(l > 0, out, 0.0).astype(o_ref.dtype)


def _paged_attn_kernel(bt_ref, cl_ref, q_ref, k_ref, v_ref, o_ref,
                       m_ref, l_ref, acc_ref, *,
                       page_size: int, window: Optional[int],
                       hkv: int, d: int):
    """One (slot, logical-block) grid step of the flash partial softmax.

    ``bt_ref``/``cl_ref`` are the scalar-prefetched block table and
    cache_len (also consumed by the index maps); ``k_ref``/``v_ref`` hold
    ONE physical page each — the page this slot's block ``j`` maps to.
    Scratch (m, l, acc) persists across the j-minor grid walk of a slot.
    """
    b = pl.program_id(0)
    j = pl.program_id(1)
    g = q_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        _init_scratch(m_ref, l_ref, acc_ref)

    cache_len = cl_ref[b]

    def live(pos):
        ok = pos < cache_len
        if window is not None:
            ok &= pos >= cache_len - window
        return ok

    mask = live(j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (g, page_size), 1))
    vvalid = live(j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (page_size, 1), 0))

    # Skip pages with no live position: keeps the running max finite and
    # skips the FLOPs past the frontier.
    @pl.when(jnp.any(vvalid))
    def _accumulate():
        _fold_page(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, mask, vvalid,
                   hkv=hkv, d=d)

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        _finalize(o_ref, l_ref, acc_ref, hkv=hkv)


def _pool_heads(k_pages: jax.Array, d: int):
    """(page_size, Hkv) of a (P, ps, Hkv*D) pool for head dim ``d``."""
    ps, width = k_pages.shape[1], k_pages.shape[2]
    assert width % d == 0, (k_pages.shape, d)
    return ps, width // d


def paged_attention_pallas(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_table: jax.Array,
                           cache_len: jax.Array, *,
                           window: Optional[int] = None,
                           interpret: bool = False) -> jax.Array:
    """Single-token attention straight off the page pool: (B, H, D) f32.

    q (B, H, D); k_pages/v_pages (P, page_size, Hkv*D) — ONE layer's pool,
    kv heads flattened head-major into the last axis; block_table
    (B, max_pages) int32 physical page ids (0 = unmapped / scratch);
    cache_len (B,) int32 live lengths (may be traced). The block table and
    cache_len ride as scalar-prefetch operands so the KV index maps can
    translate logical block → physical page before each DMA.
    """
    b, h, d = q.shape
    ps, hkv = _pool_heads(k_pages, d)
    mp = block_table.shape[1]
    assert h % hkv == 0, (h, hkv)
    g = h // hkv

    def kv_index(bi, j, bt, cl):
        # Clamp the walk to the live block range: steps outside it revisit
        # the nearest live page, and Pallas elides the DMA when the index
        # repeats — the bytes-read term drops from max_pages to
        # ceil(cache_len/ps) pages (to the ~window/ps in-window pages when
        # sliding; blocks below the window hold no valid position, their
        # compute is @pl.when-skipped, so revisiting the first in-window
        # page is safe).
        last = jnp.maximum(pl.cdiv(cl[bi], ps) - 1, 0)
        jc = jnp.minimum(j, last)
        if window is not None:
            first = jnp.clip((cl[bi] - window) // ps, 0, last)
            jc = jnp.maximum(jc, first)
        return (bt[bi, jc], 0, 0)

    q_spec = pl.BlockSpec((1, hkv, g, d), lambda bi, j, bt, cl: (bi, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mp),
        in_specs=[q_spec,
                  pl.BlockSpec((1, ps, hkv * d), kv_index),
                  pl.BlockSpec((1, ps, hkv * d), kv_index)],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((hkv, g, 1), jnp.float32),    # running max
            pltpu.VMEM((hkv, g, 1), jnp.float32),    # running sum-exp
            pltpu.VMEM((hkv, g, d), jnp.float32),    # running PV acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, page_size=ps, window=window,
                          hkv=hkv, d=d),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), jnp.float32),
        interpret=interpret,
    )(block_table, cache_len, q.reshape(b, hkv, g, d), k_pages, v_pages)
    return out.reshape(b, h, d)


# ---------------------------------------------------------------------------
# Multi-query extension: ragged rows of a mixed prefill+decode batch
# ---------------------------------------------------------------------------
def _paged_attn_mq_kernel(bt_ref, qo_ref, ql_ref, q_ref, k_ref, v_ref, o_ref,
                          m_ref, l_ref, acc_ref, *,
                          page_size: int, window: Optional[int],
                          hkv: int, d: int, g: int, tq: int):
    """One (slot, q-block, logical-KV-block) grid step.

    The q_len==1 kernel above with a query axis: each kv head's query rows
    are ``tq`` lanes x ``g`` grouped heads, row ``r`` = lane ``r // g``;
    lane ``i`` of block ``qi`` sits at logical position
    ``q_offset + qi*tq + i`` and is live iff ``qi*tq + i < q_len``.
    Scratch persists across the j-minor KV walk of one (slot, q block).
    Unlike the single-query kernel, a page the walk visits can be live for
    some lanes and dead for others, so the running max is per row.
    """
    b = pl.program_id(0)
    qi = pl.program_id(1)
    j = pl.program_id(2)
    rows = tq * g

    @pl.when(j == 0)
    def _init():
        _init_scratch(m_ref, l_ref, acc_ref)

    q_offset = qo_ref[b]
    q_len = ql_ref[b]
    live = q_offset + q_len                 # KV frontier after this tick
    base = q_offset + qi * tq               # position of the block's lane 0
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, page_size), 0)
    pos = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (rows, page_size), 1)
    # (rows, ps), with lane i = r // g compared through r alone (no vector
    # integer division): causal self-inclusive (pos <= base + i), clipped
    # at the frontier, dead for pad lanes (i < q_len - qi*tq), windowed
    # (base + i - pos < W).
    mask = (r >= (pos - base) * g) & (pos < live)
    mask &= r < (q_len - qi * tq) * g
    if window is not None:
        mask &= r < (pos + window - base) * g
    vvalid = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (page_size, 1), 0) < live

    @pl.when(jnp.any(mask))
    def _accumulate():
        _fold_page(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, mask, vvalid,
                   hkv=hkv, d=d)

    @pl.when(j == pl.num_programs(2) - 1)
    def _done():
        _finalize(o_ref, l_ref, acc_ref, hkv=hkv)


def paged_attention_pallas_mq(q: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array, block_table: jax.Array,
                              q_offset: jax.Array, q_len: jax.Array, *,
                              window: Optional[int] = None,
                              tq: Optional[int] = None,
                              interpret: bool = False) -> jax.Array:
    """Ragged multi-query attention off the page pool: (B, C, H, D) f32.

    q (B, C, H, D) — row b's query ``i`` sits at logical position
    ``q_offset[b] + i`` and is live iff ``i < q_len[b]`` (decode rows carry
    C-1 dead pad lanes; the mid-prefill row is mostly live). The pools
    (P, page_size, Hkv*D) must already hold each row's new K/V at those
    positions. ``tq`` is the q block size (defaults to C — one block; must
    divide C, and on TPU ``tq * H/Hkv`` must be a multiple of 8 unless it
    is C); the KV walk per (row, q block) is clamped to the pages that
    block's live queries can see, so DMA cost follows ``pages_read_mq``,
    and dead q blocks collapse to one elided page. Dead lanes output exact
    zeros.
    """
    b, c, h, d = q.shape
    ps, hkv = _pool_heads(k_pages, d)
    mp = block_table.shape[1]
    assert h % hkv == 0, (h, hkv)
    g = h // hkv
    tq = c if tq is None else tq
    assert c % tq == 0, (c, tq)
    nq = c // tq

    def kv_index(bi, qi, j, bt, qo, ql):
        # Clamp the walk to [first in-window page of the block's lowest
        # query, last page its highest LIVE query can see]; out-of-range
        # steps revisit a live page and Pallas elides the repeat DMA.
        hi = qo[bi] + jnp.minimum((qi + 1) * tq, ql[bi])
        last = jnp.maximum(pl.cdiv(hi, ps) - 1, 0)
        jc = jnp.minimum(j, last)
        if window is not None:
            first = jnp.clip((qo[bi] + qi * tq + 1 - window) // ps, 0, last)
            jc = jnp.maximum(jc, first)
        return (bt[bi, jc], 0, 0)

    # head-grouped rows: (B, Hkv, C*G, D), row = lane * G + grouped head
    qg = q.reshape(b, c, hkv, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, c * g, d)
    q_spec = pl.BlockSpec((1, hkv, tq * g, d),
                          lambda bi, qi, j, bt, qo, ql: (bi, 0, qi, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nq, mp),
        in_specs=[q_spec,
                  pl.BlockSpec((1, ps, hkv * d), kv_index),
                  pl.BlockSpec((1, ps, hkv * d), kv_index)],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((hkv, tq * g, 1), jnp.float32),   # running max
            pltpu.VMEM((hkv, tq * g, 1), jnp.float32),   # running sum-exp
            pltpu.VMEM((hkv, tq * g, d), jnp.float32),   # running PV acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_attn_mq_kernel, page_size=ps, window=window,
                          hkv=hkv, d=d, g=g, tq=tq),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, c * g, d), jnp.float32),
        interpret=interpret,
    )(block_table, q_offset, q_len, qg, k_pages, v_pages)
    return out.reshape(b, hkv, c, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, c, h, d)


# ---------------------------------------------------------------------------
# Serving dispatch shim
# ---------------------------------------------------------------------------
def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_table: jax.Array,
                           cache_len: jax.Array, *,
                           window: Optional[int] = None,
                           mode: Optional[str] = None) -> jax.Array:
    """Paged decode attention: q (B, 1, H, D) over the page pool -> same.

    The paged counterpart of ``decode_attention`` and the entry point
    ``attention_block``'s paged-decode branch routes through. ``mode``:

      "pallas"    the gather-free kernel above (Mosaic on TPU, interpret
                  elsewhere — the test path). ``attn_impl="paged_kernel"``.
      "fallback"  ``paged_gather`` + masked ``decode_attention`` — the
                  original materialize-then-attend pair, kept selectable for
                  comparison. ``attn_impl="gather"``.
      "auto"/None "pallas" on TPU, "fallback" elsewhere.

    ``cache_len`` must already include this tick's appended token (callers
    pass ``cache_len + 1``, exactly as for ``decode_attention``).
    """
    if resolve_mode(mode) == "pallas":
        _stats["pallas"] += 1
        out = paged_attention_pallas(q[:, 0], k_pages, v_pages, block_table,
                                     cache_len, window=window,
                                     interpret=_interpret())
        return out[:, None].astype(q.dtype)
    _stats["fallback"] += 1
    from repro.models.layers import decode_attention, paged_gather
    d = q.shape[-1]
    return decode_attention(q, paged_gather(k_pages, block_table, d),
                            paged_gather(v_pages, block_table, d),
                            cache_len, window=window)


def paged_mixed_attention(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, block_table: jax.Array,
                          q_offset: jax.Array, q_len: jax.Array, *,
                          window: Optional[int] = None,
                          mode: Optional[str] = None,
                          tq: Optional[int] = None) -> jax.Array:
    """Mixed-batch attention: ragged q (B, C, H, D) over the page pool.

    The multi-query counterpart of ``paged_decode_attention`` and the entry
    point ``attention_block``'s mixed branch routes through — one call
    serves the whole unified tick: decode rows at ``q_len == 1``, the
    mid-prefill row at its chunk width, pad lanes dead. ``mode``:

      "pallas"    the gather-free MQ kernel above (Mosaic on TPU, interpret
                  elsewhere). ``attn_impl="paged_kernel"`` — this retires
                  the gather-based chunked-prefill read path on TPU.
      "fallback"  ``paged_gather`` + masked ``mixed_attention`` — the
                  materialize-then-attend pair. ``attn_impl="gather"``.
      "auto"/None "pallas" on TPU, "fallback" elsewhere.

    The pool must already hold each row's new K/V (callers write through
    ``paged_mixed_update`` first); dead lanes output exact zeros on both
    paths.
    """
    if resolve_mode(mode) == "pallas":
        _stats["pallas_mq"] += 1
        out = paged_attention_pallas_mq(q, k_pages, v_pages, block_table,
                                        q_offset, q_len, window=window,
                                        tq=tq, interpret=_interpret())
        return out.astype(q.dtype)
    _stats["fallback_mq"] += 1
    from repro.models.layers import mixed_attention, paged_gather
    d = q.shape[-1]
    return mixed_attention(q, paged_gather(k_pages, block_table, d),
                           paged_gather(v_pages, block_table, d),
                           q_offset, q_len, window=window)
