"""Transformer building blocks: norms, RoPE, flash attention, MLP, MoE.

Attention is a chunked flash implementation in pure jnp (online softmax over
KV chunks, O(S) memory) with optional sliding-window *banding* that slices
only the needed KV range per query chunk — SWA prefill costs O(S·W) compute,
not O(S^2). Decode uses a direct single-query path whose reductions partition
over a sequence-sharded KV cache.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig, QuantCtx
from repro.sharding.rules import shard_act


# =============================================================================
# Norms / RoPE
# =============================================================================
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps)) * scale.astype(jnp.float32)) \
        .astype(dt)


def layer_norm(x, scale, bias, eps=1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, -1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x (..., S, H, D), positions (..., S) -> rotated (llama half-split)."""
    d = x.shape[-1]
    half = d // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * inv_freq  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]          # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# =============================================================================
# Flash attention (chunked online softmax)
# =============================================================================
def _attend_block(q, k, v, q_pos, k_pos, causal, window, scale):
    """One (cq x ck) score block with masking. q (B,cq,Hkv,G,D)."""
    s = jnp.einsum("bqkgd,btkd->bkgqt", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask &= (q_pos[:, None] - k_pos[None, :]) < window
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    return s


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    q_offset: int = 0, chunk: int = 1024) -> jax.Array:
    """q (B,Sq,H,D), k/v (B,Skv,Hkv,D) -> (B,Sq,H,D).

    Scans query chunks (outer) and KV chunks (inner) with a running
    (max, denom, acc) online softmax. With a sliding window, only the banded
    KV range [t0-W, t0+cq) is sliced per query chunk.
    """
    b, sq, h, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    scale = 1.0 / (d ** 0.5)
    cq = min(chunk, sq)
    while sq % cq:
        cq //= 2
    cq = max(cq, 1)

    banded = window is not None and causal and skv > window
    if banded:
        band = min(skv, window + cq)
    qg = q.reshape(b, sq, hkv, g, d)

    def q_step(_, qi):
        qc = jax.lax.dynamic_slice_in_dim(qg, qi * cq, cq, axis=1)
        q_pos = q_offset + qi * cq + jnp.arange(cq)

        if banded:
            start = jnp.clip(q_offset + qi * cq + cq - band, 0, skv - band)
            kc = jax.lax.dynamic_slice_in_dim(k, start, band, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, start, band, axis=1)
            k_pos = start + jnp.arange(band)
            s = _attend_block(qc, kc, vc, q_pos, k_pos, causal, window, scale)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - jax.lax.stop_gradient(m))
            num = jnp.einsum("bkgqt,btkd->bqkgd", p, vc.astype(jnp.float32))
            den = jnp.sum(p, axis=-1)                     # (b,hkv,g,cq)
            out = num / den.transpose(0, 3, 1, 2)[..., None]
            return None, out.reshape(b, cq, h, d).astype(q.dtype)

        ck = min(chunk, skv)
        while skv % ck:
            ck //= 2
        nk = skv // ck

        def kv_step(carry, ki):
            m, l, acc = carry
            kc = jax.lax.dynamic_slice_in_dim(k, ki * ck, ck, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, ki * ck, ck, axis=1)
            k_pos = ki * ck + jnp.arange(ck)
            s = _attend_block(qc, kc, vc, q_pos, k_pos, causal, window, scale)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l = l * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqt,btkd->bkgqd", p, vc.astype(jnp.float32))
            acc = acc * alpha[..., None] + pv
            return (m_new, l, acc), None

        m0 = jnp.full((b, hkv, g, cq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, cq), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, cq, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, cq, h, d)
        return None, out.astype(q.dtype)

    nq = sq // cq
    _, outs = jax.lax.scan(q_step, None, jnp.arange(nq))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, d)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array,
                     window: Optional[int] = None) -> jax.Array:
    """Single-token attention: q (B,1,H,D) over cache (B,Skv,Hkv,D).

    Non-scanned so the softmax reductions partition over a sequence-sharded
    cache (GSPMD turns them into psums over the `model` axis).
    """
    b, _, h, d = q.shape
    skv, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    qg = q.reshape(b, hkv, g, d)
    s = jnp.einsum("bkgd,btkd->bkgt", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) / (d ** 0.5)
    pos = jnp.arange(skv)
    valid = pos[None, :] < cache_len[:, None]                    # (B, Skv)
    if window is not None:
        valid &= pos[None, :] >= (cache_len[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    den = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bkgt,btkd->bkgd", p / den,
                     v_cache.astype(jnp.float32))
    return out.reshape(b, 1, h, d).astype(q.dtype)


def mixed_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                    q_offset: jax.Array, q_len: jax.Array,
                    window: Optional[int] = None) -> jax.Array:
    """Ragged multi-query attention: q (B,C,H,D) over cache (B,Skv,Hkv,D).

    The multi-query generalization of ``decode_attention`` for the unified
    mixed prefill+decode tick: query ``i`` of row ``b`` sits at logical
    position ``q_offset[b] + i``; lanes with ``i < q_len[b]`` attend
    causally (self-inclusive, so each query sees its own just-written K/V)
    over positions below the row's frontier ``q_offset + q_len``, within
    the sliding window; dead pad lanes output exact zeros. Deliberately the
    same op sequence as ``decode_attention`` (einsum / mask / max / exp /
    sum / div) with one extra query axis, so a ``q_len == 1`` row's output
    stays bit-identical to the single-query path on this backend — the
    mixed-vs-sequential stream-identity contract rests on that.
    """
    b, c, h, d = q.shape
    skv, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    qg = q.reshape(b, c, hkv, g, d)
    s = jnp.einsum("bikgd,btkd->bkgit", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) / (d ** 0.5)
    pos = jnp.arange(skv)
    qpos = q_offset[:, None] + jnp.arange(c)[None]               # (B, C)
    live = jnp.arange(c)[None] < q_len[:, None]                  # (B, C)
    valid = pos[None, None, :] <= qpos[:, :, None]               # (B, C, Skv)
    valid &= pos[None, None, :] < (q_offset + q_len)[:, None, None]
    valid &= live[..., None]
    if window is not None:
        valid &= (qpos[:, :, None] - pos[None, None, :]) < window
    s = jnp.where(valid[:, None, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    den = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bkgit,btkd->bikgd", p / den,
                     v_cache.astype(jnp.float32))
    # Dead lanes divide 0/0 -> NaN; force the kernel's exact-zeros contract
    # (live lanes always have >= 1 valid position: their own).
    out = jnp.where(live[..., None, None, None], out, 0.0)
    return out.reshape(b, c, h, d).astype(q.dtype)


# =============================================================================
# Paged KV cache (block-table indirection over a shared page pool)
# =============================================================================
# Layout (see docs/serving_internals.md): each attention layer owns a page
# pool (num_pages, page_size, Hkv*D) — kv heads flattened head-major into the
# last axis, so a page is one (page_size, Hkv*D) tile the paged-attention
# kernels DMA and slice per head without reshaping; a slot's KV lives in the
# physical pages
# its block-table row names, in logical order — position p maps to page
# row[p // page_size], offset p % page_size. Page 0 is a reserved scratch
# page: unmapped block-table entries point at it, so retired slots scribble
# there instead of on recycled pages, and every read of it is masked by
# cache_len. Values at any *valid* position (< cache_len) are bit-identical
# to the dense layout's, which is what makes dense-vs-paged token identity a
# testable contract rather than a tolerance.


def paged_prefill_update(pool: jax.Array, kv_new: jax.Array,
                         block_table: jax.Array,
                         start_pos=0) -> jax.Array:
    """Scatter prefill K/V (B, S, Hkv, D) into the pages each row maps.

    S is zero-padded up to a whole number of pages (matching the dense
    layout, whose cache is zero beyond the written range). Rows' mapped
    pages are disjoint by construction (the engine allocates each physical
    page to at most one slot), so the batched scatter never collides —
    except on the scratch page 0, where last-write-wins is harmless.

    ``start_pos`` (scalar, may be traced) is the logical position of the
    first written token — chunked prefill resumes at its cursor. It must be
    page-aligned (the engine aligns chunk boundaries to pages by
    construction), so the write covers pages
    ``start_pos // page_size .. + ceil(S/page_size)``.
    """
    b, s, hkv, d = kv_new.shape
    ps = pool.shape[1]
    n_p = -(-s // ps)
    pad = n_p * ps - s
    if pad:
        kv_new = jnp.pad(kv_new, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vals = kv_new.astype(pool.dtype).reshape(b * n_p, ps, hkv * d)
    ids = jax.lax.dynamic_slice_in_dim(block_table, start_pos // ps, n_p,
                                       axis=1).reshape(-1)
    return pool.at[ids].set(vals)


def paged_decode_append(pool: jax.Array, kv_tok: jax.Array,
                        block_table: jax.Array,
                        cache_len: jax.Array) -> jax.Array:
    """Write one token's K/V (B, 1, Hkv, D) at each slot's cache_len.

    The engine maps the destination page before the tick runs, so the
    translated (page, offset) is always a live page for active slots; free
    slots land on scratch page 0.
    """
    ps = pool.shape[1]
    phys = jnp.take_along_axis(block_table, (cache_len // ps)[:, None],
                               axis=1)[:, 0]
    tok = kv_tok[:, 0].reshape(kv_tok.shape[0], -1)
    return pool.at[phys, cache_len % ps].set(tok.astype(pool.dtype))


def mixed_cache_update(cache: jax.Array, kv_new: jax.Array,
                       cache_len: jax.Array, q_len: jax.Array) -> jax.Array:
    """Ragged multi-token append into a dense cache (B, Smax, Hkv, D).

    Row ``b``'s token ``i`` of ``kv_new`` (B, C, Hkv, D) lands at position
    ``cache_len[b] + i`` when ``i < q_len[b]``; pad lanes scatter out of
    bounds and are dropped. NOT ``dynamic_update_slice`` — that clamps the
    *start* index, so a width-C write for a decode row near capacity would
    slide backwards onto live positions; per-token drop semantics can
    never do that.
    """
    b, c = kv_new.shape[:2]
    smax = cache.shape[1]
    idx = cache_len[:, None] + jnp.arange(c)[None]
    idx = jnp.where(jnp.arange(c)[None] < q_len[:, None], idx, smax)
    bidx = jnp.broadcast_to(jnp.arange(b)[:, None], (b, c))
    return cache.at[bidx, idx].set(kv_new.astype(cache.dtype), mode="drop")


def paged_mixed_update(pool: jax.Array, kv_new: jax.Array,
                       block_table: jax.Array, cache_len: jax.Array,
                       q_len: jax.Array) -> jax.Array:
    """Ragged multi-token append through the block table.

    Position ``cache_len[b] + i`` (``i < q_len[b]``) maps to page
    ``block_table[b, pos // ps]``, offset ``pos % ps``; pad lanes and
    positions past the table redirect to scratch page 0 with zero values
    (collisions there are harmless — every read of page 0 is masked). The
    engine maps each row's pages before the tick (decode rows at page
    boundaries, the mid-prefill row per chunk), so valid writes always
    land on live pages, which are disjoint across slots. Unlike
    ``paged_prefill_update`` the final-chunk page tail is NOT zero-filled:
    garbage past the frontier stays finite-or-masked, the same invariant
    recycled pages already rely on.
    """
    ps = pool.shape[1]
    mp = block_table.shape[1]
    c = kv_new.shape[1]
    pos = cache_len[:, None] + jnp.arange(c)[None]               # (B, C)
    valid = jnp.arange(c)[None] < q_len[:, None]
    blk = jnp.clip(pos // ps, 0, mp - 1)
    phys = jnp.take_along_axis(block_table, blk, axis=1)
    phys = jnp.where(valid & (pos // ps < mp), phys, 0)
    vals = jnp.where(valid[..., None], kv_new.reshape(*pos.shape, -1)
                     .astype(pool.dtype), 0)
    return pool.at[phys, pos % ps].set(vals)


def paged_gather(pool: jax.Array, block_table: jax.Array,
                 head_dim: int) -> jax.Array:
    """Materialize each slot's logical KV view: (B, max_pages*ps, Hkv, D).

    A plain gather — positions come back in logical order, so the result
    drops into ``decode_attention`` exactly like a dense cache (garbage past
    cache_len is masked there, same as dense pad positions).

    This is the decode *fallback*, not the decode path: the default paged
    decode on TPU is the gather-free Pallas kernel in
    ``kernels/paged_attention.py``, which consumes the pool + block table
    directly and never materializes this dense view — attention reads scale
    with live tokens, not ``max_pages*ps``. The pair stays selectable via
    ``attn_impl="gather"`` (see ``paged_decode_attention`` and
    docs/serving_internals.md §5); chunked *prefill* still reads through
    this gather (its flash queries span the whole cache).
    """
    b, mp = block_table.shape
    pages = pool[block_table]                 # (B, MP, ps, Hkv*D)
    return pages.reshape(b, mp * pool.shape[1], -1, head_dim)


# =============================================================================
# Attention block
# =============================================================================
def attention_block(ctx: QuantCtx, x: jax.Array, p, cfg: ModelConfig,
                    positions: jax.Array, name: str,
                    kv_cache: Optional[Tuple] = None,
                    cache_len: Optional[jax.Array] = None,
                    cross_kv: Optional[Tuple] = None,
                    causal: bool = True,
                    block_table: Optional[jax.Array] = None,
                    chunk_start: Optional[jax.Array] = None,
                    q_len: Optional[jax.Array] = None,
                    attn_impl: str = "gather"):
    """Self- (or cross-) attention. Returns (out, new_kv) where new_kv is the
    (k, v) tensors produced at this layer (for cache building) or the updated
    cache in decode mode.

    With ``block_table`` set, ``kv_cache`` holds paged pools
    (num_pages, page_size, Hkv*D): the new token is appended through the
    block-table indirection and attention runs over the pool via
    ``paged_decode_attention`` — ``attn_impl="paged_kernel"`` consumes the
    block table directly in the gather-free Pallas kernel
    (kernels/paged_attention.py), ``"gather"`` materializes the slot's pages
    back into logical order first and feeds the same masked single-query
    softmax. Both read identical KV values at every valid position.

    With ``chunk_start`` set (chunked prefill; see docs/serving_internals.md
    "Admission & scheduling"), ``x`` is one prompt *chunk* whose first token
    sits at logical position ``chunk_start``: the chunk's K/V are written
    into ``kv_cache`` at that offset and its queries run flash attention
    over the whole cache with ``q_offset=chunk_start`` — the causal mask
    exposes exactly positions ``< chunk_start + S`` (everything this
    request's earlier chunks wrote, plus the chunk itself; stale data from a
    slot's previous occupant only ever sits at higher positions).

    With ``q_len`` set (the unified mixed prefill+decode tick; see
    docs/serving_internals.md §6), ``x`` is a ragged (B, C) batch: row
    ``b``'s first ``q_len[b]`` tokens are real and sit at positions
    ``cache_len[b] + i`` — decoding rows carry 1, the mid-prefill row its
    chunk. Each row's valid K/V are written at its own cursor (through the
    block table when paged), pad lanes are dropped, and attention runs the
    ragged multi-query path: ``mixed_attention`` on dense/gather,
    ``paged_mixed_attention`` (the MQ Pallas kernel) under
    ``attn_impl="paged_kernel"``."""
    b, s, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd

    if cross_kv is None:
        q = ctx.dense(x, p["wq"], name + ".wq", p.get("bq"))
        k = ctx.dense(x, p["wk"], name + ".wk", p.get("bk"))
        v = ctx.dense(x, p["wv"], name + ".wv", p.get("bv"))
        q = q.reshape(b, s, h, hd)
        k = k.reshape(b, s, hkv, hd)
        v = v.reshape(b, s, hkv, hd)
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"], cfg.norm_eps)
            k = rms_norm(k, p["k_norm"], cfg.norm_eps)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        q = shard_act(q, ("batch", None, "heads", None))
        k = shard_act(k, ("batch", None, "kv_heads", None))
    else:
        q = ctx.dense(x, p["wq"], name + ".wq").reshape(b, s, h, hd)
        k, v = cross_kv

    if kv_cache is not None and chunk_start is not None:
        # chunked prefill: write this chunk's K/V at the cursor, then attend
        # the chunk's queries over the cache (same flash kernel as monolithic
        # prefill — q_offset shifts the causal mask to the cursor).
        kc, vc = kv_cache
        if block_table is not None:
            kc = paged_prefill_update(kc, k, block_table,
                                      start_pos=chunk_start)
            vc = paged_prefill_update(vc, v, block_table,
                                      start_pos=chunk_start)
            k_view = paged_gather(kc, block_table, hd)
            v_view = paged_gather(vc, block_table, hd)
        else:
            kc = jax.lax.dynamic_update_slice_in_dim(
                kc, k.astype(kc.dtype), chunk_start, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(
                vc, v.astype(vc.dtype), chunk_start, axis=1)
            k_view, v_view = kc, vc
        out = flash_attention(q, k_view, v_view, causal=True,
                              window=cfg.sliding_window,
                              q_offset=chunk_start, chunk=cfg.seq_chunk)
        new_kv = (kc, vc)
    elif kv_cache is not None and q_len is not None:
        # mixed prefill+decode tick: every row writes its q_len valid tokens
        # at its own cache_len cursor, then its queries attend causally at
        # that offset — decode rows and the mid-prefill chunk in ONE
        # executable.
        kc, vc = kv_cache
        if block_table is not None:
            from repro.kernels.paged_attention import paged_mixed_attention
            kc = paged_mixed_update(kc, k, block_table, cache_len, q_len)
            vc = paged_mixed_update(vc, v, block_table, cache_len, q_len)
            out = paged_mixed_attention(
                q, kc, vc, block_table, cache_len, q_len,
                window=cfg.sliding_window,
                mode="pallas" if attn_impl == "paged_kernel" else "fallback")
        else:
            kc = mixed_cache_update(kc, k, cache_len, q_len)
            vc = mixed_cache_update(vc, v, cache_len, q_len)
            out = mixed_attention(q, kc, vc, cache_len, q_len,
                                  window=cfg.sliding_window)
        new_kv = (kc, vc)
    elif kv_cache is not None and block_table is not None:
        # paged decode: append through the block table, then attend over the
        # pool — gather-free kernel or gather+masked-softmax fallback per
        # attn_impl (one shim, trace-time path counters).
        from repro.kernels.paged_attention import paged_decode_attention
        kc, vc = kv_cache
        kc = paged_decode_append(kc, k, block_table, cache_len)
        vc = paged_decode_append(vc, v, block_table, cache_len)
        out = paged_decode_attention(
            q, kc, vc, block_table, cache_len + 1,
            window=cfg.sliding_window,
            mode="pallas" if attn_impl == "paged_kernel" else "fallback")
        new_kv = (kc, vc)
    elif kv_cache is not None:
        # decode: write this token's k/v at each slot's own cache_len, attend
        # over the cache. Slots advance independently (continuous batching
        # admits/retires requests per slot), so the write index is per batch
        # element — the vmapped update lowers to a scatter.
        kc, vc = kv_cache
        upd = jax.vmap(
            functools.partial(jax.lax.dynamic_update_slice_in_dim, axis=0))
        kc = upd(kc, k.astype(kc.dtype), cache_len)
        vc = upd(vc, v.astype(vc.dtype), cache_len)
        out = decode_attention(q, kc, vc, cache_len + 1,
                               window=cfg.sliding_window)
        new_kv = (kc, vc)
    else:
        if cfg.flash_vjp:
            from repro.models.flash_vjp import flash_attention_vjp
            out = flash_attention_vjp(q, k, v, causal=causal,
                                      window=cfg.sliding_window,
                                      chunk=cfg.seq_chunk)
        else:
            out = flash_attention(q, k, v, causal=causal,
                                  window=cfg.sliding_window,
                                  chunk=cfg.seq_chunk)
        new_kv = (k, v)

    out = out.reshape(b, s, h * hd)
    out = ctx.dense(out, p["wo"], name + ".wo",
                    out_logical=("batch", None, None), tp_reduce=True)
    return out, new_kv


def cross_kv_from_memory(ctx: QuantCtx, memory: jax.Array, p, cfg: ModelConfig,
                         name: str):
    """Precompute encoder-side K/V for decoder cross-attention."""
    b, se, _ = memory.shape
    k = ctx.dense(memory, p["wk"], name + ".wk") \
        .reshape(b, se, cfg.n_kv_heads, cfg.hd)
    v = ctx.dense(memory, p["wv"], name + ".wv") \
        .reshape(b, se, cfg.n_kv_heads, cfg.hd)
    return k, v


# =============================================================================
# MLP / MoE
# =============================================================================
def mlp_block(ctx: QuantCtx, x: jax.Array, p, cfg: ModelConfig, name: str):
    if cfg.act == "swiglu":
        gate = ctx.dense(x, p["w_gate"], name + ".w_gate",
                         out_logical=("batch", None, "mlp"))
        up = ctx.dense(x, p["w_up"], name + ".w_up",
                       out_logical=("batch", None, "mlp"))
        hidden = jax.nn.silu(gate) * up
    else:
        hidden = jax.nn.gelu(
            ctx.dense(x, p["w_up"], name + ".w_up", p.get("b_up"),
                      out_logical=("batch", None, "mlp")))
    return ctx.dense(hidden, p["w_down"], name + ".w_down", p.get("b_down"),
                     out_logical=("batch", None, None), tp_reduce=True)


def moe_block(ctx: QuantCtx, x: jax.Array, p, cfg: ModelConfig, name: str):
    """Top-k routed MoE with *local* routing groups + capacity dispatch.

    Each batch row routes independently (GShard-style local groups): the
    top-C gather/scatter stays inside the row's data shard, so sharding the
    batch over (pod, data) never gathers the global token axis — the only
    cross-shard traffic is the (E, B, C, d) expert operand transpose, which
    GSPMD lowers to the expected EP all-to-all when experts divide `model`.
    Capacity C = cf·S·k/E per row; dropping is by gate magnitude
    (importance-based). Returns (out, aux_load_balance_loss).
    """
    b, s, d = x.shape
    e, k = cfg.moe_experts, cfg.moe_topk

    logits = ctx.dense(x, p["router"], name + ".router").astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                      # (B, S, E)
    top_vals, top_idx = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top_vals, axis=-1)                    # (B, S, k)
    onehot = jax.nn.one_hot(top_idx, e, dtype=gates.dtype)       # (B, S, k, E)
    expert_gate = jnp.einsum("bsk,bske->bse", gates, onehot)

    cap = max(1, min(s, int(cfg.capacity_factor * s * k / e)))
    prio = expert_gate.transpose(0, 2, 1)                        # (B, E, S)
    top_gate, token_idx = jax.lax.top_k(prio, cap)               # (B, E, C)

    xe = jax.vmap(lambda xb, ib: xb[ib.reshape(-1)].reshape(e, cap, d))(
        x, token_idx)                                            # (B, E, C, d)
    xe = xe.transpose(1, 0, 2, 3)                                # (E, B, C, d)
    xe = shard_act(xe, ("experts", "batch", None, None))

    # Expert GEMMs run under vmap, where packed leaves arrive as batch
    # tracers the Pallas dispatch can't take yet — densify at point of use.
    ctx_e = ctx.no_qmm()

    def expert_ffn(pe, xi):                                      # xi (B, C, d)
        gate = ctx_e.dense(xi, pe["w_gate"], name + ".expert.w_gate")
        up = ctx_e.dense(xi, pe["w_up"], name + ".expert.w_up")
        return ctx_e.dense(jax.nn.silu(gate) * up, pe["w_down"],
                           name + ".expert.w_down")

    ye = jax.vmap(expert_ffn)(p["experts"], xe)                  # (E, B, C, d)
    ye = ye * top_gate.transpose(1, 0, 2)[..., None].astype(ye.dtype)
    ye = ye.transpose(1, 0, 2, 3)                                # (B, E, C, d)
    ye = shard_act(ye, ("batch", None, None, None))

    def scatter_row(yb, ib):
        return jnp.zeros((s, d), yb.dtype) \
            .at[ib.reshape(-1)].add(yb.reshape(e * cap, d))

    out = jax.vmap(scatter_row)(ye, token_idx)                   # (B, S, d)

    # Switch-style load-balance aux loss.
    frac_tokens = jnp.mean(onehot.sum(2), axis=(0, 1))           # (E,)
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = cfg.router_aux_coef * e * jnp.sum(frac_tokens * frac_probs)
    return out.astype(x.dtype), aux
