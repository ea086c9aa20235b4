"""Shared model infrastructure: configs, quantization context, param helpers.

Models are pure functions over nested-dict param pytrees. Every matmul weight
flows through ``QuantCtx.dense`` which injects MF-QAT fake-quantization (STE)
when enabled — this is where the paper's technique plugs into every
architecture. Block axis = 0 (the contraction dim of our (d_in, d_out)
weights), matching OCP MX dot-product blocking.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.qat import QATConfig
from repro.sharding.rules import shard_act


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture hyper-parameters (one instance per assigned arch)."""

    name: str
    family: str                     # dense | moe | hybrid | ssm | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0               # 0 -> d_model // n_heads
    rope_theta: float = 10000.0
    qk_norm: bool = False
    qkv_bias: bool = False
    mlp_bias: bool = False
    sliding_window: Optional[int] = None
    norm_eps: float = 1e-5
    act: str = "swiglu"             # swiglu | gelu
    tie_embeddings: bool = False
    # MoE
    moe_experts: int = 0
    moe_topk: int = 2
    moe_every: int = 1              # MoE at layers where i % moe_every == moe_offset
    moe_offset: int = 0
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    # Hybrid (jamba): attention at layers where i % attn_every == attn_offset
    attn_every: int = 0             # 0 -> attention everywhere
    attn_offset: int = 0
    # Mamba
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0          # 0 -> ceil(d_model / 16)
    # RWKV
    rwkv_head_dim: int = 64
    # Encoder-decoder
    enc_layers: int = 0
    # Modality frontend stubs
    vision_tokens: int = 0          # llava anyres patch embeds
    audio_downsample: int = 0       # seamless: enc frames = seq // this
    # Numerics / misc
    compute_dtype: Any = jnp.bfloat16
    scan_group: int = 1             # layers per scan step (jamba period = 8)
    seq_chunk: int = 1024           # flash-attention / loss chunking
    flash_vjp: bool = True          # custom-VJP flash (O(S) bwd memory)
    seq_sharding: bool = False      # sequence-parallel residual stream (SP)
    remat: bool = True
    remat_inner: bool = False       # also remat each layer inside a group
    #                                 (peak bwd mem = 1 layer, not the group)
    unroll: bool = False            # python-loop layers (cost-model calib)
    max_seq: int = 8192             # rope table sizing hint (not a hard cap)

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def n_groups(self) -> int:
        assert self.n_layers % self.scan_group == 0
        return self.n_layers // self.scan_group

    def is_attn_layer(self, i: int) -> bool:
        if self.family in ("ssm",):
            return False
        if self.attn_every <= 0:
            return True
        return i % self.attn_every == self.attn_offset

    def is_moe_layer(self, i: int) -> bool:
        if self.moe_experts <= 0:
            return False
        return i % self.moe_every == self.moe_offset

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def dt_rank(self) -> int:
        return self.mamba_dt_rank or max(1, -(-self.d_model // 16))


def is_packed_leaf(w) -> bool:
    """True for the packed-MX weight containers the serving trees hold."""
    from repro.core.mx import MXTensor
    if isinstance(w, MXTensor):
        return True
    try:
        from repro.serve.packed_params import PackedInt4Leaf
        return isinstance(w, PackedInt4Leaf)
    except ImportError:
        return False


def _maybe_dequant_packed(w, dtype):
    """Dequantize packed-MX weight containers at their point of use.

    Containers sliced out of a scan keep stale static `block_axis` metadata;
    ``serving_axis=True`` re-derives the contraction dim as ndim-2 per our
    stacking convention (one shared implementation in serve/packed_params).
    """
    if not is_packed_leaf(w):
        return w
    from repro.serve.packed_params import densify_leaf
    # block_size=None: derived from the leaf itself (MXTensor carries its
    # fmt; PackedInt4Leaf's is computed from its shapes — the registry
    # default would be wrong for non-default anchor block sizes).
    return densify_leaf(w, None, dtype, serving_axis=True)


@dataclasses.dataclass
class QuantCtx:
    """Threads the MF-QAT config + traced format index through the forward.

    fmt_idx semantics (see fake_quant_switch): 0..len(formats)-1 selects a
    training format, len(formats) selects the FP pass-through branch.

    ``qmm`` is the serving-path matmul hook: ``(x, packed_leaf, name) -> y``.
    When set, packed-MX weight containers skip the XLA dequant below and are
    fed straight to the fused Pallas dequant-GEMM dispatch
    (``repro.kernels.dispatch.qmatmul``) — the weight never exists dense.

    ``tp_axis`` names the tensor-parallel mesh axis when the forward runs
    inside ``shard_map`` over head/ffn-sharded weights. Row-parallel
    projections (wo, w_down) then request a single ``psum`` per projection
    pair via ``dense(..., tp_reduce=True)``; everything else is local math
    on the shard. ``None`` (the default) is the single-device path and adds
    no collectives.
    """

    qat: Optional[QATConfig] = None
    fmt_idx: Optional[jax.Array] = None
    qmm: Optional[Any] = None
    tp_axis: Optional[str] = None

    def maybe_quant(self, w: jax.Array, name: str) -> jax.Array:
        if self.qat is None or not self.qat.enabled or self.fmt_idx is None:
            return w
        return self.qat.apply(w, name, self.fmt_idx)

    def no_qmm(self) -> "QuantCtx":
        """A copy without the fused-GEMM hook (densify-at-point-of-use).

        Used under transformations the dispatch layer doesn't support yet —
        e.g. the vmapped MoE expert GEMMs, where leaves arrive as batch
        tracers and pallas_call would need a batching rule.
        """
        if self.qmm is None:
            return self
        return dataclasses.replace(self, qmm=None)

    def dense(self, x: jax.Array, w, name: str,
              b: Optional[jax.Array] = None,
              out_logical: Optional[Tuple] = None, *,
              tp_reduce: bool = False) -> jax.Array:
        """y = x @ fake_quant(w) in the compute dtype.

        `w` may be a packed-MX container (MXTensor / PackedInt4Leaf): with a
        ``qmm`` hook it flows into the fused dequant-GEMM; otherwise it is
        dequantized right here — inside the layer scan — so only one layer's
        bf16 weights are ever resident (the XLA-level analogue of the Pallas
        contract; see serve/packed_params.py).

        ``tp_reduce=True`` marks a row-parallel projection: under tensor
        parallelism (``tp_axis`` set) the shard-local partial product is
        psum'd over the mesh axis BEFORE the bias add, so the (replicated)
        bias is applied exactly once. The psum runs under the named scope
        ``tp.exchange/<projection>`` (``wo``, ``w_down``), which a device
        trace shows on its ops.
        """
        if self.qmm is not None and is_packed_leaf(w):
            y = self.qmm(x, w, name)
        else:
            w = _maybe_dequant_packed(w, x.dtype)
            wq = self.maybe_quant(w, name).astype(x.dtype)
            y = jax.lax.dot_general(x, wq, (((x.ndim - 1,), (0,)), ((), ())),
                                    preferred_element_type=x.dtype)
        if tp_reduce and self.tp_axis is not None:
            with jax.named_scope(f"tp.exchange/{name.rsplit('.', 1)[-1]}"):
                y = jax.lax.psum(y, self.tp_axis)
        if b is not None:
            y = y + b.astype(x.dtype)
        if out_logical is not None:
            y = shard_act(y, out_logical)
        return y


NO_QUANT = QuantCtx()


# =============================================================================
# Slot-level cache surgery (continuous batching)
# =============================================================================
# Cache pytrees stack a leading group/layer axis, so the batch axis is 1 on
# every leaf across all families (attn KV, mamba/rwkv state, encdec cross-KV).
CACHE_BATCH_AXIS = 1


def is_paged_cache(cache) -> bool:
    """True for the paged KV layout (shared page pools + per-slot block
    table) — its pool leaves have NO batch axis, so the dense slot-surgery
    helpers below must not touch them."""
    return isinstance(cache, dict) and "block_table" in cache


def single_slot_cache(cache, batch_axis: int = CACHE_BATCH_AXIS):
    """A zeroed copy of ``cache`` with the batch axis shrunk to one slot."""
    return jax.tree_util.tree_map(
        lambda c: jnp.zeros(
            c.shape[:batch_axis] + (1,) + c.shape[batch_axis + 1:], c.dtype),
        cache)


def slice_cache_slot(cache, slot, batch_axis: int = CACHE_BATCH_AXIS):
    """Slice slot ``slot`` of a batched cache out as a batch-1 cache pytree.

    The read half of the read-modify-write a chunked prefill needs on the
    dense KV layout: unlike ``single_slot_cache`` (a zeroed scratch), the
    slice carries the slot's already-written KV so a later chunk can attend
    over earlier chunks.
    """
    return jax.tree_util.tree_map(
        lambda c: jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=batch_axis),
        cache)


def insert_cache_slot(cache, single, slot, batch_axis: int = CACHE_BATCH_AXIS):
    """Write a batch-1 cache pytree into slot ``slot`` of a batched cache.

    ``slot`` may be traced; other slots' buffers are untouched, which is what
    lets a serving engine admit one request without re-prefilling the rest.
    """
    return jax.tree_util.tree_map(
        lambda big, sm: jax.lax.dynamic_update_slice_in_dim(
            big, sm.astype(big.dtype), slot, axis=batch_axis),
        cache, single)


def make_prefill_slot(prefill):
    """Derive a single-slot prefill-insert from a batched ``prefill``.

    Dense KV layout: runs ONE request (tokens ``(1, S)``) through a batch-1
    scratch cache and writes the result into slot ``slot`` of the live batched
    cache. Paged KV layout: no scratch/insert at all — the prompt's KV
    scatters straight into the pages the slot's block-table row maps, which
    cannot touch any other slot's pages (physical pages are allocated to at
    most one slot). Returns ``(logits (V,), new_cache, new_len scalar)``.
    """
    def prefill_slot(params, batch, cache, slot):
        if is_paged_cache(cache):
            row = jax.lax.dynamic_slice_in_dim(cache["block_table"], slot, 1,
                                               axis=0)
            logits, filled, clen = prefill(
                params, batch, dict(cache, block_table=row))
            return (logits[0],
                    dict(filled, block_table=cache["block_table"]), clen[0])
        small = single_slot_cache(cache)
        logits, filled, clen = prefill(params, batch, small)
        return logits[0], insert_cache_slot(cache, filled, slot), clen[0]
    return prefill_slot


def make_prefill_chunk_slot(prefill_chunk):
    """Derive a single-slot chunked prefill from a batched ``prefill_chunk``.

    Like ``make_prefill_slot`` but for one prompt *chunk* at cursor
    ``start_pos`` (chunked admission — docs/serving_internals.md "Admission
    & scheduling"). Paged KV: the chunk writes straight through the slot's
    block-table row, which is the isolation. Dense KV: the slot's cache row
    is sliced out (NOT a zeroed scratch — chunk N must see chunks
    0..N-1's KV), run through, and written back. Returns
    ``(logits (V,), new_cache, new_len scalar)``.
    """
    def prefill_chunk_slot(params, batch, cache, slot, start_pos):
        if is_paged_cache(cache):
            row = jax.lax.dynamic_slice_in_dim(cache["block_table"], slot, 1,
                                               axis=0)
            logits, filled, clen = prefill_chunk(
                params, batch, dict(cache, block_table=row), start_pos)
            return (logits[0],
                    dict(filled, block_table=cache["block_table"]), clen[0])
        small = slice_cache_slot(cache, slot)
        logits, filled, clen = prefill_chunk(params, batch, small, start_pos)
        return logits[0], insert_cache_slot(cache, filled, slot), clen[0]
    return prefill_chunk_slot


def spec_accept_counts(drafts, anchor_toks, budgets):
    """Per-row commit counts for a speculative verify tick (host side).

    ``drafts`` (B, k): the draft rung's greedy tokens for the burst.
    ``anchor_toks`` (B, k+1): argmax of ``ModelApi.verify_step`` logits —
    lane ``i`` is the verify format's own next token after consuming input
    token ``i`` (lane 0 after the committed last token, lane ``i>0`` after
    draft ``i-1``). A row accepts the longest prefix where
    ``drafts[:, i] == anchor_toks[:, i]`` — every accepted draft is, by
    construction, exactly the token plain verify-format decode would have
    emitted — then commits those ``m`` tokens plus the verify step's bonus
    token at lane ``m``: ``m + 1`` tokens total. The count is clamped to
    the row's remaining ``budgets`` entry (max_new / cache-capacity
    headroom), which is what keeps a speculative stream bit-identical to
    plain decode even at the retire boundary. Returns (B,) int64 commit
    counts (0 where ``budgets`` is 0; masked rows should pass budget 0).
    """
    import numpy as np
    drafts = np.asarray(drafts)
    anchor_toks = np.asarray(anchor_toks)
    b, k = drafts.shape
    if anchor_toks.shape != (b, k + 1):
        raise ValueError(
            f"anchor_toks {anchor_toks.shape} vs drafts {drafts.shape}")
    hit = drafts == anchor_toks[:, :k]                     # (B, k)
    # longest all-True prefix per row: index of first miss (k if none)
    m = np.where(hit.all(axis=1), k, hit.argmin(axis=1))
    return np.minimum(m + 1, np.asarray(budgets))


# =============================================================================
# Param init helpers
# =============================================================================
def trunc_normal(key, shape, std=0.02, dtype=jnp.float32):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def stacked_init(fn, key, n: int):
    """vmap an init over a leading layer/group dimension."""
    keys = jax.random.split(key, n)
    return jax.vmap(fn)(keys)


def count_params(params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params)
               if hasattr(x, "size"))
