"""Packed-weight continuous-batching engine for elastic-precision serving.

Implements the paper's §3.5 inference scheme end-to-end: one anchor
checkpoint (MXINT8/MXFP8) is resident; per-format weight caches hold
**packed** pytrees built by ``make_packed_params`` — MXTensor leaves (int8
codes + E8M0 scales) for >=5-bit formats, split-N nibble-packed
``PackedInt4Leaf`` for MXINT4. The decode tick serves straight from the
packed bytes under one of two contracts:

  fused (default on TPU)  — ``make_packed_serve_step(fused=True)``: every
      projection feeds its packed leaf to the Pallas dequant-GEMM via
      ``kernels.dispatch.qmatmul``; weight HBM traffic is exactly the codes
      + scales, streamed tile-by-tile into VMEM (interpret-mode off TPU —
      the test path).
  densify-inside-jit      — the XLA fallback: leaves dequantize inside the
      jitted step and XLA fuses the dequant into the consuming matmuls.

Both contracts read the same codes, so decode — HBM-bound on weight reads —
streams 2x/4x fewer bytes at mxint8/mxint4 than dense bf16, and greedy
token streams are identical across them. Deriving a new format costs one
packed-domain Slice-and-Scale pass and is cached; switching between cached
formats is free.

Slot lifecycle (continuous batching; state machine documented in
docs/serving_internals.md "Admission & scheduling"):

  admit   — each request is prefilled individually via
            ``ModelApi.prefill_slot`` into a free slot; active slots are
            never re-prefilled. Prompts are right-padded to power-of-two
            length buckets (exact masking via ``batch["lengths"]``), so the
            prefill executable compiles once per bucket, not once per
            prompt length. With ``prefill_chunk`` set, admission is instead
            *chunked*: the prompt streams in fixed-size chunks via
            ``ModelApi.prefill_chunk_slot`` (one chunk per tick, cursor in
            host state), bounding how long a long prompt can stall the
            running slots.
  decode  — one fused serve_step advances every slot per tick; free,
            finished, and mid-prefill slots are masked (their cache_len
            stops advancing and their sampled tokens are dropped).
  retire  — a slot frees the moment its request reaches ``max_new`` or cache
            capacity, and is re-admissible on the very next tick.

Sampling: greedy argmax, or temperature/top-p with **per-slot RNG streams**
— each admission seeds its slot from ``fold_in(engine_key, rid)`` and every
draw advances only that slot's key, so concurrent identical prompts decode
independently and any request's stream is reproducible from (seed, rid)
alone.

Format selection is **batch-pinned**: the policy picks once, when the engine
transitions from drained to busy, and every request admitted while any slot
is live inherits that format. Numerics therefore never switch mid-sequence
and ``Request.fmt_used`` is exact for every generated token, not just the
admission-time value.

Token draining is host-side: one device->host transfer of the whole
next-token vector per tick (``np.asarray``), with per-slot lengths mirrored
in host counters — no per-slot ``int(...)`` device syncs in the tick loop.

Failure domains & degradation (docs/serving_internals.md §7 "Failure model
& degradation ladder"): every request ends in exactly ONE terminal
``RequestStatus`` — a fault confined to one request (oversized prompt,
per-request deadline, cancellation, poisoned logits traced to one row,
page exhaustion with no reclaimable admission) retires that request with
its pages freed and its error recorded in ``stats()["failures"]``, and the
engine keeps serving the rest. Batch-wide numeric faults walk the policy's
format ladder instead: a cheap host-side NaN/Inf check on each tick's
consumed logit rows escalates the batch one rung toward the anchor
(``FormatPolicy.escalate``) and REPLAYS the tick — every attempt is a pure
function of the pre-tick (cache, cache_len, tokens), and sampling /
cache_len advance / token drain only commit after the guard settles, so a
replay cannot perturb surviving streams. Only at the anchor rung does the
engine fall back to per-row retirement (``FAILED_NUMERIC``). Chaos is
driven by a seeded ``runtime.fault.FaultInjector`` hook, and a
``PreemptionGuard`` passed to ``generate`` snapshots the host scheduler
state at the next tick boundary (``checkpoint.io.save_flat``) so
``resume()`` completes the wave with bit-identical remaining streams.
"""
from __future__ import annotations

import contextlib
import dataclasses
import enum
import functools
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import io as ckpt_io
from repro.core.anchor import AnchorModel, convert, materialize
from repro.core.formats import get_format
from repro.core.mx import MXTensor
from repro.kernels.paged_attention import (mq_rows_folded, pages_read,
                                           pages_read_mq)
from repro.models.common import spec_accept_counts
from repro.models.transformer import ModelApi, make_model
from repro.runtime.fault import InjectedFault
from repro.serve.packed_params import (PackedInt4Leaf, anchor_block_size,
                                       layout_tp_shards,
                                       make_packed_mixed_step,
                                       make_packed_params,
                                       make_packed_prefill_chunk,
                                       make_packed_prefill_slot,
                                       make_packed_serve_step,
                                       make_packed_verify_step,
                                       packed_param_shardings,
                                       weight_stream_bytes,
                                       weight_stream_bytes_local)
from repro.serve.policy import FormatPolicy, SpecConfig
from repro.serve.slo import SLOClass, tier_rank

DENSE_BF16 = "bf16"   # pseudo-format: dense anchor-precision weights

MIN_PREFILL_BUCKET = 8


# Collectives a tensor-parallel step can run, by jaxpr primitive, and
# whether their payload is the operand (a reduction) or the result (a
# gather: every chip ends up holding the whole).
_COLLECTIVES = {"psum": "in", "all_gather": "out", "reduce_scatter": "in",
                "all_to_all": "in", "ppermute": "in"}


def _collective_bytes(jaxpr) -> int:
    """Bytes of every collective a jaxpr runs, from its shapes: a
    reduction counts its operands, a gather its result, each once per run
    (a scan body times its length). Inside shard_map the shapes are one
    chip's, which for a psum is the whole of what is summed."""
    total = 0
    for e in jaxpr.eqns:
        side = _COLLECTIVES.get(e.primitive.name)
        if side is not None:
            vs = e.invars if side == "in" else e.outvars
            total += sum(v.aval.size * v.aval.dtype.itemsize for v in vs)
        subs = [j for v in e.params.values()
                for j in (v if isinstance(v, (tuple, list)) else (v,))
                if hasattr(j, "eqns") or hasattr(getattr(j, "jaxpr", None),
                                                 "eqns")]
        inner = [_collective_bytes(getattr(j, "jaxpr", j)) for j in subs]
        if inner:
            # a cond runs one branch; anything else runs each sub-jaxpr
            n = max(inner) if e.primitive.name == "cond" else sum(inner)
            total += n * int(e.params.get("length", 1))
    return total


def _bucket_len(plen: int, cap: int) -> int:
    """Smallest power-of-two bucket >= plen (floor MIN_PREFILL_BUCKET),
    clamped to the cache capacity ``cap``."""
    b = MIN_PREFILL_BUCKET
    while b < plen:
        b *= 2
    return min(b, cap)


def _sample_one(key, logits, temperature, top_p):
    """One temperature/top-p draw; returns (advanced_key, token)."""
    k_next, k_draw = jax.random.split(key)
    lg = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
    probs = jax.nn.softmax(lg)
    order = jnp.argsort(-probs)
    sp = jnp.take(probs, order)
    # nucleus: smallest prefix of descending probs reaching top_p mass
    # (top-1 always kept: its prefix-exclusive cumsum is 0 < top_p)
    keep_sorted = (jnp.cumsum(sp) - sp) < top_p
    keep = jnp.zeros_like(keep_sorted).at[order].set(keep_sorted)
    return k_next, jax.random.categorical(k_draw, jnp.where(keep, lg,
                                                            -jnp.inf))


# Per-slot temperature/top_p lanes: each request samples with its own
# params (Request.temperature/top_p; engine ctor values are the defaults).
# Scalar division/threshold per lane — numerically identical per row to the
# old broadcast-scalar vmap, so streams are bit-stable across the change.
_sample_batch = jax.jit(jax.vmap(_sample_one, in_axes=(0, 0, 0, 0)))


class RequestStatus(str, enum.Enum):
    """Lifecycle of one request. Every request ends in exactly one of the
    terminal states; non-COMPLETED terminals carry ``Request.error`` and a
    record in ``ElasticEngine.stats()["failures"]`` (the per-request
    failure domain: docs/serving_internals.md §7)."""
    QUEUED = "queued"
    RUNNING = "running"
    COMPLETED = "completed"              # reached max_new / cache capacity
    FAILED_NUMERIC = "failed_numeric"    # non-finite logits at anchor rung
    FAILED_CAPACITY = "failed_capacity"  # unservable prompt / pool starved
    TIMED_OUT = "timed_out"              # per-request deadline_s exceeded
    CANCELLED = "cancelled"              # cancel() / injected cancellation

    @property
    def terminal(self) -> bool:
        return self not in (RequestStatus.QUEUED, RequestStatus.RUNNING)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray              # (S,) int32
    max_new: int
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    fmt_used: Optional[str] = None
    done: bool = False
    ttft_s: Optional[float] = None  # wall-clock from generate() entry to the
    #                                 first sampled token (set by the engine)
    deadline_s: Optional[float] = None  # wall-clock budget from generate()
    #                                     entry; exceeded -> TIMED_OUT at the
    #                                     next tick boundary (resume-aware:
    #                                     the clock spans the interruption)
    status: RequestStatus = RequestStatus.QUEUED
    error: Optional[str] = None     # set with any non-COMPLETED terminal
    cancel_requested: bool = False
    # ---- per-request service objectives & sampling (docs §10) ----------
    slo: Optional["SLOClass"] = None    # tier + TTFT/TPOT budgets; None =
    #                                     best-effort, no budgets
    tenant: Optional[str] = None        # workload attribution (fairness
    #                                     accounting in the bench)
    arrival_tick: int = 0           # scheduler tick this request becomes
    #                                 visible to admission (0 = already
    #                                 queued, the pre-SLO behavior)
    arrival_s: Optional[float] = None   # wall clock when it came due
    #                                     (stamped by the engine; TTFT
    #                                     against the SLO is ttft_s minus
    #                                     this)
    admitted_tick: Optional[int] = None  # tick admission claimed it
    admitted_s: Optional[float] = None   # engine clock (as arrival_s) when
    #                                      admission claimed it; a requeue
    #                                      for pages re-stamps it
    temperature: Optional[float] = None  # None -> engine default
    top_p: Optional[float] = None        # None -> engine default

    def cancel(self) -> None:
        """Ask the engine to retire this request as CANCELLED at the next
        tick boundary (queued, mid-prefill, or decoding alike). Safe to
        call from outside the serving loop; already-terminal requests are
        unaffected."""
        self.cancel_requested = True


class ElasticEngine:
    """Continuous-batching engine serving from packed MX weight caches.

    ``packed=False`` swaps every format's weights for their densified bf16
    equivalent (same codes, dequantized ahead of time) — the reference path
    for packed-vs-dense equivalence tests and roofline baselines. The
    pseudo-format ``"bf16"`` serves dense anchor-precision weights.

    ``fused`` selects the packed-serving contract: the Pallas dequant-GEMM
    dispatch (True) vs XLA densify-inside-jit (False); None = fused on TPU.
    Fixed per engine instance, so each contract gets its own jitted
    executables and no stale-cache hazards exist.

    ``kv_layout`` selects the KV-cache layout: ``"dense"`` preallocates a
    contiguous (slots, max_len) buffer per layer; ``"paged"`` serves from a
    shared page pool plus per-slot block tables, committing HBM one
    ``kv_page_size``-token page at a time as sequences grow. The engine owns
    the host-side free list: pages are allocated at admission (enough to
    hold the prompt plus the first decode write), one page at a time as
    decode crosses page boundaries, and returned the moment a slot retires —
    so the pool only needs to cover the *live* token count, not
    slots × max_len. Exhaustion raises ``RuntimeError`` loudly (never a
    silent truncation); size the pool with ``kv_num_pages`` (None = dense
    capacity: slots × ceil(max_len/page) + 1 scratch page). Token streams
    are bit-identical across layouts (same values at every valid position).

    ``attn_impl`` selects the paged decode-attention read path:
    ``"paged_kernel"`` consumes the page pools + block table directly in the
    gather-free Pallas kernel (``kernels/paged_attention.py`` — Mosaic on
    TPU, interpret-mode in tests), so per-tick attention reads scale with
    live tokens (``ceil(cache_len/page)`` pages per slot); ``"gather"``
    keeps the original materialize-then-attend pair, whose reads scale with
    ``max_pages*page`` regardless of occupancy. None = kernel on TPU when
    paged, gather elsewhere. Both impls read the same KV values at every
    valid position and reduce in fp32, but the kernel's online softmax
    reorders the reduction, so logits can differ by ulps — token-stream
    equality across impls is an *empirically held* contract (asserted
    exactly by tests and the bench on this backend), not an algebraic one;
    ``stats()["attn_tokens_read"]`` accounts the read-traffic difference and
    ``benchmarks/serve_engine_bench.py`` turns it into attention-bytes/token.
    Requires ``kv_layout="paged"`` — the dense layout has no block table to
    consume.

    ``prefill_chunk`` selects the admission mode (the slot-lifecycle state
    machine is documented in docs/serving_internals.md, "Admission &
    scheduling"). ``None`` (default) admits monolithically: each prompt is
    prefilled in one call, stalling every running slot for the full prompt
    length. An int (or ``"auto"`` = one KV page when paged, else 64) splits
    admission into fixed-size chunks interleaved with decode ticks — the
    scheduler runs AT MOST one prefill chunk per tick before the batched
    decode step, so per-tick work (and therefore running slots' inter-token
    latency) is bounded by one chunk regardless of incoming prompt length.
    Token streams are bit-identical to monolithic admission (greedy and
    seeded sampling). Attention-only; when paged, the chunk must be a
    multiple of ``kv_page_size`` so chunk boundaries fall on pages and each
    chunk's pages are allocated at that chunk, not all upfront.

    ``speculative`` (a ``serve.policy.SpecConfig``) turns a pure-decode
    tick into a self-speculative one: k greedy draft steps under the
    ``draft_fmt`` packed contract (same slots, same paged pools — drafts
    write through the normal decode-append path against a LOCAL cursor),
    then ONE batched verify step at the pinned format over the k+1
    positions per slot via the multi-query mixed-attention machinery
    (``ModelApi.verify_step``). Each slot accepts its longest
    greedy-matching draft prefix plus the verify step's bonus token;
    rejected tokens roll back by rewinding that slot's ``cache_len`` (no
    copies) and returning pages past the new frontier to the free list.
    Because only verify-format argmaxes are ever committed, greedy token
    streams are **bit-identical to plain pinned-format decode at any
    acceptance rate** — speculation changes speed, never tokens
    (docs/serving_internals.md §9 "Speculative decoding"; the guard /
    quarantine interplay — a quarantined draft rung silently reverts to
    plain decode — is specified there too). Greedy-only: ``generate``
    rejects sampled decoding when speculation is on.

    ``scheduler`` selects how chunked ticks execute. ``"mixed"`` (the
    default whenever ``prefill_chunk`` is set) coalesces the prefill chunk
    INTO the decode batch: one ``mixed_step`` executable per tick, where
    each row carries a per-slot token budget — decoding slots contribute 1
    query token, the (single) mid-prefill slot contributes its chunk at its
    cursor — so decode never skips a tick during a long admission and
    ``tick_trace`` shows exactly one executable per tick. ``"sequential"``
    keeps the PR 4 shape (chunk executable, then decode executable) as the
    provably equivalent fallback. Sampling-wise the epilogue is fused but
    ordered identically: the batched draw advances every slot key exactly
    once per decode-carrying tick, and a completing admission reseeds its
    slot from ``(engine key, rid)`` AFTER the batch draw — so token streams
    are bit-identical to sequential admission (greedy and seeded) across
    all layout/contract pairings; the tests in tests/test_mixed_batch.py
    hold that line. Requires ``prefill_chunk``.

    ``mesh`` (a mesh with a ``'model'`` axis) serves the model tensor
    parallel over that axis (docs/serving_internals.md §11). Each chip's
    d_ff share is laid out once at a tile-aligned width when a rung is
    built (``layout_tp_shards``). ``donate_anchor`` says whether the engine
    takes over an anchor whose leaves already live on its mesh: at the
    anchor's rung each leaf the layout pads is deleted as soon as its
    padded copy exists, and ``self.anchor`` holds the padded leaves, so
    HBM never holds a second copy of the MLP. The caller's ``AnchorModel``
    is then spent (read ``engine.anchor``), and a second engine built from
    it refuses to build a rung. Pass False where engines share one anchor
    (``ReplicaSet`` does): the layout then copies, which needs room for
    both copies.
    """

    def __init__(self, api: ModelApi, anchor: AnchorModel, *,
                 batch_slots: int = 4, max_len: int = 256,
                 policy: Optional[FormatPolicy] = None,
                 param_template=None, packed: bool = True,
                 fused: Optional[bool] = None, seed: int = 0,
                 temperature: float = 1.0, top_p: float = 1.0,
                 bucket_prompts: bool = True,
                 kv_layout: str = "dense", kv_page_size: int = 16,
                 kv_num_pages: Optional[int] = None,
                 attn_impl: Optional[str] = None,
                 prefill_chunk=None,
                 scheduler: Optional[str] = None,
                 logit_guard: bool = True,
                 max_step_retries: int = 2,
                 fault_injector=None,
                 speculative: Optional[SpecConfig] = None,
                 admission_order: str = "fifo",
                 mesh=None, donate_anchor: bool = True):
        self.api = api
        self.anchor = anchor
        self.donate_anchor = donate_anchor
        self.slots = batch_slots
        self.max_len = max_len
        self.policy = policy or FormatPolicy(anchor.fmt_name)
        self.packed = packed
        if fused is None:             # auto: fused where Mosaic lowers and
            #                           the family has the qmm hook
            self.fused = jax.default_backend() == "tpu" \
                and api.with_qmm is not None
        else:
            if fused and api.with_qmm is None:
                raise ValueError(
                    f"fused=True but model family {api.cfg.family!r} has no "
                    "qmm hook; use fused=False (densify-inside-jit)")
            self.fused = fused
        self.temperature = temperature
        self.top_p = top_p
        # Per-slot sampling lanes (defaults now, per-request values set at
        # complete_admission — before the slot's first draw).
        self._slot_temp = np.full((self.slots,), temperature, np.float32)
        self._slot_topp = np.full((self.slots,), top_p, np.float32)
        # Admission ordering among ARRIVED queued requests (docs §10):
        # "fifo" preserves submission order; "slo" serves latency-tier
        # ahead of throughput-tier ahead of best-effort, FIFO within a
        # tier — the structural lever behind per-tier TTFT attainment.
        if admission_order not in ("fifo", "slo"):
            raise ValueError(f"unknown admission_order {admission_order!r};"
                             " one of ('fifo', 'slo')")
        self.admission_order = admission_order
        self._template = param_template if param_template is not None else \
            jax.eval_shape(api.init_params, jax.random.PRNGKey(0))
        self._block_size = anchor_block_size(anchor)
        # ---- tensor parallelism (docs/serving_internals.md §11) ----------
        # mesh: shard the packed leaves / KV pools over the mesh's 'model'
        # axis and run every step function inside shard_map — token streams
        # stay bit-identical to the single-device engine. Other mesh axes
        # must have size 1 (data parallelism = one engine per replica; see
        # serve/replicas.py).
        self.mesh = mesh
        self._tp = 1
        if mesh is not None:
            names = tuple(getattr(mesh, "axis_names", ()))
            if "model" not in names:
                raise ValueError(
                    "ElasticEngine(mesh=...) needs a mesh with a 'model' "
                    f"axis; got axes {names}")
            sizes = dict(zip(names, mesh.devices.shape))
            tp = int(sizes["model"])
            extra = {a: int(n) for a, n in sizes.items()
                     if a != "model" and n != 1}
            if extra:
                raise ValueError(
                    "ElasticEngine shards over the 'model' mesh axis only; "
                    f"axes {extra} have size > 1 — run one engine per "
                    "data-parallel slice (serve.replicas.ReplicaSet)")
            cfg_g = api.cfg
            if cfg_g.family != "dense" or cfg_g.vision_tokens > 0:
                raise ValueError(
                    "tensor-parallel serving supports pure-attention dense "
                    f"text stacks only; family {cfg_g.family!r} is not "
                    "wired for head-sharded step functions")
            bs_tp = self._block_size * tp
            bad = {k: v for k, v in {
                "n_heads": cfg_g.n_heads, "n_kv_heads": cfg_g.n_kv_heads,
                "vocab": cfg_g.vocab, "d_ff": cfg_g.d_ff}.items()
                if v % tp}
            # Row-parallel packed scales tile the contraction dim by the MX
            # block: those dims must split into whole scale rows per shard.
            # A share that splits whole but is not tile-aligned (qwen2-72b's
            # d_ff over 4 chips: 7392) is fine: weights_for lays each chip's
            # d_ff share out once at a tile-aligned width (zero codes; see
            # layout_tp_shards), and head widths are read per call as they
            # are.
            bad.update({k: v for k, v in {
                "n_heads*head_dim": cfg_g.n_heads * cfg_g.hd,
                "d_ff": cfg_g.d_ff}.items() if v % bs_tp})
            if bad:
                raise ValueError(
                    f"mesh 'model' axis size {tp} cannot shard this "
                    f"config: {bad} not divisible (block_size="
                    f"{self._block_size}). Each of heads, kv heads and "
                    "vocab must divide by tp, and n_heads*head_dim and "
                    "d_ff by tp * block_size; a d_ff share that the "
                    "kernel tiles do not divide is padded at pack time "
                    "(layout_tp_shards), not refused")
            self._tp = tp
        self._weights: Dict[str, object] = {}       # fmt -> serving pytree
        self._fmt_swaps = 0
        self._ticks = 0
        self._tp_exchange_bytes = 0   # collectives' bytes, all steps run
        self._tokens_out = 0
        self.current_fmt: Optional[str] = None
        # Length bucketing needs exact masking of right-padded prompts; the
        # recurrent mixers (mamba/rwkv) fold pad tokens into their state, so
        # only pure-attention stacks bucket.
        pure_attn = api.cfg.family not in ("ssm", "encdec") \
            and api.cfg.attn_every <= 0
        self._bucket = bucket_prompts and pure_attn
        self._pure_attn = pure_attn
        # Paged KV: only attention KV has a sequence axis to page over. The
        # pure-attention check itself lives in the model's init_cache (the
        # single source of truth for what a family can page); the eval_shape
        # below surfaces its ValueError at engine construction.
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}; "
                             "one of ('dense', 'paged')")
        self.kv_layout = kv_layout
        self.kv_page_size = kv_page_size
        self.kv_num_pages = kv_num_pages
        # Paged decode-attention read path (class docstring): auto = the
        # gather-free kernel where Mosaic lowers, the gather fallback
        # elsewhere (tests opt into the kernel explicitly -> interpret mode).
        if attn_impl is None:
            attn_impl = "paged_kernel" if (
                kv_layout == "paged"
                and jax.default_backend() == "tpu") else "gather"
        if attn_impl not in ("gather", "paged_kernel"):
            raise ValueError(f"unknown attn_impl {attn_impl!r}; one of "
                             "('gather', 'paged_kernel')")
        if attn_impl == "paged_kernel" and kv_layout != "paged":
            raise ValueError(
                "attn_impl='paged_kernel' requires kv_layout='paged' — the "
                "dense layout has no block table for the kernel to consume")
        self.attn_impl = attn_impl
        self._attn_tokens_read = 0   # KV tokens decode attention read (host
        #                              mirror; see stats()["attn_tokens_read"])
        self._mq_rows_folded = 0     # MQ kernel query rows folded / held
        self._mq_rows_padded = 0     # (see _count_mq_rows)
        cfg = api.cfg
        self._attn_layers = 0 if cfg.family == "ssm" else sum(
            cfg.is_attn_layer(j) for j in range(cfg.scan_group)) \
            * cfg.n_groups
        # HBM bytes per KV token read (K+V, all attention layers) — the one
        # multiplier behind stats()["attn_read_bytes"] and the cost model's
        # measured attention term.
        self._attn_token_bytes = self._attn_layers * 2 * cfg.n_kv_heads \
            * cfg.hd * jnp.dtype(cfg.compute_dtype).itemsize
        # Per-chip KV read bytes: pools shard over kv heads on the mesh, so
        # each chip streams 1/tp of every token's K+V (exact — n_kv_heads %
        # tp is guarded above). Single chip: identical to the global number.
        self._attn_token_bytes_chip = self._attn_token_bytes // self._tp
        # Chunked prefill admission (None = monolithic; see class docstring
        # and docs/serving_internals.md "Admission & scheduling").
        if prefill_chunk == "auto":
            prefill_chunk = kv_page_size if kv_layout == "paged" else 64
        if prefill_chunk is not None:
            if not pure_attn or api.cfg.vision_tokens > 0:
                raise ValueError(
                    "prefill_chunk requires a pure-attention text stack; "
                    f"family {api.cfg.family!r} folds the prompt into "
                    "recurrent state (or prepends vision embeds) and cannot "
                    "resume prefill mid-prompt — use prefill_chunk=None")
            if prefill_chunk < MIN_PREFILL_BUCKET:
                raise ValueError(
                    f"prefill_chunk ({prefill_chunk}) must be >= the "
                    f"minimum prefill bucket ({MIN_PREFILL_BUCKET})")
            if kv_layout == "paged" and prefill_chunk % kv_page_size:
                raise ValueError(
                    f"prefill_chunk ({prefill_chunk}) must be a multiple of "
                    f"kv_page_size ({kv_page_size}) so chunk boundaries "
                    "fall on page boundaries")
        self.prefill_chunk = prefill_chunk
        # Unified-tick scheduler (class docstring): "mixed" is the default
        # wherever chunked admission makes a mixed tick possible.
        if scheduler in (None, "auto"):
            scheduler = "mixed" if prefill_chunk is not None else "sequential"
        if scheduler not in ("sequential", "mixed"):
            raise ValueError(f"unknown scheduler {scheduler!r}; one of "
                             "('sequential', 'mixed')")
        if scheduler == "mixed":
            if prefill_chunk is None:
                raise ValueError(
                    "scheduler='mixed' coalesces the prefill chunk into the "
                    "decode batch; set prefill_chunk (or 'auto')")
            if api.mixed_step is None:
                raise ValueError(
                    f"model family {api.cfg.family!r} has no mixed_step "
                    "entry point; use scheduler='sequential'")
        self.scheduler = scheduler
        # ---- self-speculative decoding (docs/serving_internals.md §9) ----
        if speculative is not None:
            if api.verify_step is None:
                raise ValueError(
                    f"model family {api.cfg.family!r} has no verify_step "
                    "entry point; speculative decoding needs the "
                    "multi-query mixed-attention machinery "
                    "(pure-attention stacks only)")
            if not pure_attn or api.cfg.vision_tokens > 0:
                raise ValueError(
                    "speculative decoding requires a pure-attention text "
                    f"stack; family {api.cfg.family!r} cannot rewind "
                    "recurrent state (or prepends vision embeds)")
            if speculative.k < 1:
                raise ValueError(
                    f"SpecConfig.k ({speculative.k}) must be >= 1")
            if speculative.draft_fmt == DENSE_BF16:
                raise ValueError(
                    "draft_fmt='bf16' drafts at anchor precision or above — "
                    "drafting must be cheaper than verifying")
        self.speculative = speculative
        self._spec_ticks = 0        # decode ticks that ran draft+verify
        self._spec_accepted = 0     # draft tokens committed to streams
        self._spec_rejected = 0     # draft tokens rolled back
        self._spec_aborts = 0       # spec attempts abandoned mid-tick
        #                             (draft fault / page starvation)
        # ---- fault isolation (docs/serving_internals.md §7) --------------
        # logit_guard: host-side NaN/Inf check on every tick's consumed
        # logit rows; detection escalates the batch format one ladder rung
        # toward the anchor and replays the tick (per-row FAILED_NUMERIC
        # retirement only at the anchor). max_step_retries bounds same-
        # format replays of a crashed step executable (InjectedFault).
        self.logit_guard = logit_guard
        self.max_step_retries = max_step_retries
        self._fault_injector = fault_injector
        self._faults_detected = 0
        self._fmt_escalations = 0
        self._escalation_events: List[dict] = []
        self._ticks_replayed = 0
        self._failures: List[dict] = []
        self._status_counts: Dict[str, int] = {}
        self._snapshots_saved = 0
        self._resumes = 0
        self._alloc_calls = 0
        self._snap_step = 0
        self.last_snapshot: Optional[str] = None
        # Tiny jitted guard: one (rows,) bool transfer per checked tick.
        self._finite_rows = jax.jit(lambda lg: jnp.isfinite(lg).all(axis=-1))
        self._admission_requeues = 0
        self._fmt_decode_ticks: Dict[str, int] = {}  # clean decode ticks
        #                          per format (cost-model compile warmup)
        self.tick_trace: List[dict] = []   # reset per generate
        # The engine clock's origin (generate's start) and the open tick's
        # record of phases and stamps (_phase; None between ticks).
        self._t0 = time.perf_counter()
        self._tick_rec: Optional[dict] = None
        self._in_phase = False
        self._step_t: Optional[float] = None  # dispatch awaiting its fetch
        self._kv_pages_alloc = 0
        self._kv_pages_freed = 0
        self._kv_pages_hwm = 0
        cache_shape = jax.eval_shape(lambda: self._init_cache(self.slots))
        self._kv_cache_bytes = sum(
            l.size * l.dtype.itemsize
            for l in jax.tree_util.tree_leaves(cache_shape))
        self._kv_total_pages = \
            cache_shape["blocks"][0]["k_pages"].shape[1] \
            if kv_layout == "paged" else 0
        # KV tokens one decode read spans per live slot under the GATHER
        # path (the whole logical view); the kernel path reads only
        # ceil(cache_len/page)*page of it, accounted per tick in generate().
        if kv_layout == "paged":
            self._attn_read_span = \
                cache_shape["block_table"].shape[1] * kv_page_size
        else:
            self._attn_read_span = self.max_len + api.cfg.vision_tokens
        # Tensor-parallel cache placement: the K/V leaves (dense
        # (G, B, S, Hkv, D) and paged pools (G, P, ps, Hkv*D), head-major)
        # shard over kv heads on axis 3; the block table and every
        # host-built step argument stay replicated with GLOBAL page ids, so
        # the page bookkeeping in generate() is mesh-oblivious.
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            self._cache_pspecs = jax.tree_util.tree_map(
                lambda l: (PartitionSpec(None, None, None, "model")
                           if l.ndim >= 4 else PartitionSpec()),
                cache_shape)
            self._cache_shardings = jax.tree_util.tree_map(
                lambda p: NamedSharding(self.mesh, p), self._cache_pspecs)
            # Each chip allocates only its own kv heads' share: a pool made
            # whole on one chip and then split would have to fit there
            # beside that chip's weights (qwen2-72b at tp 4: 5.5 GB of pool
            # on top of 10.3 GB of weights).
            self._new_cache = jax.jit(
                functools.partial(self._init_cache, self.slots),
                out_shardings=self._cache_shardings)
        else:
            self._new_cache = functools.partial(self._init_cache, self.slots)
            self._cache_pspecs = None
            self._cache_shardings = None
        # Per-slot RNG: reseeded from (engine key, rid) at admission.
        self._key = jax.random.PRNGKey(seed)
        self._slot_keys = jax.random.split(self._key, self.slots)
        self._traces: Dict[str, int] = {}  # compiles per counted step name
        # Jitted entry points. Dense and packed trees have different pytree
        # structures, so jit caches one executable per cached format. The
        # decode steps bake attn_impl in at build time (same rationale as
        # `fused`: no stale-jit-cache hazards from flipping a global); the
        # prefill entry points are attn_impl-independent.
        # Tensor parallelism: build every step function from a LOCAL model —
        # the same architecture at per-shard head counts (head_dim pinned:
        # the derived default would recompute it from the full d_model) with
        # the GLOBAL vocab (the head all_gathers its logit slice back) — and
        # run it inside shard_map over the mesh. Two psums per layer (wo,
        # w_down), one psum for the embed lookup, one all_gather at the
        # head; everything else is local math on the shard (docs §11).
        if self.mesh is not None:
            cfg_g = api.cfg
            local_cfg = dataclasses.replace(
                cfg_g, n_heads=cfg_g.n_heads // self._tp,
                n_kv_heads=cfg_g.n_kv_heads // self._tp,
                head_dim=cfg_g.hd)
            src_api = make_model(local_cfg, api.qat, tp_axis="model")
        else:
            src_api = api
        if self.attn_impl == "gather":
            step_api = src_api
        else:
            if src_api.with_serving is None:
                raise ValueError(
                    f"model family {api.cfg.family!r} cannot rebuild its "
                    f"serving entry points with attn_impl={attn_impl!r}")
            step_api = src_api.with_serving(attn_impl=self.attn_impl)
        self._dense_step = self._mesh_jit(step_api.serve_step, 2)
        self._dense_prefill_slot = self._mesh_jit(
            self._counting(src_api.prefill_slot), 3)
        self._packed_step = self._mesh_jit(
            make_packed_serve_step(src_api, self._block_size,
                                   fused=self.fused,
                                   attn_impl=self.attn_impl), 2)
        self._packed_prefill_slot = self._mesh_jit(self._counting(
            make_packed_prefill_slot(src_api, self._block_size,
                                     fused=self.fused)), 3)
        # Chunked-admission entry points (jit is lazy: nothing compiles
        # unless prefill_chunk is actually used). Compiles once per chunk
        # bucket — the cursor is a traced argument.
        self._dense_prefill_chunk = self._mesh_jit(
            self._counting(src_api.prefill_chunk_slot), 3) \
            if src_api.prefill_chunk_slot is not None else None
        self._packed_prefill_chunk = self._mesh_jit(self._counting(
            make_packed_prefill_chunk(src_api, self._block_size,
                                      fused=self.fused)), 3) \
            if src_api.prefill_chunk_slot is not None else None
        # Unified mixed-tick entry points (lazy jit, one compile per chunk
        # width bucket — counted like chunk compiles). They bake attn_impl
        # in like the decode steps: the ragged multi-query paged read runs
        # the gather-free MQ kernel under "paged_kernel".
        self._dense_mixed = self._mesh_jit(
            self._counting(step_api.mixed_step), 2) \
            if step_api.mixed_step is not None else None
        self._packed_mixed = self._mesh_jit(self._counting(
            make_packed_mixed_step(src_api, self._block_size,
                                   fused=self.fused,
                                   attn_impl=self.attn_impl)), 2) \
            if src_api.mixed_step is not None else None
        # Speculative verify entry points (lazy jit — compile only when a
        # spec tick actually runs). Logits come back at ALL k+1 positions
        # (B, C, V), so the guard's finite check reduces the lane axis too.
        self._dense_verify = self._mesh_jit(
            self._counting(step_api.verify_step), 2) \
            if step_api.verify_step is not None else None
        self._packed_verify = self._mesh_jit(self._counting(
            make_packed_verify_step(src_api, self._block_size,
                                    fused=self.fused,
                                    attn_impl=self.attn_impl)), 2) \
            if src_api.verify_step is not None else None
        self._finite_rows_mq = jax.jit(
            lambda lg: jnp.isfinite(lg).all(axis=(-2, -1)))

    def _counting(self, fn):
        """Wrap a to-be-jitted fn so its traces (= compiles) are counted
        under its name, which the wrapper keeps: the executable compiles
        as ``jit_<name>`` (``jit_mixed_step``), as a profiler shows it."""
        name = fn.__name__

        @functools.wraps(fn)
        def wrapped(*args):
            self._traces[name] = self._traces.get(name, 0) + 1  # at trace
            return fn(*args)
        return wrapped

    def _mesh_jit(self, fn, n_out: int):
        """``jax.jit`` — or, on a TP mesh, ``jit(shard_map(fn))``.

        Every step entry point shares one calling convention: the weight
        pytree is argument 0, the cache pytree argument 2, and (of the
        ``n_out`` outputs) the cache comes back at index 1; everything else
        — batch dicts, cursors, cache_len, logits — is replicated. The
        weights' in_specs are read off their committed shardings per call
        and the wrapped executable is cached per spec tree, mirroring
        jit's one-executable-per-pytree-structure behavior across the
        dense/packed/per-format trees. ``check_vma=False``: the replicated
        outputs are bit-identical across shards BY CONSTRUCTION (the head
        all_gathers full logits everywhere), which the static replication
        checker cannot prove through psum-into-bias arithmetic.

        Each call also counts its collectives' bytes (``_add_exchange``),
        read once per call signature from the jaxpr the step traces.
        """
        if self.mesh is None:
            return jax.jit(fn)
        from jax.sharding import PartitionSpec
        from jax import shard_map
        compiled: Dict = {}
        exchange: Dict = {}     # call signature -> its collectives' bytes

        def call(weights, *rest):
            w_specs = jax.tree_util.tree_map(
                lambda l: l.sharding.spec, weights)
            flat, treedef = jax.tree_util.tree_flatten(w_specs)
            key = (treedef, tuple(flat), len(rest))
            if key not in compiled:
                in_specs = [w_specs] + [PartitionSpec()] * len(rest)
                in_specs[2] = self._cache_pspecs
                out_specs = [PartitionSpec()] * n_out
                out_specs[1] = self._cache_pspecs
                compiled[key] = jax.jit(shard_map(
                    fn, mesh=self.mesh, in_specs=tuple(in_specs),
                    out_specs=tuple(out_specs), check_vma=False))
            step = compiled[key]
            sig = (key, tuple((np.shape(l), str(getattr(l, "dtype", "")))
                              for l in jax.tree_util.tree_leaves(
                                  (weights, rest))))
            if sig not in exchange:
                # the jaxpr jit traces anyway (trace() fills its cache)
                exchange[sig] = _collective_bytes(
                    step.trace(weights, *rest).jaxpr)
            self._add_exchange(exchange[sig])
            return step(weights, *rest)
        return call

    def _add_exchange(self, nbytes: int) -> None:
        """Count one meshed step's collective bytes, for the open tick's
        record and ``stats()["tp_exchange_bytes"]``."""
        self._tp_exchange_bytes += nbytes
        if self._tick_rec is not None:
            self._tick_rec["exchange_bytes"] += nbytes

    def _weight_shardings(self, w):
        """NamedShardings placing a serving weight tree on the TP mesh —
        packed containers via ``packed_param_shardings`` (codes follow the
        dense weight's logical axes, scales the moved-last layout), dense
        bf16 trees via the plain logical-axis rules."""
        from repro.sharding.rules import param_shardings
        is_packed = lambda x: isinstance(x, (MXTensor, PackedInt4Leaf))
        if any(is_packed(l) for l in jax.tree_util.tree_leaves(
                w, is_leaf=is_packed)):
            return packed_param_shardings(w, self.api.param_axes(),
                                          self.mesh)
        return param_shardings(self.api.param_axes(), w, self.mesh)

    # ---- KV cache ---------------------------------------------------------
    def _init_cache(self, b):
        if self.kv_layout == "paged":
            return self.api.init_cache(b, self.max_len, kv_layout="paged",
                                       page_size=self.kv_page_size,
                                       num_pages=self.kv_num_pages)
        return self.api.init_cache(b, self.max_len)

    def _alloc_pages(self, free: List[int], n: int, why: str) -> List[int]:
        """Pop ``n`` physical pages off the free list, or die loudly.

        Exhaustion is an error, never a silent truncation — but since PR 7
        it is *contained*, not fatal: ``generate`` routes it through the
        per-request failure path (requeue-and-wait for admissions, largest-
        page-holder retirement with ``FAILED_CAPACITY`` for decode), so it
        escapes the engine only on an internal free-list invariant breach.
        The fault injector's ``fail_allocs`` hook raises ``InjectedFault``
        (a ``RuntimeError``) here so chaos rides the same handling paths.
        """
        self._alloc_calls += 1
        if self._fault_injector is not None:
            self._fault_injector.on_alloc(self._alloc_calls - 1)
        if len(free) < n:
            raise RuntimeError(
                f"KV page pool exhausted at {why}: need {n} page(s), "
                f"{len(free)} free (pool = {self._kv_total_pages} pages x "
                f"{self.kv_page_size} tokens, {self.slots} slots, "
                f"{self._kv_pages_hwm} pages high-water). Increase "
                "kv_num_pages, shrink batch_slots/max_len, or admit less.")
        got = [free.pop() for _ in range(n)]
        self._kv_pages_alloc += n
        in_use = self._kv_total_pages - 1 - len(free)
        self._kv_pages_hwm = max(self._kv_pages_hwm, in_use)
        return got

    # ---- weights ----------------------------------------------------------
    def _serves_packed(self, fmt_name: str) -> bool:
        return self.packed and fmt_name != DENSE_BF16

    def weights_for(self, fmt_name: str):
        """Serving weights at ``fmt_name`` (packed containers by default).

        Cache miss = one Slice-and-Scale pass from the anchor (+ nibble
        packing at 4 bits); hits are free.
        """
        if fmt_name not in self._weights:
            self._check_anchor()
            with self._phase("convert", fmt=fmt_name):
                if self._serves_packed(fmt_name):
                    w = make_packed_params(self.anchor, self._template,
                                           target_fmt=fmt_name,
                                           dtype=self.api.cfg.compute_dtype)
                else:
                    w = self.dense_weights_for(fmt_name)
                if self.mesh is not None:
                    w = self._lay_out_for_mesh(w, fmt_name)
            self._weights[fmt_name] = w
            self._fmt_swaps += 1
            if self.policy.cost is not None:
                # Replace the format's analytic weight term with the bytes
                # the cached tree actually streams (seed() keeps any
                # learned calibration factor). On a mesh both roofline
                # terms are PER-CHIP: each chip streams only its weight
                # shard and its slice of every KV token.
                wb = (weight_stream_bytes_local(w) if self.mesh is not None
                      else weight_stream_bytes(w))
                self.policy.cost.seed(
                    fmt_name, wb,
                    self._attn_read_span * self._attn_token_bytes_chip)
        return self._weights[fmt_name]

    def _check_anchor(self) -> None:
        """Refuse to build a rung from an anchor another engine has taken
        over (``donate_anchor``), naming the cause, rather than fail later
        on a deleted array."""
        dead = [k for k, v in {**self.anchor.quantized,
                               **self.anchor.raw}.items()
                if any(getattr(x, "is_deleted", lambda: False)()
                       for x in jax.tree_util.tree_leaves(v))]
        if dead:
            raise ValueError(
                f"anchor leaves {dead[:3]} have been deleted: another "
                "engine on this mesh took the anchor over "
                "(donate_anchor=True). Build every engine that shares one "
                "anchor with donate_anchor=False")

    def _lay_out_for_mesh(self, w, fmt_name: str):
        """``w`` placed on the TP mesh, each chip's shard laid out once.

        Each chip's d_ff share is zero-padded to the width the fused GEMM
        tiles without a pad (``layout_tp_shards``), so no step copies a
        weight to pad it; split-N int4 nibbles, which interleave the output
        halves, are re-nibbled per shard, or half the head / ff-block
        contributions would pair wrong inside shard_map. At the anchor's
        own rung the served leaves ARE the anchor's: the padded copies
        replace them in this engine's anchor (later rungs convert from it
        and come out laid out already), and with ``donate_anchor`` inputs
        that already live on the mesh are deleted once copied, since a
        second copy of a sharded MLP would not fit beside the first.
        """
        shardings = self._weight_shardings(w)
        own = self.donate_anchor and fmt_name == self.anchor.fmt_name \
            and self._serves_packed(fmt_name)
        w, laid = layout_tp_shards(w, shardings, self.api.param_axes(),
                                   self._tp, donate=own)
        w = jax.device_put(w, self._weight_shardings(w))
        if own and laid:
            flat = {jax.tree_util.keystr(p): l for p, l in
                    jax.tree_util.tree_flatten_with_path(
                        w, is_leaf=lambda x: isinstance(x, MXTensor))[0]}
            a = self.anchor
            self.anchor = AnchorModel(
                quantized={k: flat[k] for k in a.quantized},
                raw={k: flat.get(k, v) if k in laid else v
                     for k, v in a.raw.items()},
                fmt_name=a.fmt_name)
        return w

    def dense_weights_for(self, fmt_name: str):
        """Dense reference weights at ``fmt_name`` — numerically identical to
        the packed tree (same codes, dequantized eagerly). Not cached."""
        model = self.anchor
        if fmt_name not in (DENSE_BF16, self.anchor.fmt_name):
            model = convert(self.anchor,
                            get_format(fmt_name, self._block_size))
        return materialize(model, self._template,
                           dtype=self.api.cfg.compute_dtype)

    def set_format(self, fmt_name: str):
        self.current_fmt = fmt_name
        return self.weights_for(fmt_name)

    def prompt_logits(self, prompt, fmt_name: str) -> np.ndarray:
        """Next-token logits (V,) f32 after prefilling ``prompt`` alone.

        The prompt goes into slot 0 of a fresh cache one ``prefill_chunk``
        at a time through this engine's own mixed-step executable — its
        weight contract, attention read path and mesh — with every other
        row masked exactly as a free slot is during ``generate``. Engines
        that differ only in those knobs must agree here up to rounding:
        the logits-level check across contracts and meshes. Needs the
        mixed scheduler and the paged layout.
        """
        if self.scheduler != "mixed" or self.kv_layout != "paged":
            raise ValueError("prompt_logits needs scheduler='mixed' and "
                             "kv_layout='paged'")
        prompt = np.asarray(prompt, np.int32)
        if not 0 < prompt.size <= self.prompt_capacity:
            raise ValueError(f"prompt length {prompt.size} outside "
                             f"[1, {self.prompt_capacity}]")
        b, c = self.slots, self.prefill_chunk
        cache = self._new_cache()
        bt = np.zeros(cache["block_table"].shape, np.int32)
        n_pages = -(-prompt.size // self.kv_page_size)
        bt[0, :n_pages] = np.arange(1, n_pages + 1)
        cache["block_table"] = jnp.asarray(bt)
        if self.mesh is not None:
            cache = jax.device_put(cache, self._cache_shardings)
        fn = self._packed_mixed if self._serves_packed(fmt_name) \
            else self._dense_mixed
        weights = self.weights_for(fmt_name)
        cache_len = jnp.zeros((b,), jnp.int32)
        for start in range(0, prompt.size, c):
            take = min(c, prompt.size - start)
            tokens = np.zeros((b, c), np.int32)
            tokens[0, :take] = prompt[start:start + take]
            q_len = np.ones(b, np.int32)
            q_len[0] = take
            logits, cache = fn(weights, {"tokens": jnp.asarray(tokens),
                                         "q_len": jnp.asarray(q_len)},
                               cache, cache_len)
            cache_len = cache_len.at[0].add(take)
        return np.asarray(logits[0], np.float32)

    # ---- admission helpers ------------------------------------------------
    @property
    def prompt_capacity(self) -> int:
        """Longest admissible prompt: ``max_len - 1`` tokens.

        THE single home of this invariant (admission asserts against it,
        prompt bucketing clamps to it, retire-at-capacity compares
        ``slot_len`` to it, and the paged block table — sized from
        ``max_len`` — therefore always covers any bucketed length):
        the cache holds ``max_len`` positions and the first generated
        token's KV is written at position ``plen`` before any retire check
        runs, so one position past the prompt must always exist.
        """
        return self.max_len - 1

    def _prefill_batch(self, prompt: np.ndarray):
        """Tokens (+ true length when bucketing) for one admission."""
        plen = prompt.size
        if not self._bucket:
            return {"tokens": jnp.asarray(prompt[None])}
        blen = _bucket_len(plen, self.prompt_capacity)
        padded = np.zeros(blen, np.int32)
        padded[:plen] = prompt
        return {"tokens": jnp.asarray(padded[None]),
                "lengths": jnp.asarray([plen], jnp.int32)}

    # ---- failure domains (docs/serving_internals.md §7) --------------------
    def _finish(self, r: Request, status: RequestStatus,
                error: Optional[str] = None) -> None:
        """Terminal transition: exactly one per request. Non-COMPLETED
        terminals record their error in ``stats()["failures"]`` — the
        engine-side audit trail a caller reads after a chaotic wave."""
        r.status = status
        r.done = True
        if error is not None:
            r.error = error
        self._status_counts[status.value] = \
            self._status_counts.get(status.value, 0) + 1
        if status is not RequestStatus.COMPLETED:
            self._failures.append({"rid": r.rid, "status": status.value,
                                   "error": error})

    def _max_pages_needed(self, plen: int) -> int:
        """Peak page count one request's admission path will ever hold:
        pages covering the (bucket-padded) prompt plus the first decode
        write. Under chunked admission the peak is at the FINAL chunk
        (earlier chunks hold a prefix of it). The one home of the sizing
        arithmetic that ``_admission_reject`` checks against the whole
        pool."""
        ps = self.kv_page_size
        chunk = self.prefill_chunk
        if chunk is None:
            blen = _bucket_len(plen, self.prompt_capacity) if self._bucket \
                else plen
            return max(-(-blen // ps), plen // ps + 1)
        start = ((plen - 1) // chunk) * chunk        # final chunk's cursor
        take = plen - start
        padded = _bucket_len(take, chunk) if self._bucket else take
        end = min(start + padded, self.max_len)
        return max(-(-end // ps), plen // ps + 1)

    def _admission_reject(self, r: Request) -> Optional[str]:
        """Why this request can NEVER be served (None = admissible): a
        prompt past cache capacity, or (paged) a page demand beyond the
        whole pool even when empty — for those, requeue-and-wait could
        never succeed, so they fail fast instead of wedging the queue."""
        plen = int(np.asarray(r.prompt).size)
        if plen > self.prompt_capacity:
            return (f"prompt ({plen} tokens) exceeds capacity "
                    f"({self.prompt_capacity} = max_len - 1)")
        if self.kv_layout == "paged":
            need = self._max_pages_needed(plen)
            allocatable = self._kv_total_pages - 1    # page 0 is scratch
            if need > allocatable:
                return (f"prompt ({plen} tokens) needs {need} KV page(s) "
                        f"at its admission peak; the pool has only "
                        f"{allocatable} allocatable")
        return None

    def _pop_admissible(self, pending: List[Request],
                        tick: Optional[int] = None) -> Optional[Request]:
        """Next servable ARRIVED request off the queue. Unservable ones
        (``_admission_reject``) terminate FAILED_CAPACITY right here: a
        malformed request costs itself, never the engine or the queue
        behind it.

        ``tick`` gates arrivals (``Request.arrival_tick``; None = treat
        everything as arrived). Among arrived requests, ``admission_order``
        decides: "fifo" takes the earliest-queued; "slo" the best (tier
        rank, queue position) pair — latency-tier first, FIFO within a
        tier, so within-tier fairness is positional and starvation-free
        (a finite workload drains tier by tier).
        """
        while True:
            best_key, idx = None, None
            for j, r in enumerate(pending):
                if tick is not None and r.arrival_tick > tick:
                    continue
                key = (tier_rank(r.slo), j) \
                    if self.admission_order == "slo" else (0, j)
                if best_key is None or key < best_key:
                    best_key, idx = key, j
            if idx is None:
                return None
            r = pending.pop(idx)
            reason = self._admission_reject(r)
            if reason is None:
                if tick is not None:
                    r.admitted_tick = tick
                    r.admitted_s = time.perf_counter() - self._t0
                return r
            self._finish(r, RequestStatus.FAILED_CAPACITY, reason)

    @staticmethod
    def _capacity_victim(active: List[Optional[Request]],
                         bt: np.ndarray) -> Optional[int]:
        """Slot to retire when decode starves the pool with no admission
        to roll back: the largest page-holder (ties -> lowest slot), i.e.
        the retirement that frees the most pages for the survivors."""
        best, best_pages = None, 0
        for j, r in enumerate(active):
            if r is None:
                continue
            held = int((bt[j] != 0).sum())
            if held > best_pages:
                best, best_pages = j, held
        return best

    @staticmethod
    def _nan_pool_page(cache, page: int):
        """NaN-fill physical page ``page`` of every layer's K/V pool —
        injected persistent HBM corruption (chaos only). Unlike a logit
        poison, replays re-read the same poisoned page, so recovery must
        come from escalation/retirement of the rows mapping it; pages no
        row maps (scratch, never-allocated) are provably harmless, and a
        recycled page is fully overwritten by its next prefill."""
        blocks = [dict(blk, **{k: blk[k].at[:, page].set(jnp.nan)
                               for k in ("k_pages", "v_pages") if k in blk})
                  for blk in cache["blocks"]]
        return dict(cache, blocks=blocks)

    def _escalate_or_none(self, fmt: str, tick: int,
                          what: str) -> Optional[str]:
        """One rung toward the anchor (quarantining the rung that just
        misbehaved so later waves never pick it), or None at the anchor —
        the caller then retires the affected rows instead."""
        nxt = self.policy.escalate(fmt)
        if nxt is None:
            return None
        self.policy.quarantine(fmt)
        self._fmt_escalations += 1
        self._escalation_events.append(
            {"tick": tick, "from": fmt, "to": nxt, "at": what})
        self.set_format(nxt)
        return nxt

    def _guarded_prefill(self, attempt, pinned: str, tick: int, what: str,
                         kind: str):
        """Numeric guardrail around one admission executable (a monolithic
        prompt or a final chunk — the ones whose logits are consumed).
        Escalate-and-replay until finite or at the anchor; each attempt is
        a pure function of the pre-tick cache, so replays are safe.
        ``kind`` (``prefill`` | ``chunk``) names the attempts' dispatches.
        Returns ``(logits, cache, new_len, pinned, fail_reason, execs)``.
        """
        execs = 0
        while True:
            with self._phase("dispatch", kind=kind, fmt=pinned):
                logits, cache2, new_len = attempt(pinned)
            execs += 1
            if not self.logit_guard:
                return logits, cache2, new_len, pinned, None, execs
            with self._phase("fetch", what="guard"):
                finite = bool(np.asarray(self._finite_rows(logits)))
            if finite:
                return logits, cache2, new_len, pinned, None, execs
            self._faults_detected += 1
            nxt = self._escalate_or_none(pinned, tick, what)
            if nxt is None:
                return logits, cache2, new_len, pinned, (
                    f"non-finite prefill logits at the anchor rung "
                    f"({pinned}) during {what}"), execs
            pinned = nxt
            self._ticks_replayed += 1

    def _guarded_decode(self, attempt, pinned: str, consumed: List[int],
                        tick: int, kind: str, finite_fn=None):
        """Run one decode/mixed/verify executable under the guardrail.

        Replay semantics (docs/serving_internals.md §7): every attempt is
        a pure function of the PRE-tick ``(cache, cache_len, tokens)`` —
        the caller commits sampling, cache_len advance, and token drain
        only after this returns, so per-slot RNG chains stay "seed + one
        advance per decode tick" and surviving streams are bit-identical
        across replays. KV writes are idempotent (positions >= cache_len
        are simply recomputed — a speculative VERIFY attempt likewise
        overwrites every draft-written position before attending, §9, so
        it replays safely too). An ``InjectedFault`` from the step retries
        at the SAME format (transient-crash model, bounded by
        ``max_step_retries``); non-finite logits in any *consumed* row
        escalate the format one rung and replay; at the anchor the dead
        rows are returned for per-row retirement. ``finite_fn`` overrides
        the per-row finiteness reduction (the verify step's (B, C, V)
        logits reduce the lane axis too). ``kind`` (``decode`` | ``mixed``
        | ``verify``) names the attempts' dispatches.
        Returns ``(logits, cache, pinned, dead_rows, execs)``.
        """
        retries = 0
        execs = 0
        while True:
            try:
                with self._phase("dispatch", kind=kind, fmt=pinned):
                    logits, cache2 = attempt(pinned)
                execs += 1
            except InjectedFault:
                self._faults_detected += 1
                if retries >= self.max_step_retries:
                    raise
                retries += 1
                self._ticks_replayed += 1
                continue
            if not self.logit_guard or not consumed:
                return logits, cache2, pinned, [], execs
            with self._phase("fetch", what="guard"):
                finite = np.asarray((finite_fn or self._finite_rows)(logits))
            dead = [i for i in consumed if not finite[i]]
            if not dead:
                return logits, cache2, pinned, [], execs
            self._faults_detected += 1
            nxt = self._escalate_or_none(pinned, tick,
                                         f"decode tick {tick}")
            if nxt is None:
                return logits, cache2, pinned, dead, execs
            pinned = nxt
            self._ticks_replayed += 1

    # ---- serving loop -----------------------------------------------------
    def generate(self, requests: List[Request], greedy: bool = True,
                 fmt_override: Optional[str] = None, *,
                 guard=None, snapshot_dir: Optional[str] = None,
                 _state: Optional[dict] = None) -> List[Request]:
        """Serve requests to completion with slot-level continuous batching.

        Slot lifecycle (docs/serving_internals.md "Admission & scheduling"):
        free -> prefilling(cursor) -> decoding -> retired. With
        ``prefill_chunk`` set, at most ONE slot is mid-prefill at a time and
        each scheduler tick runs at most one prefill chunk before the
        batched decode step; ``tick_trace`` records the per-tick work so
        that bound is testable, and each ``Request.ttft_s`` is stamped when
        its first token is sampled. Each tick is a profiler span over its
        host phases (``_phase``; docs/serving_internals.md §6).

        Fault isolation (docs/serving_internals.md §7): per-request faults
        (oversized prompt, deadline, cancellation, capacity starvation,
        row-confined NaN at the anchor rung) end that request in a terminal
        ``RequestStatus`` and the loop keeps serving; batch-wide numeric
        faults escalate the pinned format one ladder rung and replay the
        tick from pre-tick state. ``guard`` (a
        ``runtime.fault.PreemptionGuard``) is checked at every tick
        boundary: once triggered, the engine snapshots its host scheduler
        state to ``snapshot_dir`` (if given) and returns with the wave
        incomplete — ``resume(snapshot_dir)`` finishes it with bit-identical
        remaining streams. ``_state`` is the internal resume path; callers
        never pass it.
        """
        if self.speculative is not None and not greedy:
            raise ValueError(
                "speculative decoding is greedy-only: the acceptance rule "
                "compares greedy argmaxes token-for-token; build the "
                "engine without speculative= for sampled decoding")
        b = self.slots
        paged = self.kv_layout == "paged"
        chunk = self.prefill_chunk         # None => monolithic admission
        ps = self.kv_page_size
        fi = self._fault_injector
        if _state is None:
            pending = list(requests)
            active: List[Optional[Request]] = [None] * b
            slot_len = [0] * b             # host mirror of cache_len
            # on a mesh: pools/dense KV sharded over kv heads, block table
            # replicated
            cache = self._new_cache()
            cache_len = jnp.zeros((b,), jnp.int32)
            tokens = jnp.zeros((b, 1), jnp.int32)
            pinned: Optional[str] = None   # format for this batch's lifetime
            filling: Optional[Request] = None   # the (single) mid-prefill
            fill_slot, fill_cursor = -1, 0
            wait_pages = False  # requeued admission waits for a retire to
            #                     free pages before retrying (no hot loop)
            elapsed0 = 0.0
            tick_no = 0     # per-wave scheduler tick: keys the injector and
            #                 survives snapshot/resume (unlike self._ticks,
            #                 which counts only decode ticks, engine-wide)
            if paged:
                # host-side page bookkeeping: the block table mirror ships
                # to the device as a (tiny) step argument whenever it
                # changes; page 0 is reserved scratch, allocatable 1..P-1.
                free_pages = list(range(self._kv_total_pages - 1, 0, -1))
                bt = np.zeros((b, cache["block_table"].shape[1]), np.int32)
            else:
                free_pages, bt = [], None
        else:
            pending = _state["pending"]
            active = _state["active"]
            slot_len = _state["slot_len"]
            cache = _state["cache"]
            cache_len = _state["cache_len"]
            tokens = _state["tokens"]
            pinned = _state["pinned"]
            filling = _state["filling"]
            fill_slot = _state["fill_slot"]
            fill_cursor = _state["fill_cursor"]
            wait_pages = _state["wait_pages"]
            free_pages = _state["free_pages"]
            bt = _state["bt"]
            elapsed0 = _state["elapsed_s"]
            tick_no = _state["tick_no"]
        t0 = time.perf_counter() - elapsed0  # deadline clock spans resumes
        self._t0 = t0
        self.tick_trace = []

        def repin(new_fmt: str) -> str:
            # Escalation mid-wave: fmt_used stays exact for every request
            # whose remaining tokens now come from the escalated rung.
            for a in active:
                if a is not None:
                    a.fmt_used = new_fmt
            return new_fmt

        def release_slot(i: int) -> None:
            # Pages back to the free list + block-table row -> scratch.
            nonlocal wait_pages
            if paged:
                self._free_slot_pages(free_pages, bt, i)
                cache["block_table"] = jnp.asarray(bt)
            wait_pages = False     # freed pages: admission may retry

        def complete_admission(i: int, r: Request, logits) -> None:
            """prefilling -> decoding (or straight to retired): seed the
            slot's RNG stream, sample the first token from the prefill
            logits, stamp TTFT. Seeding happens HERE — at prefill
            completion, right before the first draw — so chunked admission
            (whose mid-prefill slots see decode ticks advance every slot
            key) samples the same stream as monolithic. Called between
            phases: it opens its own."""
            nonlocal tokens
            with self._phase("retire"):
                self._slot_keys = self._slot_keys.at[i].set(
                    jax.random.fold_in(self._key, r.rid))
                # Per-request sampling params land with the RNG reseed —
                # before the first draw, so the whole stream (first token
                # included) uses them.
                self._slot_temp[i] = self.temperature \
                    if r.temperature is None else r.temperature
                self._slot_topp[i] = self.top_p if r.top_p is None \
                    else r.top_p
                drawn = self._sample(logits[None], greedy, slot=i)[0]
            with self._phase("fetch", what="first_token", rid=r.rid):
                first = int(drawn)
            with self._phase("retire"):
                tokens = tokens.at[i, 0].set(first)
                r.fmt_used = pinned            # pinned for the whole sequence
                r.out_tokens.append(first)
                r.ttft_s = time.perf_counter() - t0
                self._tokens_out += 1
                if len(r.out_tokens) >= r.max_new:
                    self._finish(r, RequestStatus.COMPLETED)  # max_new<=1
                    release_slot(i)        # row -> scratch BEFORE any reuse
                else:
                    r.status = RequestStatus.RUNNING
                    active[i] = r

        while pending or filling is not None \
                or any(a is not None for a in active):
            t_tick = time.perf_counter()
            with self._phase("tick", tick=tick_no):
                # ---- tick boundary: the atomic unit of fault handling. A
                # preemption raised mid-tick (real signal or injector) is
                # acted on HERE, with no executable in flight and host state
                # consistent — snapshot and hand the wave back to the caller.
                with self._phase("boundary"):
                    preempted = guard is not None and guard.preempted
                if preempted:
                    if snapshot_dir is not None:
                        with self._phase("fetch", what="snapshot"):
                            self.last_snapshot = self._save_snapshot(
                                snapshot_dir, requests, dict(
                                    pending=pending, active=active,
                                    slot_len=slot_len, cache=cache,
                                    cache_len=cache_len, tokens=tokens,
                                    pinned=pinned, filling=filling,
                                    fill_slot=fill_slot,
                                    fill_cursor=fill_cursor,
                                    wait_pages=wait_pages,
                                    free_pages=free_pages, bt=bt,
                                    elapsed_s=time.perf_counter() - t0,
                                    tick_no=tick_no),
                                greedy, fmt_override)
                        self._snapshots_saved += 1
                    return requests
                tick = tick_no
                tick_no += 1
                with self._phase("sweep"):
                    # ---- per-request sweeps: cancellation (client- or
                    # injector-driven) and deadlines, across queued,
                    # mid-prefill, and decoding requests alike. Each hit is
                    # one terminal status and freed pages; nothing else in
                    # the batch is perturbed.
                    if fi is not None:
                        rid_cancel = fi.cancel_rid(tick)
                        if rid_cancel is not None:
                            for r in pending + [a for a in active if a] + \
                                    ([filling] if filling is not None
                                     else []):
                                if r.rid == rid_cancel:
                                    r.cancel_requested = True
                    now_elapsed = time.perf_counter() - t0

                    def expired(r):
                        if r.cancel_requested:
                            return (RequestStatus.CANCELLED,
                                    "cancelled by client")
                        if r.deadline_s is not None \
                                and now_elapsed > r.deadline_s:
                            return (RequestStatus.TIMED_OUT,
                                    f"deadline {r.deadline_s:.3f}s exceeded "
                                    f"({now_elapsed:.3f}s into the wave)")
                        return None

                    for r in list(pending):
                        if r.arrival_s is None and r.arrival_tick <= tick:
                            r.arrival_s = now_elapsed  # came due this tick;
                            #                    SLO TTFT counts from here
                        verdict = expired(r)
                        if verdict is not None:
                            pending.remove(r)
                            self._finish(r, *verdict)
                    if filling is not None:
                        verdict = expired(filling)
                        if verdict is not None:
                            release_slot(fill_slot)
                            self._finish(filling, *verdict)
                            filling = None
                    for i, r in enumerate(active):
                        if r is None:
                            continue
                        verdict = expired(r)
                        if verdict is not None:
                            active[i] = None
                            release_slot(i)
                            self._finish(r, *verdict)
                    if not (pending or filling is not None
                            or any(a is not None for a in active)):
                        break          # the sweep drained the wave
                    # Injected pool corruption lands before any executable
                    # runs.
                    if fi is not None and paged:
                        page = fi.pool_poison_page(tick)
                        if page is not None:
                            cache = self._nan_pool_page(cache, page)

                    # ---- arrival gating: nothing live and every queued
                    # request still in the future (Request.arrival_tick)
                    # makes this an idle tick — record it and advance the
                    # clock so arrivals come due (the workload generator
                    # schedules in scheduler ticks).
                    idle = filling is None \
                        and not any(a is not None for a in active) \
                        and not any(r.arrival_tick <= tick for r in pending)
                    if idle:
                        pinned = None
                    elif pinned is None:   # engine drained: re-pick format
                        # Load counts ARRIVED queued requests AND their
                        # pending prompt tokens, so a queue of long prompts
                        # downshifts before the admissions start, not after
                        # (serve/policy.py). With a cost model attached the
                        # wave's tightest TPOT budget and expected decode
                        # occupancy drive the pick instead (docs §10);
                        # fmt_override remains operator law.
                        arrived = [r for r in pending
                                   if r.arrival_tick <= tick]
                        pinned = self.policy.pick(
                            queue_depth=len(arrived), active=0,
                            prefill_tokens=sum(r.prompt.size
                                               for r in arrived),
                            tpot_budget_ms=self._tightest_tpot_ms(arrived),
                            decode_rows=max(1, min(b, len(arrived))),
                            override=fmt_override)
                if idle:
                    self._record_tick(0, 0, 0, time.perf_counter() - t_tick,
                                      execs=0, rows=0, decode_rows=0)
                    continue
                self.set_format(pinned)    # a cache miss: engine.convert
                tick_pf_tokens = 0
                tick_pf_chunks = 0
                tick_execs = 0             # executables dispatched this tick
                tick_rows = 0              # batch rows those executables ran
                chunk_tok = None           # staged chunk for the mixed tick

                if chunk is None:
                    # ---- monolithic admission: one whole prompt per free
                    # slot, active slots untouched (but stalled for the full
                    # prefill)
                    for i in range(b):
                        if active[i] is not None or wait_pages:
                            continue
                        with self._phase("admit") as span:
                            r = self._pop_admissible(pending, tick)
                            if r is None:
                                break
                            span.set_metadata(rid=r.rid)
                            r.status = RequestStatus.RUNNING
                            prompt = np.asarray(r.prompt, np.int32)
                            pbatch = self._prefill_batch(prompt)
                            if paged:
                                # Pages to hold the (possibly bucket-padded)
                                # prompt AND the first decode write at
                                # position prompt.size.
                                blen = pbatch["tokens"].shape[1]
                                need = max(-(-blen // ps),
                                           prompt.size // ps + 1)
                                try:
                                    got = self._alloc_pages(
                                        free_pages, need,
                                        f"admission of rid={r.rid}")
                                except RuntimeError as e:
                                    # Admission never outranks running
                                    # work: requeue and wait for a retire to
                                    # free pages (the whole-pool check in
                                    # _pop_admissible guarantees the wait
                                    # can end). An injected failure just
                                    # retries next tick; a real one with
                                    # nothing running means the free list
                                    # leaked — raise.
                                    r.status = RequestStatus.QUEUED
                                    pending.insert(0, r)
                                    self._admission_requeues += 1
                                    if isinstance(e, InjectedFault):
                                        break
                                    if not any(a is not None for a in active):
                                        raise
                                    wait_pages = True
                                    break
                                bt[i, :need] = got
                                cache["block_table"] = jnp.asarray(bt)

                        def attempt(fmt, pb=pbatch, slot=i):
                            fn = self._packed_prefill_slot \
                                if self._serves_packed(fmt) \
                                else self._dense_prefill_slot
                            lg, c2, nl = fn(self.weights_for(fmt), pb, cache,
                                            slot)
                            if fi is not None:
                                lg = fi.maybe_poison_logits(tick, fmt, lg)
                            return lg, c2, nl

                        logits, cache, new_len, new_pinned, fail, execs = \
                            self._guarded_prefill(attempt, pinned, tick,
                                                  f"prefill of rid={r.rid}",
                                                  "prefill")
                        with self._phase("retire"):
                            if new_pinned != pinned:
                                pinned = repin(new_pinned)
                            tick_pf_tokens += pbatch["tokens"].shape[1]
                            tick_pf_chunks += 1
                            tick_execs += execs
                            tick_rows += execs
                            if fail is not None:
                                release_slot(i)
                                self._finish(r, RequestStatus.FAILED_NUMERIC,
                                             fail)
                                continue
                            cache_len = cache_len.at[i].set(new_len)
                            slot_len[i] = prompt.size
                        complete_admission(i, r, logits)
                else:
                    # ---- chunked admission bookkeeping: claim the (single)
                    # mid-prefill request and allocate THIS chunk's pages
                    # (release-and-requeue on exhaustion). Whether the
                    # staged chunk runs as its own executable or rides the
                    # decode batch is the scheduler's call, below.
                    with self._phase("admit") as span:
                        if filling is None and not wait_pages \
                                and None in active:
                            cand = self._pop_admissible(pending, tick)
                            if cand is not None:
                                fill_slot = active.index(None)
                                filling, fill_cursor = cand, 0
                                filling.status = RequestStatus.RUNNING
                                # The mixed tick reads the fill row's cursor
                                # from cache_len; zero the stale value from
                                # the slot's previous occupant at claim
                                # time.
                                cache_len = cache_len.at[fill_slot].set(0)
                        if filling is not None:
                            r, i = filling, fill_slot
                            span.set_metadata(rid=r.rid)
                            prompt = np.asarray(r.prompt, np.int32)
                            plen = prompt.size
                            start = fill_cursor
                            take = min(chunk, plen - start)
                            final = start + take >= plen
                            padded = take if (final and not self._bucket) \
                                else (_bucket_len(take, chunk) if final
                                      else chunk)
                            padded = min(padded, self.max_len - start)
                        if filling is not None and paged:
                            # This chunk's pages only — chunk N's pages are
                            # allocated at chunk N, never all upfront. The
                            # first decode write's page is the decode
                            # tick's job.
                            first_pg = start // ps
                            last_pg = -(-(start + padded) // ps)
                            try:
                                got = self._alloc_pages(
                                    free_pages, last_pg - first_pg,
                                    f"prefill chunk at {start} of "
                                    f"rid={r.rid}")
                            except RuntimeError as e:
                                # Partial admission must not starve the
                                # pool: release the pages already held,
                                # requeue, and retry once a retire frees
                                # pages (injected failures retry next tick
                                # without waiting). With nothing running and
                                # a _pop_admissible-sized prompt, only a
                                # leaked free list gets here — re-raise.
                                self._free_slot_pages(free_pages, bt, i)
                                cache["block_table"] = jnp.asarray(bt)
                                r.status = RequestStatus.QUEUED
                                pending.insert(0, r)
                                filling = None
                                self._admission_requeues += 1
                                if isinstance(e, InjectedFault):
                                    pass   # transient: retry next tick
                                elif any(a is not None for a in active):
                                    wait_pages = True
                                else:
                                    raise
                            else:
                                bt[i, first_pg:last_pg] = got
                                cache["block_table"] = jnp.asarray(bt)
                    if filling is not None:
                        with self._phase("stage"):
                            ctoks = np.zeros(padded, np.int32)
                            ctoks[:take] = prompt[start:start + take]
                            chunk_tok = (start, take, padded, final)

                    # A staged chunk runs as its own executable under the
                    # sequential scheduler — and when no slot is decoding,
                    # where the two schedulers coincide (one executable
                    # either way, identical numerics).
                    chunk_ran_alone = False
                    if chunk_tok is not None and (
                            self.scheduler == "sequential"
                            or not any(a is not None for a in active)):
                        chunk_ran_alone = True
                        start, take, padded, final = chunk_tok
                        with self._phase("stage"):
                            pbatch = {"tokens": jnp.asarray(ctoks[None]),
                                      "lengths": jnp.asarray([plen],
                                                             jnp.int32)}

                        def chunk_attempt(fmt, pb=pbatch, slot=i, st=start):
                            fn = self._packed_prefill_chunk \
                                if self._serves_packed(fmt) \
                                else self._dense_prefill_chunk
                            lg, c2, nl = fn(self.weights_for(fmt), pb, cache,
                                            slot, st)
                            if fi is not None:
                                # A non-final chunk's logits are never
                                # consumed, so a poison landing there is
                                # invisible — as a real corruption of
                                # unread outputs would be.
                                lg = fi.maybe_poison_logits(tick, fmt, lg)
                            return lg, c2, nl

                        if final:
                            # Only the final chunk's logits are consumed
                            # (they seed the first sampled token) — guard
                            # them.
                            (logits, cache, new_len, new_pinned, fail,
                             execs) = self._guarded_prefill(
                                 chunk_attempt, pinned, tick,
                                 f"final chunk of rid={r.rid}", "chunk")
                        else:
                            with self._phase("dispatch", kind="chunk",
                                             fmt=pinned):
                                logits, cache, new_len = \
                                    chunk_attempt(pinned)
                            new_pinned, fail, execs = pinned, None, 1
                        with self._phase("retire"):
                            if new_pinned != pinned:
                                pinned = repin(new_pinned)
                            tick_pf_tokens += padded
                            tick_pf_chunks += 1
                            tick_execs += execs
                            tick_rows += execs
                            if fail is not None:
                                release_slot(i)
                                self._finish(r, RequestStatus.FAILED_NUMERIC,
                                             fail)
                                filling = None
                            else:
                                if not final:
                                    # Nothing of a non-final chunk is
                                    # fetched: wait for it, or the next
                                    # dispatch runs ahead and every chained
                                    # step's output pool is held at once
                                    # (qwen2-72b at tp 4: 1.39 GB a chip
                                    # each, in 5.7 GB of headroom).
                                    jax.block_until_ready(cache)
                                cache_len = cache_len.at[i].set(new_len)
                                fill_cursor = start + take
                                if final:
                                    slot_len[i] = plen
                        if fail is None and final:
                            complete_admission(i, r, logits)
                            filling = None
                        chunk_tok = None

                # Injected preemption fires mid-tick; the guard's flag is
                # acted on at the NEXT tick boundary, exactly like a real
                # signal.
                if fi is not None and guard is not None:
                    fi.maybe_preempt(tick, guard)

                all_free = all(a is None for a in active)
                if all_free or (chunk is not None and chunk_ran_alone
                                and self.scheduler == "mixed"):
                    # No decode this tick. Under the mixed scheduler a chunk
                    # that ran alone ends the tick even when it just
                    # completed admission — the new slot's first decode is
                    # next tick's (one) executable, never a second one on
                    # this tick. The slot's stream is unchanged: its key
                    # advances once per decode tick it sits in, wherever
                    # that tick falls.
                    self._record_tick(tick_pf_tokens, tick_pf_chunks, 0,
                                      time.perf_counter() - t_tick,
                                      execs=tick_execs, rows=tick_rows,
                                      decode_rows=0)
                    if all_free and filling is None:
                        pinned = None      # drained; next wave re-picks
                    continue

                # ---- decode tick: fused step over all slots; free and
                # mid-prefill slots are masked (their cache_len doesn't
                # advance and their sampled tokens are dropped)
                with self._phase("stage"):
                    if paged:
                        # Map the page each active slot's write position
                        # lands in BEFORE the step runs — this is where the
                        # pool grows (and where exhaustion surfaces,
                        # contained, mid-stream).
                        dirty = False
                        for i in range(b):
                            r = active[i]
                            if r is None:
                                continue
                            pg = slot_len[i] // ps
                            while active[i] is not None and bt[i, pg] == 0:
                                try:
                                    got = self._alloc_pages(
                                        free_pages, 1,
                                        f"decode tick for rid={r.rid}")
                                    bt[i, pg] = got[0]
                                    dirty = True
                                except RuntimeError as e:
                                    dirty = True
                                    if filling is not None:
                                        # A decoding slot outranks a partial
                                        # admission: release the mid-prefill
                                        # slot's pages (this tick's staged
                                        # chunk included), requeue it, and
                                        # retry. Restarting the admission
                                        # from chunk 0 later cannot perturb
                                        # its stream (the slot RNG seeds at
                                        # prefill completion).
                                        self._free_slot_pages(free_pages, bt,
                                                              fill_slot)
                                        filling.status = RequestStatus.QUEUED
                                        pending.insert(0, filling)
                                        filling = None
                                        chunk_tok = None
                                        self._admission_requeues += 1
                                        wait_pages = True
                                        continue
                                    # No admission to roll back: the largest
                                    # page-holder retires FAILED_CAPACITY
                                    # and the engine keeps serving the rest
                                    # (raising instead would destroy every
                                    # in-flight stream). The victim may be
                                    # this very slot.
                                    victim = self._capacity_victim(active, bt)
                                    if victim is None:
                                        raise  # free-list invariant breach
                                    vr = active[victim]
                                    held = int((bt[victim] != 0).sum())
                                    active[victim] = None
                                    self._free_slot_pages(free_pages, bt,
                                                          victim)
                                    wait_pages = False
                                    self._finish(
                                        vr, RequestStatus.FAILED_CAPACITY,
                                        f"KV pool exhausted at decode; "
                                        f"retired as largest page-holder "
                                        f"({held} page(s)) after "
                                        f"{len(vr.out_tokens)} token(s): {e}")
                        if dirty:
                            cache["block_table"] = jnp.asarray(bt)
                if chunk_tok is None and all(a is None for a in active):
                    # Victim retirement emptied the batch; nothing left to
                    # run this tick. Survivors-to-be (queued work) admit
                    # next tick.
                    self._record_tick(tick_pf_tokens, tick_pf_chunks, 0,
                                      time.perf_counter() - t_tick,
                                      execs=tick_execs, rows=tick_rows,
                                      decode_rows=0)
                    if filling is None:
                        pinned = None
                    continue

                with self._phase("stage"):
                    mask = np.asarray([a is not None for a in active],
                                      np.int32)
                    # Rows whose logits this tick actually consumes — the
                    # guard checks exactly these (free/masked rows may hold
                    # garbage).
                    consumed = [i for i in range(b) if active[i] is not None]
                    if chunk_tok is not None and chunk_tok[3] \
                            and filling is not None:
                        consumed.append(fill_slot)

                    # ---- speculative decode tick (docs/serving_internals.md
                    # §9): k draft steps at the cheap rung against a LOCAL
                    # cursor, one batched pinned-format verify over the k+1
                    # positions, commit the longest greedy-matching prefix +
                    # bonus token per slot, rewind the rest. Only on
                    # pure-decode ticks (no staged chunk), and only while
                    # the policy says drafting pays for itself.
                    sc = self.speculative
                    spec_now = sc is not None and chunk_tok is None \
                        and bool(consumed)
                    if spec_now:
                        tot = self._spec_accepted + self._spec_rejected
                        rate = (self._spec_accepted / tot
                                if self._spec_ticks >= sc.window and tot
                                else None)
                        spec_now = self.policy.allow_speculation(
                            sc.draft_fmt, pinned, rate, sc.min_acceptance)
                    if spec_now:
                        # Burst length this tick: never write past the cache
                        # (the verify write frontier is slot_len + k_eff <=
                        # max_len - 1) and never draft deeper than the
                        # hungriest slot can still commit (budget - 1 drafts
                        # + the bonus token).
                        buds = {i: min(active[i].max_new
                                       - len(active[i].out_tokens),
                                       self.prompt_capacity - slot_len[i])
                                for i in consumed}
                        k_eff = min(sc.k,
                                    self.max_len - 1
                                    - max(slot_len[i] for i in consumed),
                                    max(buds.values()) - 1)
                        spec_now = k_eff >= 1
                    if spec_now and paged:
                        # Draft-ahead pages covering positions slot_len..
                        # slot_len + k_eff per slot, ON TOP of the
                        # plain-decode page the loop above already mapped.
                        # Speculation never outranks anything: starvation
                        # hands the pages back and runs a plain tick.
                        spec_extra = []
                        try:
                            for i in consumed:
                                base_pg = slot_len[i] // ps
                                for pg in range(base_pg + 1,
                                                (slot_len[i] + k_eff) // ps
                                                + 1):
                                    if bt[i, pg] == 0:
                                        bt[i, pg] = self._alloc_pages(
                                            free_pages, 1,
                                            f"spec draft-ahead for "
                                            f"rid={active[i].rid}")[0]
                                        spec_extra.append((i, pg))
                        except RuntimeError:
                            for i, pg in spec_extra:
                                free_pages.append(int(bt[i, pg]))
                                bt[i, pg] = 0
                                self._kv_pages_freed += 1
                            spec_extra = []
                            self._spec_aborts += 1
                            spec_now = False
                        if spec_extra:
                            cache["block_table"] = jnp.asarray(bt)
                if spec_now:
                    # ---- draft phase: k_eff greedy serve_steps at
                    # draft_fmt. The committed (cache_len, tokens) never
                    # advance — local copies do — so abandoning the burst at
                    # any point needs no undo: draft KV sits past every
                    # committed cursor, masked, and the next write there
                    # overwrites it.
                    with self._phase("stage"):
                        adv = jnp.asarray(mask)
                        loc_len, loc_tok = cache_len, tokens
                        drafts = np.zeros((b, k_eff), np.int64)
                    draft_execs = 0
                    draft_ok = True
                    for j in range(k_eff):
                        try:
                            with self._phase("dispatch", kind="draft",
                                             fmt=sc.draft_fmt):
                                if fi is not None:
                                    fi.maybe_raise_step(tick)
                                fn = self._packed_step \
                                    if self._serves_packed(sc.draft_fmt) \
                                    else self._dense_step
                                lg, cache = fn(
                                    self.weights_for(sc.draft_fmt),
                                    {"tokens": loc_tok}, cache, loc_len)
                                if fi is not None:
                                    lg = fi.maybe_poison_logits(
                                        tick, sc.draft_fmt, lg)
                                d = jnp.argmax(lg, -1)
                        except InjectedFault:
                            # Transient crash mid-burst: drop the burst,
                            # decode plain this tick (the injector fires
                            # once per tick, so the plain attempt below runs
                            # clean).
                            self._faults_detected += 1
                            draft_ok = False
                            break
                        draft_execs += 1
                        if self.logit_guard:
                            with self._phase("fetch", what="guard"):
                                finite = np.asarray(self._finite_rows(lg))
                            if not all(finite[i] for i in consumed):
                                # The draft rung itself is sick: quarantine
                                # it (allow_speculation then vetoes the rest
                                # of the wave — plain anchor-side decode
                                # from here on) and abandon the burst.
                                # Nothing was committed, so there is nothing
                                # to double-emit.
                                self._faults_detected += 1
                                self.policy.quarantine(sc.draft_fmt)
                                draft_ok = False
                                break
                        with self._phase("fetch", what="draft"):
                            drafts[:, j] = np.asarray(d)
                        with self._phase("stage"):
                            loc_tok = d[:, None].astype(jnp.int32)
                            loc_len = loc_len + adv
                    if not draft_ok:
                        self._spec_aborts += 1
                        spec_now = False
                if spec_now:
                    # ---- verify phase: ONE pinned-format executable scores
                    # [last committed token, d_1..d_k] per slot (q_len =
                    # k+1; masked rows ride at q_len 1 exactly as in a mixed
                    # tick). It writes pinned-format K/V over every
                    # draft-written position BEFORE attending, so each
                    # attempt is a pure function of committed state —
                    # _guarded_decode's escalate-and-replay applies
                    # unchanged, and the drafts are never re-run on a
                    # replay.
                    with self._phase("stage"):
                        cdim = k_eff + 1
                        tok2d = jnp.zeros((b, cdim), jnp.int32) \
                            .at[:, 0].set(tokens[:, 0]) \
                            .at[:, 1:].set(jnp.asarray(drafts, jnp.int32))
                        q_np = np.ones(b, np.int32)
                        q_np[mask.astype(bool)] = cdim
                        batch_v = {"tokens": tok2d,
                                   "q_len": jnp.asarray(q_np)}

                    def vattempt(fmt, bv=batch_v):
                        if fi is not None:
                            fi.maybe_raise_step(tick)
                        fn = self._packed_verify if self._serves_packed(fmt) \
                            else self._dense_verify
                        lg, c2 = fn(self.weights_for(fmt), bv, cache,
                                    cache_len)
                        if fi is not None:
                            lg = fi.maybe_poison_logits(tick, fmt, lg)
                        return lg, c2

                    logits3, cache, new_pinned, dead, vexecs = \
                        self._guarded_decode(vattempt, pinned, consumed, tick,
                                             "verify",
                                             finite_fn=self._finite_rows_mq)
                    with self._phase("fetch", what="tokens"):
                        # every committed token is the VERIFY format's own
                        # argmax (accepted drafts equal it by definition),
                        # which is the whole bit-identity guarantee
                        anchor_toks = np.asarray(
                            jnp.argmax(logits3, -1))  # (b, C)
                    with self._phase("retire"):
                        if new_pinned != pinned:
                            pinned = repin(new_pinned)
                        tick_execs += draft_execs + vexecs
                        tick_rows += b * (draft_execs + vexecs)

                        # ---- accept/commit
                        budgets = np.zeros(b, np.int64)
                        for i in consumed:
                            if i not in dead:
                                budgets[i] = buds[i]
                        commit = spec_accept_counts(drafts, anchor_toks,
                                                    budgets)
                        cache_len = cache_len \
                            + jnp.asarray(commit, jnp.int32) \
                            * jnp.asarray(mask)
                        nxt_np = np.array(
                            [anchor_toks[i, max(int(commit[i]) - 1, 0)]
                             for i in range(b)], np.int64)
                        tokens = jnp.asarray(nxt_np, jnp.int32)[:, None]
                        self._ticks += 1
                        self._spec_ticks += 1
                        for i in consumed:
                            if i not in dead:
                                acc = int(commit[i]) - 1
                                self._spec_accepted += acc
                                self._spec_rejected += k_eff - acc

                        # Attention-read accounting: k_eff single-query
                        # walks at a growing cursor plus vexecs multi-query
                        # walks per live slot (mirrors the plain tick's
                        # arithmetic below).
                        window = self.api.cfg.sliding_window
                        kernel = paged and self.attn_impl == "paged_kernel"
                        for i in range(b):
                            if not kernel:
                                self._attn_tokens_read += \
                                    self._attn_read_span \
                                    * (draft_execs + vexecs)
                            elif active[i] is not None:
                                for j in range(draft_execs):
                                    self._attn_tokens_read += pages_read(
                                        slot_len[i] + 1 + j, ps, window) * ps
                                self._attn_tokens_read += \
                                    vexecs * pages_read_mq(
                                        slot_len[i], cdim, ps, window) * ps
                            elif filling is not None and i == fill_slot:
                                self._attn_tokens_read += \
                                    (draft_execs + vexecs) * pages_read(
                                        fill_cursor + 1, ps, window) * ps
                            else:
                                self._attn_tokens_read += \
                                    (draft_execs + vexecs) * ps
                        if kernel:
                            self._count_mq_rows(q_np, cdim, vexecs)

                        # Dead rows (non-finite verify logits at the anchor
                        # rung): retire before the drain, exactly like a
                        # plain tick — no draft of theirs was committed
                        # (budget forced to 0).
                        for i in dead:
                            r_dead = active[i]
                            if r_dead is None:
                                continue
                            active[i] = None
                            release_slot(i)
                            self._finish(
                                r_dead, RequestStatus.FAILED_NUMERIC,
                                f"non-finite logits in this request's row "
                                f"at the anchor rung ({pinned}), verify tick "
                                f"{tick}")

                        # ---- drain + rewind: commit[i] tokens enter the
                        # stream; pages past the new frontier go straight
                        # back to the free list (the KV "rollback" is just
                        # these two lines — no data moves, stale positions
                        # are masked by cache_len).
                        for i, r in enumerate(active):
                            if r is None:
                                continue
                            n_c = int(commit[i])
                            slot_len[i] += n_c
                            r.out_tokens.extend(int(t)
                                                for t in anchor_toks[i, :n_c])
                            self._tokens_out += n_c
                            if paged:
                                self._rollback_slot_pages(free_pages, bt, i,
                                                          slot_len[i])
                            if len(r.out_tokens) >= r.max_new or \
                                    slot_len[i] >= self.prompt_capacity:
                                self._finish(r, RequestStatus.COMPLETED)
                                active[i] = None
                                release_slot(i)
                        if paged:
                            cache["block_table"] = jnp.asarray(bt)
                    self._record_tick(tick_pf_tokens, tick_pf_chunks, 1,
                                      time.perf_counter() - t_tick,
                                      execs=tick_execs, rows=tick_rows,
                                      decode_rows=int(mask.sum()),
                                      draft_execs=draft_execs,
                                      verify_execs=vexecs)
                    if all(a is None for a in active) and filling is None:
                        pinned = None
                    continue

                if chunk_tok is not None:
                    # ---- mixed tick: the staged chunk rides the decode
                    # batch as ONE executable. Decode rows keep their
                    # 1-token budget in column 0; the fill row carries the
                    # whole chunk at its cursor. Free rows stay masked
                    # exactly as under serve_step (q_len=1, cursor frozen,
                    # scratch-page writes).
                    start, take, padded, final = chunk_tok
                    with self._phase("stage"):
                        tok2d = jnp.zeros((b, padded), jnp.int32) \
                            .at[:, 0].set(tokens[:, 0]) \
                            .at[fill_slot].set(jnp.asarray(ctoks))
                        q_len_np = np.ones(b, np.int32)
                        q_len_np[fill_slot] = take
                        batch_mx = {"tokens": tok2d,
                                    "q_len": jnp.asarray(q_len_np)}

                    def attempt(fmt, bm=batch_mx):
                        if fi is not None:
                            fi.maybe_raise_step(tick)
                        fn = self._packed_mixed if self._serves_packed(fmt) \
                            else self._dense_mixed
                        lg, c2 = fn(self.weights_for(fmt), bm, cache,
                                    cache_len)
                        if fi is not None:
                            lg = fi.maybe_poison_logits(tick, fmt, lg)
                        return lg, c2
                else:
                    def attempt(fmt):
                        if fi is not None:
                            fi.maybe_raise_step(tick)
                        fn = self._packed_step if self._serves_packed(fmt) \
                            else self._dense_step
                        lg, c2 = fn(self.weights_for(fmt),
                                    {"tokens": tokens}, cache, cache_len)
                        if fi is not None:
                            lg = fi.maybe_poison_logits(tick, fmt, lg)
                        return lg, c2

                # Escalate-and-replay runs HERE, against pre-tick state; the
                # commits below (cache_len advance, batched draw, token
                # drain) happen exactly once, after the guard settles.
                logits, cache, new_pinned, dead, execs = \
                    self._guarded_decode(
                        attempt, pinned, consumed, tick,
                        "mixed" if chunk_tok is not None else "decode")
                with self._phase("retire"):
                    if new_pinned != pinned:
                        pinned = repin(new_pinned)
                    tick_execs += execs
                    tick_rows += b * execs
                    if chunk_tok is not None:
                        adv = mask.copy()
                        adv[fill_slot] = take
                        cache_len = cache_len + jnp.asarray(adv)
                        tick_pf_tokens += padded
                        tick_pf_chunks += 1
                    else:
                        cache_len = cache_len + jnp.asarray(mask)
                    # The batched draw advances EVERY slot key once per
                    # decode-carrying tick — the fill row's draw is
                    # discarded, and if its chunk completed this tick,
                    # complete_admission reseeds the key from scratch below,
                    # so the stream matches sequential admission bit for
                    # bit.
                    nxt = self._sample(logits, greedy)
                    tokens = nxt[:, None].astype(jnp.int32)
                    self._ticks += 1
                    attn_before = self._attn_tokens_read

                    # Attention-read accounting for the tick that just ran.
                    # Every batch row is processed (free/mid-prefill slots
                    # are masked, not removed): gather (and the dense
                    # layout) materializes the full logical span for ALL
                    # rows; the kernel walks pages_read(...) distinct pages
                    # (kernels/paged_attention.py — the one home of that
                    # clamp arithmetic) for rows with mapped pages —
                    # decoding slots at slot_len+1, the mid-prefill slot at
                    # its cursor+1 — and a single clamped-revisit scratch
                    # page for zeroed rows (every walk step maps to page 0,
                    # so Pallas elides the repeats).
                    window = self.api.cfg.sliding_window
                    kernel = paged and self.attn_impl == "paged_kernel"
                    if kernel and chunk_tok is not None:
                        self._count_mq_rows(q_len_np, padded, execs)
                    for i in range(b):
                        if not kernel:
                            self._attn_tokens_read += self._attn_read_span
                        elif active[i] is not None:
                            self._attn_tokens_read += \
                                pages_read(slot_len[i] + 1, ps, window) * ps
                        elif chunk_tok is not None and i == fill_slot:
                            # Mixed tick: the fill row's ragged query span
                            # walks its own clamped page range
                            # (pages_read_mq mirrors the MQ kernel's
                            # arithmetic the way pages_read mirrors the
                            # single-query kernel's).
                            self._attn_tokens_read += \
                                pages_read_mq(start, take, ps, window) * ps
                        elif filling is not None and i == fill_slot:
                            self._attn_tokens_read += \
                                pages_read(fill_cursor + 1, ps, window) * ps
                        else:
                            self._attn_tokens_read += ps

                    # ---- dead rows (non-finite logits at the anchor rung):
                    # the fault is confined to these requests — retire them
                    # BEFORE the drain so no poisoned token ever enters a
                    # stream; every other slot's draw this tick is
                    # untouched.
                    for i in dead:
                        if filling is not None and i == fill_slot:
                            release_slot(i)
                            self._finish(
                                filling, RequestStatus.FAILED_NUMERIC,
                                f"non-finite final-chunk logits in this "
                                f"request's row at the anchor rung "
                                f"({pinned}), tick {tick}")
                            filling = None
                            continue
                        r_dead = active[i]
                        if r_dead is None:
                            continue
                        active[i] = None
                        release_slot(i)
                        self._finish(
                            r_dead, RequestStatus.FAILED_NUMERIC,
                            f"non-finite logits in this request's row at "
                            f"the anchor rung ({pinned}), tick {tick}")

                # ---- retire: ONE host transfer per tick drains every slot
                with self._phase("fetch", what="tokens"):
                    drained = np.asarray(nxt)
                with self._phase("retire"):
                    for i, r in enumerate(active):
                        if r is None:
                            continue
                        slot_len[i] += 1
                        r.out_tokens.append(int(drained[i]))
                        self._tokens_out += 1
                        if len(r.out_tokens) >= r.max_new or \
                                slot_len[i] >= self.prompt_capacity:
                            self._finish(r, RequestStatus.COMPLETED)
                            active[i] = None   # slot re-admissible next tick
                            release_slot(i)    # pages recycle on next admit
                    # ---- mixed-tick chunk epilogue: advance the cursor, and
                    # if the chunk reached the prompt end, complete
                    # admission from the fill row's logits — AFTER the
                    # batched draw above, so the reseed overwrites the
                    # discarded draw's key advance. (A dead fill row already
                    # retired FAILED_NUMERIC above.)
                    admitted = chunk_tok is not None and final \
                        and filling is not None
                    if chunk_tok is not None:
                        fill_cursor = start + take
                    if admitted:
                        slot_len[fill_slot] = plen
                        fill_logits = logits[fill_slot]
                if admitted:
                    complete_admission(fill_slot, filling, fill_logits)
                    filling = None
                # ---- cost-model calibration: only CLEAN pure-decode ticks
                # (no prefill work, exactly one executable — no replays) are
                # attributable to the pinned format's per-tick cost; the
                # measured attention read refreshes the per-row byte term.
                with self._phase("retire"):
                    cost = self.policy.cost
                    rows_d = int(mask.sum())
                    if cost is not None and rows_d and tick_pf_chunks == 0 \
                            and tick_execs == 1:
                        seen = self._fmt_decode_ticks.get(pinned, 0)
                        self._fmt_decode_ticks[pinned] = seen + 1
                        if seen:   # a format's first clean tick pays jit
                            #        compile — warmup, not cost; never fold
                            #        it into the model
                            cost.observe(
                                pinned, rows_d, time.perf_counter() - t_tick,
                                attn_bytes_per_row=(self._attn_tokens_read
                                                    - attn_before)
                                * self._attn_token_bytes / rows_d)
                self._record_tick(tick_pf_tokens, tick_pf_chunks, 1,
                                  time.perf_counter() - t_tick,
                                  execs=tick_execs, rows=tick_rows,
                                  decode_rows=rows_d)
                if all(a is None for a in active) and filling is None:
                    pinned = None
        return requests

    @staticmethod
    def _tightest_tpot_ms(reqs: List[Request]) -> Optional[float]:
        """The wave's binding per-token budget: the minimum ``tpot_ms``
        among requests that carry one (None when nobody does — the policy
        then falls back to its threshold table)."""
        vals = [r.slo.tpot_ms for r in reqs
                if r.slo is not None and r.slo.tpot_ms is not None]
        return min(vals) if vals else None

    def _count_mq_rows(self, q_len, c: int, execs: int) -> None:
        """Account ``execs`` runs of one multi-query step whose rows carry
        ``q_len`` live queries in a block of ``c`` lanes: the query rows
        the MQ kernel folds per kv head (``mq_rows_folded``, the narrow
        fold) against the ``B * C * G`` rows the block holds.
        ``stats()["mq_rows_folded"] / stats()["mq_rows_padded"]`` is the
        share of the padded fold that runs."""
        g = self.api.cfg.n_heads // self.api.cfg.n_kv_heads
        self._mq_rows_padded += execs * len(q_len) * c * g
        self._mq_rows_folded += execs * sum(
            mq_rows_folded(int(n), c, g) for n in q_len)

    def _record_tick(self, prefill_tokens: int, prefill_chunks: int,
                     decode: int, wall_s: float, *, execs: int = 0,
                     rows: int = 0, decode_rows: int = 0,
                     draft_execs: int = 0, verify_execs: int = 0) -> None:
        """Append one scheduler-tick trace entry (reset per ``generate``).

        ``prefill_tokens`` counts padded prompt tokens prefilled this tick
        (one chunk at most under chunked admission; whole prompts under
        monolithic), ``decode`` is 1 when a batched decode step ran.
        ``execs`` counts device executables dispatched this tick — the
        mixed scheduler's invariant, exactly one per work tick, is asserted
        from it in tests (monolithic admission may run several: one prefill
        per admitted slot plus the decode step). ``rows`` counts batch rows
        those executables processed and ``decode_rows`` the subset that were
        live decoding slots; ``benchmarks/serve_engine_bench.py`` derives
        its decode-occupancy and decode-stall columns from these plus
        ``wall_s``. ``draft_execs``/``verify_execs`` split ``execs`` on a
        speculative tick (both 0 otherwise), so the execs-per-tick
        invariants stay assertable under speculation: a non-spec tick's
        plain executables are exactly
        ``execs - draft_execs - verify_execs``.

        The open tick's record (``_phase``) adds: ``kind``, the kind of
        its last step dispatch (``idle`` when none ran); ``phase_s``, host
        seconds per phase, which sum to at most ``wall_s`` since this runs
        after the tick's last phase closes; and, on the engine clock
        (seconds since ``generate`` started, as ``Request.arrival_s``),
        ``dispatched_s`` when its first step dispatch began, ``fetched_s``
        when its last device-to-host fetch ended and ``step_s``, the last
        dispatch's start to the end of the first fetch after it — the step
        latency the host observed. Each is None when the tick dispatched
        (or fetched) nothing; a chunk that runs alone with no logits to
        read is never fetched. ``exchange_bytes`` sums the collectives of
        the tick's steps on a tensor-parallel mesh (``_mesh_jit``): each
        all-reduce's operand and the logits' all-gather result, from the
        shapes the step ran; 0 on one chip.
        """
        rec = self._tick_rec
        self.tick_trace.append({"prefill_tokens": prefill_tokens,
                                "prefill_chunks": prefill_chunks,
                                "decode": decode, "wall_s": wall_s,
                                "execs": execs, "rows": rows,
                                "decode_rows": decode_rows,
                                "draft_execs": draft_execs,
                                "verify_execs": verify_execs,
                                "kind": rec["kind"],
                                "phase_s": dict(rec["phase_s"]),
                                "dispatched_s": rec["dispatched_s"],
                                "fetched_s": rec["fetched_s"],
                                "step_s": rec["step_s"],
                                "exchange_bytes": rec["exchange_bytes"]})

    @contextlib.contextmanager
    def _phase(self, name: str, **attrs):
        """Time one stretch of host work as the span ``engine.<name>``.

        The span is a ``jax.profiler.TraceAnnotation``: while a profiler
        runs, it lands on the host plane on the clock of the device's ops,
        so an operator's trace names each idle gap of the device by the
        host phase over it; with no profiler it costs well under a
        microsecond. ``tick`` opens a tick's record (``_record_tick``).
        The phases under it are siblings and add their seconds to
        ``phase_s``; a ``dispatch`` (attribute ``kind``) and a ``fetch``
        also stamp the tick. A phase opened inside another (a conversion
        under a dispatch) is a span only: its time is its parent's.
        """
        t = time.perf_counter()
        if name == "tick":
            self._tick_rec = {"kind": "idle", "phase_s": {},
                              "dispatched_s": None, "fetched_s": None,
                              "step_s": None, "exchange_bytes": 0}
            self._step_t = None
        rec, nested = self._tick_rec, self._in_phase
        self._in_phase = name != "tick"
        try:
            with jax.profiler.TraceAnnotation(f"engine.{name}",
                                              **attrs) as span:
                yield span
        finally:
            end = time.perf_counter()
            self._in_phase = nested
            if name == "tick":
                self._tick_rec = None
            elif rec is not None and not nested:
                ph = rec["phase_s"]
                ph[name] = ph.get(name, 0.0) + end - t
                if name == "dispatch":
                    rec["kind"] = attrs["kind"]
                    if rec["dispatched_s"] is None:
                        rec["dispatched_s"] = t - self._t0
                    self._step_t = t
                elif name == "fetch":
                    rec["fetched_s"] = end - self._t0
                    if self._step_t is not None:
                        rec["step_s"] = end - self._step_t
                        self._step_t = None

    def _free_slot_pages(self, free_pages: List[int], bt: np.ndarray,
                         slot: int) -> None:
        """Return a retired slot's pages to the free list and point its
        block-table row at the scratch page (0) so any further masked write
        from the still-batched slot lands there, never on a recycled page."""
        used = bt[slot][bt[slot] != 0]
        free_pages.extend(int(p) for p in used)
        self._kv_pages_freed += used.size
        bt[slot, :] = 0

    def _rollback_slot_pages(self, free_pages: List[int], bt: np.ndarray,
                             slot: int, frontier: int) -> None:
        """Speculative rewind, page half: free this slot's pages strictly
        past the one holding position ``frontier - 1`` (the last committed
        token after acceptance). Earlier pages — and every other slot's
        block-table row — are untouched; the freed pages' stale draft KV
        is unreachable (masked by ``cache_len`` until recycled, then
        overwritten by the next occupant's writes before any read). This
        restores the plain-decode steady-state invariant exactly: a slot
        holds ``ceil(slot_len / page)`` pages between ticks, so
        ``alloc == freed`` at retire regardless of accept/reject history.
        """
        keep = -(-frontier // self.kv_page_size)
        tail = bt[slot, keep:]
        drop = tail[tail != 0]
        free_pages.extend(int(p) for p in drop)
        self._kv_pages_freed += drop.size
        bt[slot, keep:] = 0

    def _sample(self, logits, greedy: bool, slot: Optional[int] = None):
        """Greedy argmax, or a temperature/top-p draw from per-slot streams.

        ``slot=None`` advances every slot's key by one draw (the decode
        tick); a slot index draws for that slot only (admission). Free
        slots' draws are discarded by the caller; advancing their keys is
        harmless and keeps the tick one fused vmap.
        """
        if greedy or self.temperature <= 0:
            return jnp.argmax(logits, -1)
        temps = jnp.asarray(self._slot_temp)
        tops = jnp.asarray(self._slot_topp)
        if slot is None:
            self._slot_keys, toks = _sample_batch(
                self._slot_keys, logits, temps, tops)
            return toks
        new_key, toks = _sample_batch(
            self._slot_keys[slot][None], logits, temps[slot][None],
            tops[slot][None])
        self._slot_keys = self._slot_keys.at[slot].set(new_key[0])
        return toks

    # ---- snapshot / resume (docs/serving_internals.md §7) ------------------
    @staticmethod
    def _encode_leaf(x) -> np.ndarray:
        """``np.savez`` degrades ml_dtypes leaves (bfloat16) to opaque void
        bytes; widen them to float32 (exact — every bf16 is an f32) for the
        archive. ``resume`` casts each leaf back through the cache
        template's dtype, so the round trip is bit-faithful."""
        a = np.asarray(x)
        if a.dtype.kind not in "iufb" or a.dtype == np.dtype(jnp.bfloat16):
            a = a.astype(np.float32)
        return a

    def _snapshot_fingerprint(self) -> dict:
        """The engine-config facts a snapshot's cache arrays and scheduler
        state are only meaningful under. ``resume`` refuses a snapshot whose
        fingerprint differs — silently resuming onto a different layout
        would corrupt streams, not fail loudly."""
        return {
            "family": self.api.cfg.family,
            "slots": self.slots,
            "max_len": self.max_len,
            "kv_layout": self.kv_layout,
            "kv_page_size": self.kv_page_size,
            "kv_total_pages": self._kv_total_pages,
            "attn_impl": self.attn_impl,
            "fused": bool(self.fused),
            "packed": self.packed,
            "prefill_chunk": self.prefill_chunk,
            "scheduler": self.scheduler,
            "bucket": self._bucket,
            "temperature": self.temperature,
            "top_p": self.top_p,
            "admission_order": self.admission_order,
            # string-encoded so the JSON manifest round-trips exactly
            "speculative": (f"{self.speculative.draft_fmt}:k"
                            f"{self.speculative.k}"
                            if self.speculative is not None else None),
            # "DxM" mesh shape (None = single device): a snapshot taken on
            # a mesh holds sharded-layout state and must resume on the
            # same mesh shape.
            "mesh": self._mesh_str(),
        }

    def _mesh_str(self) -> Optional[str]:
        if self.mesh is None:
            return None
        n_dev = int(np.prod(self.mesh.devices.shape))
        return f"{n_dev // self._tp}x{self._tp}"

    def _save_snapshot(self, root: str, requests: List[Request], st: dict,
                       greedy: bool, fmt_override: Optional[str]) -> str:
        """Serialize the wave's complete scheduler state at a tick boundary
        via ``checkpoint.io.save_flat`` (atomic, manifest-driven). Arrays:
        the KV cache's flattened leaves, cache_len/tokens, the RNG keys, the
        block-table mirror, and each request's prompt + emitted tokens;
        everything host-structural (queues, cursors, counters, statuses)
        rides the manifest. ``resume`` reconstructs from these alone, so a
        FRESH engine process (same config) can finish the wave."""
        arrays: Dict[str, np.ndarray] = {}
        leaves, _ = jax.tree_util.tree_flatten(st["cache"])
        for n, leaf in enumerate(leaves):
            arrays[f"cache_{n:04d}"] = self._encode_leaf(leaf)
        arrays["cache_len"] = np.asarray(st["cache_len"])
        arrays["tokens"] = np.asarray(st["tokens"])
        arrays["slot_keys"] = np.asarray(self._slot_keys)
        arrays["engine_key"] = np.asarray(self._key)
        arrays["slot_temp"] = self._slot_temp.copy()
        arrays["slot_topp"] = self._slot_topp.copy()
        if st["bt"] is not None:
            arrays["bt"] = np.asarray(st["bt"])
        for r in requests:
            arrays[f"prompt_{r.rid}"] = np.asarray(r.prompt, np.int32)
            # int64 + explicit dtype: an empty out_tokens list must not
            # round-trip as float64.
            arrays[f"out_{r.rid}"] = np.asarray(r.out_tokens, np.int64)
        meta = {
            "kind": "elastic-engine-snapshot",
            "fingerprint": self._snapshot_fingerprint(),
            "greedy": bool(greedy),
            "fmt_override": fmt_override,
            "pinned": st["pinned"],
            "elapsed_s": float(st["elapsed_s"]),
            "tick_no": int(st["tick_no"]),
            "requests": [{"rid": r.rid, "max_new": int(r.max_new),
                          "status": r.status.value, "error": r.error,
                          "fmt_used": r.fmt_used, "ttft_s": r.ttft_s,
                          "deadline_s": r.deadline_s, "done": bool(r.done),
                          "cancel_requested": bool(r.cancel_requested),
                          "slo": (r.slo.to_dict() if r.slo is not None
                                  else None),
                          "tenant": r.tenant,
                          "arrival_tick": int(r.arrival_tick),
                          "arrival_s": r.arrival_s,
                          "admitted_tick": r.admitted_tick,
                          "admitted_s": r.admitted_s,
                          "temperature": r.temperature,
                          "top_p": r.top_p}
                         for r in requests],
            "pending": [r.rid for r in st["pending"]],
            "active": [(a.rid if a is not None else None)
                       for a in st["active"]],
            "slot_len": [int(v) for v in st["slot_len"]],
            "filling": (st["filling"].rid if st["filling"] is not None
                        else None),
            "fill_slot": int(st["fill_slot"]),
            "fill_cursor": int(st["fill_cursor"]),
            "wait_pages": bool(st["wait_pages"]),
            "free_pages": [int(p) for p in st["free_pages"]],
            "quarantined": sorted(self.policy.quarantined),
            "counters": {
                "ticks": self._ticks,
                "tokens_out": self._tokens_out,
                "kv_pages_alloc": self._kv_pages_alloc,
                "kv_pages_freed": self._kv_pages_freed,
                "kv_pages_hwm": self._kv_pages_hwm,
                "faults_detected": self._faults_detected,
                "fmt_escalations": self._fmt_escalations,
                "ticks_replayed": self._ticks_replayed,
                "admission_requeues": self._admission_requeues,
                "attn_tokens_read": self._attn_tokens_read,
                "mq_rows_folded": self._mq_rows_folded,
                "mq_rows_padded": self._mq_rows_padded,
                "spec_ticks": self._spec_ticks,
                "spec_accepted": self._spec_accepted,
                "spec_rejected": self._spec_rejected,
                "spec_aborts": self._spec_aborts,
                "status_counts": self._status_counts,
                "failures": self._failures,
                "escalation_events": self._escalation_events,
            },
        }
        self._snap_step += 1
        return ckpt_io.save_flat(root, self._snap_step, arrays,
                                 extra_meta=meta)

    def resume(self, snapshot_dir: str, *, guard=None,
               step: Optional[int] = None) -> List[Request]:
        """Finish a preempted wave from its snapshot (LATEST by default).

        Reconstructs the Request objects, scheduler queues, KV cache, and
        RNG streams saved by ``_save_snapshot`` and re-enters ``generate``
        mid-wave; remaining token streams are bit-identical to the
        uninterrupted run (each slot key advanced once per decode tick it
        actually sat in, on either side of the cut). The engine must be
        configured identically to the one that snapshotted — a fingerprint
        mismatch raises ``ValueError`` rather than corrupting streams.
        Returns the reconstructed (completed) request list."""
        arrays, manifest = ckpt_io.restore_flat(snapshot_dir, step)
        meta = manifest["meta"]
        if meta.get("kind") != "elastic-engine-snapshot":
            raise ValueError(
                f"{snapshot_dir} holds {meta.get('kind')!r}, not an "
                "elastic-engine-snapshot")
        fp_saved = meta["fingerprint"]
        fp_now = self._snapshot_fingerprint()
        if fp_saved != fp_now:
            diff = {k: {"snapshot": fp_saved.get(k), "engine": fp_now.get(k)}
                    for k in sorted(set(fp_saved) | set(fp_now))
                    if fp_saved.get(k) != fp_now.get(k)}
            raise ValueError(
                "snapshot/engine fingerprint mismatch — resume requires an "
                f"identically configured engine; differs on: {diff}")
        tmpl_leaves, treedef = jax.tree_util.tree_flatten(
            jax.eval_shape(lambda: self._init_cache(self.slots)))
        cache = jax.tree_util.tree_unflatten(treedef, [
            np.asarray(arrays[f"cache_{n:04d}"]).astype(t.dtype)
            for n, t in enumerate(tmpl_leaves)])
        # from the host straight to each chip's share (see _new_cache)
        cache = jax.device_put(cache, self._cache_shardings)
        self._key = jnp.asarray(arrays["engine_key"])
        self._slot_keys = jnp.asarray(arrays["slot_keys"])
        if "slot_temp" in arrays:
            self._slot_temp = np.asarray(arrays["slot_temp"],
                                         np.float32).copy()
            self._slot_topp = np.asarray(arrays["slot_topp"],
                                         np.float32).copy()
        by_rid: Dict[int, Request] = {}
        requests: List[Request] = []
        for rd in meta["requests"]:
            r = Request(rid=rd["rid"], prompt=arrays[f"prompt_{rd['rid']}"],
                        max_new=rd["max_new"])
            r.out_tokens = [int(t) for t in arrays[f"out_{rd['rid']}"]]
            r.status = RequestStatus(rd["status"])
            r.error = rd["error"]
            r.fmt_used = rd["fmt_used"]
            r.ttft_s = rd["ttft_s"]
            r.deadline_s = rd["deadline_s"]
            r.done = rd["done"]
            r.cancel_requested = rd["cancel_requested"]
            sd = rd.get("slo")
            r.slo = SLOClass.from_dict(sd) if sd is not None else None
            r.tenant = rd.get("tenant")
            r.arrival_tick = int(rd.get("arrival_tick", 0))
            r.arrival_s = rd.get("arrival_s")
            r.admitted_tick = rd.get("admitted_tick")
            r.admitted_s = rd.get("admitted_s")
            r.temperature = rd.get("temperature")
            r.top_p = rd.get("top_p")
            by_rid[r.rid] = r
            requests.append(r)
        c = meta["counters"]
        self._ticks = c["ticks"]
        self._tokens_out = c["tokens_out"]
        self._kv_pages_alloc = c["kv_pages_alloc"]
        self._kv_pages_freed = c["kv_pages_freed"]
        self._kv_pages_hwm = c["kv_pages_hwm"]
        self._faults_detected = c["faults_detected"]
        self._fmt_escalations = c["fmt_escalations"]
        self._ticks_replayed = c["ticks_replayed"]
        self._admission_requeues = c["admission_requeues"]
        self._attn_tokens_read = c["attn_tokens_read"]
        self._mq_rows_folded = c.get("mq_rows_folded", 0)
        self._mq_rows_padded = c.get("mq_rows_padded", 0)
        self._spec_ticks = c.get("spec_ticks", 0)
        self._spec_accepted = c.get("spec_accepted", 0)
        self._spec_rejected = c.get("spec_rejected", 0)
        self._spec_aborts = c.get("spec_aborts", 0)
        self._status_counts = dict(c["status_counts"])
        self._failures = list(c["failures"])
        self._escalation_events = list(c["escalation_events"])
        self.policy.quarantined |= set(meta["quarantined"])
        self._resumes += 1
        state = dict(
            pending=[by_rid[rid] for rid in meta["pending"]],
            active=[by_rid[rid] if rid is not None else None
                    for rid in meta["active"]],
            slot_len=[int(v) for v in meta["slot_len"]],
            cache=cache,
            cache_len=jnp.asarray(arrays["cache_len"]),
            tokens=jnp.asarray(arrays["tokens"]),
            pinned=meta["pinned"],
            filling=(by_rid[meta["filling"]]
                     if meta["filling"] is not None else None),
            fill_slot=meta["fill_slot"],
            fill_cursor=meta["fill_cursor"],
            wait_pages=meta["wait_pages"],
            free_pages=list(meta["free_pages"]),
            bt=(np.asarray(arrays["bt"]).copy()
                if "bt" in arrays else None),
            elapsed_s=meta["elapsed_s"],
            tick_no=meta["tick_no"])
        return self.generate(requests, greedy=meta["greedy"],
                             fmt_override=meta["fmt_override"],
                             guard=guard, snapshot_dir=snapshot_dir,
                             _state=state)

    # ---- introspection ----------------------------------------------------
    @property
    def stats(self):
        def containers(tree):
            kinds = {type(l).__name__
                     for l in jax.tree_util.tree_leaves(
                         tree, is_leaf=lambda x: isinstance(
                             x, (MXTensor, PackedInt4Leaf)))
                     if isinstance(l, (MXTensor, PackedInt4Leaf))}
            return sorted(kinds) or ["dense"]

        return {
            "formats_cached": sorted(self._weights),
            "containers": {f: containers(t)
                           for f, t in self._weights.items()},
            "weight_bytes": {f: weight_stream_bytes(t)
                             for f, t in self._weights.items()},
            "weight_bytes_per_chip": {f: weight_stream_bytes_local(t)
                                      for f, t in self._weights.items()},
            "mesh": self._mesh_str(),
            "tp_exchange_bytes": self._tp_exchange_bytes,
            "fmt_swaps": self._fmt_swaps,
            "ticks": self._ticks,
            "tokens_out": self._tokens_out,
            "fused": self.fused,
            "prefill_traces": sum(self._traces.values()),
            "traces": dict(self._traces),
            "prefill_chunk": self.prefill_chunk,
            "admission_requeues": self._admission_requeues,
            "kv_layout": self.kv_layout,
            "kv_bytes_per_slot": self._kv_cache_bytes // self.slots,
            "kv_page_size": self.kv_page_size,
            "kv_total_pages": self._kv_total_pages,
            "kv_pages_alloc": self._kv_pages_alloc,
            "kv_pages_freed": self._kv_pages_freed,
            "kv_pages_hwm": self._kv_pages_hwm,
            "speculative": (dataclasses.asdict(self.speculative)
                            if self.speculative is not None else None),
            "spec_ticks": self._spec_ticks,
            "spec_accepted": self._spec_accepted,
            "spec_rejected": self._spec_rejected,
            "spec_aborts": self._spec_aborts,
            "spec_acceptance_rate": (
                self._spec_accepted
                / (self._spec_accepted + self._spec_rejected)
                if self._spec_accepted + self._spec_rejected else None),
            "faults_detected": self._faults_detected,
            "fmt_escalations": self._fmt_escalations,
            "escalation_events": list(self._escalation_events),
            "ticks_replayed": self._ticks_replayed,
            "request_statuses": dict(self._status_counts),
            "failures": list(self._failures),
            "snapshots_saved": self._snapshots_saved,
            "resumes": self._resumes,
            "quarantined_formats": sorted(self.policy.quarantined),
            "attn_impl": self.attn_impl,
            "attn_tokens_read": self._attn_tokens_read,
            "attn_read_bytes": self._attn_tokens_read
            * self._attn_token_bytes,
            "mq_rows_folded": self._mq_rows_folded,
            "mq_rows_padded": self._mq_rows_padded,
            "admission_order": self.admission_order,
            "cost_model": (self.policy.cost.snapshot()
                           if self.policy.cost is not None else None),
        }
