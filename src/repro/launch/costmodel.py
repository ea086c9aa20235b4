"""Analytic per-(arch x shape x mesh) cost model for the roofline analysis.

Why analytic: XLA:CPU ``cost_analysis()`` counts a ``while`` body ONCE, not
times its trip count (verified empirically — see EXPERIMENTS.md §Roofline
methodology), and every stack here is a scan-over-layers with scans inside.
So FLOPs/bytes/collective-bytes are derived from the model algebra — exact
for matmul-dominated transformers — and *validated* against compiled HLO
counts on small unrolled configs (tests/test_costmodel.py). The dry-run
still provides compile success, memory analysis, and the structural list of
collectives; this module provides the magnitudes.

Conventions:
  - FLOPs count multiply+add as 2 (XLA convention).
  - Backward matmul cost = 2x forward (dgrad + wgrad); full remat adds one
    extra forward: train factor = 2 (fwd) + 4 (bwd) + 2 (remat) = 8x the
    per-matmul MACs... expressed as ``TRAIN_MM_FACTOR * fwd_flops`` with
    fwd counted once.
  - Flash attention computes masked full blocks: causal costs the full
    S x S_kv rectangle (honest about the implementation; the banded SWA path
    costs S x min(S, W + chunk)).
"""
from __future__ import annotations

import dataclasses
from typing import Dict

from repro.configs.shapes import ShapeSpec, decode_cache_len
from repro.models.common import ModelConfig

TRAIN_MM_FACTOR = 8.0     # fwd + bwd(2x) + remat refwd
FWD_ONLY = 2.0            # fwd matmul flops = 2 * MACs; factor on MACs
ACT_BYTES_PER_LAYER_CONST = 14   # resid/norm/qkv/attnout/mlp traffic, bf16


@dataclasses.dataclass(frozen=True)
class MeshDesc:
    pod: int = 1
    data: int = 16
    model: int = 16

    @property
    def chips(self) -> int:
        return self.pod * self.data * self.model

    @property
    def dp(self) -> int:
        return self.pod * self.data


# =============================================================================
# Parameter counting
# =============================================================================
def layer_param_macs(cfg: ModelConfig, j: int) -> Dict[str, float]:
    """MAC-relevant weight sizes (= params in matmuls) for in-group layer j."""
    d, hd = cfg.d_model, cfg.hd
    out: Dict[str, float] = {}
    from repro.models.transformer import ffn_kind, mixer_kind
    mk, fk = mixer_kind(cfg, j), ffn_kind(cfg, j)
    if mk == "attn":
        out["attn"] = d * (cfg.n_heads * hd) * 2 + \
            d * (cfg.n_kv_heads * hd) * 2
    elif mk == "mamba":
        di, n, dtr = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.dt_rank
        out["mamba"] = d * 2 * di + di * (dtr + 2 * n) + dtr * di + di * d
    else:
        out["rwkv_time"] = 5 * d * d + 2 * d * 64
    if fk == "mlp":
        out["mlp"] = (3 if cfg.act == "swiglu" else 2) * d * cfg.d_ff
    elif fk == "moe":
        out["router"] = d * cfg.moe_experts
        out["moe_active"] = cfg.moe_topk * 3 * d * cfg.d_ff
        out["moe_total"] = cfg.moe_experts * 3 * d * cfg.d_ff
    else:
        out["rwkv_channel"] = 2 * d * cfg.d_ff + d * d
    return out


def stack_macs_per_token(cfg: ModelConfig, active: bool = True) -> float:
    """Sum of matmul MACs per token across the whole stack."""
    total = 0.0
    per_group = 0.0
    for j in range(cfg.scan_group):
        lp = layer_param_macs(cfg, j)
        for k, v in lp.items():
            if k == "moe_total":
                continue
            if k == "moe_active" and not active:
                continue
            per_group += v
    total = per_group * cfg.n_groups
    if cfg.family == "encdec":
        # decoder layers add cross-attn; encoder counted separately in callers
        total += cfg.n_layers * (cfg.d_model * cfg.n_heads * cfg.hd
                                 + 2 * cfg.d_model * cfg.n_kv_heads * cfg.hd)
    return total


def total_params(cfg: ModelConfig) -> float:
    """All weights (incl. every expert) + embeddings."""
    per_group = 0.0
    for j in range(cfg.scan_group):
        for k, v in layer_param_macs(cfg, j).items():
            if k == "moe_active":
                continue
            per_group += v
    stack = per_group * cfg.n_groups
    if cfg.family == "encdec":
        enc = cfg.enc_layers * (
            2 * cfg.d_model * cfg.n_heads * cfg.hd
            + 2 * cfg.d_model * cfg.n_kv_heads * cfg.hd
            + 2 * cfg.d_model * cfg.d_ff)
        cross = cfg.n_layers * (cfg.d_model * cfg.n_heads * cfg.hd * 2
                                + 2 * cfg.d_model * cfg.n_kv_heads * cfg.hd)
        stack += enc + cross
    embed = cfg.vocab * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    return stack + embed


def active_params(cfg: ModelConfig) -> float:
    per_group = 0.0
    for j in range(cfg.scan_group):
        for k, v in layer_param_macs(cfg, j).items():
            if k == "moe_total":
                continue
            per_group += v
    stack = per_group * cfg.n_groups
    embed = cfg.vocab * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    return stack + embed


# =============================================================================
# Attention / mixer extra flops (beyond weight matmuls)
# =============================================================================
def _attn_layers(cfg: ModelConfig) -> int:
    return sum(1 for j in range(cfg.scan_group)
               if cfg.is_attn_layer(j)) * cfg.n_groups \
        if cfg.family != "ssm" else 0


def attn_score_macs(cfg: ModelConfig, sq: int, skv: int, batch: int) -> float:
    """scores + pv MACs for one pass over all attention layers."""
    if cfg.family == "ssm":
        return 0.0
    if cfg.sliding_window is not None and skv > cfg.sliding_window:
        skv_eff = min(skv, cfg.sliding_window + min(cfg.seq_chunk, sq))
    else:
        skv_eff = skv
    per_layer = 2.0 * batch * cfg.n_heads * sq * skv_eff * cfg.hd
    return per_layer * _attn_layers(cfg)


def mixer_state_macs(cfg: ModelConfig, s: int, batch: int) -> float:
    """mamba scan / rwkv wkv extra MACs for one pass."""
    total = 0.0
    if cfg.family in ("hybrid",):
        n_mamba = (cfg.scan_group - sum(
            1 for j in range(cfg.scan_group) if cfg.is_attn_layer(j))) \
            * cfg.n_groups
        di, n = cfg.mamba_d_inner, cfg.mamba_d_state
        total += 5.0 * batch * s * di * n * n_mamba
    if cfg.family == "ssm":
        hd = cfg.rwkv_head_dim
        c = 64  # WKV_CHUNK
        per_tok = cfg.d_model * (4 * hd + 3 * c)
        total += batch * s * per_tok * cfg.n_layers
    return total


# =============================================================================
# Entry-point FLOPs
# =============================================================================
def flops_train(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, float]:
    b, s = shape.global_batch, shape.seq_len
    tokens = b * s
    mm = stack_macs_per_token(cfg, active=True) * tokens
    if cfg.family == "encdec":
        se = s // max(cfg.audio_downsample, 1)
        enc_mm = cfg.enc_layers * (
            2 * cfg.d_model * cfg.n_heads * cfg.hd
            + 2 * cfg.d_model * cfg.n_kv_heads * cfg.hd
            + 2 * cfg.d_model * cfg.d_ff) * b * se
        mm += enc_mm
        attn = attn_score_macs(cfg, s, s, b) \
            + attn_score_macs(cfg, se, se, b) \
            + 2.0 * b * cfg.n_heads * s * se * cfg.hd * cfg.n_layers
    elif cfg.family == "vlm":
        s_tot = s + cfg.vision_tokens
        mm = stack_macs_per_token(cfg) * b * s_tot
        attn = attn_score_macs(cfg, s_tot, s_tot, b)
    else:
        attn = attn_score_macs(cfg, s, s, b)
    head = cfg.d_model * cfg.vocab * tokens
    mixer = mixer_state_macs(cfg, s, b)
    fwd2 = FWD_ONLY * (mm + attn + head + mixer)      # flops of one forward
    total = TRAIN_MM_FACTOR / FWD_ONLY * fwd2
    qat_overhead = 10.0 * active_params(cfg) * len(
        ("mxint2", "mxint4", "mxint6", "mxint8")) / 4.0   # fake-quant pass
    model_flops = 6.0 * active_params(cfg) * tokens
    return {"total": total + qat_overhead, "forward": fwd2,
            "model_flops": model_flops}


def flops_prefill(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, float]:
    b, s = shape.global_batch, shape.seq_len
    tokens = b * s
    mm = stack_macs_per_token(cfg) * tokens
    if cfg.family == "vlm":
        s_tot = s + cfg.vision_tokens
        mm = stack_macs_per_token(cfg) * b * s_tot
        attn = attn_score_macs(cfg, s_tot, s_tot, b)
    elif cfg.family == "encdec":
        se = s // max(cfg.audio_downsample, 1)
        mm += cfg.enc_layers * (2 * cfg.d_model * cfg.n_heads * cfg.hd
                                + 2 * cfg.d_model * cfg.n_kv_heads * cfg.hd
                                + 2 * cfg.d_model * cfg.d_ff) * b * se
        attn = attn_score_macs(cfg, s, s, b) + attn_score_macs(cfg, se, se, b)\
            + 2.0 * b * cfg.n_heads * s * se * cfg.hd * cfg.n_layers
    else:
        attn = attn_score_macs(cfg, s, s, b)
    head = cfg.d_model * cfg.vocab * b            # last position only
    mixer = mixer_state_macs(cfg, s, b)
    total = FWD_ONLY * (mm + attn + head + mixer)
    return {"total": total,
            "model_flops": 2.0 * active_params(cfg) * tokens}


def flops_decode(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, float]:
    b = shape.global_batch
    cache = decode_cache_len(cfg, shape)
    mm = stack_macs_per_token(cfg) * b            # 1 token
    attn = attn_score_macs(cfg, 1, cache, b)
    head = cfg.d_model * cfg.vocab * b
    mixer = mixer_state_macs(cfg, 1, b)
    total = FWD_ONLY * (mm + attn + head + mixer)
    return {"total": total,
            "model_flops": 2.0 * active_params(cfg) * b}


# =============================================================================
# HBM bytes per device
# =============================================================================
def hbm_train(cfg: ModelConfig, shape: ShapeSpec, mesh: MeshDesc) -> float:
    p_local = total_params(cfg) / mesh.chips
    # f32 master read + fake-quant write/read (bf16) + grad write (f32) +
    # AdamW m/v read+write (f32 or bf16; assume f32) + remat weight re-read
    param_traffic = p_local * (4 + 2 + 2 + 4 + 16 + 2)
    tokens_local = shape.global_batch * shape.seq_len / mesh.dp
    d_model_local = cfg.d_model    # activations replicated over model axis
    act = tokens_local * d_model_local * cfg.n_layers * \
        ACT_BYTES_PER_LAYER_CONST * 2   # fwd+bwd
    return param_traffic + act


def hbm_prefill(cfg: ModelConfig, shape: ShapeSpec, mesh: MeshDesc) -> float:
    p_local = total_params(cfg) * 2 / mesh.chips     # bf16 serve weights
    tokens_local = shape.global_batch * shape.seq_len / mesh.dp
    act = tokens_local * cfg.d_model * cfg.n_layers * ACT_BYTES_PER_LAYER_CONST
    return p_local + act


def hbm_decode(cfg: ModelConfig, shape: ShapeSpec, mesh: MeshDesc,
               weight_bits: int = 16, weight_stationary: bool = False) -> float:
    """Decode is weight + KV-cache bound: every step reads all local weights
    + this batch's cache shard.

    FSDP layout shards weight reads over all chips (cheap HBM, collective
    psums per layer); weight-stationary replicates over (pod, data) so each
    chip reads its full model-shard (bits/8 x p / model) but psums vanish.
    """
    if weight_stationary:
        p_local = active_params(cfg) * weight_bits / 8 / mesh.model
    else:
        p_local = active_params(cfg) * weight_bits / 8 / mesh.chips
    cache = decode_cache_len(cfg, shape)
    b_local = max(shape.global_batch / mesh.dp, 1)
    kv = 2 * _attn_layers(cfg) * cfg.n_kv_heads * cfg.hd * cache * 2 \
        * b_local / mesh.model
    state = 0.0
    if cfg.family == "ssm":
        hh = cfg.d_model // cfg.rwkv_head_dim
        state = cfg.n_layers * hh * cfg.rwkv_head_dim ** 2 * 4 * b_local * 2
    if cfg.family == "hybrid":
        n_mamba = cfg.n_layers - _attn_layers(cfg)
        state = n_mamba * cfg.mamba_d_inner * cfg.mamba_d_state * 4 \
            * b_local * 2 / mesh.model
    return p_local + kv + state


# =============================================================================
# Serving-engine roofline terms (the measured-cost-model seed)
# =============================================================================
# These are the analytic counterparts of the byte counters the packed-weight
# serving engine actually measures — ``serve.packed_params.weight_stream_bytes``
# over the cached tree and ``ElasticEngine.stats()["attn_read_bytes"]`` — and
# they are a *tested contract*: tests/test_costmodel.py asserts they agree
# with a real engine run within a stated tolerance, per format x {dense,
# paged}. ``serve.slo.CostModel.from_roofline`` seeds its per-format terms
# from them, then calibrates online from observed tick timings.

def serve_weight_stream_bytes(cfg: ModelConfig, fmt_name: str,
                              block_size: int = 32) -> float:
    """Bytes one decode tick streams for the packed serving tree at
    ``fmt_name`` (codes + E8M0 scales for the quantized stack, raw leaves
    at ``cfg.compute_dtype``; the ``"bf16"`` pseudo-format is the dense
    tree). Mirrors ``make_packed_params``'s packing rules: every ndim>=2
    stack matmul weight is quantized, embeddings and norm vectors stay raw
    (norm vectors are dropped here — they are O(d_model) noise)."""
    import jax.numpy as jnp
    item = jnp.dtype(cfg.compute_dtype).itemsize
    embed = cfg.vocab * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    stack = total_params(cfg) - embed
    if fmt_name == "bf16":
        return (stack + embed) * item
    from repro.core.formats import get_format
    fmt = get_format(fmt_name, block_size)
    code_bytes = 0.5 if (fmt.kind == "int" and fmt.bits == 4) else 1.0
    return stack * (code_bytes + 1.0 / block_size) + embed * item


def serve_attn_read_span(cfg: ModelConfig, max_len: int,
                         kv_layout: str = "dense",
                         kv_page_size: int = 16) -> int:
    """KV tokens one gather-path decode read spans per batch row: the whole
    logical view — ``max_len`` (+ vision prefix) for the dense layout, the
    block table's page span for the paged layout. The gather-free kernel
    reads only ``ceil(cache_len/page)`` pages of it; the engine accounts
    that difference per tick, this term is the layout's upper bound."""
    logical = max_len + cfg.vision_tokens
    if kv_layout == "paged":
        return -(-logical // kv_page_size) * kv_page_size
    return logical


def serve_attn_bytes_per_row(cfg: ModelConfig, span_tokens: int) -> float:
    """HBM bytes one decode row's attention reads per tick when its read
    spans ``span_tokens`` KV positions: K+V at ``cfg.compute_dtype`` across
    every attention layer. The analytic twin of the engine's
    ``attn_read_bytes`` accounting (same per-token multiplier)."""
    import jax.numpy as jnp
    item = jnp.dtype(cfg.compute_dtype).itemsize
    return float(span_tokens) * _attn_layers(cfg) * 2 \
        * cfg.n_kv_heads * cfg.hd * item


def serve_roofline_terms(cfg: ModelConfig, formats,
                         *, max_len: int, kv_layout: str = "dense",
                         kv_page_size: int = 16, block_size: int = 32,
                         n_model: int = 1) -> Dict[str, Dict[str, float]]:
    """Per-format decode roofline terms for the serving cost model:
    ``{fmt: {"weight_bytes": <per tick>, "attn_bytes_per_row": <per row per
    tick>}}``. The weight read happens once per tick regardless of batch
    occupancy (one fused step streams the whole tree); the attention read
    scales with live rows.

    ``n_model``: tensor-parallel shards. The roofline is PER CHIP — a
    meshed engine streams only its weight shard and its kv-head slice of
    every token read, so both terms divide by the mesh's 'model' axis size
    (the single-chip HBM bandwidth the cost model divides by stays a per-chip
    number either way). Replicated leaves (norms, biases) are O(d_model)
    noise at this granularity, same as the unsharded approximation.
    """
    if n_model < 1:
        raise ValueError(f"n_model ({n_model}) must be >= 1")
    span = serve_attn_read_span(cfg, max_len, kv_layout, kv_page_size)
    attn = serve_attn_bytes_per_row(cfg, span) / n_model
    return {f: {"weight_bytes":
                serve_weight_stream_bytes(cfg, f, block_size) / n_model,
                "attn_bytes_per_row": attn}
            for f in formats}


# =============================================================================
# Collective bytes per device
# =============================================================================
def collectives_train(cfg: ModelConfig, shape: ShapeSpec,
                      mesh: MeshDesc) -> Dict[str, float]:
    """Per-device cross-chip traffic per train step (ring estimates)."""
    p = total_params(cfg)
    # FSDP: all-gather bf16 weights fwd + remat-fwd + bwd, reduce-scatter f32
    fsdp_shards = mesh.dp
    ag = 3 * (p / mesh.model) * 2 * (fsdp_shards - 1) / fsdp_shards
    rs = (p / mesh.model) * 4 * (fsdp_shards - 1) / fsdp_shards
    # TP: all-reduce activations, 2 row-parallel matmuls/layer, fwd+bwd+remat
    tokens_local = shape.global_batch * shape.seq_len / mesh.dp
    tp_ar = 2 * cfg.n_layers * tokens_local * cfg.d_model * 2 * 3 \
        * 2 * (mesh.model - 1) / mesh.model
    # vocab-parallel CE: lse/max all-reduce + dgrad all-reduce
    ce = tokens_local * (8 + cfg.d_model * 4) * 2 * (mesh.model - 1) \
        / mesh.model
    # MoE all-to-all (EP policy only: experts divide model axis)
    a2a = 0.0
    if cfg.moe_experts and cfg.moe_experts % mesh.model == 0:
        n_moe = sum(1 for j in range(cfg.scan_group)
                    if cfg.is_moe_layer(j)) * cfg.n_groups
        a2a = 3 * n_moe * tokens_local * cfg.moe_topk * cfg.d_model * 2
    return {"all_gather": ag, "reduce_scatter": rs, "tp_allreduce": tp_ar,
            "ce": ce, "all_to_all": a2a,
            "total": ag + rs + tp_ar + ce + a2a}


def collectives_decode(cfg: ModelConfig, shape: ShapeSpec,
                       mesh: MeshDesc, weight_stationary: bool = False,
                       weight_bits: int = 16) -> Dict[str, float]:
    b_local = max(shape.global_batch / mesh.dp, 1)
    # TP all-reduce of per-token activations, 2/layer
    tp_ar = 2 * cfg.n_layers * b_local * cfg.d_model * 2 \
        * 2 * (mesh.model - 1) / mesh.model
    # attention over seq-sharded cache: psum of (b, H, hd) partials + stats
    attn_ar = _attn_layers(cfg) * b_local * (cfg.n_heads * cfg.hd * 4 + 8) \
        * 2 * (mesh.model - 1) / mesh.model
    logits = b_local * cfg.vocab * 4 / mesh.model * 2
    # FSDP-layout serving: GSPMD keeps weights sharded over `data` and psums
    # per-layer partial activations across it (observed in post-cache-fix
    # HLO; pre-fix it gathered the full bf16 weights instead). The
    # weight-stationary layout eliminates the fsdp-axis traffic entirely.
    fsdp_ar = 0.0
    if not weight_stationary and mesh.dp > 1:
        per_layer_acts = b_local * cfg.d_model * 4        # f32 partials
        matmuls_per_layer = 4 if cfg.moe_experts else 3
        fsdp_ar = cfg.n_layers * matmuls_per_layer * per_layer_acts \
            * 2 * (mesh.dp - 1) / mesh.dp
        # MoE expert-operand gathers (dispatch spans the fsdp axis)
        if cfg.moe_experts:
            cap = max(1, int(cfg.capacity_factor * cfg.moe_topk
                             / cfg.moe_experts))
            fsdp_ar += cfg.n_layers * cfg.moe_experts * b_local * cap \
                * cfg.d_model * 4
    return {"tp_allreduce": tp_ar, "attn_psum": attn_ar, "logits": logits,
            "fsdp_allreduce": fsdp_ar,
            "total": tp_ar + attn_ar + logits + fsdp_ar}


def collectives_prefill(cfg: ModelConfig, shape: ShapeSpec,
                        mesh: MeshDesc) -> Dict[str, float]:
    tokens_local = shape.global_batch * shape.seq_len / mesh.dp
    tp_ar = 2 * cfg.n_layers * tokens_local * cfg.d_model * 2 \
        * 2 * (mesh.model - 1) / mesh.model
    wgt_ag = (total_params(cfg) / mesh.model) * 2 \
        * (mesh.dp - 1) / mesh.dp
    return {"tp_allreduce": tp_ar, "weight_allgather": wgt_ag,
            "total": tp_ar + wgt_ag}


# =============================================================================
# Roofline terms
# =============================================================================
def roofline(cfg: ModelConfig, shape: ShapeSpec, mesh: MeshDesc,
             weight_bits_decode: int = 16,
             weight_stationary: bool = False) -> Dict[str, float]:
    """Roofline terms of one step on ``mesh`` v5e chips (the production
    fleet; peaks from ``launch.mesh.PEAKS``)."""
    from repro.launch.mesh import V5E, device_peaks
    peak = device_peaks(V5E)
    if shape.kind == "train":
        fl = flops_train(cfg, shape)
        hbm = hbm_train(cfg, shape, mesh)
        coll = collectives_train(cfg, shape, mesh)
    elif shape.kind == "prefill":
        fl = flops_prefill(cfg, shape)
        hbm = hbm_prefill(cfg, shape, mesh)
        coll = collectives_prefill(cfg, shape, mesh)
    else:
        fl = flops_decode(cfg, shape)
        hbm = hbm_decode(cfg, shape, mesh, weight_bits_decode,
                         weight_stationary=weight_stationary)
        coll = collectives_decode(cfg, shape, mesh,
                                  weight_stationary=weight_stationary,
                                  weight_bits=weight_bits_decode)
    t_comp = fl["total"] / mesh.chips / peak["flops_bf16"]
    t_mem = hbm / peak["hbm_bytes_per_s"]
    t_coll = coll["total"] / peak["ici_bytes_per_s_per_link"]
    dominant = max(("compute", t_comp), ("memory", t_mem),
                   ("collective", t_coll), key=lambda kv: kv[1])[0]
    bound = max(t_comp, t_mem, t_coll)
    return {
        "flops_global": fl["total"],
        "model_flops": fl.get("model_flops", 0.0),
        "useful_ratio": fl.get("model_flops", 0.0) / max(fl["total"], 1.0),
        "hbm_bytes_per_dev": hbm,
        "coll_bytes_per_dev": coll["total"],
        "coll_breakdown": coll,
        "t_compute": t_comp, "t_memory": t_mem, "t_collective": t_coll,
        "dominant": dominant,
        "roofline_fraction": t_comp / bound if bound > 0 else 0.0,
        "step_time_lower_bound": bound,
    }
