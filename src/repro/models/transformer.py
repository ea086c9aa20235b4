"""Generic decoder-only LM covering the dense / MoE / hybrid / SSM families.

One stack implementation serves 8 of the 10 assigned architectures:
mixtral-8x22b, mixtral-8x7b, jamba-1.5-large, llava backbone, qwen3-4b,
qwen2-72b, smollm-135m, starcoder2-3b, rwkv6-7b. The layer *pattern* within a
scan group is static (group size = the arch's period: 1 for homogeneous
stacks, 8 for Jamba's 1:7 attn:mamba interleave), and parameters are stacked
over groups so the whole stack lowers as one ``lax.scan`` — compile time and
HLO size stay flat in depth, which matters at 512 devices.

Entry points: ``train_loss``, ``prefill``, ``serve_step`` (one token against
a preallocated cache), all QAT-aware via QuantCtx.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.qat import QATConfig
from repro.models import layers as L
from repro.models import rwkv as R
from repro.models import ssm as S
from repro.models.common import (ModelConfig, QuantCtx,
                                 make_prefill_chunk_slot, make_prefill_slot,
                                 stacked_init, trunc_normal)
from repro.sharding.rules import shard_act


# =============================================================================
# Layer-kind plumbing
# =============================================================================
def mixer_kind(cfg: ModelConfig, j: int) -> str:
    """Mixer for in-group position j (pattern is periodic in scan_group)."""
    if cfg.family == "ssm":
        return "rwkv"
    if cfg.is_attn_layer(j):
        return "attn"
    return "mamba"


def ffn_kind(cfg: ModelConfig, j: int) -> str:
    if cfg.family == "ssm":
        return "cmix"
    return "moe" if cfg.is_moe_layer(j) else "mlp"


# =============================================================================
# Init
# =============================================================================
def _init_attn(key, cfg: ModelConfig):
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": trunc_normal(ks[0], (d, h * hd)),
        "wk": trunc_normal(ks[1], (d, hkv * hd)),
        "wv": trunc_normal(ks[2], (d, hkv * hd)),
        "wo": trunc_normal(ks[3], (h * hd, d), std=0.02 / cfg.n_layers ** 0.5),
    }
    if cfg.qkv_bias:
        p.update(bq=jnp.zeros((h * hd,)), bk=jnp.zeros((hkv * hd,)),
                 bv=jnp.zeros((hkv * hd,)))
    if cfg.qk_norm:
        p.update(q_norm=jnp.ones((hd,)), k_norm=jnp.ones((hd,)))
    return p


def _attn_axes(cfg: ModelConfig):
    p = {"wq": ("fsdp", "model"), "wk": ("fsdp", "model"),
         "wv": ("fsdp", "model"), "wo": ("model", "fsdp")}
    if cfg.qkv_bias:
        p.update(bq=("model",), bk=("model",), bv=("model",))
    if cfg.qk_norm:
        p.update(q_norm=(None,), k_norm=(None,))
    return p


def _init_mlp(key, cfg: ModelConfig):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    down_std = 0.02 / cfg.n_layers ** 0.5
    if cfg.act == "swiglu":
        p = {"w_gate": trunc_normal(ks[0], (d, f)),
             "w_up": trunc_normal(ks[1], (d, f)),
             "w_down": trunc_normal(ks[2], (f, d), std=down_std)}
    else:
        p = {"w_up": trunc_normal(ks[0], (d, f)),
             "w_down": trunc_normal(ks[1], (f, d), std=down_std)}
    if cfg.mlp_bias:
        p.update(b_up=jnp.zeros((f,)), b_down=jnp.zeros((d,)))
    return p


def _mlp_axes(cfg: ModelConfig):
    if cfg.act == "swiglu":
        p = {"w_gate": ("fsdp", "mlp"), "w_up": ("fsdp", "mlp"),
             "w_down": ("mlp", "fsdp")}
    else:
        p = {"w_up": ("fsdp", "mlp"), "w_down": ("mlp", "fsdp")}
    if cfg.mlp_bias:
        p.update(b_up=("mlp",), b_down=(None,))
    return p


def _init_moe(key, cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe_experts
    ks = jax.random.split(key, 4)
    down_std = 0.02 / cfg.n_layers ** 0.5

    def one_expert(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"w_gate": trunc_normal(k1, (d, f)),
                "w_up": trunc_normal(k2, (d, f)),
                "w_down": trunc_normal(k3, (f, d), std=down_std)}

    return {"router": trunc_normal(ks[0], (d, e)),
            "experts": stacked_init(one_expert, ks[1], e)}


def _moe_axes(cfg: ModelConfig):
    return {"router": ("fsdp", None),
            "experts": {"w_gate": ("experts", "fsdp", "mlp"),
                        "w_up": ("experts", "fsdp", "mlp"),
                        "w_down": ("experts", "mlp", "fsdp")}}


def init_block(key, cfg: ModelConfig, j: int) -> Dict:
    """One layer (in-group position j)."""
    kmix, kffn = jax.random.split(key)
    mk, fk = mixer_kind(cfg, j), ffn_kind(cfg, j)
    p: Dict[str, Any] = {"mixer_norm": jnp.ones((cfg.d_model,))}
    if mk == "attn":
        p["attn"] = _init_attn(kmix, cfg)
    elif mk == "mamba":
        p["mamba"] = S.init_mamba_params(kmix, cfg)
    else:
        p["rwkv"] = R.init_rwkv_params(kmix, cfg)["time"]
    if fk != "cmix":
        p["ffn_norm"] = jnp.ones((cfg.d_model,))
        p["moe" if fk == "moe" else "mlp"] = \
            _init_moe(kffn, cfg) if fk == "moe" else _init_mlp(kffn, cfg)
    else:
        p["ffn_norm"] = jnp.ones((cfg.d_model,))
        p["cmix"] = R.init_rwkv_params(kffn, cfg)["channel"]
    return p


def block_axes(cfg: ModelConfig, j: int) -> Dict:
    mk, fk = mixer_kind(cfg, j), ffn_kind(cfg, j)
    p: Dict[str, Any] = {"mixer_norm": (None,), "ffn_norm": (None,)}
    if mk == "attn":
        p["attn"] = _attn_axes(cfg)
    elif mk == "mamba":
        p["mamba"] = S.mamba_param_axes(cfg)
    else:
        p["rwkv"] = R.rwkv_param_axes(cfg)["time"]
    if fk == "moe":
        p["moe"] = _moe_axes(cfg)
    elif fk == "mlp":
        p["mlp"] = _mlp_axes(cfg)
    else:
        p["cmix"] = R.rwkv_param_axes(cfg)["channel"]
    return p


def init_params(key, cfg: ModelConfig) -> Dict:
    k_embed, k_blocks, k_head = jax.random.split(key, 3)
    blocks = []
    bkeys = jax.random.split(k_blocks, cfg.scan_group)
    for j in range(cfg.scan_group):
        blocks.append(stacked_init(
            lambda k, j=j: init_block(k, cfg, j), bkeys[j], cfg.n_groups))
    params = {
        "embed": trunc_normal(k_embed, (cfg.vocab, cfg.d_model)),
        "blocks": blocks,
        "final_norm": jnp.ones((cfg.d_model,)),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = trunc_normal(k_head, (cfg.d_model, cfg.vocab))
    return params


def param_axes(cfg: ModelConfig) -> Dict:
    def stackax(tree):
        return jax.tree_util.tree_map(
            lambda ax: (None,) + ax, tree,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x))

    axes = {
        "embed": ("vocab", "fsdp"),
        "blocks": [stackax(block_axes(cfg, j)) for j in range(cfg.scan_group)],
        "final_norm": (None,),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("fsdp", "vocab")
    return axes


# =============================================================================
# Forward
# =============================================================================
def _layer(ctx: QuantCtx, x, p, cfg: ModelConfig, j: int, positions,
           cache_slice, cache_len, prefill: bool, block_table=None,
           chunk_start=None, q_len=None, attn_impl: str = "gather"):
    """One block. Returns (x, new_cache_slice).

    ``block_table`` (B, max_pages) selects the paged KV layout: attention
    cache slices hold page pools (``k_pages``/``v_pages``) instead of
    per-slot contiguous buffers, and all reads/writes go through the
    block-table indirection (see layers.py paged helpers). ``attn_impl``
    picks the paged *decode* read path — the gather-free Pallas kernel
    (``"paged_kernel"``, kernels/paged_attention.py) vs gather + masked
    softmax (``"gather"``); ignored outside paged decode.

    ``chunk_start`` (scalar, may be traced; implies ``prefill=True``)
    selects chunked prefill: ``x`` is one prompt chunk whose first token
    sits at that logical position, K/V are written at the cursor, and
    attention reads back the cache so the chunk sees every earlier chunk.
    Attention-only — recurrent mixers fold the prompt into their state in
    one pass and cannot resume mid-prompt, so they reject loudly.

    ``q_len`` (B,), decode-mode only, selects the unified mixed
    prefill+decode tick: ``x`` is a ragged (B, C) batch where row ``b``'s
    first ``q_len[b]`` tokens are real, each sitting at the row's own
    ``cache_len`` cursor — attention-only, same as chunked prefill (the
    mid-prefill row resumes mid-prompt).
    """
    mk, fk = mixer_kind(cfg, j), ffn_kind(cfg, j)
    name = f"blk{j}.{mk}"
    chunked = prefill and chunk_start is not None and cache_slice is not None
    mixed = q_len is not None and not prefill and cache_slice is not None
    if (chunked or mixed) and mk != "attn":
        raise ValueError(
            f"{'chunked prefill' if chunked else 'the mixed tick'} requires "
            f"attention mixers; layer {j} of "
            f"family {cfg.family!r} is {mk!r} (its recurrent state cannot "
            "resume mid-prompt) — use monolithic admission")
    new_cache: Dict[str, Any] = {}
    h = L.rms_norm(x, p["mixer_norm"], cfg.norm_eps)
    if mk == "attn":
        paged = cache_slice is not None and "k_pages" in cache_slice
        kv = None
        if cache_slice is not None and (chunked or not prefill):
            kv = (cache_slice["k_pages"], cache_slice["v_pages"]) if paged \
                else (cache_slice["k"], cache_slice["v"])
        out, new_kv = L.attention_block(
            ctx, h, p["attn"], cfg, positions, name,
            kv_cache=kv, cache_len=cache_len,
            block_table=block_table if paged else None,
            chunk_start=chunk_start if chunked else None,
            q_len=q_len if mixed else None,
            attn_impl=attn_impl)
        if cache_slice is not None:
            if chunked:
                new_cache = {"k_pages": new_kv[0], "v_pages": new_kv[1]} \
                    if paged else {"k": new_kv[0], "v": new_kv[1]}
            elif prefill and paged:
                k_new, v_new = new_kv
                new_cache = {
                    "k_pages": L.paged_prefill_update(
                        cache_slice["k_pages"], k_new, block_table),
                    "v_pages": L.paged_prefill_update(
                        cache_slice["v_pages"], v_new, block_table)}
            elif prefill:
                k_new, v_new = new_kv
                kc = jax.lax.dynamic_update_slice_in_dim(
                    cache_slice["k"], k_new.astype(cache_slice["k"].dtype),
                    0, axis=1)
                vc = jax.lax.dynamic_update_slice_in_dim(
                    cache_slice["v"], v_new.astype(cache_slice["v"].dtype),
                    0, axis=1)
                new_cache = {"k": kc, "v": vc}
            elif paged:
                new_cache = {"k_pages": new_kv[0], "v_pages": new_kv[1]}
            else:
                new_cache = {"k": new_kv[0], "v": new_kv[1]}
    elif mk == "mamba":
        state = None
        if cache_slice is not None and not prefill:
            state = (cache_slice["h"], cache_slice["conv"])
        out, (hst, conv) = S.mamba_block(ctx, h, p["mamba"], cfg, name,
                                         state=state)
        if cache_slice is not None:
            new_cache = {"h": hst, "conv": conv}
    else:  # rwkv time mix
        state = None
        if cache_slice is not None and not prefill:
            state = (cache_slice["shift_t"], cache_slice["wkv"])
        out, (shift_t, wkv) = R.rwkv_time_mix(ctx, h, p["rwkv"], cfg, name,
                                              state=state)
        if cache_slice is not None:
            new_cache = {"shift_t": shift_t, "wkv": wkv}
    x = x + out

    h = L.rms_norm(x, p["ffn_norm"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    name_f = f"blk{j}.{fk}"
    if fk == "mlp":
        out = L.mlp_block(ctx, h, p["mlp"], cfg, name_f)
    elif fk == "moe":
        out, aux = L.moe_block(ctx, h, p["moe"], cfg, name_f)
    else:
        state = None
        if cache_slice is not None and not prefill:
            state = cache_slice["shift_c"]
        out, shift_c = R.rwkv_channel_mix(ctx, h, p["cmix"], cfg, name_f,
                                          state=state)
        if cache_slice is not None:
            new_cache["shift_c"] = shift_c
    x = x + out
    return x, new_cache, aux


def forward_hidden(ctx: QuantCtx, params, cfg: ModelConfig, x, positions,
                   cache=None, cache_len=None, prefill: bool = False,
                   chunk_start=None, q_len=None, attn_impl: str = "gather"):
    """Run the block stack. x (B,S,d). Returns (hidden, new_cache, aux)."""
    # Sequence-parallel residual: the per-group saved activation (the scan
    # carry, which dominates train memory at depth) shards its seq dim over
    # `model` — a Megatron-SP analogue. No-op when seq doesn't divide.
    resid_axes = ("batch", "seq_sp" if (cfg.seq_sharding and x.shape[1] > 1)
                  else "seq", None)
    # Paged KV layout: the block table is per-slot and shared across layers
    # (each layer has its own pool of identical shape), so it rides outside
    # the scanned cache leaves and the scan body closes over it.
    block_table = cache.get("block_table") if cache is not None else None

    def group_body(carry, xs):
        xv, aux = carry
        group_params, group_cache = xs
        new_slices = []
        for j in range(cfg.scan_group):
            cs = group_cache[j] if group_cache is not None else None

            def layer_call(xv_, p_, cs_, _j=j):
                return _layer(ctx, xv_, p_, cfg, _j, positions, cs_,
                              cache_len, prefill, block_table, chunk_start,
                              q_len, attn_impl)

            if cfg.remat_inner and cfg.scan_group > 1:
                layer_call = jax.checkpoint(
                    layer_call,
                    policy=jax.checkpoint_policies.nothing_saveable)
            xv, nc, a = layer_call(xv, group_params[j], cs)
            new_slices.append(nc)
            aux = aux + a
        xv = shard_act(xv, resid_axes)
        return (xv, aux), new_slices

    body = group_body
    if cfg.remat:
        body = jax.checkpoint(group_body,
                              policy=jax.checkpoint_policies.nothing_saveable)

    if cfg.unroll:
        # python loop over groups — exact HLO op counts (cost-model calib)
        carry = (x, jnp.zeros((), jnp.float32))
        new_blocks = []
        for g in range(cfg.n_groups):
            gp = jax.tree_util.tree_map(lambda t: t[g], params["blocks"])
            gc = jax.tree_util.tree_map(lambda t: t[g], cache["blocks"]) \
                if cache is not None else None
            carry, slices = body(carry, (gp, gc))
            new_blocks.append(slices)
        (x, aux) = carry
        if cache is not None:
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *new_blocks)
            new_cache = {"blocks": stacked}
            if block_table is not None:
                new_cache["block_table"] = block_table
        else:
            new_cache = None
    elif cache is None:
        def body_nc(carry, gp):
            (xv, aux), ncs = body(carry, (gp, None))
            return (xv, aux), None

        (x, aux), _ = jax.lax.scan(body_nc, (x, jnp.zeros((), jnp.float32)),
                                   params["blocks"])
        new_cache = None
    else:
        (x, aux), new_blocks = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)),
            (params["blocks"], cache["blocks"]))
        new_cache = {"blocks": new_blocks}
        if block_table is not None:
            new_cache["block_table"] = block_table
    hidden = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return hidden, new_cache, aux


def _embed(params, cfg: ModelConfig, tokens, tp_axis=None):
    emb = params["embed"]
    if tp_axis is not None and emb.shape[0] != cfg.vocab:
        # Vocab-sharded table inside shard_map: each token's row lives on
        # exactly one shard. Offset the ids into the local range, mask the
        # out-of-range rows to zero, and psum — every shard contributes the
        # true row or an exact zero, so the sum is bit-identical to the
        # unsharded lookup.
        v_local = emb.shape[0]
        local = tokens - jax.lax.axis_index(tp_axis) * v_local
        ok = (local >= 0) & (local < v_local)
        rows = jnp.take(emb, jnp.where(ok, local, 0), axis=0)
        x = jnp.where(ok[..., None], rows, jnp.zeros((), emb.dtype))
        with jax.named_scope("tp.exchange/embed"):
            x = jax.lax.psum(x, tp_axis)
        x = x.astype(cfg.compute_dtype)
        return shard_act(x, ("batch", None, None))
    x = jnp.take(emb, tokens, axis=0).astype(cfg.compute_dtype)
    return shard_act(x, ("batch", None, None))


def _lm_head_w(params, cfg: ModelConfig):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def _head_logits(ctx: QuantCtx, params, cfg: ModelConfig, h_last):
    """lm-head projection over the last-position hidden states (B, d).

    In fused serving the params tree stays packed: a quantized lm_head leaf
    (non-default QAT exclusions) routes through the dequant-GEMM hook like
    every other projection instead of crashing on `.astype`.

    Under tensor parallelism the head weight is vocab-sharded (lm_head
    columns / tied embed rows), so the local matmul yields a vocab slice;
    a tiled all_gather reassembles the exact global logits (pure
    concatenation — no arithmetic, so bit-identical).
    """
    from repro.models.common import is_packed_leaf
    if not cfg.tie_embeddings and ctx.qmm is not None and \
            is_packed_leaf(params["lm_head"]):
        logits = ctx.qmm(h_last.astype(jnp.float32), params["lm_head"],
                         "lm_head")
    else:
        logits = jax.lax.dot_general(
            h_last.astype(jnp.float32),
            _lm_head_w(params, cfg).astype(jnp.float32),
            (((1,), (0,)), ((), ())))
    if ctx.tp_axis is not None and logits.shape[-1] != cfg.vocab:
        with jax.named_scope("tp.exchange/logits"):
            logits = jax.lax.all_gather(logits, ctx.tp_axis,
                                        axis=logits.ndim - 1, tiled=True)
    return logits


def _last_hidden(hidden, cache_len):
    """hidden (B, S, d) -> (B, d) at each row's own last valid position."""
    return jax.vmap(lambda h, i: jax.lax.dynamic_index_in_dim(
        h, i, 0, keepdims=False))(hidden, cache_len - 1)


def chunked_ce_loss(ctx: QuantCtx, hidden, head_w, labels, mask,
                    cfg: ModelConfig):
    """Cross entropy over vocab-sharded logits, chunked along seq."""
    b, s, d = hidden.shape
    c = min(cfg.seq_chunk, s)
    while s % c:
        c //= 2
    nc = s // c

    def chunk(carry, i):
        tot, cnt = carry
        hs = jax.lax.dynamic_slice_in_dim(hidden, i * c, c, axis=1)
        lb = jax.lax.dynamic_slice_in_dim(labels, i * c, c, axis=1)
        mk = jax.lax.dynamic_slice_in_dim(mask, i * c, c, axis=1)
        logits = jax.lax.dot_general(
            hs.astype(jnp.float32), head_w.astype(jnp.float32),
            (((2,), (0,)), ((), ())))                       # (B,c,V) f32
        logits = shard_act(logits, ("batch", None, "vocab"))
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, lb[..., None], axis=-1)[..., 0]
        nll = (lse - tgt) * mk
        return (tot + jnp.sum(nll), cnt + jnp.sum(mk)), None

    (tot, cnt), _ = jax.lax.scan(
        chunk, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        jnp.arange(nc))
    return tot / jnp.maximum(cnt, 1.0)


# =============================================================================
# Model API
# =============================================================================
@dataclasses.dataclass
class ModelApi:
    cfg: ModelConfig
    qat: Optional[QATConfig]
    init_params: Callable
    param_axes: Callable
    train_loss: Callable          # (params, batch, fmt_idx) -> (loss, aux)
    init_cache: Callable          # (batch, cache_len, dtype) -> cache pytree
    cache_axes: Callable
    prefill: Callable             # (params, batch) -> (logits, cache, len)
    serve_step: Callable          # (params, batch, cache, len) -> (logits, cache)
    prefill_slot: Callable = None  # (params, batch(1,S), cache, slot)
    #                                -> (logits (V,), cache, len scalar);
    #                                single-request prefill-insert: fills one
    #                                slot without touching the others
    prefill_chunk: Callable = None  # (params, batch(B,C), cache, start_pos)
    #                                -> (logits, cache, len): one prompt
    #                                chunk at cursor start_pos (chunked
    #                                admission; attention families only)
    prefill_chunk_slot: Callable = None  # single-slot prefill_chunk:
    #                                (params, batch(1,C), cache, slot,
    #                                start_pos) -> (logits (V,), cache, len)
    mixed_step: Callable = None    # (params, batch{tokens (B,C), q_len (B,)},
    #                                cache, cache_len) -> (logits (B,V),
    #                                cache): ONE mixed prefill+decode tick —
    #                                decode rows at q_len 1, the mid-prefill
    #                                row at its chunk width, each at its own
    #                                cache_len cursor (attention-only)
    verify_step: Callable = None   # mixed_step's speculative sibling: same
    #                                (params, batch{tokens (B,C), q_len (B,)},
    #                                cache, cache_len) contract but logits at
    #                                ALL C positions -> (logits (B,C,V),
    #                                cache): the single-executable anchor-side
    #                                check of a k-token draft burst
    #                                (docs/serving_internals.md §9)
    with_qmm: Callable = None      # (qmm) -> ModelApi whose serving entry
    #                                points route packed weight leaves
    #                                through the fused dequant-GEMM hook
    with_serving: Callable = None  # (qmm=None, attn_impl="gather") ->
    #                                ModelApi with BOTH serving knobs baked
    #                                into the rebuilt entry points: the
    #                                dequant-GEMM hook and the paged decode
    #                                attention path ("gather" |
    #                                "paged_kernel"); the derived api's
    #                                with_qmm preserves its attn_impl, so
    #                                chaining composes rather than resetting
    attn_impl: str = "gather"      # paged decode read path the serving
    #                                entry points were built with
    tp_axis: Optional[str] = None  # tensor-parallel mesh axis the serving
    #                                entry points psum/all_gather over when
    #                                run inside shard_map (make_model
    #                                tp_axis=...); None = single-device math


def _cache_for_block(cfg: ModelConfig, j: int, b: int, s_max: int, dtype):
    mk = mixer_kind(cfg, j)
    c: Dict[str, Any] = {}
    if mk == "attn":
        c["k"] = jnp.zeros((b, s_max, cfg.n_kv_heads, cfg.hd), dtype)
        c["v"] = jnp.zeros((b, s_max, cfg.n_kv_heads, cfg.hd), dtype)
    elif mk == "mamba":
        c["h"] = jnp.zeros((b, cfg.mamba_d_inner, cfg.mamba_d_state),
                           jnp.float32)
        c["conv"] = jnp.zeros((b, cfg.mamba_d_conv - 1, cfg.mamba_d_inner),
                              dtype)
    else:
        hh = cfg.d_model // cfg.rwkv_head_dim
        c["shift_t"] = jnp.zeros((b, 1, cfg.d_model), dtype)
        c["wkv"] = jnp.zeros((b, hh, cfg.rwkv_head_dim, cfg.rwkv_head_dim),
                             jnp.float32)
        c["shift_c"] = jnp.zeros((b, 1, cfg.d_model), dtype)
    return c


def _cache_axes_for_block(cfg: ModelConfig, j: int):
    mk = mixer_kind(cfg, j)
    if mk == "attn":
        return {"k": (None, "batch", "kv_seq", None, None),
                "v": (None, "batch", "kv_seq", None, None)}
    if mk == "mamba":
        return {"h": (None, "batch", "model", None),
                "conv": (None, "batch", None, "model")}
    return {"shift_t": (None, "batch", None, None),
            "wkv": (None, "batch", "heads", None, None),
            "shift_c": (None, "batch", None, None)}


def make_model(cfg: ModelConfig, qat: Optional[QATConfig] = None, *,
               tp_axis: Optional[str] = None) -> ModelApi:
    """Build the ModelApi. ``tp_axis`` names the tensor-parallel mesh axis
    to reduce over when the serving entry points run inside ``shard_map``
    with head/ffn/vocab-sharded weights — pass ``cfg`` with the LOCAL head
    counts (and ``head_dim`` pinned) but the GLOBAL vocab (see
    serve/engine.py's mesh path and docs/serving_internals.md §11).
    Training entry points ignore it."""
    n_fmts = len(qat.formats) if qat else 0

    def _ctx(fmt_idx):
        if qat is None or not qat.enabled:
            return QuantCtx()
        idx = fmt_idx if fmt_idx is not None else jnp.int32(n_fmts)
        return QuantCtx(qat=qat, fmt_idx=idx)

    # ---- training ---------------------------------------------------------
    def train_loss(params, batch, fmt_idx=None):
        ctx = _ctx(fmt_idx)
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = _embed(params, cfg, tokens)
        extra = 0
        if cfg.vision_tokens > 0:
            ve = batch["vision_embeds"].astype(cfg.compute_dtype)
            x = jnp.concatenate([ve, x], axis=1)
            extra = ve.shape[1]
        positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None],
                                     (b, x.shape[1]))
        hidden, _, aux = forward_hidden(ctx, params, cfg, x, positions)
        hidden = hidden[:, extra:]
        labels = batch["labels"]
        mask = batch.get("mask", jnp.ones_like(labels, jnp.float32))
        loss = chunked_ce_loss(ctx, hidden, _lm_head_w(params, cfg),
                               labels, mask.astype(jnp.float32), cfg)
        return loss + aux, {"ce": loss, "aux": aux}

    # ---- serving ----------------------------------------------------------
    def init_cache(b, s_max, dtype=None, *, kv_layout="dense",
                   page_size=16, num_pages=None):
        """KV cache pytree.

        ``kv_layout="dense"`` (default): per-slot contiguous buffers
        (B, s_max, Hkv, D) — s_max HBM is committed per slot up front.

        ``kv_layout="paged"``: a shared page pool per layer
        (num_pages, page_size, Hkv*D) plus a per-slot ``block_table``
        (B, ceil(s_max/page_size)) of physical page ids, so s_max is a
        per-request *bound* and HBM is committed page-by-page as sequences
        grow. Physical page 0 is reserved scratch (unmapped entries point
        there); ``num_pages=None`` sizes the pool to dense-equivalent
        capacity + the scratch page. Requires a pure-attention stack —
        recurrent state (mamba/rwkv) has no sequence axis to page.
        """
        dtype = dtype or cfg.compute_dtype
        s_max = s_max + cfg.vision_tokens   # room for prepended image embeds
        if kv_layout == "dense":
            return {"blocks": [
                jax.tree_util.tree_map(
                    lambda x: jnp.broadcast_to(x[None],
                                               (cfg.n_groups,) + x.shape),
                    _cache_for_block(cfg, j, b, s_max, dtype))
                for j in range(cfg.scan_group)]}
        if kv_layout != "paged":
            raise ValueError(f"unknown kv_layout {kv_layout!r}; "
                             "one of ('dense', 'paged')")
        bad = [mixer_kind(cfg, j) for j in range(cfg.scan_group)
               if mixer_kind(cfg, j) != "attn"]
        if bad:
            raise ValueError(
                f"kv_layout='paged' requires a pure-attention stack; "
                f"family {cfg.family!r} has {bad} mixers whose recurrent "
                "state cannot be paged — use kv_layout='dense'")
        pages_per_slot = -(-s_max // page_size)
        if num_pages is None:
            num_pages = b * pages_per_slot + 1   # + reserved scratch page 0
        pool = functools.partial(
            jnp.zeros, (cfg.n_groups, num_pages, page_size,
                        cfg.n_kv_heads * cfg.hd), dtype)
        return {"blocks": [{"k_pages": pool(), "v_pages": pool()}
                           for _ in range(cfg.scan_group)],
                "block_table": jnp.zeros((b, pages_per_slot), jnp.int32)}

    def cache_axes(kv_layout="dense"):
        if kv_layout == "paged":
            # pools (G, P, ps, Hkv*D): shard the page axis like the dense
            # sequence axis; the tiny block table replicates per batch row.
            ax = {"k_pages": (None, "kv_seq", None, None),
                  "v_pages": (None, "kv_seq", None, None)}
            return {"blocks": [dict(ax) for _ in range(cfg.scan_group)],
                    "block_table": ("batch", None)}
        return {"blocks": [_cache_axes_for_block(cfg, j)
                           for j in range(cfg.scan_group)]}

    def _serving_fns(qmm=None, attn_impl="gather"):
        """Build (prefill, serve_step) sharing one matmul hook.

        ``qmm=None`` is the XLA contract (packed leaves dequantized at point
        of use / pre-densified trees); a hook routes every packed projection
        through the fused Pallas dequant-GEMM dispatch. ``attn_impl`` bakes
        the paged decode attention path into serve_step: ``"paged_kernel"``
        (the gather-free block-table kernel, kernels/paged_attention.py) or
        ``"gather"`` (materialize + masked softmax). Prefill — monolithic
        and chunked — is unaffected (its flash queries span the cache).
        """
        if attn_impl not in ("gather", "paged_kernel"):
            raise ValueError(
                f"unknown attn_impl {attn_impl!r}; one of "
                "('gather', 'paged_kernel')")

        def prefill(params, batch, cache):
            """Process the full prompt, fill the cache, return last-pos
            logits.

            Serving never fake-quantizes: weights arrive already PTQ'd /
            SS-converted (running the QAT switch here would upcast weights to
            f32 and double the FSDP all-gather bytes — found via dry-run
            HLO).

            ``batch["lengths"]`` (B,), optional: true prompt lengths when
            tokens are right-padded to a length bucket. Attention is causal,
            so pad positions never influence real ones; logits are read at
            each row's own last real token and cache_len is the true length,
            which exactly masks the pad KV entries at decode.
            """
            ctx = QuantCtx(qmm=qmm, tp_axis=tp_axis)
            tokens = batch["tokens"]
            b, s = tokens.shape
            x = _embed(params, cfg, tokens, tp_axis)
            extra = 0
            if cfg.vision_tokens > 0:
                ve = batch["vision_embeds"].astype(cfg.compute_dtype)
                x = jnp.concatenate([ve, x], axis=1)
                extra = ve.shape[1]
            positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None],
                                         (b, x.shape[1]))
            hidden, new_cache, _ = forward_hidden(
                ctx, params, cfg, x, positions, cache=cache,
                cache_len=jnp.zeros((b,), jnp.int32), prefill=True)
            lengths = batch.get("lengths")
            if lengths is None:
                cache_len = jnp.full((b,), x.shape[1], jnp.int32)
                h_last = hidden[:, -1]
            else:
                cache_len = lengths.astype(jnp.int32) + extra
                h_last = _last_hidden(hidden, cache_len)
            logits = _head_logits(ctx, params, cfg, h_last)
            return logits, new_cache, cache_len

        def prefill_chunk(params, batch, cache, start_pos):
            """One prompt chunk at cursor ``start_pos`` (chunked admission).

            ``batch["tokens"]`` (B, C) is the prompt slice
            ``[start_pos, start_pos + C)`` (the final chunk may be
            right-padded); ``batch["lengths"]`` (B,) is the TRUE TOTAL
            prompt length. K/V land in the cache at the cursor and the
            chunk's queries attend over everything written so far, so
            running the chunks in order is bit-identical to monolithic
            ``prefill`` (see docs/serving_internals.md "Admission &
            scheduling"). Returns ``(logits, cache, new_len)`` with
            ``new_len = min(lengths, start_pos + C)`` — on the final chunk
            that is the true prompt length and ``logits`` is read at the
            last real token (earlier chunks' logits are discarded by the
            engine).
            """
            if cfg.vision_tokens > 0:
                raise ValueError(
                    "chunked prefill does not support prepended vision "
                    "embeds; use monolithic admission")
            ctx = QuantCtx(qmm=qmm, tp_axis=tp_axis)   # no fake-quant in
            #                                            serving (see prefill)
            tokens = batch["tokens"]
            b, c = tokens.shape
            x = _embed(params, cfg, tokens, tp_axis)
            start = jnp.asarray(start_pos, jnp.int32)
            positions = start + jnp.broadcast_to(jnp.arange(c)[None], (b, c))
            hidden, new_cache, _ = forward_hidden(
                ctx, params, cfg, x, positions, cache=cache,
                cache_len=jnp.zeros((b,), jnp.int32), prefill=True,
                chunk_start=start)
            new_len = jnp.minimum(batch["lengths"].astype(jnp.int32),
                                  start + c)
            h_last = _last_hidden(hidden, new_len - start)
            logits = _head_logits(ctx, params, cfg, h_last)
            return logits, new_cache, new_len

        def serve_step(params, batch, cache, cache_len):
            """One decode step: batch['tokens'] (B,1) against the cache."""
            ctx = QuantCtx(qmm=qmm, tp_axis=tp_axis)   # no fake-quant in
            #                                            serving (see prefill)
            tokens = batch["tokens"]
            b = tokens.shape[0]
            x = _embed(params, cfg, tokens, tp_axis)
            positions = cache_len[:, None]
            hidden, new_cache, _ = forward_hidden(
                ctx, params, cfg, x, positions, cache=cache,
                cache_len=cache_len, prefill=False, attn_impl=attn_impl)
            logits = _head_logits(ctx, params, cfg, hidden[:, -1])
            logits = shard_act(logits, ("batch", "vocab"))
            return logits, new_cache

        def mixed_step(params, batch, cache, cache_len):
            """One unified mixed prefill+decode tick (the single-executable
            scheduler; docs/serving_internals.md §6).

            ``batch["tokens"]`` (B, C): each row's new tokens, left-aligned;
            ``batch["q_len"]`` (B,): how many are real — decoding rows carry
            1, the (single) mid-prefill row carries its chunk, pad lanes are
            masked and never written. Row ``b``'s token ``i`` sits at
            logical position ``cache_len[b] + i``; K/V land there (through
            the block table when paged) and logits come back at each row's
            LAST real token — next-token logits for decode rows, chunk-final
            logits for the mid-prefill row (meaningful only on its final
            chunk; the engine discards the rest). Returns (logits, cache).
            """
            if cfg.vision_tokens > 0:
                raise ValueError(
                    "mixed_step does not support prepended vision embeds; "
                    "use sequential admission")
            ctx = QuantCtx(qmm=qmm, tp_axis=tp_axis)   # no fake-quant in
            #                                            serving (see prefill)
            tokens = batch["tokens"]
            q_len = batch["q_len"].astype(jnp.int32)
            b, c = tokens.shape
            x = _embed(params, cfg, tokens, tp_axis)
            positions = cache_len[:, None] + \
                jnp.broadcast_to(jnp.arange(c)[None], (b, c))
            hidden, new_cache, _ = forward_hidden(
                ctx, params, cfg, x, positions, cache=cache,
                cache_len=cache_len, prefill=False, q_len=q_len,
                attn_impl=attn_impl)
            h_last = _last_hidden(hidden, q_len)
            logits = _head_logits(ctx, params, cfg, h_last)
            logits = shard_act(logits, ("batch", "vocab"))
            return logits, new_cache

        def verify_step(params, batch, cache, cache_len):
            """One speculative-verify tick: logits at EVERY query position.

            Same contract as ``mixed_step`` — ``batch["tokens"]`` (B, C)
            left-aligned new tokens, ``batch["q_len"]`` (B,) how many are
            real, row ``b``'s token ``i`` at logical position
            ``cache_len[b] + i`` — but the head projects ALL C positions,
            returning logits (B, C, V) so the engine can compare every
            draft token against this format's own greedy choice in one
            executable. K/V for all C tokens land at the per-row cursor
            BEFORE attention reads them (the standard mixed
            write-then-attend order), so a verify pass overwrites whatever
            a draft pass wrote at those positions: each verify attempt is
            a pure function of the committed cache, which is what makes
            guard escalate-and-replay safe under speculation
            (docs/serving_internals.md §9). Pad lanes past a row's q_len
            return meaningless logits; callers must only read live lanes.
            """
            if cfg.vision_tokens > 0:
                raise ValueError(
                    "verify_step does not support prepended vision embeds; "
                    "disable speculative decoding for VLM configs")
            ctx = QuantCtx(qmm=qmm, tp_axis=tp_axis)   # no fake-quant in
            #                                            serving (see prefill)
            tokens = batch["tokens"]
            q_len = batch["q_len"].astype(jnp.int32)
            b, c = tokens.shape
            x = _embed(params, cfg, tokens, tp_axis)
            positions = cache_len[:, None] + \
                jnp.broadcast_to(jnp.arange(c)[None], (b, c))
            hidden, new_cache, _ = forward_hidden(
                ctx, params, cfg, x, positions, cache=cache,
                cache_len=cache_len, prefill=False, q_len=q_len,
                attn_impl=attn_impl)
            d = hidden.shape[-1]
            logits = _head_logits(ctx, params, cfg, hidden.reshape(b * c, d))
            logits = logits.reshape(b, c, -1)
            logits = shard_act(logits, ("batch", None, "vocab"))
            return logits, new_cache

        return prefill, serve_step, prefill_chunk, mixed_step, verify_step

    (prefill, serve_step, prefill_chunk, mixed_step,
     verify_step) = _serving_fns(None)

    def with_serving(qmm=None, attn_impl="gather"):
        p, s, pc, ms, vs = _serving_fns(qmm, attn_impl)
        return dataclasses.replace(
            api, prefill=p, serve_step=s, prefill_slot=make_prefill_slot(p),
            prefill_chunk=pc,
            prefill_chunk_slot=make_prefill_chunk_slot(pc),
            mixed_step=ms, verify_step=vs,
            attn_impl=attn_impl,
            # with_qmm on the derived api keeps ITS attn_impl (chaining must
            # not silently reset the decode path to the base default)
            with_qmm=lambda q: with_serving(qmm=q, attn_impl=attn_impl))

    def with_qmm(qmm):
        return with_serving(qmm=qmm)

    api = ModelApi(
        cfg=cfg, qat=qat,
        init_params=functools.partial(init_params, cfg=cfg),
        param_axes=functools.partial(param_axes, cfg=cfg),
        train_loss=train_loss,
        init_cache=init_cache,
        cache_axes=cache_axes,
        prefill=prefill,
        serve_step=serve_step,
        prefill_slot=make_prefill_slot(prefill),
        prefill_chunk=prefill_chunk,
        prefill_chunk_slot=make_prefill_chunk_slot(prefill_chunk),
        mixed_step=mixed_step,
        verify_step=verify_step,
        with_qmm=with_qmm,
        with_serving=with_serving,
        tp_axis=tp_axis,
    )
    return api
