"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This file proves the distribution config is coherent without hardware:
``jax.jit(step).lower(**ShapeDtypeStructs).compile()`` must succeed on the
single-pod 16x16 mesh and the 2x16x16 multi-pod mesh for every assigned
(architecture x input shape) pair, and its memory/cost analyses feed the
roofline (EXPERIMENTS.md). Results are written incrementally to JSON so the
sweep is resumable cell-by-cell.
"""
# The VERY FIRST lines, before any other import: 512 placeholder devices.
# Never clobber flags the caller already set (CI exports its own XLA_FLAGS
# for CPU-mesh tests), and skip entirely when a host-device-count flag is
# already present — the caller's device count wins.
import os

HOST_DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"


def _merged_xla_flags(existing: str, n: int = 512):
    """XLA_FLAGS value with ``--xla_force_host_platform_device_count=n``
    appended to ``existing``, or None when ``existing`` already pins a host
    device count (setting it twice would silently override the caller's)."""
    if HOST_DEVICE_COUNT_FLAG in existing:
        return None
    return f"{existing} {HOST_DEVICE_COUNT_FLAG}={n}".strip()


_flags = _merged_xla_flags(os.environ.get("XLA_FLAGS", ""))
if _flags is not None:
    os.environ["XLA_FLAGS"] = _flags
del _flags

import argparse          # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from typing import Dict, Optional  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np       # noqa: E402

from repro.configs import (SHAPES, applicable, decode_cache_len,  # noqa: E402
                           get_config, list_archs)
from repro.core.formats import TRAIN_FORMATS_MXINT  # noqa: E402
from repro.core.qat import QATConfig                # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import get_model                  # noqa: E402
from repro.optim.adamw import AdamWConfig, init_opt_state  # noqa: E402
from repro.sharding.rules import (DEFAULT_RULES, LogicalRules,  # noqa: E402
                                  param_shardings, spec_for_axes, use_rules)
from repro.train.state import TrainState, build_train_step  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s+(?:\(?)([a-z0-9]+)\[([\d,]*)\][^=]*?"
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")


def parse_collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum per-device result bytes of every collective in optimized HLO.

    Per-chip traffic factors (ring algorithms on N shards):
      all-gather: result bytes (each chip receives the full result),
      all-reduce: 2x operand, reduce-scatter: operand, all-to-all: operand,
      collective-permute: operand.
    """
    out = {"all-gather": 0.0, "all-reduce": 0.0, "reduce-scatter": 0.0,
           "all-to-all": 0.0, "collective-permute": 0.0}
    for m in _COLL_RE.finditer(hlo_text):
        dt, dims, kind = m.group(1), m.group(2), m.group(3)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[kind] += n * _DTYPE_BYTES[dt]
    factors = {"all-gather": 1.0, "all-reduce": 2.0, "reduce-scatter": 1.0,
               "all-to-all": 1.0, "collective-permute": 1.0}
    out["total_weighted"] = sum(out[k] * factors[k] for k in factors)
    return out


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def batch_specs(cfg, shape, kind: str):
    b, s = shape.global_batch, shape.seq_len
    if kind == "train":
        batch = {"tokens": _sds((b, s), jnp.int32),
                 "labels": _sds((b, s), jnp.int32)}
    elif kind == "prefill":
        batch = {"tokens": _sds((b, s), jnp.int32)}
    else:
        batch = {"tokens": _sds((b, 1), jnp.int32)}
    if cfg.family == "vlm" and kind != "decode":
        batch["vision_embeds"] = _sds((b, cfg.vision_tokens, cfg.d_model),
                                      jnp.float32)
    if cfg.family == "encdec" and kind != "decode":
        batch["frame_embeds"] = _sds(
            (b, max(1, s // max(cfg.audio_downsample, 1)), cfg.d_model),
            jnp.float32)
    return batch


def batch_sharding(batch, mesh):
    def one(sds):
        axes = ("batch",) + (None,) * (len(sds.shape) - 1)
        return NamedSharding(mesh, spec_for_axes(sds.shape, axes, mesh))
    return jax.tree_util.tree_map(one, batch)


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               variant: str = "baseline",
               rules_override: Optional[dict] = None):
    """Lower+compile one cell; returns the result record.

    Variants (the §Perf ladder):
      baseline     — as-shipped defaults (flash-VJP on, local-group MoE)
      novjp        — flash attention without the custom VJP (the original
                     implementation; records the O(S^2)-residual memory)
      sp           — + sequence-parallel residual stream saves
      sp_mb4       — sp + 4-way microbatched gradient accumulation
    """
    import dataclasses as _dc
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if not applicable(cfg, shape):
        return {"status": "skipped", "reason": "full-attention arch at 500k"}
    variant_label = variant
    microbatch = 1
    if variant == "novjp":
        cfg = _dc.replace(cfg, flash_vjp=False)
    elif variant == "sp":
        cfg = _dc.replace(cfg, seq_sharding=True)
    elif variant == "sp_mb4":
        cfg = _dc.replace(cfg, seq_sharding=True)
        microbatch = 4
    elif variant == "inner":
        cfg = _dc.replace(cfg, remat_inner=True)
    elif variant == "inner_mb4":
        cfg = _dc.replace(cfg, remat_inner=True)
        microbatch = 4
    elif variant == "inner_mb8":
        cfg = _dc.replace(cfg, remat_inner=True)
        microbatch = 8
    if variant.endswith("tp") or variant.endswith("scan"):
        # weight-stationary serving: weights replicate over (pod, data) and
        # stay TP-sharded over model — no per-step weight all-gather. Packed
        # MX weights (w8/w4) are what make the biggest models *fit* this
        # layout (bf16 replicated doesn't for 141B+); the *scan variants
        # additionally dequantize per layer inside the scan, so no resident
        # bf16 weight copy exists either.
        rules_override = dict(rules_override or {})
        rules_override["fsdp"] = ()
        variant_bits = {"w16tp": None, "w8tp": "w8", "w4tp": "w4"}
        variant = variant_bits.get(variant, variant) or "baseline_tp"

    mesh = make_production_mesh(multi_pod=multi_pod)
    table = dict(DEFAULT_RULES)
    if rules_override:
        table.update(rules_override)
    rules = LogicalRules(table)

    qat = QATConfig(formats=TRAIN_FORMATS_MXINT, block_size=32)
    api = get_model(cfg, qat)
    t0 = time.time()

    with use_rules(mesh, rules):
        params_s = jax.eval_shape(api.init_params, jax.random.PRNGKey(0))
        p_shard = param_shardings(api.param_axes(), params_s, mesh, rules)
        scalar = NamedSharding(mesh, P())

        if shape.kind == "train":
            moment_dtype = jnp.bfloat16 if "jamba" in arch else jnp.float32
            opt_cfg = AdamWConfig(moment_dtype=moment_dtype)
            opt_s = jax.eval_shape(
                lambda p: init_opt_state(p, opt_cfg), params_s)
            opt_shard = {"step": scalar, "m": p_shard, "v": p_shard}
            state_s = TrainState(params_s, opt_s, _sds((), jnp.int32))
            state_shard = TrainState(p_shard, opt_shard, scalar)
            batch = batch_specs(cfg, shape, "train")
            step_fn = build_train_step(api, opt_cfg, microbatch=microbatch)
            jitted = jax.jit(step_fn,
                             in_shardings=(state_shard,
                                           batch_sharding(batch, mesh),
                                           scalar),
                             out_shardings=(state_shard, scalar),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_s, batch, _sds((), jnp.int32))
        else:
            # serving: bf16 dense params
            params_bf16 = jax.tree_util.tree_map(
                lambda sds: _sds(sds.shape, jnp.bfloat16)
                if jnp.issubdtype(sds.dtype, jnp.floating) else sds, params_s)
            b = shape.global_batch
            if shape.kind == "prefill":
                cache_len_alloc = shape.seq_len
                cache_s = jax.eval_shape(
                    lambda: api.init_cache(b, cache_len_alloc))
                c_shard = param_shardings(api.cache_axes(), cache_s, mesh,
                                          rules)
                batch = batch_specs(cfg, shape, "prefill")
                jitted = jax.jit(api.prefill,
                                 in_shardings=(p_shard,
                                               batch_sharding(batch, mesh),
                                               c_shard),
                                 out_shardings=(scalar, c_shard, scalar))
                lowered = jitted.lower(params_bf16, batch, cache_s)
            else:
                # round the cache allocation up to a model-axis-shardable
                # length: a non-divisible kv_seq dim silently drops the
                # sequence sharding and GSPMD then head-gathers the cache
                # in f32 (found via dry-run HLO; see EXPERIMENTS.md §Perf)
                cache_len_alloc = decode_cache_len(cfg, shape) + 1
                cache_len_alloc = -(-cache_len_alloc // 128) * 128
                cache_s = jax.eval_shape(
                    lambda: api.init_cache(b, cache_len_alloc))
                c_shard = param_shardings(api.cache_axes(), cache_s, mesh,
                                          rules)
                batch = batch_specs(cfg, shape, "decode")
                len_s = _sds((b,), jnp.int32)
                if variant in ("w8", "w4", "w8scan", "w4scan"):
                    # packed-MX serving weights (the paper's deployment
                    # artifact): int8 anchor codes, or SS->int4 nibble-packed
                    from repro.core.anchor import make_anchor
                    from repro.core.formats import get_format
                    from repro.serve.packed_params import (
                        make_packed_params, make_packed_serve_step,
                        packed_param_shardings)
                    bits = 8 if variant.startswith("w8") else 4
                    anchor_fmt = get_format("mxint8", qat.block_size)
                    packed_s = jax.eval_shape(
                        lambda p: make_packed_params(
                            make_anchor(p, qat, anchor_fmt), p,
                            target_bits=bits),
                        params_s)
                    pk_shard = packed_param_shardings(
                        packed_s, api.param_axes(), mesh, rules)
                    if variant.endswith("scan"):
                        # packed weights flow INTO the layer scan; dense()
                        # dequantizes per layer (Pallas-GEMM contract at the
                        # XLA level) — no resident bf16 weight copy.
                        step = api.serve_step
                    else:
                        step = make_packed_serve_step(api, qat.block_size)
                    jitted = jax.jit(
                        step,
                        in_shardings=(pk_shard, batch_sharding(batch, mesh),
                                      c_shard, scalar),
                        out_shardings=(scalar, c_shard))
                    lowered = jitted.lower(packed_s, batch, cache_s, len_s)
                else:
                    jitted = jax.jit(
                        api.serve_step,
                        in_shardings=(p_shard, batch_sharding(batch, mesh),
                                      c_shard, scalar),
                        out_shardings=(scalar, c_shard))
                    lowered = jitted.lower(params_bf16, batch, cache_s,
                                           len_s)

        compiled = lowered.compile()

    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = parse_collective_bytes(hlo)
    rec = {
        "status": "ok",
        "arch": arch, "shape": shape_name,
        "mesh": "pod2x16x16" if multi_pod else "16x16",
        "variant": variant_label,
        "compile_s": round(time.time() - t0, 1),
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "collectives": coll,
        "memory": {
            k: int(getattr(mem, k, 0)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes",
             "alias_size_in_bytes")
        },
        "n_devices": int(np.prod(mesh.devices.shape)),
    }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--out", default="out/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_tag = "pod2x16x16" if mp else "16x16"
                path = os.path.join(
                    args.out,
                    f"{arch}__{shape}__{mesh_tag}__{args.variant}.json")
                if os.path.exists(path) and not args.force:
                    print(f"skip (exists): {path}")
                    continue
                print(f"=== {arch} x {shape} x {mesh_tag} ===", flush=True)
                try:
                    rec = lower_cell(arch, shape, mp, variant=args.variant)
                except Exception as e:  # record failures — they are bugs
                    rec = {"status": "error", "arch": arch, "shape": shape,
                           "mesh": mesh_tag, "variant": args.variant,
                           "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                print(json.dumps({k: v for k, v in rec.items()
                                  if k != "trace"})[:600], flush=True)


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
