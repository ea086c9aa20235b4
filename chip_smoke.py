#!/usr/bin/env python3
"""Smoke run of MF-QAT's main path on a TPU.

    python chip_smoke.py                # one chip: train -> anchor -> serve
    python chip_smoke.py --four-chips   # tensor-parallel serving, 4 chips

Everything runs in this one process: a process that has touched JAX holds
the chip, so no phase may start a child that needs it. The default run
drives the paper's pipeline through the entry points a user calls
(``get_config`` -> ``get_model`` -> ``run_training`` -> ``make_anchor`` ->
``ElasticEngine.generate``) on smollm-135m at its published widths
(30 layers, d_model 576, 9/3 heads, d_ff 1536, vocab 49152), with random
weights and synthetic tokens made from ``--seed``:

1. ``train``: 3 multi-format QAT steps (the sequential mxint schedule);
   every loss must be finite.
2. ``anchor``: an MXINT8 anchor from the trained params.
3. ``serve``: 8 requests (prompts 64-512 tokens, 16 new tokens each) at
   mxint8, mxint6 and mxint4, the lower rungs Slice-and-Scaled from the
   anchor, over the paged KV cache with chunked admission and the mixed
   scheduler, through the fused dequant-GEMM and both paged-attention
   kernels. Every request must complete, no GEMM may densify, both kernels
   must have been traced and the logit guard must never escalate.
4. ``reference``: the same requests through the reference contract
   (weights densified inside jit, gather attention). Each rung's logits
   for the first prompt must agree with the served path's within
   ``LOGIT_RTOL``; token agreement is printed, not gated.

``--four-chips`` runs only the tensor-parallel path: qwen3-4b at published
widths with depth cut to 4 layers (smollm's 9/3 heads do not split four
ways), a (1, 4)-mesh engine against the single-device engine on device 0
on the same greedy requests. Gates: first-prompt logits within
``LOGIT_RTOL``, per-chip weight bytes about a quarter of the whole, every
request completed.

Earlier lines give each phase's wall and compile seconds; they are set-up
times, not measurements of the system. The last line is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
Without a TPU the script exits 2 before any phase runs.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import get_format, make_anchor  # noqa: E402
from repro.core.formats import TRAIN_FORMATS_MXINT  # noqa: E402
from repro.core.qat import QATConfig  # noqa: E402
from repro.data.pipeline import DataConfig, LMDataset  # noqa: E402
from repro.kernels import dispatch, paged_attention  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_debug_mesh  # noqa: E402
from repro.models import get_model  # noqa: E402
from repro.optim.adamw import AdamWConfig  # noqa: E402
from repro.serve.engine import ElasticEngine, Request, RequestStatus  # noqa
from repro.train.loop import LoopConfig, run_training  # noqa: E402

SERVE_FORMATS = ("mxint8", "mxint6", "mxint4")
# Relative L2 distance allowed between the served path's next-token logits
# and the reference's. Both keep activations in bf16 (unit roundoff 2^-9)
# between the projections of every layer, but sum each GEMM and each
# softmax in a different order (Pallas tiles and an online softmax against
# XLA's dot and a gathered softmax; on a mesh, a psum of per-shard partial
# sums), so outputs round one bf16 ulp apart here and there and the
# residual stream carries that forward through the depth. That stays near
# 1e-2; a wrong scale, page, head or shard moves the logits by O(1).
LOGIT_RTOL = 5e-2
# weight_bytes_per_chip / weight_bytes on a (1, tp) mesh: 1/tp, plus the
# norm vectors every chip keeps whole.
TP_BYTES_SLACK = 1.08


class Phases:
    """Per-phase wall time and XLA compile time, printed as each ends."""

    def __init__(self):
        self._compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration_s, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self._compile_s += duration_s

    @contextlib.contextmanager
    def __call__(self, name):
        t0, c0 = time.perf_counter(), self._compile_s
        yield
        print(f"phase {name}: wall {time.perf_counter() - t0:.1f} s, "
              f"compile {self._compile_s - c0:.1f} s", flush=True)


def require_tpu():
    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev[0].platform!r}",
              file=sys.stderr)
        sys.exit(2)
    print(f"device: {dev[0].platform} {dev[0].device_kind} x{len(dev)}",
          flush=True)
    return {"platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": len(dev)}


def check(cond, what):
    if not cond:
        raise AssertionError(what)


def make_requests(cfg, seed, n, plen_lo, plen_hi, max_new, unit=16):
    """``n`` requests whose prompt lengths, multiples of ``unit``, span
    [plen_lo, plen_hi] with both ends present."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(plen_lo // unit, plen_hi // unit + 1, n) * unit
    lens[:2] = plen_hi, plen_lo
    return [Request(rid=i, prompt=rng.integers(1, cfg.vocab, int(ln))
                    .astype(np.int32), max_new=max_new)
            for i, ln in enumerate(lens)]


def fresh(reqs):
    return [Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new)
            for r in reqs]


def serve(eng, reqs, fmt):
    out = eng.generate(fresh(reqs), greedy=True, fmt_override=fmt)
    bad = [(r.rid, r.status.value, r.error) for r in out
           if r.status != RequestStatus.COMPLETED
           or len(r.out_tokens) != r.max_new]
    check(not bad, f"{fmt}: requests not completed: {bad}")
    return [r.out_tokens for r in out]


def rel_l2(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def compare_logits(what, got, want):
    check(np.isfinite(got).all() and np.isfinite(want).all(),
          f"{what}: non-finite logits")
    err = rel_l2(got, want)
    print(f"{what}: logits rel-L2 {err:.3e} (limit {LOGIT_RTOL}), max abs "
          f"{float(np.abs(got - want).max()):.3e}, argmax "
          f"{'agrees' if got.argmax() == want.argmax() else 'differs'}",
          flush=True)
    check(err <= LOGIT_RTOL, f"{what}: logits rel-L2 {err} > {LOGIT_RTOL}")


def token_agreement(a, b):
    same = sum(x == y for sa, sb in zip(a, b) for x, y in zip(sa, sb))
    return same, sum(len(s) for s in a)


def smoke(cfg, phase, *, seed=0, train_seq=512, train_batch=8, steps=3,
          n_requests=8, plen=(64, 512), max_new=16, slots=8, page=16):
    """Train -> anchor -> serve -> reference for ``cfg``; raises on any
    failed gate."""
    qat = QATConfig(formats=TRAIN_FORMATS_MXINT, block_size=32)
    with phase("train"):
        api = get_model(cfg, qat)
        data = LMDataset(DataConfig(vocab=cfg.vocab, seq_len=train_seq,
                                    global_batch=train_batch, seed=seed))
        out = run_training(api, data, AdamWConfig(lr=1e-4),
                           LoopConfig(total_steps=steps,
                                      schedule="multiformat"), seed=seed)
        losses = [h["loss"] for h in out["history"]]
        print(f"train losses {losses} at format indices "
              f"{[h['fmt_idx'] for h in out['history']]}", flush=True)
        check(len(losses) == steps and np.isfinite(losses).all(),
              f"train: losses {losses}")
        params = out["state"].params

    with phase("anchor"):
        anchor = make_anchor(params, qat, get_format("mxint8", 32))
        jax.block_until_ready(anchor.quantized)

    serve_api = get_model(cfg, None)
    reqs = make_requests(cfg, seed, n_requests, *plen, max_new)
    engine_kw = dict(batch_slots=slots, max_len=plen[1] + max_new + page,
                     kv_layout="paged", kv_page_size=page,
                     prefill_chunk="auto", param_template=params, seed=seed)
    with phase("serve"):
        dispatch.reset_stats()
        paged_attention.reset_stats()
        eng = ElasticEngine(serve_api, anchor, fused=True,
                            attn_impl="paged_kernel", **engine_kw)
        check(eng.scheduler == "mixed", f"scheduler {eng.scheduler}")
        streams = {f: serve(eng, reqs, f) for f in SERVE_FORMATS}
        served = {f: eng.prompt_logits(reqs[0].prompt, f)
                  for f in SERVE_FORMATS}
        gemms, attn = dispatch.stats(), paged_attention.stats()
        esc = eng.stats["fmt_escalations"]
        print(f"served {len(reqs)} requests x {SERVE_FORMATS}; dequant-GEMM "
              f"paths {gemms}; paged-attention paths {attn}; escalations "
              f"{esc}", flush=True)
        check(gemms["densify"] == 0, f"densified GEMMs: {gemms}")
        check(gemms["pallas"] > 0 and gemms["pallas_int4"] > 0,
              f"fused GEMM kernels not traced: {gemms}")
        check(attn["pallas"] > 0 and attn["pallas_mq"] > 0,
              f"paged-attention kernels not traced: {attn}")
        check(attn["fallback"] == 0 and attn["fallback_mq"] == 0,
              f"paged attention fell back: {attn}")
        check(esc == 0, f"logit guard escalated {esc} time(s)")

    with phase("reference"):
        ref = ElasticEngine(serve_api, anchor, fused=False,
                            attn_impl="gather", **engine_kw)
        for f in SERVE_FORMATS:
            same, total = token_agreement(streams[f], serve(ref, reqs, f))
            print(f"{f}: {same}/{total} tokens agree with the reference "
                  f"contract", flush=True)
            compare_logits(f"{f} fused+paged_kernel vs densify+gather",
                           served[f], ref.prompt_logits(reqs[0].prompt, f))


def four_chips(cfg, phase, *, tp=4, seed=0, n_requests=4, plen=(64, 256),
               max_new=8, slots=4, page=16, fmts=("mxint8", "mxint4")):
    """A (1, tp)-mesh engine against the single-device engine; raises on
    any failed gate."""
    check(len(jax.devices()) >= tp,
          f"--four-chips needs {tp} devices, found {len(jax.devices())}")
    qat = QATConfig(formats=("mxint8",), block_size=32)
    with phase("anchor"):
        api = get_model(cfg, None)
        params = api.init_params(jax.random.PRNGKey(seed))
        template = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        anchor = make_anchor(params, qat, get_format("mxint8", 32))
        jax.block_until_ready(anchor.quantized)
        del params
    reqs = make_requests(cfg, seed, n_requests, *plen, max_new)
    kw = dict(batch_slots=slots, max_len=plen[1] + max_new + page,
              kv_layout="paged", kv_page_size=page, prefill_chunk="auto",
              fused=True, attn_impl="paged_kernel", param_template=template,
              seed=seed)
    runs = {}
    for name, mesh in (("single", None), ("mesh", make_debug_mesh(1, tp))):
        with phase(f"serve-{name}"):
            eng = ElasticEngine(api, anchor, mesh=mesh, **kw)
            streams = serve(eng, reqs, fmts[0])
            logits = {f: eng.prompt_logits(reqs[0].prompt, f) for f in fmts}
            runs[name] = (eng, streams, logits)
    eng, streams, logits = runs["mesh"]
    _, want_streams, want_logits = runs["single"]
    same, total = token_agreement(streams, want_streams)
    print(f"{fmts[0]}: {same}/{total} tokens agree mesh vs single device",
          flush=True)
    for f in fmts:
        compare_logits(f"{f} mesh (1,{tp}) vs single device", logits[f],
                       want_logits[f])
        st = eng.stats
        ratio = st["weight_bytes_per_chip"][f] / st["weight_bytes"][f]
        print(f"{f}: weight bytes per chip / whole = {ratio:.4f}",
              flush=True)
        check(1 / tp <= ratio < TP_BYTES_SLACK / tp,
              f"{f}: per-chip weight ratio {ratio}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tensor-parallel path on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    device = require_tpu()
    print(f"compile cache: {use_compile_cache()}", flush=True)
    phase = Phases()
    t0 = time.perf_counter()
    if args.four_chips:
        cfg = dataclasses.replace(get_config("qwen3-4b"), n_layers=4)
        print(f"model: {cfg.name} at published widths, depth cut to "
              f"{cfg.n_layers} layers", flush=True)
        four_chips(cfg, phase, seed=args.seed)
    else:
        cfg = get_config("smollm-135m")
        print(f"model: {cfg.name} at published widths, {cfg.n_layers} "
              "layers", flush=True)
        smoke(cfg, phase, seed=args.seed)
    print(f"total wall {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
