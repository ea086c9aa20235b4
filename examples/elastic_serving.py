"""Elastic-precision serving demo: one anchor checkpoint, load-adaptive
precision, packed-weight continuous batching.

A burst of requests hits the engine; the FormatPolicy watches queue depth at
each batch admission and drops precision under load (mxint8 -> 6 -> 4),
recovering when the queue drains. Every format is served from a single
MXINT8 anchor via Slice-and-Scale, and the decode tick reads *packed* MX
codes (MXTensor / split-N nibble-packed PackedInt4Leaf) — on TPU each
projection streams them through the fused Pallas dequant-GEMM
(`kernels.dispatch.qmatmul`); elsewhere the dequant runs inside the jitted
step — either way HBM weight traffic is the packed bytes. Requests are
admitted into individual slots (staggered arrivals never re-prefill active
sequences; prompts pad to power-of-two buckets so prefill compiles once per
bucket), and the format is pinned per batch, never switched mid-sequence.

The engine runs with the paged KV cache (kv_layout="paged"): KV HBM is
committed one page at a time as sequences grow and recycled the moment a
request retires, instead of preallocating max_len per slot — token streams
are identical to the dense layout (see docs/serving_internals.md §5).
Decode attention reads the pool through the attn_impl knob: --attn-impl
paged_kernel runs the gather-free block-table kernel
(kernels/paged_attention.py; interpret mode off TPU), --attn-impl gather
materializes each slot's logical view first — token streams are identical
either way, and the stats line reports the attention bytes each path read.

With --prefill-chunk N, admission is *chunked* (docs/serving_internals.md
§6): long prompts stream in N-token chunks interleaved with decode ticks —
at most one chunk of prefill per tick — so running slots' inter-token
latency stays bounded while a long prompt admits. Token streams are
bit-identical either way.

With --speculative, the demo adds self-speculative decoding
(docs/serving_internals.md §9): each decode tick drafts k=4 tokens with
the mxint4 rung of the SAME checkpoint (no second model — Slice-and-Scale
already keeps the cheap rung resident) and verifies all of them in one
multi-query step at the anchor rung, rewinding whatever the anchor
disagrees with (cursor + page rollback). The demo runs the same burst
plain and speculative and prints the acceptance rate, the decode-tick
cut, and the fact that matters: the token streams are bit-identical.

The final section demonstrates the failure model (docs/serving_internals.md
§7): a deterministic FaultInjector makes the lowest rung produce NaN
logits at runtime, and the engine's logit guard escalates the live batch
one rung toward the anchor, replays the tick, quarantines the bad rung,
and completes every request — degradation costs precision, never streams.
"""
import argparse
import sys

import jax
import numpy as np

sys.path.insert(0, "src")

from repro.configs import get_reduced  # noqa: E402
from repro.core import get_format, make_anchor  # noqa: E402
from repro.core.qat import QATConfig  # noqa: E402
from repro.models import get_model  # noqa: E402
from repro.runtime.fault import FaultInjector  # noqa: E402
from repro.serve.engine import ElasticEngine, Request  # noqa: E402
from repro.serve.policy import FormatPolicy  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked admission: tokens per prefill chunk "
                         "(multiple of the 8-token page size); default "
                         "monolithic")
    ap.add_argument("--attn-impl", default=None,
                    choices=("gather", "paged_kernel"),
                    help="paged decode-attention read path (default: "
                         "kernel on TPU, gather elsewhere)")
    ap.add_argument("--scheduler", default=None,
                    choices=("sequential", "mixed"),
                    help="chunked-tick scheduler: 'mixed' (default with "
                         "--prefill-chunk) coalesces the chunk into the "
                         "decode batch — one executable per tick")
    ap.add_argument("--speculative", action="store_true",
                    help="demo self-speculative decoding: draft k=4 "
                         "tokens/tick at mxint4, verify at the anchor, "
                         "compare streams + ticks against plain decode")
    ap.add_argument("--slo", action="store_true",
                    help="demo SLO-tiered serving: tiered admission + "
                         "cost-model format picks on a bursty two-tenant "
                         "trace (docs/serving_internals.md §10)")
    ap.add_argument("--hbm-bytes-per-s", type=float, default=None,
                    help="HBM bandwidth the --slo cost model's prior "
                         "divides by (default: the device's published "
                         "peak; a CPU host has none, so pass one there)")
    args = ap.parse_args()

    cfg = get_reduced(args.arch)
    api = get_model(cfg, None)
    params = api.init_params(jax.random.PRNGKey(0))
    qat = QATConfig(formats=("mxint4", "mxint8"), anchor="mxint8",
                    block_size=32)
    anchor = make_anchor(params, qat, get_format("mxint8", 32))

    policy = FormatPolicy(anchor="mxint8",
                          ladder=((12, "mxint4"), (6, "mxint6"),
                                  (0, "mxint8")),
                          hysteresis=1)
    chunked = args.prefill_chunk is not None
    eng = ElasticEngine(api, anchor, batch_slots=4, max_len=64,
                        policy=policy, param_template=params,
                        kv_layout="paged", kv_page_size=8,
                        attn_impl=args.attn_impl,
                        prefill_chunk=args.prefill_chunk,
                        scheduler=args.scheduler,
                        kv_num_pages=4 * (7 if chunked else 3) + 1)
    #   pool is live-token sized, not slots*max_len — pages recycle across
    #   the burst (the chunked demo's long prompts need more live pages)

    rng = np.random.default_rng(0)

    if chunked:
        print(f"CHUNKED ADMISSION: short requests admit first and keep "
              f"decoding while a 41-token prompt trickles in "
              f"{args.prefill_chunk}-token chunks behind them")
        reqs = [Request(rid=200 + i, prompt=rng.integers(0, cfg.vocab, 8)
                        .astype(np.int32), max_new=10) for i in range(3)] + \
               [Request(rid=203, prompt=rng.integers(0, cfg.vocab, 41)
                        .astype(np.int32), max_new=4)]
        eng.generate(reqs)
        tt = eng.tick_trace
        print(f"  {len(tt)} scheduler ticks, max prefill tokens in any "
              f"tick: {max(t['prefill_tokens'] for t in tt)} "
              f"(chunk={args.prefill_chunk}; monolithic admission would "
              "run all 63 — the capped length bucket — in one tick)")
        stalled = sum(1 for t in tt if t["decode"] and t["prefill_tokens"])
        print(f"  {stalled} ticks interleaved a prefill chunk with the "
              "running slots' decode step")
        print(f"  scheduler={eng.scheduler}: max executables in any tick = "
              f"{max(t['execs'] for t in tt)} (mixed coalesces chunk + "
              "decode into one mixed_step; sequential runs two)")
        for r in reqs:
            print(f"  req {r.rid}: plen={r.prompt.size} ttft={r.ttft_s:.3f}s"
                  f" n_out={len(r.out_tokens)}")
        print()
    if args.speculative:
        from repro.serve.policy import SpecConfig
        print("SPECULATIVE DECODE: draft k=4 at mxint4, verify at the "
              "anchor in one multi-query step, rewind what it rejects "
              "(docs/serving_internals.md §9)")
        #   +4 draft-ahead tokens per slot past max_new — the verify
        #   frontier runs k positions past the committed length
        spec_pages = 4 * -(-(8 + 10 + 4) // 8) + 1
        runs = {}
        for label, sc in (("plain", None),
                          ("spec", SpecConfig(draft_fmt="mxint4", k=4))):
            e = ElasticEngine(api, anchor, batch_slots=4, max_len=64,
                              param_template=params, kv_layout="paged",
                              kv_page_size=8, kv_num_pages=spec_pages,
                              attn_impl=args.attn_impl, speculative=sc)
            rs = [Request(rid=400 + i,
                          prompt=np.random.default_rng(5)
                          .integers(0, cfg.vocab, (8, 8))[i % 2]
                          .astype(np.int32), max_new=10)
                  for i in range(6)]
            e.generate(rs, greedy=True, fmt_override="mxint8")
            runs[label] = (e, [list(r.out_tokens) for r in rs])
        (ep, sp), (es, ss) = runs["plain"], runs["spec"]
        ssst = es.stats
        print(f"  streams bit-identical to plain anchor decode: {sp == ss}")
        print(f"  decode ticks {ep.stats['ticks']} -> {ssst['ticks']} "
              f"({ssst['spec_ticks']} spec ticks, acceptance rate "
              f"{ssst['spec_acceptance_rate']:.2f}, "
              f"{ssst['spec_accepted']} drafts accepted / "
              f"{ssst['spec_rejected']} rewound)")
        print(f"  pages {ssst['kv_pages_alloc']} alloc / "
              f"{ssst['kv_pages_freed']} freed — rollback returns "
              "draft-ahead pages exactly")
        print()

    if args.slo:
        from repro.serve.slo import CostModel, SLOClass
        print("SLO TIERS: a latency-tier trickle shares the engine with a "
              "best-effort burst arriving at tick 2; admission_order='slo' "
              "serves the latency tenant first and the policy picks the "
              "widest rung whose measured cost fits its TPOT budget "
              "(docs/serving_internals.md §10)")
        pol = FormatPolicy(anchor="mxint8",
                           ladder=((12, "mxint4"), (0, "mxint8")),
                           hysteresis=1,
                           cost=CostModel.from_roofline(
                               cfg, ("mxint4", "mxint8"), max_len=64,
                               kv_layout="paged", kv_page_size=8,
                               hbm_bytes_per_s=args.hbm_bytes_per_s))
        slo_eng = ElasticEngine(api, anchor, batch_slots=2, max_len=64,
                                policy=pol, param_template=params,
                                kv_layout="paged", kv_page_size=8,
                                kv_num_pages=17,
                                admission_order="slo")
        reqs = [Request(rid=500 + i, prompt=rng.integers(0, cfg.vocab, 8)
                        .astype(np.int32), max_new=6, tenant="burst",
                        arrival_tick=2) for i in range(4)] + \
               [Request(rid=504, prompt=rng.integers(0, cfg.vocab, 8)
                        .astype(np.int32), max_new=6, tenant="vip",
                        arrival_tick=2,
                        slo=SLOClass.latency(ttft_ms=1e4, tpot_ms=1e4))]
        slo_eng.generate(reqs)
        for r in sorted(reqs, key=lambda r: (r.admitted_tick, r.rid)):
            tier = r.slo.tier if r.slo else "best_effort"
            print(f"  req {r.rid} [{r.tenant}/{tier}]: arrived t="
                  f"{r.arrival_tick} admitted t={r.admitted_tick} "
                  f"fmt={r.fmt_used} n_out={len(r.out_tokens)}")
        terms = slo_eng.stats["cost_model"]
        for fmt, t in sorted(terms.items()):
            print(f"  cost[{fmt}]: predict_1row="
                  f"{t['predict_1row_ms']:.2f}ms after "
                  f"{t['ticks_observed']} clean decode ticks "
                  f"(factor {t['factor']:.0f}x roofline on this backend)")
        print()

    print("LOW LOAD: 3 requests")
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 8)
                    .astype(np.int32), max_new=6) for i in range(3)]
    eng.generate(reqs)
    for r in reqs:
        print(f"  req {r.rid}: fmt={r.fmt_used} tokens={r.out_tokens}")

    print("\nSTAGGERED: lengths differ, slots retire and refill "
          "independently")
    reqs = [Request(rid=50 + i, prompt=rng.integers(0, cfg.vocab, 8)
                    .astype(np.int32), max_new=3 + 2 * i) for i in range(6)]
    eng.generate(reqs)
    for r in reqs:
        print(f"  req {r.rid}: fmt={r.fmt_used} n_out={len(r.out_tokens)}")

    print("\nBURST: 20 requests")
    reqs = [Request(rid=100 + i, prompt=rng.integers(0, cfg.vocab, 8)
                    .astype(np.int32), max_new=6) for i in range(20)]
    eng.generate(reqs)
    fmts = sorted({r.fmt_used for r in reqs})
    print(f"  formats used across the burst: {fmts}")

    print("\nDEGRADATION LADDER: mxint4 turns out numerically bad at "
          "runtime (injected NaN logits, fmt-scoped) — the guard escalates "
          "the live batch one rung toward the anchor, replays the tick, "
          "and quarantines the bad rung; survivors keep streaming")
    fi = FaultInjector(poison_logits={2: None}, poison_fmt="mxint4")
    chaos = ElasticEngine(api, anchor, batch_slots=4, max_len=64,
                          policy=FormatPolicy(
                              anchor="mxint8",
                              ladder=((12, "mxint4"), (6, "mxint6"),
                                      (0, "mxint8")), hysteresis=1),
                          param_template=params, kv_layout="paged",
                          kv_page_size=8, kv_num_pages=13,
                          fault_injector=fi)
    reqs = [Request(rid=300 + i, prompt=rng.integers(0, cfg.vocab, 8)
                    .astype(np.int32), max_new=6) for i in range(4)]
    chaos.generate(reqs, fmt_override="mxint4")
    cs = chaos.stats
    for ev in cs["escalation_events"]:
        print(f"  tick {ev['tick']}: {ev['from']} -> {ev['to']} "
              f"(quarantined: {sorted(chaos.policy.quarantined)})")
    print(f"  faults detected={cs['faults_detected']} "
          f"ticks replayed={cs['ticks_replayed']} "
          f"statuses={cs['request_statuses']} "
          f"pages {cs['kv_pages_alloc']} alloc / "
          f"{cs['kv_pages_freed']} freed")
    for r in reqs:
        print(f"  req {r.rid}: fmt={r.fmt_used} status={r.status.value} "
              f"n_out={len(r.out_tokens)}")

    st = eng.stats
    contract = "fused Pallas dequant-GEMM" if st["fused"] \
        else "XLA densify-inside-jit"
    print(f"\nengine stats: ticks={st['ticks']} tokens={st['tokens_out']} "
          f"swaps={st['fmt_swaps']} prefill_compiles={st['prefill_traces']} "
          f"contract={contract}")
    for fmt in st["formats_cached"]:
        print(f"  {fmt:>7}: containers={st['containers'][fmt]} "
              f"weight_bytes={st['weight_bytes'][fmt]}")
    print(f"kv cache: layout={st['kv_layout']} "
          f"bytes/slot={st['kv_bytes_per_slot']} "
          f"(pool={st['kv_total_pages']} pages x {st['kv_page_size']} tok, "
          f"high-water {st['kv_pages_hwm']}, "
          f"{st['kv_pages_alloc']} allocs / {st['kv_pages_freed']} frees "
          "-> pages recycled across the burst)")
    print(f"decode attention: impl={st['attn_impl']} "
          f"read {st['attn_read_bytes']} KV bytes total "
          f"({st['attn_tokens_read']} token-positions; the gather path "
          "spans the full logical view every tick, the paged kernel only "
          "the live pages)")
    print("one anchor checkpoint served "
          f"{len(st['formats_cached'])} precisions; each decode tick streams "
          "the PACKED bytes above, not dense bf16.")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
