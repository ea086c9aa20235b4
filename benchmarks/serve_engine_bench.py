"""Engine-level serving benchmark: fused-kernel vs densify-inside-jit,
dense vs paged KV, monolithic vs chunked prefill admission, and gather vs
gather-free paged decode attention.

Runs the packed-weight continuous-batching ElasticEngine at dense bf16,
mxint8 (MXTensor codes) and mxint4 (split-N nibble-packed) under BOTH
packed-serving contracts — the Pallas dequant-GEMM dispatch (``fused``) and
the XLA densify-inside-jit fallback (``densify``) — and reports one table:

  - tokens_per_tick: generated tokens / decode ticks (continuous batching
    keeps slots full, so this approaches batch_slots under load)
  - weight_bytes_per_token: the roofline weight-read term — bytes one decode
    tick must stream for the weight pytree, divided by tokens/tick. This is
    the quantity the paper's §3.5 claim is about: packed mxint8/mxint4 cut it
    ~2x/~4x vs dense bf16 (exact ratio depends on the raw-leaf fraction).
    Identical across paths by construction (same packed tree) — the fused
    rows demonstrate the bytes contract is served by the explicit kernels,
    not just hoped for from XLA fusion.
  - kv_bytes_per_slot: resident KV-cache HBM divided by batch slots. The
    dense layout commits max_len tokens per slot up front; the paged layout
    (kv_layout="paged") commits only the page pool, which this bench sizes
    to the workload's live-token demand — the measured (not asserted) memory
    win of block-table paging. Token streams are bit-identical across
    layouts, so the kv rows differ ONLY in this column and wall time.
  - attn_bytes_per_token: decode-attention KV reads per generated token
    (per-layer K+V bytes actually spanned, from the engine's host-side
    accounting). The paged rows run BOTH attn impls: ``gather``
    materializes every slot's full logical view (max_pages×page_size
    tokens) each tick, the gather-free kernel (``paged_kernel``,
    kernels/paged_attention.py) reads only ``ceil(cache_len/page)`` pages
    per slot — the measured roofline win of block-table attention. Token
    streams are bit-identical across impls; the bench verifies that like
    the cross-admission check.
  - ttft_p50_ms / ttft_p99_ms / stall_p99_ms / max_pf_tok: the admission
    latency columns. The workload mixes short prompts with long ones
    (every ``--long-every``-th request is ``--long-len`` tokens), and the
    engine's per-tick trace records how much prefill work shared a tick
    with decoding. Monolithic admission stalls every running slot for a
    whole prompt (max_pf_tok ~ the long bucket; stall_p99 ~ a full
    prefill); chunked admission (``prefill_chunk``) bounds per-tick prefill
    work to one chunk, so the decode-stall tail collapses while token
    streams stay BIT-IDENTICAL — the bench verifies that identity and
    prints it.
  - decode_occupancy / tick_exec / adm_decode_tpt: the unified-tick
    columns, from the engine's per-tick ``rows`` / ``decode_rows`` /
    ``execs`` counters. ``decode_occupancy`` is the fraction of dispatched
    batch rows that were live decoders; ``tick_exec`` the mean executables
    per work tick — 1.0 under ``scheduler="mixed"`` (the chunk rides the
    decode batch), up to 2.0 under ``"sequential"`` (chunk then decode);
    ``adm_decode_tpt`` the decode tokens per tick over ticks that carried
    prefill work — the "decode does not starve during a long admission"
    number, comparable against the monolithic baseline rows. The chunked
    rows run BOTH schedulers and the bench verifies their token streams
    are bit-identical, same as the cross-admission check.

CPU wall-clock is reported for completeness but is NOT the serving claim —
off-TPU the fused path runs the Pallas interpreter (slow, correctness-only)
and the dequant is not the bottleneck; the bytes column is the modeled
HBM-bound behavior the TPU kernels realize. The *relative* stall/TTFT tail
between admission modes, however, is a scheduling property and survives the
interpreter overhead.
"""
import argparse
import dataclasses
import sys
import time

sys.path.insert(0, "src")

import jax               # noqa: E402
import numpy as np       # noqa: E402

from repro.configs import get_reduced                  # noqa: E402
from repro.core import get_format, make_anchor         # noqa: E402
from repro.core.qat import QATConfig                   # noqa: E402
from repro.models import get_model                     # noqa: E402
from repro.serve.engine import ElasticEngine, Request  # noqa: E402

FORMATS = ("bf16", "mxint8", "mxint4")
PROMPT_LEN = 8
WARMUP = 2               # first short + first long request: compiles every
#                          prefill bucket / chunk executable before timing


def _pct(xs, q):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * len(xs)))] if xs else 0.0


def bench_path(api, anchor, params, fmt, fused, *, slots, max_len,
               n_requests, max_new, vocab, kv_layout="dense", page_size=8,
               admission="monolithic", prefill_chunk=8, long_every=3,
               long_len=40, attn_impl="gather", scheduler="sequential"):
    kv_kw = {}
    if kv_layout == "paged":
        # Size the pool to the workload's live-token demand (longest prompt
        # + generated tokens per slot), NOT to slots*max_len — that sizing
        # freedom is the whole point of paging.
        per_slot = -(-(long_len + max_new) // page_size)
        kv_kw = dict(kv_layout="paged", kv_page_size=page_size,
                     kv_num_pages=slots * per_slot + 1,
                     attn_impl=attn_impl)
    eng = ElasticEngine(
        api, anchor, batch_slots=slots, max_len=max_len,
        param_template=params, fused=fused,
        prefill_chunk=prefill_chunk if admission == "chunked" else None,
        scheduler=scheduler if admission == "chunked" else None,
        **kv_kw)
    rng = np.random.default_rng(0)
    # every long_every-th request is long (long_every=1 => all long); the
    # offset keeps one long prompt inside the warmup window so its bucket /
    # chunk executables compile before timing starts
    is_long = lambda i: i % long_every == 1 % long_every
    reqs = [Request(rid=i,
                    prompt=rng.integers(
                        0, vocab,
                        long_len if is_long(i) else PROMPT_LEN)
                    .astype(np.int32),
                    max_new=max_new) for i in range(n_requests)]
    eng.generate(reqs[:WARMUP], fmt_override=fmt)  # warmup: compile + SS
    t0 = time.perf_counter()
    ticks0, toks0 = eng.stats["ticks"], eng.stats["tokens_out"]
    attn0 = eng.stats["attn_read_bytes"]
    eng.generate(reqs[WARMUP:], fmt_override=fmt)
    dt = time.perf_counter() - t0
    st = eng.stats
    ticks = st["ticks"] - ticks0
    # decode tokens only: each admission also samples one token from its
    # prefill logits, which costs no decode tick — excluding them keeps
    # tokens/tick <= batch_slots and bytes/token an honest roofline term
    toks = st["tokens_out"] - toks0 - (len(reqs) - WARMUP)
    wbytes = st["weight_bytes"][fmt]
    tpt = toks / max(ticks, 1)
    ttfts = [r.ttft_s for r in reqs[WARMUP:]]
    stalls = [t["wall_s"] for t in eng.tick_trace if t["decode"]]
    work = [t for t in eng.tick_trace if t["rows"] > 0]
    adm = [t for t in eng.tick_trace if t["prefill_tokens"] > 0]
    return {
        "fmt": fmt,
        "path": ("fused" if fused else "densify") if fmt != "bf16"
                else "dense",
        "kv": kv_layout,
        "attn": st["attn_impl"],
        "attn_bytes_per_token": (st["attn_read_bytes"] - attn0)
        / max(toks, 1),
        "admission": admission,
        "scheduler": eng.scheduler,
        "decode_occupancy": sum(t["decode_rows"] for t in work)
        / max(sum(t["rows"] for t in work), 1),
        "tick_exec": sum(t["execs"] for t in work) / max(len(work), 1),
        "adm_decode_tpt": sum(t["decode_rows"] for t in adm)
        / max(len(adm), 1),
        "adm_decode_tps": sum(t["decode_rows"] for t in adm)
        / max(sum(t["wall_s"] for t in adm), 1e-9),
        "containers": "+".join(st["containers"][fmt]),
        "weight_bytes": wbytes,
        "ticks": ticks,
        "tokens": toks,
        "tokens_per_tick": tpt,
        "weight_bytes_per_token": wbytes / max(tpt, 1e-9),
        "kv_bytes_per_slot": st["kv_bytes_per_slot"],
        "ttft_p50_ms": _pct(ttfts, 0.50) * 1e3,
        "ttft_p99_ms": _pct(ttfts, 0.99) * 1e3,
        "stall_p99_ms": _pct(stalls, 0.99) * 1e3,
        "max_pf_tok": max((t["prefill_tokens"] for t in eng.tick_trace),
                          default=0),
        "wall_s": dt,
        "streams": [list(r.out_tokens) for r in reqs],
    }


def bench_chaos(api, anchor, params, *, slots, max_len, n_requests,
                max_new, vocab, rates, seed=0):
    """The --chaos sweep (docs/serving_internals.md §7): one row per fault
    rate, all at the ANCHOR rung so every injected fault is either
    recovered by a same-format replay (transient crash), absorbed by the
    capacity path (alloc failure -> requeue), or confined to one request
    (row poison -> FAILED_NUMERIC). Two hard gates, both process-failing:

      - stream identity: every request that COMPLETED under chaos carries a
        token stream bit-identical to the fault-free (rate 0) run;
      - page accounting: kv_pages_alloc == kv_pages_freed at drain — chaos
        must not leak the free list.

    A final "ladder" demo row starts at mxint4 with a format-following
    poison and reports the escalation walk instead of the identity gate
    (its streams are the escalated rung's, deliberately different)."""
    from repro.runtime.fault import FaultInjector, random_plan
    from repro.serve.engine import RequestStatus
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, vocab, PROMPT_LEN).astype(np.int32)
               for _ in range(n_requests)]

    def run(fi, fmt):
        eng = ElasticEngine(api, anchor, batch_slots=slots, max_len=max_len,
                            param_template=params, kv_layout="paged",
                            kv_page_size=8, fault_injector=fi)
        reqs = [Request(rid=i, prompt=prompts[i].copy(), max_new=max_new)
                for i in range(n_requests)]
        t0 = time.perf_counter()
        eng.generate(reqs, fmt_override=fmt)
        dt = time.perf_counter() - t0
        st = eng.stats
        if st["kv_pages_alloc"] != st["kv_pages_freed"]:
            raise SystemExit(
                f"chaos leaked KV pages: {st['kv_pages_alloc']} allocated, "
                f"{st['kv_pages_freed']} freed")
        if not all(r.done and r.status.terminal for r in reqs):
            raise SystemExit("chaos left a request without a terminal "
                             "status")
        return eng, reqs, st, dt

    print("chaos,fault_rate,injected,recovered_ticks,escalations,"
          "completed,failed_numeric,failed_capacity,timed_out,cancelled,"
          "requeues,tokens,wall_s")

    def emit(label, rate, fi, eng, reqs, st, dt):
        counts = st["request_statuses"]
        print(f"{label},{rate},{len(fi.events) if fi else 0},"
              f"{st['ticks_replayed']},{st['fmt_escalations']},"
              f"{counts.get('completed', 0)},"
              f"{counts.get('failed_numeric', 0)},"
              f"{counts.get('failed_capacity', 0)},"
              f"{counts.get('timed_out', 0)},{counts.get('cancelled', 0)},"
              f"{st['admission_requeues']},"
              f"{sum(len(r.out_tokens) for r in reqs)},{dt:.2f}")

    base_streams = None
    for rate in rates:
        fi = random_plan(seed, rate, horizon=64, slots=slots) \
            if rate > 0 else None
        eng, reqs, st, dt = run(fi, "mxint8")
        emit("sweep", rate, fi, eng, reqs, st, dt)
        streams = {r.rid: list(r.out_tokens) for r in reqs
                   if r.status is RequestStatus.COMPLETED}
        if rate == 0:
            base_streams = streams
        elif base_streams is not None:
            diverged = [rid for rid, s in streams.items()
                        if base_streams.get(rid) != s]
            if diverged:
                raise SystemExit(
                    f"chaos rate {rate}: surviving streams diverged from "
                    f"the fault-free run for rids {diverged} — fault "
                    "isolation broke bit-identity")
    if base_streams is not None and len(rates) > 1:
        print("# chaos survivors bit-identical to the fault-free run "
              "across all rates = True")

    # Degradation-ladder demo: a rung that fails at runtime walks toward
    # the anchor and the wave still completes.
    fi = FaultInjector(poison_logits={2: None}, poison_fmt="mxint4")
    eng, reqs, st, dt = run(fi, "mxint4")
    emit("ladder", "-", fi, eng, reqs, st, dt)
    ev = st["escalation_events"]
    print(f"# ladder: {' -> '.join([ev[0]['from']] + [e['to'] for e in ev])}"
          f" (quarantined: {','.join(st['quarantined_formats'])}); "
          f"completed {st['request_statuses'].get('completed', 0)}"
          f"/{n_requests}")


def bench_speculative(api, anchor, params, *, slots, max_len, n_requests,
                      max_new, vocab, draft_fmt="mxint4", k=4, page_size=8,
                      long_every=3, long_len=40):
    """The --speculative sweep (docs/serving_internals.md §9): plain anchor
    decode vs self-speculative decode (draft at ``draft_fmt``, verify at the
    pinned anchor rung) over both packed contracts x both paged attention
    impls. Two outputs:

      - an acceptance column set: spec_ticks, acceptance_rate,
        accepted_tok_per_tick — the measured usefulness of the cheap rung's
        guesses on this workload;
      - a HARD stream-identity gate (process-failing): every request's
        token stream under speculation must be bit-identical to plain
        anchor decode — speculation is a pure speed knob, never a token
        knob. A second gate requires a decode-tick win (fewer verify ticks
        than plain ticks for the same tokens): if drafting ever stops
        paying for itself on this deterministic workload, the bench fails
        rather than shipping a regression silently.
    """
    from repro.serve.policy import SpecConfig
    rng = np.random.default_rng(0)
    is_long = lambda i: i % long_every == 1 % long_every
    prompts = [rng.integers(0, vocab,
                            long_len if is_long(i) else PROMPT_LEN)
               .astype(np.int32) for i in range(n_requests)]
    # draft-ahead headroom: the verify frontier runs k tokens past the
    # committed length, so size the pool for it
    per_slot = -(-(long_len + max_new + k) // page_size)

    def run(spec, fused, attn):
        eng = ElasticEngine(
            api, anchor, batch_slots=slots, max_len=max_len,
            param_template=params, fused=fused, kv_layout="paged",
            kv_page_size=page_size, kv_num_pages=slots * per_slot + 1,
            attn_impl=attn, speculative=spec)
        reqs = [Request(rid=i, prompt=prompts[i].copy(), max_new=max_new)
                for i in range(n_requests)]
        eng.generate(reqs[:WARMUP], fmt_override="mxint8")
        t0 = time.perf_counter()
        ticks0 = eng.stats["ticks"]
        eng.generate(reqs[WARMUP:], fmt_override="mxint8")
        dt = time.perf_counter() - t0
        st = eng.stats
        if st["kv_pages_alloc"] != st["kv_pages_freed"]:
            raise SystemExit(
                f"speculative run leaked KV pages: {st['kv_pages_alloc']} "
                f"allocated, {st['kv_pages_freed']} freed")
        return (st["ticks"] - ticks0, st,
                [list(r.out_tokens) for r in reqs], dt)

    print("spec,path,attn,draft,k,ticks_plain,ticks_spec,spec_ticks,"
          "acceptance_rate,accepted_tok_per_tick,tok_per_tick_plain,"
          "tok_per_tick_spec,wall_plain_s,wall_spec_s")
    wins = []
    for fused in (False, True):
        for attn in ("gather", "paged_kernel"):
            ticks_p, _, streams_p, dt_p = run(None, fused, attn)
            sc = SpecConfig(draft_fmt=draft_fmt, k=k)
            ticks_s, st, streams_s, dt_s = run(sc, fused, attn)
            if streams_s != streams_p:
                raise SystemExit(
                    f"speculative streams diverged from plain anchor "
                    f"decode (fused={fused}, attn={attn}) — the draft/"
                    f"verify/rollback loop broke bit-identity")
            toks = sum(len(s) for s in streams_s[WARMUP:]) \
                - (n_requests - WARMUP)
            rate = st["spec_acceptance_rate"]
            acc_pt = st["spec_accepted"] / max(st["spec_ticks"], 1)
            path = "fused" if fused else "densify"
            print(f"spec,{path},{attn},{draft_fmt},{k},{ticks_p},{ticks_s},"
                  f"{st['spec_ticks']},"
                  f"{-1.0 if rate is None else rate:.2f},{acc_pt:.2f},"
                  f"{toks / max(ticks_p, 1):.2f},{toks / max(ticks_s, 1):.2f},"
                  f"{dt_p:.2f},{dt_s:.2f}")
            wins.append((ticks_p, ticks_s))
    print(f"# speculative vs plain: token streams identical across all "
          f"configs = True; decode ticks "
          f"{sum(p for p, _ in wins)} -> {sum(s for _, s in wins)} "
          f"({sum(p for p, _ in wins) / max(sum(s for _, s in wins), 1):.2f}x"
          f" cut at draft={draft_fmt}, k={k})")
    if not all(s < p for p, s in wins):
        raise SystemExit("speculation won no decode ticks — drafting is "
                         "not paying for itself on this workload")


def bench_mesh(api, anchor, params, *, mesh_spec, slots, max_len,
               n_requests, max_new, vocab, page_size=8, long_every=3,
               long_len=40):
    """The --mesh sweep (docs/serving_internals.md §11): the single-device
    engine vs the tensor-parallel engine on a (data, model) mesh, SAME
    workload, across {dense, paged} x every format. Two outputs:

      - a HARD stream-identity gate (process-failing): greedy and seeded
        token streams on the mesh must be bit-identical to the
        single-device engine — sharding is a placement knob, never a
        token knob;
      - the per-chip weight stream: each chip reads only its shard, so
        weight_bytes_per_chip must land near 1/n_model of the global
        bytes (replicated norm vectors keep it just above exact).

    On CPU run under XLA_FLAGS=--xla_force_host_platform_device_count=N
    to expose enough host devices.
    """
    from repro.launch.mesh import make_debug_mesh, parse_mesh
    n_data, n_model = parse_mesh(mesh_spec)
    need = n_data * n_model
    if len(jax.devices()) < need:
        raise SystemExit(
            f"--mesh {mesh_spec} needs {need} devices; only "
            f"{len(jax.devices())} visible — on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need}")
    mesh = make_debug_mesh(n_data, n_model)
    rng = np.random.default_rng(0)
    is_long = lambda i: i % long_every == 1 % long_every
    prompts = [rng.integers(0, vocab,
                            long_len if is_long(i) else PROMPT_LEN)
               .astype(np.int32) for i in range(n_requests)]
    per_slot = -(-(long_len + max_new) // page_size)

    def run(m, fmt, kv, greedy):
        kv_kw = dict(kv_layout="paged", kv_page_size=page_size,
                     kv_num_pages=slots * per_slot + 1) \
            if kv == "paged" else {}
        eng = ElasticEngine(api, anchor, batch_slots=slots,
                            max_len=max_len, param_template=params,
                            fused=False, seed=0, mesh=m, temperature=0.9,
                            top_p=0.95, **kv_kw)
        reqs = [Request(rid=i, prompt=prompts[i].copy(), max_new=max_new)
                for i in range(n_requests)]
        eng.generate(reqs[:WARMUP], fmt_override=fmt, greedy=greedy)
        t0 = time.perf_counter()
        ticks0 = eng.stats["ticks"]
        eng.generate(reqs[WARMUP:], fmt_override=fmt, greedy=greedy)
        dt = time.perf_counter() - t0
        st = eng.stats
        if st["kv_pages_alloc"] != st["kv_pages_freed"]:
            raise SystemExit(
                f"--mesh leaked KV pages: {st['kv_pages_alloc']} "
                f"allocated, {st['kv_pages_freed']} freed")
        return ([list(r.out_tokens) for r in reqs], st,
                st["ticks"] - ticks0, dt)

    print(f"# mesh {n_data}x{n_model} vs single device, "
          f"{n_requests} requests, slots={slots}")
    print("mesh,fmt,kv,sampling,weight_bytes,weight_bytes_per_chip,"
          "chip_ratio,ticks_single,ticks_mesh,wall_single_s,wall_mesh_s")
    checked = 0
    for kv in ("dense", "paged"):
        for fmt in FORMATS:
            for greedy in (True, False):
                s1, _, t1, w1 = run(None, fmt, kv, greedy)
                s2, st, t2, w2 = run(mesh, fmt, kv, greedy)
                if s1 != s2:
                    raise SystemExit(
                        f"--mesh streams diverged from the single-device "
                        f"engine (fmt={fmt}, kv={kv}, greedy={greedy}) — "
                        f"sharding broke bit-identity")
                checked += 1
                wb = st["weight_bytes"][fmt]
                wbc = st["weight_bytes_per_chip"][fmt]
                print(f"{st['mesh']},{fmt},{kv},"
                      f"{'greedy' if greedy else 'seeded'},{wb},{wbc},"
                      f"{wbc / wb:.3f},{t1},{t2},{w1:.2f},{w2:.2f}")
    print(f"# mesh vs single device: token streams identical across "
          f"{checked} configs = True")
    ratios = []
    for fmt in FORMATS:
        # per-chip stream must approach 1/n_model (norms stay replicated)
        eng = ElasticEngine(api, anchor, batch_slots=slots,
                            max_len=max_len, param_template=params,
                            fused=False, mesh=mesh)
        st_w = eng.weights_for(fmt)  # noqa: F841 — populates stats
        st = eng.stats
        ratios.append(st["weight_bytes_per_chip"][fmt]
                      / st["weight_bytes"][fmt])
    lo, hi = 1.0 / n_model, 1.0 / n_model + 0.06
    if not all(lo <= r < hi for r in ratios):
        raise SystemExit(
            f"per-chip weight stream ratios {ratios} outside "
            f"[{lo:.3f}, {hi:.3f}) — the packed leaves are not sharded")
    print(f"# per-chip weight stream: {ratios[0]:.3f}/"
          f"{ratios[1]:.3f}/{ratios[2]:.3f} of global bytes "
          f"(bf16/mxint8/mxint4) at n_model={n_model} = gate passed")


def bench_slo(api, anchor, params, *, slots, max_len, horizon, wl_seed,
              page_size=8, burst_thresh=6, hbm_bytes_per_s=None):
    """The --slo sweep (docs/serving_internals.md §10): SLO-tiered serving
    from the measured cost model vs the static queue-depth policy, on the
    SAME deterministic bursty multi-tenant workload.

    Run A (static): FIFO admission, threshold-table policy — the pre-SLO
    engine. It doubles as the calibration run: the per-tier TTFT budgets
    are set from ITS measured percentiles, so the attainment gates are
    machine-speed-independent. Run B (slo): tiered admission
    (latency > throughput > best-effort), roofline-seeded + online-
    calibrated CostModel driving the rung pick against the wave's tightest
    TPOT budget.

    Hard gates (process-failing):
      - page accounting: kv_pages_alloc == kv_pages_freed in both runs;
      - per-tier stream identity: every COMPLETED run-B request's stream
        is bit-identical to a plain non-SLO engine serving the same
        (rid, prompt) at run B's chosen format — SLO machinery moves
        requests and formats, never tokens;
      - tier ordering: run B's latency-tier TTFT attainment >= its
        throughput-tier's (same budget, so this isolates admission order);
      - the win: run B's latency-tier mean queue wait (ticks, arrival ->
        admission — deterministic) <= run A's, at equal-or-better
        aggregate decode ticks (B <= 1.05x A for the same token count).
    """
    from repro.serve.policy import FormatPolicy
    from repro.serve.slo import CostModel
    from repro.serve.engine import RequestStatus
    from workloads import (TenantSpec, default_tenants, generate_workload,
                           tenant_summary)

    cfg = api.cfg
    ladder = ((burst_thresh, "mxint4"), (0, "mxint8"))
    eng_kw = dict(batch_slots=slots, max_len=max_len, param_template=params,
                  fused=False, kv_layout="paged", kv_page_size=page_size,
                  prefill_chunk="auto")

    def make_workload(ttft_ms=None, tpot_ms=None):
        tenants = default_tenants(ttft_ms=ttft_ms, tpot_ms=tpot_ms)
        if ttft_ms is not None:
            # Same TTFT budget on the throughput tenant: the attainment
            # gap between tiers then measures admission order alone.
            tenants = [dataclasses.replace(t, ttft_ms=ttft_ms)
                       if t.tier == "throughput" else t for t in tenants]
        return tenants, generate_workload(
            tenants, horizon=horizon, vocab=cfg.vocab,
            prompt_cap=max_len - 1, seed=wl_seed)

    def run(reqs, policy, order):
        eng = ElasticEngine(api, anchor, policy=policy,
                            admission_order=order, **eng_kw)
        # Warm every ladder rung's executables (full + partial chunk,
        # decode) before the timed wave: TTFT budgets must measure
        # scheduling, not jit compiles — and the warmup's clean decode
        # ticks hand the cost model measured factors for BOTH rungs, so
        # run B's picks are cost-driven from its first wave.
        wrng = np.random.default_rng(2**20)
        for nf, wfmt in enumerate(dict.fromkeys(f for _, f in ladder)):
            eng.generate(
                [Request(rid=10_000 + 10 * nf + j,
                         prompt=wrng.integers(1, cfg.vocab, size=pl)
                         .astype(np.int32), max_new=3)
                 for j, pl in enumerate((8, 13))],
                fmt_override=wfmt)
        t0 = time.perf_counter()
        eng.generate(reqs)
        dt = time.perf_counter() - t0
        st = eng.stats
        if st["kv_pages_alloc"] != st["kv_pages_freed"]:
            raise SystemExit(
                f"--slo ({order}) leaked KV pages: "
                f"{st['kv_pages_alloc']} allocated, "
                f"{st['kv_pages_freed']} freed")
        return eng, st, dt

    def ttft_from_arrival_ms(r):
        if r.ttft_s is None or r.arrival_s is None:
            return None
        return (r.ttft_s - r.arrival_s) * 1e3

    def tier_of(r):
        return r.slo.tier if r.slo is not None else "best_effort"

    def tier_rows(reqs, ttft_budget_ms):
        rows = {}
        for tier in ("latency", "throughput", "best_effort"):
            sub = [r for r in reqs if tier_of(r) == tier]
            if not sub:
                continue
            ttfts = [t for t in map(ttft_from_arrival_ms, sub)
                     if t is not None]
            waits = [r.admitted_tick - r.arrival_tick for r in sub
                     if r.admitted_tick is not None]
            # Both runs score against the SAME calibrated budget (run A's
            # requests carry no SLOClass budgets — they predate the SLO)
            budget = ttft_budget_ms if tier != "best_effort" else None
            attain = None
            if budget is not None and ttfts:
                attain = sum(t <= budget for t in ttfts) / len(ttfts)
            rows[tier] = {
                "n": len(sub),
                "completed": sum(r.status is RequestStatus.COMPLETED
                                 for r in sub),
                "ttft_attain": attain,
                "ttft_p50_ms": _pct(ttfts, 0.5),
                "wait_p50": _pct(waits, 0.5),
                "wait_max": max(waits, default=0),
                "wait_mean": sum(waits) / max(len(waits), 1),
            }
        return rows

    # ---- run A: static queue-depth policy, FIFO admission (also the
    # budget-calibration run) ---------------------------------------------
    _, reqs_a = make_workload()
    pol_a = FormatPolicy(anchor="mxint8", ladder=ladder)
    eng_a, st_a, dt_a = run(reqs_a, pol_a, "fifo")
    ttfts_a = [t for t in map(ttft_from_arrival_ms, reqs_a)
               if t is not None]
    decode_walls = [t["wall_s"] * 1e3 for t in eng_a.tick_trace
                    if t["decode"]]
    ttft_budget = _pct(ttfts_a, 0.6)
    tpot_budget = _pct(decode_walls, 0.75)

    # ---- run B: measured-cost-model policy, tiered admission ------------
    _, reqs_b = make_workload(ttft_ms=ttft_budget, tpot_ms=tpot_budget)
    cost = CostModel.from_roofline(
        cfg, [f for _, f in ladder], max_len=max_len, kv_layout="paged",
        kv_page_size=page_size, block_size=32,
        hbm_bytes_per_s=hbm_bytes_per_s)
    pol_b = FormatPolicy(anchor="mxint8", ladder=ladder, cost=cost)
    eng_b, st_b, dt_b = run(reqs_b, pol_b, "slo")

    # ---- per-tier attainment table --------------------------------------
    rows_a = tier_rows(reqs_a, ttft_budget)
    rows_b = tier_rows(reqs_b, ttft_budget)
    toks_a = sum(len(r.out_tokens) for r in reqs_a)
    toks_b = sum(len(r.out_tokens) for r in reqs_b)
    print(f"# workload: {len(reqs_a)} requests / {horizon} arrival ticks "
          f"(seed {wl_seed}); budgets calibrated from run A: "
          f"ttft<={ttft_budget:.1f}ms (p60), tpot<={tpot_budget:.1f}ms "
          f"(p75 decode tick)")
    print("slo,run,tier,requests,completed,ttft_attain,ttft_p50_ms,"
          "wait_p50_ticks,wait_mean_ticks,wait_max_ticks")
    for label, rows in (("static", rows_a), ("slo", rows_b)):
        for tier, d in rows.items():
            att = "n/a" if d["ttft_attain"] is None \
                else f"{d['ttft_attain']:.2f}"
            print(f"slo,{label},{tier},{d['n']},{d['completed']},{att},"
                  f"{d['ttft_p50_ms']:.1f},{d['wait_p50']},"
                  f"{d['wait_mean']:.2f},{d['wait_max']}")
    for label, st, toks, dt, pol in (("static", st_a, toks_a, dt_a, pol_a),
                                     ("slo", st_b, toks_b, dt_b, pol_b)):
        fmts = ",".join(f"{f}:{pol.history.count(f)}"
                        for f in dict.fromkeys(pol.history))
        print(f"# {label}: {toks} tokens / {st['ticks']} decode ticks "
              f"({toks / max(st['ticks'], 1):.2f} tok/tick, "
              f"{toks / max(dt, 1e-9):.0f} tok/s wall), "
              f"requeues={st['admission_requeues']}, "
              f"failed_capacity="
              f"{st['request_statuses'].get('failed_capacity', 0)}, "
              f"picks=[{fmts}]")
    print("# per-tenant (slo run):")
    for name, d in sorted(tenant_summary(reqs_b).items()):
        print(f"#   {name}: {d['requests']} reqs, {d['tokens_out']} tok, "
              f"wait p50/max {d['wait_ticks_p50']}/{d['wait_ticks_max']} "
              f"ticks, statuses {d['statuses']}")
    if st_b["cost_model"]:
        terms = {f: f"{v['predict_1row_ms']:.2f}ms*"
                 if not v["ticks_observed"] else
                 f"{v['predict_1row_ms']:.2f}ms({v['ticks_observed']}t)"
                 for f, v in st_b["cost_model"].items()}
        print(f"# cost model (1-row tick, * = prior-only): {terms}")

    # ---- gate: per-format stream identity vs a plain non-SLO engine -----
    by_fmt = {}
    for r in reqs_b:
        if r.status is RequestStatus.COMPLETED:
            by_fmt.setdefault(r.fmt_used, []).append(r)
    for fmt, group in sorted(by_fmt.items()):
        eng_ref = ElasticEngine(api, anchor, **eng_kw)
        refs = [Request(rid=r.rid, prompt=np.asarray(r.prompt).copy(),
                        max_new=r.max_new) for r in group]
        eng_ref.generate(refs, fmt_override=fmt)
        diverged = [ref.rid for ref, r in zip(refs, group)
                    if ref.out_tokens != r.out_tokens]
        if diverged:
            raise SystemExit(
                f"--slo streams diverged from the plain non-SLO engine at "
                f"{fmt} for rids {diverged} — SLO machinery must never "
                f"change tokens")
    print(f"# streams bit-identical to the plain non-SLO engine across "
          f"{sum(len(g) for g in by_fmt.values())} completed requests in "
          f"{len(by_fmt)} format group(s) = True")

    # ---- gate: tier ordering within run B -------------------------------
    att_lat = rows_b.get("latency", {}).get("ttft_attain")
    att_thr = rows_b.get("throughput", {}).get("ttft_attain")
    if att_lat is not None and att_thr is not None and att_lat < att_thr:
        raise SystemExit(
            f"latency-tier TTFT attainment ({att_lat:.2f}) fell below "
            f"throughput-tier's ({att_thr:.2f}) under tiered admission")

    # ---- gate: the win over the static policy ---------------------------
    att_lat_a = rows_a.get("latency", {}).get("ttft_attain")
    if att_lat_a is not None and att_lat is not None \
            and att_lat < att_lat_a:
        raise SystemExit(
            f"slo run's latency-tier TTFT attainment ({att_lat:.2f}) fell "
            f"below the static policy's ({att_lat_a:.2f})")
    wait_a = rows_a.get("latency", {}).get("wait_mean", 0.0)
    wait_b = rows_b.get("latency", {}).get("wait_mean", 0.0)
    if wait_b > wait_a:
        raise SystemExit(
            f"slo run's latency-tier mean queue wait ({wait_b:.2f} ticks) "
            f"exceeds the static policy's ({wait_a:.2f}) — tiered "
            f"admission lost to FIFO")
    if st_b["ticks"] > 1.05 * max(st_a["ticks"], 1):
        raise SystemExit(
            f"slo run spent {st_b['ticks']} decode ticks vs the static "
            f"policy's {st_a['ticks']} (> 1.05x) — the SLO win is not "
            f"allowed to cost aggregate throughput")
    print(f"# gates: latency wait {wait_a:.2f} -> {wait_b:.2f} ticks "
          f"(static -> slo), attain lat/thr "
          f"{'n/a' if att_lat is None else f'{att_lat:.2f}'}/"
          f"{'n/a' if att_thr is None else f'{att_thr:.2f}'}, decode ticks "
          f"{st_a['ticks']} -> {st_b['ticks']} = all passed")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=9)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--paths", default="both",
                    choices=("both", "fused", "densify"),
                    help="packed-serving contract(s) to benchmark")
    ap.add_argument("--kv", default="both",
                    choices=("both", "dense", "paged"),
                    help="KV-cache layout(s) to benchmark")
    ap.add_argument("--page-size", type=int, default=8,
                    help="tokens per KV page for the paged layout")
    ap.add_argument("--admission", default="both",
                    choices=("both", "monolithic", "chunked"),
                    help="prompt admission mode(s) to benchmark")
    ap.add_argument("--attn", default="both",
                    choices=("both", "gather", "paged_kernel"),
                    help="paged decode-attention impl(s) to benchmark "
                         "(paged rows only; dense KV has no block table)")
    ap.add_argument("--scheduler", default="both",
                    choices=("both", "sequential", "mixed"),
                    help="chunked-tick scheduler(s) to benchmark "
                         "(chunked rows only; monolithic admission has no "
                         "chunk to coalesce)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunk size for the chunked admission rows "
                         "(default: one KV page, min 8)")
    ap.add_argument("--long-every", type=int, default=3,
                    help="every Nth request gets the long prompt")
    ap.add_argument("--long-len", type=int, default=40,
                    help="long-prompt length (the admission-stall driver)")
    ap.add_argument("--chaos", action="store_true",
                    help="run the fault-injection sweep instead of the "
                         "perf matrix: seeded chaos at increasing fault "
                         "rates, with hard gates on survivor-stream "
                         "identity and page accounting, plus a format-"
                         "ladder degradation demo")
    ap.add_argument("--fault-rates", default="0,0.1,0.25",
                    help="comma-separated per-tick fault rates for --chaos")
    ap.add_argument("--speculative", action="store_true",
                    help="run the self-speculative sweep instead of the "
                         "perf matrix: plain vs draft-and-verify decode "
                         "with a hard stream-identity gate, an acceptance-"
                         "rate column, and a decode-tick-win gate")
    ap.add_argument("--draft-fmt", default="mxint4",
                    help="draft rung for --speculative")
    ap.add_argument("--k", type=int, default=4,
                    help="draft depth for --speculative")
    ap.add_argument("--hbm-bytes-per-s", type=float, default=None,
                    help="HBM bandwidth the --slo cost model's prior "
                         "divides by (default: the device's published "
                         "peak, launch/mesh.py::PEAKS; a CPU host has "
                         "none, so pass a nominal one there)")
    ap.add_argument("--slo", action="store_true",
                    help="run the SLO-tier sweep instead of the perf "
                         "matrix: static queue-depth policy vs measured-"
                         "cost-model policy on a deterministic bursty "
                         "multi-tenant workload, with per-tier TTFT/wait "
                         "attainment columns and hard identity/ordering/"
                         "throughput gates")
    ap.add_argument("--horizon", type=int, default=24,
                    help="arrival-window ticks for the --slo workload")
    ap.add_argument("--wl-seed", type=int, default=0,
                    help="workload seed for --slo")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="run the tensor-parallel sweep instead of the "
                         "perf matrix: single-device vs meshed engine on "
                         "a (data, model) mesh, with a hard stream-"
                         "identity gate and the per-chip weight-stream "
                         "ratio (e.g. --mesh 1x2; on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=2)")
    args = ap.parse_args()

    cfg = get_reduced(args.arch)
    api = get_model(cfg, None)
    params = api.init_params(jax.random.PRNGKey(0))
    qat = QATConfig(formats=("mxint4", "mxint8"), anchor="mxint8",
                    block_size=32)
    anchor = make_anchor(params, qat, get_format("mxint8", 32))

    if args.mesh:
        bench_mesh(api, anchor, params, mesh_spec=args.mesh,
                   slots=args.slots, max_len=args.max_len,
                   n_requests=args.requests, max_new=args.max_new,
                   vocab=cfg.vocab, page_size=args.page_size,
                   long_every=args.long_every, long_len=args.long_len)
        return

    if args.chaos:
        bench_chaos(api, anchor, params, slots=args.slots,
                    max_len=args.max_len, n_requests=args.requests,
                    max_new=args.max_new, vocab=cfg.vocab,
                    rates=[float(x) for x in args.fault_rates.split(",")])
        return

    if args.slo:
        bench_slo(api, anchor, params, slots=args.slots,
                  max_len=args.max_len, horizon=args.horizon,
                  wl_seed=args.wl_seed, page_size=args.page_size,
                  hbm_bytes_per_s=args.hbm_bytes_per_s)
        return

    if args.speculative:
        bench_speculative(api, anchor, params, slots=args.slots,
                          max_len=args.max_len, n_requests=args.requests,
                          max_new=args.max_new, vocab=cfg.vocab,
                          draft_fmt=args.draft_fmt, k=args.k,
                          page_size=args.page_size,
                          long_every=args.long_every,
                          long_len=args.long_len)
        return

    # default chunk: one KV page (floored at the minimum prefill bucket) so
    # the chunked rows satisfy the page-alignment rule for any --page-size
    chunk = args.prefill_chunk or max(args.page_size, 8)
    kw = dict(slots=args.slots, max_len=args.max_len,
              n_requests=args.requests, max_new=args.max_new,
              vocab=cfg.vocab, page_size=args.page_size,
              prefill_chunk=chunk,
              long_every=args.long_every, long_len=args.long_len)
    want_fused = args.paths in ("both", "fused")
    want_dense = args.paths in ("both", "densify")
    layouts = ("dense", "paged") if args.kv == "both" else (args.kv,)
    admissions = ("monolithic", "chunked") if args.admission == "both" \
        else (args.admission,)
    attns = ("gather", "paged_kernel") if args.attn == "both" \
        else (args.attn,)
    schedulers = ("sequential", "mixed") if args.scheduler == "both" \
        else (args.scheduler,)
    rows = []
    for adm in admissions:
        for sched in (schedulers if adm == "chunked" else ("sequential",)):
            for kv in layouts:
                for attn in (attns if kv == "paged" else ("gather",)):
                    for fmt in FORMATS:
                        if fmt == "bf16":  # dense pseudo-format: one path
                            rows.append(bench_path(
                                api, anchor, params, fmt, False,
                                kv_layout=kv, admission=adm,
                                attn_impl=attn, scheduler=sched, **kw))
                            continue
                        if want_fused:
                            rows.append(bench_path(
                                api, anchor, params, fmt, True,
                                kv_layout=kv, admission=adm,
                                attn_impl=attn, scheduler=sched, **kw))
                        if want_dense:
                            rows.append(bench_path(
                                api, anchor, params, fmt, False,
                                kv_layout=kv, admission=adm,
                                attn_impl=attn, scheduler=sched, **kw))

    base = next(r for r in rows if r["fmt"] == "bf16")
    # KV ratios are vs the DENSE layout; without a dense row (--kv paged)
    # there is no baseline to compare against, so print n/a rather than a
    # misleading same-layout 1.00x.
    kv_base = next((r for r in rows if r["kv"] == "dense"), None)
    print("fmt,path,kv,attn,admission,scheduler,containers,weight_bytes,"
          "ticks,tokens,tokens_per_tick,weight_bytes_per_token,"
          "bytes_cut_vs_bf16,kv_bytes_per_slot,kv_cut_vs_dense,"
          "attn_bytes_per_token,decode_occupancy,tick_exec,adm_decode_tpt,"
          "ttft_p50_ms,ttft_p99_ms,stall_p99_ms,max_pf_tok,wall_s")
    for r in rows:
        cut = base["weight_bytes_per_token"] / r["weight_bytes_per_token"]
        kv_cut = "n/a" if kv_base is None else \
            f"{kv_base['kv_bytes_per_slot'] / max(r['kv_bytes_per_slot'], 1):.2f}x"
        print(f"{r['fmt']},{r['path']},{r['kv']},{r['attn']},"
              f"{r['admission']},{r['scheduler']},{r['containers']},"
              f"{r['weight_bytes']},{r['ticks']},{r['tokens']},"
              f"{r['tokens_per_tick']:.2f},"
              f"{r['weight_bytes_per_token']:.0f},{cut:.2f}x,"
              f"{r['kv_bytes_per_slot']},{kv_cut},"
              f"{r['attn_bytes_per_token']:.0f},"
              f"{r['decode_occupancy']:.2f},{r['tick_exec']:.2f},"
              f"{r['adm_decode_tpt']:.2f},"
              f"{r['ttft_p50_ms']:.1f},{r['ttft_p99_ms']:.1f},"
              f"{r['stall_p99_ms']:.1f},{r['max_pf_tok']},"
              f"{r['wall_s']:.2f}")

    if len(attns) == 2 and "paged" in layouts:
        # The attention-impl contract: the gather-free kernel changes the
        # bytes read, never the tokens produced.
        keyed = {}
        for r in rows:
            if r["kv"] != "paged":
                continue
            keyed.setdefault((r["fmt"], r["path"], r["admission"],
                              r["scheduler"]), {})[r["attn"]] = r
        pairs = [p for p in keyed.values() if len(p) == 2]
        identical = all(p["gather"]["streams"] == p["paged_kernel"]["streams"]
                        for p in pairs)
        g_bytes = _pct([p["gather"]["attn_bytes_per_token"]
                        for p in pairs], 0.5)
        k_bytes = _pct([p["paged_kernel"]["attn_bytes_per_token"]
                        for p in pairs], 0.5)
        print(f"# paged_kernel vs gather: token streams identical across "
              f"all configs = {identical}; median attn bytes/token "
              f"{g_bytes:.0f} -> {k_bytes:.0f} "
              f"({g_bytes / max(k_bytes, 1e-9):.2f}x cut)")
        if not identical:
            raise SystemExit("token streams diverged between attention "
                             "impls — the paged kernel broke bit-identity")

    if len(schedulers) == 2 and "chunked" in admissions:
        # The unified-tick contract: coalescing the chunk into the decode
        # batch is a pure re-scheduling — same tokens, ~1 executable/tick.
        keyed = {}
        for r in rows:
            if r["admission"] != "chunked":
                continue
            keyed.setdefault((r["fmt"], r["path"], r["kv"], r["attn"]),
                             {})[r["scheduler"]] = r
        pairs = [p for p in keyed.values() if len(p) == 2]
        identical = all(p["sequential"]["streams"] == p["mixed"]["streams"]
                        for p in pairs)
        s_exec = _pct([p["sequential"]["tick_exec"] for p in pairs], 0.5)
        m_exec = _pct([p["mixed"]["tick_exec"] for p in pairs], 0.5)
        print(f"# mixed vs sequential: token streams identical across all "
              f"configs = {identical}; median executables/tick "
              f"{s_exec:.2f} -> {m_exec:.2f}")
        if not identical:
            raise SystemExit("token streams diverged between schedulers — "
                             "the mixed tick broke bit-identity")

    if len(admissions) == 2:
        # The chunked-admission contract: same tokens, smaller stall tail,
        # and decode throughput during a long admission no worse than the
        # monolithic baseline. One scheduler's chunked rows suffice — the
        # cross-scheduler check above pins mixed == sequential.
        keyed = {}
        adm_sched = "sequential" if "sequential" in schedulers \
            else schedulers[0]
        for r in rows:
            if r["admission"] == "chunked" and r["scheduler"] != adm_sched:
                continue
            keyed.setdefault((r["fmt"], r["path"], r["kv"], r["attn"]),
                             {})[r["admission"]] = r
        identical = all(p["monolithic"]["streams"] == p["chunked"]["streams"]
                        for p in keyed.values() if len(p) == 2)
        pairs = [p for p in keyed.values() if len(p) == 2]
        mono_stall = _pct([p["monolithic"]["stall_p99_ms"] for p in pairs],
                          0.5)
        chnk_stall = _pct([p["chunked"]["stall_p99_ms"] for p in pairs], 0.5)
        mono_adm = _pct([p["monolithic"]["adm_decode_tpt"] for p in pairs],
                        0.5)
        chnk_adm = _pct([p["chunked"]["adm_decode_tpt"] for p in pairs], 0.5)
        mono_tps = _pct([p["monolithic"]["adm_decode_tps"] for p in pairs],
                        0.5)
        chnk_tps = _pct([p["chunked"]["adm_decode_tps"] for p in pairs], 0.5)
        # tokens/tick alone flatters monolithic: its one admission tick
        # counts the freshly admitted slots' first decodes while stalling
        # everything for the whole prompt — the per-second number is the
        # decode throughput running slots actually see during an admission.
        print(f"# chunked vs monolithic: token streams identical across all "
              f"configs = {identical}; median stall_p99 "
              f"{mono_stall:.1f}ms -> {chnk_stall:.1f}ms; decode during "
              f"admission {mono_adm:.2f} -> {chnk_adm:.2f} tokens/tick, "
              f"{mono_tps:.0f} -> {chnk_tps:.0f} tokens/s")
        if not identical:
            raise SystemExit("token streams diverged between admission "
                             "modes — chunked prefill broke bit-identity")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
