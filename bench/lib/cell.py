"""One run of one cell: set up, measure a window, check, report.

Phases, in one process (a process that has touched JAX holds the chip):

1. ``weights``: the MXINT8 anchor, made on the device from the seed.
2. ``engine``: ``ElasticEngine`` on the cell's path (paged KV, the
   paged-attention kernels, the fused dequant-GEMMs, the mixed scheduler).
3. ``conversion``: ``weights_for(rung)`` from the anchor, to the device.
4. ``warmup``: short waves at the cell's slots, chunk and rung that run
   every shape the traffic will use.
5. ``ramp``: the open loop starts ``ramp_s`` before the window opens.
6. the window, ``--seconds`` long; then serving goes on until every
   request due in the window has its first token.

``setup_s`` runs from process start to the window's opening. After the
window the program's state is freed and the plain reference checks a
sample of the finished requests.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from bench.lib import spec, stats
from bench.lib.driver import Pacer, Window, make_requests
from bench.lib.traffic import make_arrivals
from bench.lib.work import Shapes

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Compiles:
    """Counts compilations (and compile-cache loads) as JAX reports them."""

    def __init__(self):
        import jax
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration_s, **kw):
        if event in COMPILE_EVENTS:
            self.count += 1
            self.seconds += duration_s


class Phases:
    """Wall and compile seconds of each set-up phase."""

    def __init__(self, compiles: Compiles):
        self.compiles = compiles
        self.wall: Dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0, c0 = time.perf_counter(), self.compiles.seconds
        yield
        self.wall[name] = time.perf_counter() - t0
        log(f"phase {name}: {self.wall[name]:.3f} s, compile "
            f"{self.compiles.seconds - c0:.3f} s")


class Tracer:
    """The profiler around the traced part of the window."""

    def __init__(self, out_dir: Path):
        self.dir = out_dir
        self._span = None

    def start(self):
        import jax
        from bench.lib import trace as tr
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(tr.WINDOW_SPAN)
        self._span.__enter__()

    def stop(self):
        import jax
        self._span.__exit__(None, None, None)
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        log(f"trace: stop_trace took {time.perf_counter() - t0:.3f} s")


@dataclasses.dataclass
class RunData:
    """Everything a per-layer reader may read (``bench/metrics``)."""
    cell: dict
    shapes: Shapes
    slots: int
    chunk: int
    page: int
    bits: int
    peaks: Optional[dict]
    arrivals: list
    requests: list
    pacer: Pacer
    tick_trace: List[dict]
    phases: Dict[str, float]
    trace: object = None          # bench.lib.trace.Reduced
    traced: Optional[dict] = None  # bench.lib.work.tick_totals over traced


def rung_bits(rung: str) -> int:
    return int(rung.replace("mxint", ""))


def warmup_waves(slots: int, chunk: int, vocab: int, seed: int):
    """Prompts that run every executable shape of the cell once.

    Wave 1, one request at a time (``max_new=1`` retires each at its first
    token, so nothing decodes): a chunk alone at every padded width, plus a
    full non-final chunk. Wave 2, behind a long decoder: a mixed tick at
    every width (final and non-final chunks), then plain decode ticks.
    """
    widths = sorted({min(2 ** k, chunk) for k in range(3, 16)
                     if 2 ** (k - 1) < chunk})
    rng = np.random.default_rng([seed % 2 ** 64, 99])

    def toks(n):
        return rng.integers(1, vocab, n).astype(np.int32)

    alone = [(toks(w), 1) for w in widths] + [(toks(chunk + 8), 1)]
    n_mixed = 2 * len(widths)
    mixed = [(toks(8), 2 * n_mixed + 8)] + \
        [(toks(w), 2) for w in widths] + \
        [(toks(chunk + w), 2) for w in widths]
    return [alone, mixed]


def sample_for_check(requests, arrivals, seed: int, tokens: int,
                     max_requests: int):
    """Finished requests to check: the longest, then others drawn from the
    seed, until ``tokens`` served tokens or ``max_requests``."""
    from repro.serve.engine import RequestStatus
    done = [i for i, r in enumerate(requests)
            if r.status == RequestStatus.COMPLETED and r.out_tokens]
    if not done:
        return []
    longest = max(done, key=lambda i: len(arrivals[i].prompt)
                  + len(requests[i].out_tokens))
    rest = [i for i in done if i != longest]
    np.random.default_rng([seed % 2 ** 64, 7]).shuffle(rest)
    pick, n = [longest], len(requests[longest].out_tokens)
    for i in rest:
        if n >= tokens or len(pick) >= max_requests:
            break
        pick.append(i)
        n += len(requests[i].out_tokens)
    return pick


class Session:
    """A cell's program set up once: weights, engine, rung, warm-up.

    ``serve`` may then run the open loop more than once (the knee sweep
    runs one rate after another on one session). ``engine`` overrides the
    configuration's engine settings (the sweep tries other slots and
    chunks).
    """

    def __init__(self, workload: str, seed: int, *, root: Path = spec.ROOT,
                 rung: Optional[str] = None, chip: bool = True,
                 tamper: Optional[Callable] = None,
                 engine: Optional[dict] = None):
        import jax
        from bench.lib.peaks import peaks_for
        if str(spec.ROOT / "src") not in sys.path:
            sys.path.insert(0, str(spec.ROOT / "src"))
        from repro.models import get_model
        from repro.models.common import ModelConfig
        from repro.serve.engine import ElasticEngine, Request
        from bench.lib.anchor import make_anchor_on_device

        self.workload, self.seed, self.root = workload, seed, root
        self.c = c = spec.cell(workload, root)
        self.cfg, self.mix = c["config"], c["traffic"]
        self.eng_cfg = dict(self.cfg["engine"], **(engine or {}))
        self.model = self.cfg["model"]
        self.rung = rung or self.mix["rung"]
        dev = jax.devices()
        self.dev = dev[0]
        self.device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
                       "count": len(dev)}
        self.peaks = peaks_for(dev[0].device_kind) if chip else None
        log(f"device: {self.device['platform']} {self.device['kind']} "
            f"x{self.device['count']}")
        self.compiles = Compiles()
        self.phase = Phases(self.compiles)
        self.Request = Request
        e = self.eng_cfg
        self.slots, self.chunk = e["batch_slots"], e["prefill_chunk"]
        self.page = e["kv_page_size"]
        with self.phase("weights"):
            self.anchor = make_anchor_on_device(self.model, seed, e["anchor"],
                                                e["block_size"])
            jax.block_until_ready(self.anchor)
        with self.phase("engine"):
            mcfg = ModelConfig(name=self.cfg["name"], family="dense",
                               **self.model)
            api = get_model(mcfg, None)
            template = jax.eval_shape(api.init_params, jax.random.PRNGKey(0))
            self.eng = ElasticEngine(
                api, self.anchor, batch_slots=self.slots,
                max_len=e["max_len"], param_template=template,
                kv_layout="paged", kv_page_size=self.page,
                kv_num_pages=e.get("kv_num_pages"),
                prefill_chunk=self.chunk, attn_impl="paged_kernel",
                fused=True, seed=seed)
            if tamper is not None:
                tamper(self.eng)
        with self.phase("conversion"):
            jax.block_until_ready(self.eng.weights_for(self.rung))
        with self.phase("warmup"):
            for wave in warmup_waves(self.slots, self.chunk,
                                     self.model["vocab"], seed):
                self.eng.generate(
                    [Request(rid=10 ** 6 + i, prompt=p, max_new=n)
                     for i, (p, n) in enumerate(wave)],
                    greedy=True, fmt_override=self.rung)

    def serve(self, seconds: float, trace: bool,
              rate: Optional[float] = None) -> RunData:
        """Ramp, window and drain of the open loop at ``rate``."""
        mix = self.mix
        ramp, drain = float(mix["ramp_s"]), float(mix["drain_limit_s"])
        window = Window(open_s=ramp, close_s=ramp + seconds,
                        drain_limit_s=drain,
                        trace_s=min(float(mix["trace_s"]), seconds)
                        if trace else 0.0)
        arrivals = make_arrivals(mix, self.model["vocab"], self.seed,
                                 [ramp, seconds, drain], rate_per_s=rate)
        requests = make_requests(arrivals, self.Request)
        self.tracer = Tracer(self.root / ".bench_out" / "trace"
                             / self.workload)
        pacer = Pacer(arrivals, requests, window,
                      compiles=lambda: self.compiles.count,
                      tracer=self.tracer if trace else None)
        self.serve_t0 = time.perf_counter()
        with self.phase("serve"):
            self.eng.generate(requests, greedy=True,
                              fmt_override=self.rung, guard=pacer)
        return RunData(cell=self.c, shapes=Shapes.from_model(
                           self.model, self.eng_cfg["block_size"]),
                       slots=self.slots, chunk=self.chunk, page=self.page,
                       bits=rung_bits(self.rung), peaks=self.peaks,
                       arrivals=arrivals, requests=requests, pacer=pacer,
                       tick_trace=list(self.eng.tick_trace),
                       phases=dict(self.phase.wall))

    def close(self) -> None:
        """Free the program's device state."""
        del self.eng, self.anchor
        gc.collect()


def summarize(data: RunData, seconds: float, rate: float) -> dict:
    """Earlier lines of a run, and the window's request outcomes."""
    from repro.serve.engine import RequestStatus
    p, reqs = data.pacer, data.requests
    win = [i for i in range(len(data.arrivals)) if p.in_window(i)]
    failed = [i for i in win if reqs[i].status not in (
        RequestStatus.COMPLETED, RequestStatus.RUNNING, RequestStatus.QUEUED)]
    late = p.lateness_s()
    in_win = (p.compiles_at_close if p.compiles_at_close is not None
              else 0) - (p.compiles_at_open or 0)
    close_tick = p.closed_tick if p.closed_tick is not None \
        else len(p.boundaries)
    backlog = sum(1 for i in win if reqs[i].admitted_tick is None
                  or reqs[i].admitted_tick >= close_tick)
    log(f"requests due in the window: {len(win)} (rate {rate}/s over "
        f"{seconds} s), failed {len(failed)}, with a first token "
        f"{sum(1 for i in win if reqs[i].out_tokens)}, not admitted by the "
        f"close {backlog}")
    log(f"release lateness: max {max(late, default=0) * 1e3:.3f} ms, p99 "
        f"{(stats.percentile(late, 99) or 0) * 1e3:.3f} ms over "
        f"{len(late)} requests")
    log(f"compiles inside the window: {in_win}")
    log(f"ticks: {len(data.tick_trace)}; phases: " + ", ".join(
        f"{k} {v:.3f} s" for k, v in data.phases.items()))
    t0 = p.origin + p.window.open_s
    t1 = p.origin + p.window.close_s
    firsts = [reqs[i].out_tokens.stamps[0] if reqs[i].out_tokens else None
              for i in win]
    ttft = stats.ttft_samples([p.due_abs(i) for i in win], firsts,
                              [i in failed for i in win])
    gaps = [g for r in reqs
            for g in stats.gaps_in_window(r.out_tokens.stamps, t0, t1)]
    log(f"ttft samples {len(ttft)}, median {stats.percentile(ttft, 50)}; "
        f"itl samples {len(gaps)}, median {stats.percentile(gaps, 50)}")
    return {"win": win, "failed": failed, "backlog": backlog,
            "compiles_in_window": in_win,
            "ttft_p90_ms": _ms(stats.percentile(ttft, 90)),
            "itl_p95_ms": _ms(stats.percentile(gaps, 95))}


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, root: Path = spec.ROOT, rung: Optional[str] = None,
        ref_rung: Optional[str] = None, chip: bool = True,
        tamper: Optional[Callable] = None, control=None) -> dict:
    """One run; returns the result object (``run.py`` prints it).

    The controls take the program's place in the check: ``rung`` serves
    another rung than the mix pins and ``ref_rung`` checks against another
    than the one served (the program's own lower rung against the cell's
    reference); ``control`` builds a lower-precision reference whose first
    choices, at the positions the program served, are checked in place of
    the served tokens.
    """
    s = Session(workload, seed, root=root, rung=rung, chip=chip,
                tamper=tamper)
    data = s.serve(seconds, trace)
    setup_s = data.pacer.origin + data.pacer.window.open_s - t_start
    ph = s.phase.wall
    named = ("weights", "engine", "conversion", "warmup")
    ramp = data.pacer.origin + data.pacer.window.open_s - s.serve_t0
    log(f"setup_s {setup_s:.3f}: " + ", ".join(
        f"{k} {ph[k]:.3f}" for k in named) + f", ramp {ramp:.3f}, other "
        f"{setup_s - ramp - sum(ph[k] for k in named):.3f}")
    mem = s.dev.memory_stats() or {}
    device = dict(s.device, memory_peak_bytes=int(
        mem.get("peak_bytes_in_use", 0)))
    summ = summarize(data, seconds, s.mix["rate_per_s"])
    result = {"correct": False, "attempted": len(summ["win"]),
              "failed": len(summ["failed"]), "metrics": {}, "device": device}
    if trace:
        _reduce_trace(data, s.tracer, device, result)
        for m in s.c["per_layer"]:
            v = spec.reader(m["name"], root)(data)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(summ, setup_s=setup_s)
        for m in s.c["end_to_end"]:
            if values.get(m["name"]) is not None:
                result["metrics"][m["name"]] = {"value": values[m["name"]],
                                                "unit": m["unit"]}
    reqs, arrivals = data.requests, data.arrivals
    pick = sample_for_check(reqs, arrivals, seed,
                            s.mix["check"]["served_tokens"],
                            s.mix["check"]["max_requests"])
    prompts = [arrivals[i].prompt for i in pick]
    served = [list(reqs[i].out_tokens) for i in pick]
    model, eng_cfg, limits = s.model, s.eng_cfg, s.c["limits"]
    ref_rung = ref_rung or s.rung
    del data, reqs
    s.close()
    if control is not None:
        control = control(model, seed, eng_cfg, ref_rung)
    checks = check_served(model, eng_cfg, ref_rung, seed, prompts, served,
                          limits, control=control)
    result["checks"] = checks
    result["correct"] = all(v["value"] <= v["limit"]
                            for v in checks.values())
    return result


def check_served(model: dict, eng_cfg: dict, rung: str, seed: int,
                 prompts, served, limits: dict, control=None) -> dict:
    """The numbers the check compares, each with its limit: the widest gap
    by which a served token's logit lies below the reference's best. With
    ``control`` (a lower-precision reference), the tokens it puts first
    stand in for the served ones."""
    from bench.lib.reference import Reference, served_gaps
    t0 = time.perf_counter()
    ref = Reference(model, seed, anchor_bits=rung_bits(eng_cfg["anchor"]),
                    bits=rung_bits(rung), block_size=eng_cfg["block_size"])
    gaps = served_gaps(ref, prompts, served, control=control) \
        if prompts else []
    n = sum(len(g) for g in gaps)
    log(f"reference: {len(prompts)} requests, {n} "
        f"{'control' if control is not None else 'served'} tokens, "
        f"{time.perf_counter() - t0:.1f} s")
    return {"logit_gap": {"value": _widest(gaps),
                          "limit": limits["logit_gap"]["limit"]}}


def _widest(gaps) -> float:
    return float(max((g.max() for g in gaps), default=math.inf))


def _ms(v):
    return None if v is None else v * 1e3


def _reduce_trace(data: RunData, tracer: Tracer, device: dict,
                  result: dict) -> None:
    from bench.lib import trace as tr
    from bench.lib.ticks import rebuild
    from bench.lib.work import tick_totals
    p = data.pacer
    if p.trace_ticks is None or p.trace_ticks[1] is None:
        log("trace: the traced window never closed")
        return
    k0, k1 = p.trace_ticks
    ticks, bad = rebuild(data.tick_trace, data.requests, p.tick_of,
                         data.slots, data.chunk, k0, k1)
    log(f"traced ticks {k0}..{k1}: {len(ticks)}, rebuilt rows disagree "
        f"with the engine on {bad}")
    pk = data.peaks or {}
    data.traced = tick_totals(data.shapes, ticks, data.bits, data.page,
                              pk.get("flops_bf16", math.inf),
                              pk.get("hbm_bytes_per_s", math.inf))
    path = tr.find_xplane(str(tracer.dir))
    if path is None:
        log("trace: no xplane file")
        return
    t0 = time.perf_counter()
    red = tr.load(path, (0.0, 0.0))
    span = [e for e in red.host if e.name == tr.WINDOW_SPAN]
    if not span or not red.ops:
        log(f"trace: {len(span)} traced spans, device planes "
            f"{list(red.ops)}")
        return
    red.window = (span[0].start, span[0].end)
    data.trace = red
    window_s = (red.window[1] - red.window[0]) / 1e9
    busy = [tr.busy_ns(evs, red.window) / 1e9 for evs in red.ops.values()]
    device["busy_s"] = sum(busy) / len(busy)
    device["window_s"] = window_s
    plane = sorted(red.ops)[0]
    result["breakdown"] = {
        "device_ops": [[n, s] for n, s in tr.top_ops(red.ops[plane],
                                                     red.window)],
        "idle_gaps": [[n, s] for n, s in tr.top_gaps(red.ops[plane],
                                                     red.host, red.window)]}
    log(f"trace: {path}, read in {time.perf_counter() - t0:.1f} s, window "
        f"{window_s:.3f} s, busy {device['busy_s']:.3f} s")
