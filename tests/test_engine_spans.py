"""Spans and stamps inside ``ElasticEngine.generate``.

Each scheduler tick is a ``jax.profiler.TraceAnnotation`` span,
``engine.tick``, over sibling phase spans (``engine.boundary``, ``sweep``,
``admit``, ``stage``, ``dispatch``, ``fetch``, ``retire``), so a profiler
trace names the host work over each idle gap of the device. The same
phases time themselves into ``tick_trace`` (``phase_s``) and stamp each
tick on the engine clock (``dispatched_s``, ``fetched_s``, ``step_s``);
``Request.admitted_s`` stamps admission on that clock. The counted step
executables keep the names of the functions they wrap, so they compile
as ``jit_<name>`` and a trace tells them apart.
"""
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import make_anchor
from repro.core.qat import QATConfig
from repro.models import get_model
from repro.serve.engine import ElasticEngine, Request, RequestStatus

QAT = QATConfig(formats=("mxint4", "mxint8"), anchor="mxint8", block_size=32)
PS = 8
PHASES = {"boundary", "sweep", "admit", "stage", "dispatch", "fetch",
          "retire", "convert"}


@pytest.fixture(scope="module")
def setup():
    cfg = get_reduced("smollm-135m")
    api = get_model(cfg, None)
    params = api.init_params(jax.random.PRNGKey(0))
    anchor = make_anchor(params, QAT)
    return cfg, api, params, anchor


def _engine(setup, **kw):
    _, api, params, anchor = setup
    kw.setdefault("batch_slots", 2)
    kw.setdefault("max_len", 48)
    return ElasticEngine(api, anchor, param_template=params,
                         kv_layout="paged", kv_page_size=PS,
                         prefill_chunk=PS, **kw)


def _reqs(cfg, plens=(21, 5, 13), max_new=4, arrivals=None, seed=0):
    """A 3-chunk prompt that admits alone (two non-final chunks with no
    logits to read), then short ones that ride mixed ticks."""
    rng = np.random.default_rng(seed)
    arrivals = arrivals or [0] * len(plens)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab, n)
                    .astype(np.int32), max_new=max_new, arrival_tick=a)
            for i, (n, a) in enumerate(zip(plens, arrivals))]


def _host_events(trace_dir):
    """Every host event of the trace: (name, start, end, stats), the
    stats read for ``engine.*`` spans only."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats) if e.name.startswith("engine.") \
                    else {}
                out.append((e.name, e.start_ns, e.end_ns, stats))
    return out


def test_profiler_trace_holds_nested_engine_spans(setup, tmp_path):
    cfg = setup[0]
    eng = _engine(setup)
    with jax.profiler.trace(str(tmp_path)):
        eng.generate(_reqs(cfg), fmt_override="mxint8")
    evs = _host_events(str(tmp_path))
    eng_evs = [e for e in evs if e[0].startswith("engine.")]
    ticks = [e for e in eng_evs if e[0] == "engine.tick"]
    assert len(ticks) == len(eng.tick_trace)
    assert sorted(t[3]["tick"] for t in ticks) == list(range(len(ticks)))
    names = {e[0] for e in eng_evs}
    assert {"engine.dispatch", "engine.fetch", "engine.retire",
            "engine.convert"} <= names
    phases = [e for e in eng_evs if e[0] != "engine.tick"]
    assert {e[0][len("engine."):] for e in phases} <= PHASES
    for name, s, t, _ in phases:
        assert any(ts <= s and t <= te for _, ts, te, _ in ticks), name
    # the phases of a tick are siblings: none overlaps another
    phases.sort(key=lambda e: e[1])
    for a, b in zip(phases, phases[1:]):
        assert a[2] <= b[1], (a[0], b[0])
    kinds = {e[3]["kind"] for e in phases if e[0] == "engine.dispatch"}
    assert kinds == {"chunk", "mixed", "decode"}
    assert {e[3]["fmt"] for e in phases if e[0] == "engine.dispatch"} \
        == {"mxint8"}
    whats = {e[3]["what"] for e in phases if e[0] == "engine.fetch"}
    assert whats == {"guard", "tokens", "first_token"}
    assert all("rid" in e[3] for e in phases
               if e[0] == "engine.fetch" and e[3]["what"] == "first_token")
    assert {e[3].get("rid") for e in phases if e[0] == "engine.admit"} \
        >= {0, 1, 2}
    # the step executables' dispatches carry their functions' names
    jitted = {e[0] for e in evs if e[0].startswith("PjitFunction(")}
    assert "PjitFunction(mixed_step)" in jitted
    assert "PjitFunction(prefill_chunk_slot)" in jitted
    assert "PjitFunction(wrapped)" not in jitted


def test_tick_trace_stamps_and_phases(setup):
    cfg = setup[0]
    eng = _engine(setup)
    reqs = _reqs(cfg, arrivals=[2, 2, 3])
    eng.generate(reqs, fmt_override="mxint8")
    tt = eng.tick_trace
    assert [e["kind"] for e in tt[:2]] == ["idle", "idle"]
    ran = 0
    for e in tt:
        assert set(e["phase_s"]) <= PHASES
        assert sum(e["phase_s"].values()) <= e["wall_s"] + 1e-9
        if e["kind"] == "idle":
            assert e["execs"] == 0
            assert e["dispatched_s"] is e["fetched_s"] is e["step_s"] \
                is None
            continue
        ran += 1
        want = {(1, 1): "mixed", (1, 0): "decode", (0, 1): "chunk"}
        assert e["kind"] == want[(e["decode"], e["prefill_chunks"])]
        assert e["dispatched_s"] is not None
        if e["fetched_s"] is None:
            # a non-final chunk alone: the host never waits for it
            assert e["kind"] == "chunk" and e["step_s"] is None
            continue
        assert e["dispatched_s"] < e["fetched_s"]
        assert 0 < e["step_s"] <= e["fetched_s"] - e["dispatched_s"]
    assert ran and any(e["fetched_s"] is None for e in tt)
    steps = [e for e in tt if e["dispatched_s"] is not None]
    for a, b in zip(steps, steps[1:]):
        assert a["dispatched_s"] < b["dispatched_s"]


def test_admitted_s_between_arrival_and_first_token(setup):
    cfg = setup[0]
    eng = _engine(setup, batch_slots=1)
    reqs = _reqs(cfg, arrivals=[0, 1, 1])
    eng.generate(reqs, fmt_override="mxint8")
    assert all(r.status is RequestStatus.COMPLETED for r in reqs)
    for r in reqs:
        assert r.arrival_s <= r.admitted_s <= r.ttft_s, r.rid
    # one slot: the later two wait in the engine's queue for it
    assert reqs[2].admitted_s - reqs[2].arrival_s \
        > reqs[1].admitted_s - reqs[1].arrival_s


class _CutAt:
    """A preemption guard that lets ``n`` ticks run, then preempts."""

    def __init__(self, n):
        self.n, self.reads = n, 0

    @property
    def preempted(self):
        self.reads += 1
        return self.reads > self.n


def test_admitted_s_survives_snapshot_and_resume(setup, tmp_path):
    cfg = setup[0]
    eng = _engine(setup)
    reqs = _reqs(cfg)
    eng.generate(reqs, fmt_override="mxint8", guard=_CutAt(5),
                 snapshot_dir=str(tmp_path))
    assert not all(r.done for r in reqs)
    before = {r.rid: r.admitted_s for r in reqs}
    assert before[0] is not None and before[1] is not None
    done = _engine(setup).resume(str(tmp_path))
    assert all(r.status is RequestStatus.COMPLETED for r in done)
    for r in done:
        if before[r.rid] is not None:
            assert r.admitted_s == before[r.rid]
        assert r.arrival_s <= r.admitted_s <= r.ttft_s, r.rid


@pytest.mark.parametrize("sched", ["mixed", "sequential"])
def test_counted_steps_keep_their_names(setup, sched):
    cfg = setup[0]
    eng = _engine(setup, scheduler=sched)
    eng.generate(_reqs(cfg), fmt_override="mxint8")
    traces = eng.stats["traces"]
    assert "prefill_chunk_slot" in traces and "wrapped" not in traces
    assert ("mixed_step" in traces) == (sched == "mixed")
    assert eng.stats["prefill_traces"] == sum(traces.values())
    assert eng._packed_mixed.__name__ == "mixed_step"
