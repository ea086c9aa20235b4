"""SLO classes and the measured serving cost model (serve/slo.py).

Unit coverage for ``SLOClass``/``CostModel`` plus the engine-level
behaviors the SLO machinery adds: arrival-gated admission, tiered
admission order, and snapshot round-trips of the new per-request fields.
The contract under test throughout: SLO machinery moves *requests* and
*formats*, never tokens — see test_serve_engine.py for the paired
bit-identity cases.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import make_anchor
from repro.core.qat import QATConfig
from repro.models import get_model
from repro.serve.engine import ElasticEngine, Request, RequestStatus
from repro.serve.policy import FormatPolicy
from repro.serve.slo import TIERS, CostModel, SLOClass, tier_rank

QAT = QATConfig(formats=("mxint4", "mxint8"), anchor="mxint8",
                block_size=32)


def _engine(slots=2, max_len=48, **kw):
    cfg = get_reduced("smollm-135m")
    api = get_model(cfg, None)
    params = api.init_params(jax.random.PRNGKey(0))
    anchor = make_anchor(params, QAT)
    eng = ElasticEngine(api, anchor, batch_slots=slots, max_len=max_len,
                        param_template=params, **kw)
    return cfg, eng


def _req(cfg, rid, *, plen=6, max_new=3, **kw):
    rng = np.random.default_rng(100 + rid)
    return Request(rid=rid,
                   prompt=rng.integers(1, cfg.vocab, plen).astype(np.int32),
                   max_new=max_new, **kw)


# ---------------------------------------------------------------- SLOClass

def test_slo_class_validation_and_rank():
    assert SLOClass.latency().rank < SLOClass.throughput().rank \
        < SLOClass.best_effort().rank
    assert SLOClass().tier == "best_effort"
    with pytest.raises(ValueError):
        SLOClass(tier="platinum")
    with pytest.raises(ValueError):
        SLOClass(ttft_ms=0.0)
    with pytest.raises(ValueError):
        SLOClass(tpot_ms=-1.0)


def test_slo_class_dict_roundtrip():
    for slo in (SLOClass.latency(ttft_ms=120.0, tpot_ms=9.0),
                SLOClass.throughput(ttft_ms=500.0),
                SLOClass.best_effort()):
        assert SLOClass.from_dict(slo.to_dict()) == slo


def test_tier_rank_none_is_best_effort():
    assert tier_rank(None) == TIERS.index("best_effort")
    assert tier_rank(SLOClass.latency()) == 0
    assert tier_rank(SLOClass.latency()) < tier_rank(None)


# ---------------------------------------------------------------- CostModel

def test_cost_model_seed_and_raw_predict():
    cm = CostModel(hbm_bytes_per_s=1e9)
    assert not cm.has_estimate("mxint8")
    assert cm.raw_predict_s("mxint8", 4) is None
    cm.seed("mxint8", 2e6, 1e5)        # 2ms base + 0.1ms/row at 1 GB/s
    assert cm.has_estimate("mxint8")
    assert not cm.measured("mxint8")
    assert cm.raw_predict_s("mxint8", 0) == pytest.approx(2e-3)
    assert cm.raw_predict_s("mxint8", 4) == pytest.approx(2.4e-3)
    # Unmeasured + no prior: predicted == raw roofline (factor 1.0).
    assert cm.predict_ms("mxint8", 4) == pytest.approx(2.4)


def test_cost_model_observe_calibrates_factor():
    cm = CostModel(hbm_bytes_per_s=1e9, ema=0.5, min_ticks=2)
    cm.seed("mxint8", 1e6, 0.0)        # raw = 1ms regardless of rows
    cm.observe("mxint8", 1, 3e-3)      # first obs sets factor outright
    assert cm.terms["mxint8"].factor == pytest.approx(3.0)
    assert not cm.measured("mxint8")   # min_ticks=2 not reached yet
    cm.observe("mxint8", 1, 5e-3)      # EWMA: 0.5*3 + 0.5*5
    assert cm.terms["mxint8"].factor == pytest.approx(4.0)
    assert cm.measured("mxint8") and cm.any_measured()
    assert cm.predict_ms("mxint8", 1) == pytest.approx(4.0)


def test_cost_model_prior_factor_for_unmeasured_rung():
    """A rung with no observations borrows the median measured factor —
    calibrated vs raw-roofline predictions must never compete."""
    cm = CostModel(hbm_bytes_per_s=1e9, min_ticks=1)
    cm.seed("mxint8", 1e6, 0.0)
    cm.seed("mxint4", 5e5, 0.0)
    cm.observe("mxint8", 1, 10e-3)     # factor 10 on the measured rung
    assert cm.predict_ms("mxint8", 1) == pytest.approx(10.0)
    # mxint4 raw is 0.5ms; borrowed factor 10 -> 5ms, not 0.5ms.
    assert cm.predict_ms("mxint4", 1) == pytest.approx(5.0)


def test_cost_model_observe_refreshes_per_row_term():
    cm = CostModel(hbm_bytes_per_s=1e9, min_ticks=1)
    cm.seed("mxint8", 1e6, 1e5)
    cm.observe("mxint8", 2, 2e-3, attn_bytes_per_row=2e5)
    assert cm.terms["mxint8"].per_row_s == pytest.approx(2e-4)
    # factor uses the refreshed raw: 2ms / (1ms + 2*0.2ms) = 10/7
    assert cm.terms["mxint8"].factor == pytest.approx(2.0 / 1.4)


def test_cost_model_unseeded_observe_bootstraps_flat_term():
    cm = CostModel(hbm_bytes_per_s=1e9, min_ticks=1)
    cm.observe("bf16", 3, 4e-3)
    assert cm.has_estimate("bf16")
    assert cm.terms["bf16"].per_row_s == 0.0
    assert cm.predict_ms("bf16", 1) == pytest.approx(4.0)
    assert cm.predict_ms("bf16", 7) == pytest.approx(4.0)  # rows-flat


def test_cost_model_snapshot_and_validation():
    with pytest.raises(ValueError):
        CostModel(hbm_bytes_per_s=1e9, ema=0.0)
    cm = CostModel(hbm_bytes_per_s=1e9)
    cm.seed("mxint8", 1e6, 1e5)
    snap = cm.snapshot()
    assert set(snap) == {"mxint8"}
    assert set(snap["mxint8"]) == {"base_s", "per_row_s", "factor",
                                   "ticks_observed", "predict_1row_ms"}
    assert snap["mxint8"]["ticks_observed"] == 0


def test_cost_model_from_roofline_seeds_every_format():
    cfg = get_reduced("smollm-135m")
    cm = CostModel.from_roofline(cfg, ("mxint4", "mxint8", "bf16"),
                                 max_len=64, kv_layout="paged",
                                 kv_page_size=8, hbm_bytes_per_s=1e9)
    for f in ("mxint4", "mxint8", "bf16"):
        assert cm.has_estimate(f)
        assert cm.raw_predict_s(f, 1) > 0
    # The analytic shape the policy relies on: narrower formats stream
    # fewer weight bytes per tick.
    assert cm.terms["mxint4"].base_s < cm.terms["mxint8"].base_s \
        < cm.terms["bf16"].base_s
    # Attention term is format-independent (KV stays at compute dtype).
    assert cm.terms["mxint4"].per_row_s \
        == pytest.approx(cm.terms["mxint8"].per_row_s)


# ------------------------------------------------- engine: arrivals & tiers

def test_engine_rejects_unknown_admission_order():
    with pytest.raises(ValueError):
        _engine(admission_order="sjf")


@pytest.mark.slow
def test_arrival_tick_gates_admission():
    """A request is invisible to the scheduler before its arrival tick:
    the engine idles (or serves others) until it comes due, then stamps
    ``arrival_s``/``admitted_tick``."""
    cfg, eng = _engine(slots=2)
    now = _req(cfg, 0, max_new=2)
    late = _req(cfg, 1, max_new=2, arrival_tick=4)
    eng.generate([now, late], fmt_override="mxint8")
    assert now.status is RequestStatus.COMPLETED
    assert late.status is RequestStatus.COMPLETED
    assert now.admitted_tick == 0
    assert late.admitted_tick >= 4
    assert late.arrival_s is not None and late.ttft_s >= late.arrival_s


@pytest.mark.slow
def test_slo_admission_order_serves_latency_tier_first():
    """With one slot and simultaneous arrivals, ``admission_order="slo"``
    admits the latency-tier request before earlier-queued lower tiers;
    FIFO admits by queue position. Token streams are unaffected either
    way (per-slot RNG is keyed by rid, not admission order)."""
    def run(order):
        cfg, eng = _engine(slots=1, admission_order=order)
        reqs = [_req(cfg, 0, max_new=2, slo=SLOClass.best_effort()),
                _req(cfg, 1, max_new=2, slo=SLOClass.throughput()),
                _req(cfg, 2, max_new=2, slo=SLOClass.latency(
                    ttft_ms=1e4, tpot_ms=1e4))]
        eng.generate(reqs, fmt_override="mxint8")
        assert all(r.status is RequestStatus.COMPLETED for r in reqs)
        return {r.rid: r.admitted_tick for r in reqs}, \
            {r.rid: r.out_tokens for r in reqs}

    fifo_adm, fifo_tok = run("fifo")
    slo_adm, slo_tok = run("slo")
    assert fifo_adm[0] < fifo_adm[1] < fifo_adm[2]      # queue position
    assert slo_adm[2] < slo_adm[1] < slo_adm[0]         # tier rank
    assert fifo_tok == slo_tok                          # streams untouched


@pytest.mark.slow
def test_snapshot_roundtrip_preserves_slo_fields(tmp_path):
    """Snapshot/resume carries the new per-request fields (slo, tenant,
    arrival/admission stamps, sampling params) and the per-slot sampling
    lanes, and the resumed engine finishes the wave identically."""
    from repro.runtime.fault import FaultInjector, PreemptionGuard

    def build(order, injector=None):
        cfg, eng = _engine(slots=2, admission_order=order,
                           temperature=0.8, top_p=0.9,
                           fault_injector=injector)
        reqs = [_req(cfg, 0, max_new=6, slo=SLOClass.latency(
                         ttft_ms=1e4, tpot_ms=1e4),
                     tenant="interactive", temperature=0.7, top_p=0.95),
                _req(cfg, 1, max_new=6, tenant="bulk", arrival_tick=1)]
        return cfg, eng, reqs

    cfg, eng, reqs = build("slo", FaultInjector(preempt_at=3))
    eng.generate(list(reqs), fmt_override="mxint8", greedy=False,
                 guard=PreemptionGuard(), snapshot_dir=str(tmp_path))
    assert not all(r.done for r in reqs)       # genuinely interrupted
    _, eng2, _ = build("slo")
    resumed = eng2.resume(str(tmp_path))
    by_rid = {r.rid: r for r in resumed}
    assert by_rid[0].slo == SLOClass.latency(ttft_ms=1e4, tpot_ms=1e4)
    assert by_rid[0].tenant == "interactive"
    assert by_rid[0].temperature == 0.7 and by_rid[0].top_p == 0.95
    assert by_rid[1].tenant == "bulk" and by_rid[1].arrival_tick == 1

    # Reference: the same wave run straight through, no snapshot detour.
    cfg3, eng3, ref = build("slo")
    eng3.generate(list(ref), fmt_override="mxint8", greedy=False)
    assert {r.rid: r.out_tokens for r in ref} \
        == {r.rid: r.out_tokens for r in resumed}


@pytest.mark.slow
def test_stats_expose_cost_model_and_admission_order():
    """After a wave with a cost model attached, stats() reports the
    calibrated terms; the engine re-seeds the model from *measured* packed
    bytes when it builds a format's serving tree."""
    cfg = get_reduced("smollm-135m")
    pol = FormatPolicy(anchor="mxint8",
                       ladder=((6, "mxint4"), (0, "mxint8")),
                       cost=CostModel.from_roofline(
                           cfg, ("mxint4", "mxint8"), max_len=48,
                           hbm_bytes_per_s=1e9))
    seeded_base = pol.cost.terms["mxint8"].base_s
    _, eng = _engine(slots=2, policy=pol, admission_order="slo")
    reqs = [_req(cfg, i, max_new=6,
                 slo=SLOClass.latency(ttft_ms=1e4, tpot_ms=1e4))
            for i in range(2)]
    eng.generate(reqs, fmt_override="mxint8")
    st = eng.stats
    assert st["admission_order"] == "slo"
    assert "mxint8" in st["cost_model"]
    term = st["cost_model"]["mxint8"]
    # Re-seeded from the measured packed tree (exact bytes, not analytic).
    # The analytic seed must have been close — it feeds the policy before
    # the first wave — but the term of record is the measured one.
    assert term["base_s"] * pol.cost.hbm_bytes_per_s \
        == pytest.approx(st["weight_bytes"]["mxint8"])
    assert term["base_s"] == pytest.approx(seeded_base, rel=0.05)
    # Clean pure-decode ticks were observed (first one skipped as jit
    # warmup), so the rung is on its way to "measured".
    assert term["ticks_observed"] >= 1
    assert term["predict_1row_ms"] > 0
