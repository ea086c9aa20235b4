"""The qwen2-72b cell's form at a small size on the CPU: a Qwen2-style
model (q/k/v biases, untied lm_head, no q/k norm, a GQA group of 8) on the
harness's ``(1, 2)`` mesh, whose per-chip d_ff share (1056) the program
lays out tile-aligned at pack time; the exchange's bytes and roofline.

The small cell's limit, 0.01, sits between the readings these sizes give
(CPU runs): the program 0.0, 0.0011 and 0.0039 at seeds 2**31+3, +5, +7,
and 0.0057 and 0.0075 on seed 2**31+3's weights with the arrivals of
seeds +5 and +7; the float8 control 0.052, 0.035 and 0.056 on the first
three.
"""
import importlib.util
import json
import types

import pytest

from bench.lib import cell, spec
from bench.lib.readings import fp8_control
from bench.lib.trace import Event, Reduced
from bench.lib.work import Shapes
from bench.tests.test_harness import SEED, make_root

QWEN2_SMALL = {"name": "small", "model": {
    "n_layers": 2, "d_model": 256, "n_heads": 16, "n_kv_heads": 2,
    "head_dim": 16, "d_ff": 2112, "vocab": 8192, "act": "swiglu",
    "norm_eps": 1e-6, "rope_theta": 1e6, "qk_norm": False, "qkv_bias": True,
    "mlp_bias": False, "tie_embeddings": False},
    "engine": {"anchor": "mxint8", "block_size": 32, "batch_slots": 4,
               "prefill_chunk": 16, "kv_page_size": 8, "max_len": 128,
               "tp": 2}}
LIMIT = 0.01


def _exchange():
    path = spec.BENCH / "metrics" / "exchange_roofline.py"
    s = importlib.util.spec_from_file_location("exchange_roofline", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = make_root(tmp_path_factory.mktemp("bench_root_qwen2"), tp=2)
    (r / "bench" / "configs" / "small.json").write_text(
        json.dumps(QWEN2_SMALL))
    (r / "bench" / "limits" / "small.chat.mxint8.json").write_text(
        json.dumps({"logit_gap": {"limit": LIMIT}}))
    return r


def run(root, **kw):
    return cell.run("small.chat.mxint8", SEED, 3.0, kw.pop("trace", False),
                    t_start=0.0, root=root, chip=False, **kw)


@pytest.mark.parametrize("trace", [False, True])
def test_the_qwen2_form_at_tp2_is_correct(root, trace):
    r = run(root, trace=trace)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    if trace:
        # no device trace on the CPU: the device readers read nothing
        assert "collective_share" not in r["metrics"]
        assert "exchange_roofline" not in r["metrics"]
        assert 0 < r["metrics"]["useful_token_share"]["value"] <= 100
    else:
        assert set(r["metrics"]) == {"ttft_p90_ms", "itl_p95_ms",
                                     "setup_s"}


def test_its_float8_control_fails(root):
    r = run(root, control=fp8_control)
    assert r["correct"] is False
    assert r["checks"]["logit_gap"]["value"] > LIMIT


def test_the_program_counts_the_readers_exchange_bytes(root):
    """The engine's per-tick ``exchange_bytes`` (from the collectives in
    the jaxpr of the step it ran) equals the reader's algorithmic payload
    for a decode tick and a mixed tick."""
    s = cell.Session("small.chat.mxint8", SEED, root=root, chip=False)
    payload = _exchange().payload
    shapes = Shapes.from_model(s.model, 32, s.tp)
    seen = set()
    for e in s.eng.tick_trace:
        if e["kind"] == "decode":
            m = s.slots
        elif e["kind"] == "mixed":
            m = s.slots * e["prefill_tokens"]
        else:
            continue
        assert e["exchange_bytes"] == sum(payload(shapes, e["kind"], m,
                                                  s.slots)), e
        seen.add(e["kind"])
    assert seen == {"decode", "mixed"}
    s.close()


def _synthetic(tp, collective_ns):
    """A run of ``n`` decode ticks whose every chip spent
    ``collective_ns`` in all-reduces inside a one-second window."""
    shapes = Shapes.from_model(QWEN2_SMALL["model"], 32, tp)
    n, slots = 5, 4
    ticks = [{"decode": 1, "prefill_chunks": 0, "prefill_tokens": 0,
              "decode_rows": 0}] * n
    ops = {f"/device:TPU:{i}": [
        Event("all-reduce.1", 1e8, 1e8 + collective_ns / 2),
        Event("fusion.2", 2e8, 3e8),
        Event("all-gather-done.3", 4e8, 4e8 + collective_ns / 2)]
        for i in range(tp)}
    return types.SimpleNamespace(
        shapes=shapes, slots=slots, chunk=16, tick_trace=ticks, requests=[],
        pacer=types.SimpleNamespace(trace_ticks=(0, n), tick_of=lambda s: 0),
        trace=Reduced(ops=ops, host=[], window=(0.0, 1e9)))


def test_exchange_roofline_reads_the_least_time_share(monkeypatch):
    mod = _exchange()
    bw = 200e9
    monkeypatch.setattr(mod, "ici_bytes_per_s", lambda kind: bw)
    shapes = Shapes.from_model(QWEN2_SMALL["model"], 32, 2)
    red, gat = mod.payload(shapes, "decode", 4, 4)
    least_ns = 5 * (red + gat / 2) / bw * 1e9      # n = 2: 2(n-1)/n = 1
    assert mod.read(_synthetic(2, least_ns)) == pytest.approx(100.0)
    assert mod.read(_synthetic(2, 4 * least_ns)) == pytest.approx(25.0)
    # one chip has no exchange, and a trace without collectives no reading
    assert mod.read(_synthetic(1, least_ns)) is None
    assert mod.read(_synthetic(2, 0.0)) is None


def test_the_configuration_keeps_the_published_widths():
    """``bench/configs/qwen2-72b.json`` runs the repo's Qwen2-72B at every
    width, cut only in the depth its ``reduced`` lists."""
    import jax.numpy as jnp
    from repro.configs.qwen2_72b import CONFIG
    f = spec.load_json(spec.BENCH / "configs" / "qwen2-72b.json")
    m = f["model"]
    got = {k: m[k] for k in ("d_model", "n_heads", "n_kv_heads", "head_dim",
                             "d_ff", "vocab", "act", "rope_theta",
                             "qkv_bias", "mlp_bias", "qk_norm",
                             "tie_embeddings")}
    assert got == {"d_model": CONFIG.d_model, "n_heads": CONFIG.n_heads,
                   "n_kv_heads": CONFIG.n_kv_heads, "head_dim": CONFIG.hd,
                   "d_ff": CONFIG.d_ff, "vocab": CONFIG.vocab,
                   "act": CONFIG.act, "rope_theta": CONFIG.rope_theta,
                   "qkv_bias": CONFIG.qkv_bias, "mlp_bias": CONFIG.mlp_bias,
                   "qk_norm": CONFIG.qk_norm,
                   "tie_embeddings": CONFIG.tie_embeddings}
    assert (CONFIG.n_layers, m["n_layers"]) == (80, 40)
    assert f["num_hidden_layers"] == m["n_layers"]
    assert (f["hidden_size"], f["intermediate_size"], f["vocab_size"]) == \
        (CONFIG.d_model, CONFIG.d_ff, CONFIG.vocab)
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == "qwen2-72b")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == f["source"] == \
        "https://huggingface.co/Qwen/Qwen2-72B"
    assert CONFIG.compute_dtype == jnp.bfloat16
    e = f["engine"]
    assert e["tp"] == 4 and e["kv_num_pages"] == \
        e["batch_slots"] * -(-e["max_len"] // e["kv_page_size"]) + 1
