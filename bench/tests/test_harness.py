"""A whole run on the CPU at a small size: the harness without its look for
a chip, the timed path underneath intact, broken, or swapped for its
control.

The small cell keeps the real cells' shape (GQA with q/k norms, a vocabulary
of 8192 so that near-ties occur) at widths an interpret-mode run can hold.
Its limit, 0.008, sits between the readings these sizes give: the program
read 0.0007-0.0035 over seeds 1-3, the float8 control 0.017-0.030 and the
program at MXINT4 against the MXINT8 reference 0.09-0.20 (CPU runs).
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench.lib import cell, spec
from bench.lib.readings import fp8_control

BENCH = Path(__file__).resolve().parents[1]
CFG = {"name": "small", "model": {
    "n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
    "head_dim": 32, "d_ff": 256, "vocab": 8192, "act": "swiglu",
    "norm_eps": 1e-5, "rope_theta": 1e6, "qk_norm": True, "qkv_bias": False,
    "mlp_bias": False, "tie_embeddings": False},
    "engine": {"anchor": "mxint8", "block_size": 32, "batch_slots": 4,
               "prefill_chunk": 16, "kv_page_size": 8, "max_len": 128}}
MIX = {"name": "chat.mxint8", "rate_per_s": 3.0, "ramp_s": 1,
       "drain_limit_s": 30, "trace_s": 1, "rung": "mxint8",
       "prompt": {"mean": 20, "sigma": 0.8, "min": 4, "max": 48},
       "output": {"mean": 24, "sigma": 0.3, "min": 8, "max": 48},
       "check": {"served_tokens": 160, "max_requests": 8}}
SEED = 2 ** 31 + 3


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = tmp_path_factory.mktemp("bench_root")
    (r / "bench").mkdir()
    shutil.copytree(BENCH / "metrics", r / "bench" / "metrics")
    for d in ("configs", "traffic", "limits"):
        (r / "bench" / d).mkdir()
    doc = spec.benchmark()
    doc["configs"] = [{"name": "small", "source": "x", "reduced": [],
                       "file": "bench/configs/small.json", "why": "test"}]
    doc["workloads"] = [{"name": "small.chat.mxint8", "config": "small",
                         "traffic": "chat.mxint8", "chips": 1,
                         "why": "test"}]
    for m in doc["per_layer"]:
        m["workloads"] = ["small.chat.mxint8"]
    (r / "BENCHMARK.json").write_text(json.dumps(doc))
    (r / "bench" / "configs" / "small.json").write_text(json.dumps(CFG))
    (r / "bench" / "traffic" / "chat.mxint8.json").write_text(
        json.dumps(MIX))
    (r / "bench" / "limits" / "small.chat.mxint8.json").write_text(
        json.dumps({"logit_gap": {"limit": 0.008}}))
    return r


def run(root, **kw):
    return cell.run("small.chat.mxint8", SEED, 3.0, kw.pop("trace", False),
                    t_start=0.0, root=root, chip=False, **kw)


def test_a_sound_run_is_correct(root, capsys):
    r = run(root)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"ttft_p90_ms", "itl_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert r["checks"]["logit_gap"]["limit"] == 0.008
    err = capsys.readouterr().err
    assert "compiles inside the window: 0" in err
    assert "release lateness" in err


def test_a_traced_run_reads_the_counters(root, capsys):
    r = run(root, trace=True)
    assert r["correct"] is True
    m = r["metrics"]
    # no device trace on the CPU: only the readers of counters and spans
    assert 0 < m["useful_token_share"]["value"] <= 100
    assert m["queue_wait_p90_ms"]["value"] >= 0
    assert m["ss_convert_s"]["value"] >= 0
    assert "gemm_roofline" not in m and "idle_share" not in m
    assert "rebuilt rows disagree with the engine on 0" in \
        capsys.readouterr().err


def test_a_token_altered_where_it_is_produced_fails(root):
    """The engine's sampler returns a wrong token for slot 0 on every
    decode tick."""
    import jax.numpy as jnp

    def tamper(eng):
        real = eng._sample

        def sample(logits, greedy, slot=None):
            tok = real(logits, greedy, slot)
            if slot is None:
                tok = tok.at[0].set((tok[0] + 1) % logits.shape[-1])
            return tok.astype(jnp.int32)
        eng._sample = sample
    assert run(root, tamper=tamper)["correct"] is False


def test_the_float8_control_fails(root):
    r = run(root, control=fp8_control)
    assert r["correct"] is False
    assert r["checks"]["logit_gap"]["value"] > 0.008


def test_the_program_at_the_lower_rung_fails(root):
    assert run(root, rung="mxint4", ref_rung="mxint8")["correct"] is False


def test_run_exits_nonzero_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen3-4b.chat.mxint8", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=spec.ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 2 and p.stdout == ""
    assert "accelerator" in p.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen3-4b.chat.mxint8", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""
