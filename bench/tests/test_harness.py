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


def make_root(r: Path, tp: int = 1, chips: int = None) -> Path:
    """A checkout root that holds the small cell, on a ``(1, tp)`` mesh
    over ``chips`` chips (``tp`` where None)."""
    (r / "bench").mkdir()
    shutil.copytree(BENCH / "metrics", r / "bench" / "metrics")
    for d in ("configs", "traffic", "limits"):
        (r / "bench" / d).mkdir()
    doc = spec.benchmark()
    doc["configs"] = [{"name": "small", "source": "x", "reduced": [],
                       "file": "bench/configs/small.json", "why": "test"}]
    doc["workloads"] = [{"name": "small.chat.mxint8", "config": "small",
                         "traffic": "chat.mxint8",
                         "chips": tp if chips is None else chips,
                         "why": "test"}]
    for m in doc["per_layer"]:
        m["workloads"] = ["small.chat.mxint8"]
    (r / "BENCHMARK.json").write_text(json.dumps(doc))
    cfg = dict(CFG, engine=dict(CFG["engine"], tp=tp)) if tp > 1 else CFG
    (r / "bench" / "configs" / "small.json").write_text(json.dumps(cfg))
    (r / "bench" / "traffic" / "chat.mxint8.json").write_text(
        json.dumps(MIX))
    (r / "bench" / "limits" / "small.chat.mxint8.json").write_text(
        json.dumps({"logit_gap": {"limit": 0.008}}))
    return r


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench_root"))


@pytest.fixture(scope="module")
def root_tp2(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench_root_tp2"), tp=2)


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


def test_a_run_on_a_tp2_mesh_is_correct(root_tp2):
    import jax
    assert len(jax.devices()) >= 2
    r = run(root_tp2)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"ttft_p90_ms", "itl_p95_ms", "setup_s"}


def test_a_tp2_mesh_serves_the_tokens_of_one_device(root, root_tp2):
    """The harness's sharded anchor and meshed engine serve, request for
    request, the tokens of the one-device engine (greedy, one wave), up to
    a tie that rounding decides: in bfloat16 a row-parallel projection's
    partial sums are rounded before their psum, so the two engines' logits
    differ by a few thousandths. Where a stream departs, the one-device
    engine's logits of the two tokens lie closer together than the two
    engines' logits lie apart: that is the criterion.

    Readings (CPU, 8 requests): at this seed 4 streams depart, at seeds
    2**31+101 and +207 3 and 2, each at a top-2 gap of 0.0003-0.0028
    under a logit difference of 0.0037-0.0059. With the row-parallel
    partials summed in float32 (a copy of the program so changed), all 8
    streams agree on all three seeds: rounding, not sharding, parts them.
    The floor of half the streams whole is the fewest those seeds read;
    a path that departed more often than near-ties explain fails it even
    where each departure passes."""
    import numpy as np
    from bench.lib.traffic import make_arrivals
    sessions = [cell.Session("small.chat.mxint8", SEED, root=r, chip=False)
                for r in (root, root_tp2)]
    assert [s.tp for s in sessions] == [1, 2]
    arrivals = make_arrivals(sessions[0].mix, CFG["model"]["vocab"], SEED,
                             [1, 3, 1])[:8]
    streams = []
    for s in sessions:
        reqs = [s.Request(rid=i, prompt=a.prompt, max_new=a.max_new)
                for i, a in enumerate(arrivals)]
        s.eng.generate(reqs, greedy=True, fmt_override=s.rung)
        streams.append([list(q.out_tokens) for q in reqs])
    one, tp2 = streams
    assert all(one) and [len(t) for t in one] == [len(t) for t in tp2]
    same = 0
    for a, x, y in zip(arrivals, one, tp2):
        j = next((j for j, (u, v) in enumerate(zip(x, y)) if u != v), None)
        if j is None:
            same += 1
            continue
        prefix = np.concatenate([a.prompt, np.asarray(x[:j], np.int32)])
        l1, l2 = (s.eng.prompt_logits(prefix, s.rung) for s in sessions)
        assert abs(l1[x[j]] - l1[y[j]]) < np.max(np.abs(l1 - l2)) < 0.02
    assert same >= len(arrivals) // 2
    for s in sessions:
        s.close()


def test_a_cell_whose_chips_are_not_its_tp_is_refused(tmp_path):
    r = make_root(tmp_path, tp=2, chips=1)
    with pytest.raises(ValueError, match="tp 2"):
        cell.Session("small.chat.mxint8", SEED, root=r, chip=False)


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
