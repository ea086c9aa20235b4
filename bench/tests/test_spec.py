"""A configuration, a mix, a cell and a metric added as files are found by
name, with no edit to any file that is there."""
import json
import shutil
from pathlib import Path

from bench.lib import spec

BENCH = Path(__file__).resolve().parents[1]


def test_added_files_are_found_by_name(tmp_path):
    root = tmp_path
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    doc = spec.benchmark(spec.ROOT)
    cfg = json.loads((BENCH / "configs" / "qwen3-4b.json").read_text())
    cfg["name"] = "newmodel"
    (root / "bench" / "configs" / "newmodel.json").write_text(
        json.dumps(cfg))
    mix = json.loads((BENCH / "traffic" / "chat.mxint8.json").read_text())
    mix.update(name="burst.mxint4", rung="mxint4", rate_per_s=0.25)
    (root / "bench" / "traffic" / "burst.mxint4.json").write_text(
        json.dumps(mix))
    (root / "bench" / "limits" / "newmodel.burst.mxint4.json").write_text(
        json.dumps({"logit_gap": {"limit": 0.5}}))
    (root / "bench" / "metrics" / "decode_rows.py").write_text(
        "def read(run):\n    return float(len(run))\n")
    doc["configs"].append({"name": "newmodel", "source": "x",
                           "file": "bench/configs/newmodel.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "newmodel.burst.mxint4",
                             "config": "newmodel", "traffic": "burst.mxint4",
                             "chips": 1, "why": "test"})
    doc["per_layer"].append({"name": "decode_rows", "unit": "rows",
                             "better": "higher", "source": "program_counter",
                             "layer": "scheduler", "moves": "itl_p95_ms",
                             "workloads": ["newmodel.burst.mxint4"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))

    c = spec.cell("newmodel.burst.mxint4", root)
    assert c["config"]["name"] == "newmodel"
    assert c["traffic"]["rung"] == "mxint4"
    assert c["limits"]["logit_gap"]["limit"] == 0.5
    assert "decode_rows" in [m["name"] for m in c["per_layer"]]
    assert spec.reader("decode_rows", root)([1, 2, 3]) == 3.0
    # the metric is restricted to its cell; the others do not list it
    old = spec.cell("qwen3-4b.chat.mxint8", root)
    assert "decode_rows" not in [m["name"] for m in old["per_layer"]]


def test_every_cell_of_the_benchmark_resolves():
    doc = spec.benchmark()
    for w in doc["workloads"]:
        c = spec.cell(w["name"])
        assert c["config"]["name"] == w["config"]
        assert c["traffic"]["name"] == w["traffic"]
        assert c["limits"]["logit_gap"]["limit"] > 0
    for m in doc["per_layer"]:
        assert callable(spec.reader(m["name"]))
