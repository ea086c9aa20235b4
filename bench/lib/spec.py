"""Finding a cell's files by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
files sit at fixed places under ``bench/``:

* ``configs/<config>.json``: the model as run, its source, its deployment
  (anchor, slots, chunk, page size, context);
* ``traffic/<traffic>.json``: the mix (``bench/lib/traffic.py``);
* ``limits/<workload>.json``: each number the correctness check compares,
  with its limit and the readings it was set from;
* ``metrics/<metric>.py``: one reader per per-layer metric, ``read(run)``.

A later change adds a configuration, a mix, a cell or a metric by adding
files and entries; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell(name: str, root: Path = ROOT) -> dict:
    """The workload entry with its config, mix and limits loaded."""
    spec = benchmark(root)
    for w in spec["workloads"]:
        if w["name"] == name:
            bench = root / "bench"
            return {"workload": w,
                    "config": load_json(bench / "configs"
                                        / f"{w['config']}.json"),
                    "traffic": load_json(bench / "traffic"
                                         / f"{w['traffic']}.json"),
                    "limits": load_json(bench / "limits" / f"{name}.json"),
                    "end_to_end": [m for m in spec["end_to_end"]
                                   if name in m.get("workloads", [name])],
                    "per_layer": [m for m in spec["per_layer"]
                                  if name in m.get("workloads", [name])]}
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in spec['workloads']]}")


def reader(metric: str, root: Path = ROOT):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
