#!/usr/bin/env python3
"""Run one benchmark cell once, in this process, and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; ``bench/lib/cell.py`` loads the weights, builds the engine,
warms up, measures the window and checks the served tokens against the
plain reference. ``--trace 0`` reports the end-to-end metrics, ``--trace
1`` the per-layer ones, read from a profiler trace of the window's last
seconds. The last line of standard output is one JSON object; the numbers
the check compared are the last lines of standard error and the result's
last key.

Without an accelerator, or with fewer chips than the cell asks for, it
exits 2 before any phase runs. JAX's compilation cache lives at
``<checkout>/.jax_cache``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.lib import cell, spec  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_cache(root: Path) -> None:
    """Every program in the persistent cache, small ones included, at a
    fixed directory inside the checkout."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def chips_ok(need: int) -> bool:
    import jax
    devs = jax.devices()
    if devs[0].platform not in ("tpu", "gpu") or len(devs) < need:
        print(f"bench: needs {need} accelerator chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return False
    return True


def emit(result: dict) -> None:
    """The checks as the last lines of stderr and the last key of the
    result line."""
    checks = result.pop("checks", {})
    for name, v in checks.items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    result["checks"] = checks
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    entry = spec.cell(args.workload)["workload"]
    use_cache(ROOT)
    if not chips_ok(entry["chips"]):
        return 2
    emit(cell.run(args.workload, args.seed, args.seconds, bool(args.trace),
                  t_start=T_START))
    return 0


if __name__ == "__main__":
    sys.exit(main())
