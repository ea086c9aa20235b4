"""Readings that set a cell's correctness limit: program and control.

    python3 bench/lib/readings.py --workload <cell> --seeds 1,2,3 \
        --seconds 10 [--control program:mxint4 | --control fp8]

One process, one seed after another (the compiled programs carry over, so
each seed pays only its weights and its window). For each seed it prints
one JSON line with the program's reading (``logit_gap`` of the served
tokens against the cell's reference, and ``correct``) and, with
``--control``, one with the control's, which takes the program's place in
the same check and has to come out not correct:

* ``program:<rung>``: the program serving the lower rung ``<rung>``,
  checked against the cell's own reference;
* ``fp8``: the reference computing every projection's input in
  float8_e4m3fn, its first choices at the program's served positions
  checked in place of the served tokens.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def fp8_control(model, seed, eng_cfg, rung):
    import jax.numpy as jnp
    from bench.lib.cell import rung_bits
    from bench.lib.reference import Reference
    return Reference(model, seed, anchor_bits=rung_bits(eng_cfg["anchor"]),
                     bits=rung_bits(rung), block_size=eng_cfg["block_size"],
                     act_dtype=jnp.float8_e4m3fn)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", default=None)
    args = ap.parse_args(argv)

    from bench.run import chips_ok, use_cache
    from bench.lib import cell, spec
    control = {}
    if args.control == "fp8":
        control = {"control": fp8_control}
    elif args.control and args.control.startswith("program:"):
        control = {"rung": args.control.split(":", 1)[1],
                   "ref_rung": spec.cell(args.workload)["traffic"]["rung"]}
    elif args.control:
        ap.error(f"unknown --control {args.control!r}")
    use_cache(ROOT)
    if not chips_ok(spec.cell(args.workload)["workload"]["chips"]):
        return 2
    for seed in [int(x) for x in args.seeds.split(",")]:
        # each reading is printed as soon as it is taken, so that a control
        # that fails leaves the program's reading behind
        for name, kw in [("program", {})] + ([("control", control)]
                                             if control else []):
            t0 = time.perf_counter()
            r = cell.run(args.workload, seed, args.seconds, False,
                         t_start=t0, **kw)
            print(json.dumps({
                "workload": args.workload, "seed": seed, "run": name,
                "logit_gap": r["checks"]["logit_gap"]["value"],
                "correct": r["correct"],
                "ttft_p90_ms": r["metrics"].get("ttft_p90_ms", {}).get(
                    "value"),
                "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
