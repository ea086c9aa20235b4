"""The knee: one process steps one cell through fixed arrival rates.

    python3 bench/lib/sweep.py --workload <cell> --rates 0.5,1,1.5 \
        --seconds 30 [--rung mxint4] [--slots 8] [--chunk 128] [--seed N]

Set-up runs once; each rate then runs its own ramp, window and drain on the
same engine, and prints one JSON line: the rate, the requests due in the
window, those not admitted by its close (a backlog that grows with the
window means the rate is past the knee), the first-token and gap tails, and
the share of requests that met both limits of ``--ttft-limit-ms`` and
``--itl-limit-ms``. The knee is the highest rate at which the backlog stays
near zero; a cell's ``rate_per_s`` is four fifths of it.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=12345)
    ap.add_argument("--rung", default=None)
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--chunk", type=int, default=None)
    ap.add_argument("--ttft-limit-ms", type=float, default=2000.0)
    ap.add_argument("--itl-limit-ms", type=float, default=200.0)
    args = ap.parse_args(argv)

    from bench.run import chips_ok, use_cache
    from bench.lib import cell, spec, stats
    use_cache(ROOT)
    if not chips_ok(spec.cell(args.workload)["workload"]["chips"]):
        return 2
    over = {k: v for k, v in (("batch_slots", args.slots),
                              ("prefill_chunk", args.chunk)) if v}
    s = cell.Session(args.workload, args.seed, rung=args.rung, engine=over)
    for rate in [float(r) for r in args.rates.split(",")]:
        t0 = time.perf_counter()
        data = s.serve(args.seconds, False, rate)
        summ = cell.summarize(data, args.seconds, rate)
        p, reqs = data.pacer, data.requests
        met = 0
        for i in summ["win"]:
            st = reqs[i].out_tokens.stamps
            if i in summ["failed"] or not st:
                continue
            gaps = [b - a for a, b in zip(st, st[1:])]
            if (st[0] - p.due_abs(i)) * 1e3 <= args.ttft_limit_ms and \
                    (stats.percentile(gaps, 95) or 0) * 1e3 \
                    <= args.itl_limit_ms:
                met += 1
        print(json.dumps({
            "workload": args.workload, "rung": s.rung, "rate": rate,
            "slots": s.slots, "chunk": s.chunk,
            "due": len(summ["win"]), "backlog_at_close": summ["backlog"],
            "failed": len(summ["failed"]),
            "ttft_p90_ms": summ["ttft_p90_ms"],
            "itl_p95_ms": summ["itl_p95_ms"],
            "met_both": met / max(1, len(summ["win"])),
            "compiles_in_window": summ["compiles_in_window"],
            "wall_s": time.perf_counter() - t0}), flush=True)
    s.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
