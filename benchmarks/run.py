"""Benchmark driver: one benchmark per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV rows per benchmark. Heavy QAT
benchmarks train 6 model variants each; pass --fast to skip the two longest
(fig1 / table12).
"""
import argparse
import sys
import time
import traceback


def _run(name, fn):
    print(f"\n===== {name} =====", flush=True)
    t0 = time.time()
    try:
        fn()
        print(f"[{name}] ok in {time.time() - t0:.1f}s", flush=True)
        return True
    except Exception:
        traceback.print_exc()
        print(f"{name},FAILED,", flush=True)
        return False


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default=None)
    args, rest = ap.parse_known_args()
    sys.argv = [sys.argv[0]]     # sub-benchmarks parse argv themselves

    from benchmarks import (appc_ss_mse, fig1_multiformat_qat, fig23_ss_ppl,
                            fig4_anchor_pipeline, kernels_bench, perf_ladder,
                            roofline, table12_downstream)

    benches = [
        ("appc_ss_mse", appc_ss_mse.main),
        ("fig23_ss_ppl", fig23_ss_ppl.main),
        ("fig4_anchor_pipeline", fig4_anchor_pipeline.main),
        ("kernels_bench", kernels_bench.main),
        ("roofline", roofline.main),
        ("perf_ladder", perf_ladder.main),
    ]
    if not args.fast:
        benches.insert(1, ("fig1_multiformat_qat", fig1_multiformat_qat.main))
        benches.insert(4, ("table12_downstream", table12_downstream.main))

    ok = True
    for name, fn in benches:
        if args.only and args.only != name:
            continue
        ok &= _run(name, fn)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
