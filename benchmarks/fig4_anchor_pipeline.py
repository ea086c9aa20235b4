"""Figure 4: the full pipeline — multi-format QAT *with anchor storage* (§3.5)
vs plain multi-format QAT.

Anchor variant trains with W_t = Q_{A->t}(Q_A(W)) (STE through both) cycling
target formats uniformly, stores only the anchor, and serves every format via
SS. Claim C3: the SS-anchored curve closely matches plain MF-QAT across the
precision range (MXINT nearly indistinguishable; small MXFP gap at
intermediate widths).
"""
import time

from benchmarks._qat_harness import (EVAL_MXFP, EVAL_MXINT, HarnessConfig,
                                     eval_ppl, train_variant)


def run(kind="mxint"):
    if kind == "mxint":
        fmts, evals, anchor = (("mxint2", "mxint4", "mxint6", "mxint8"),
                               EVAL_MXINT, "mxint8")
    else:
        fmts, evals, anchor = (("mxfp4", "mxfp6", "mxfp8"), EVAL_MXFP,
                               "mxfp8")

    plain = train_variant(HarnessConfig(train_formats=fmts), "multiformat")
    anchored = train_variant(
        HarnessConfig(train_formats=fmts, anchor=anchor), "interleaved")

    rows = []
    for ef in evals:
        hc = HarnessConfig(train_formats=fmts, anchor=anchor)
        p_plain = eval_ppl(plain["cfg"], plain["api"], plain["params"],
                           ef, hc)
        p_anchor_ss = eval_ppl(anchored["cfg"], anchored["api"],
                               anchored["params"], ef, hc,
                               use_anchor_ss=True)
        rows.append({"fmt": ef, "ppl_multiformat": p_plain,
                     "ppl_anchor_ss": p_anchor_ss})
    return rows


def main():
    t0 = time.time()
    worst = 0.0
    for kind in ("mxint", "mxfp"):
        rows = run(kind)
        print(f"# fig4 {kind}: plain MF-QAT vs MF-QAT + anchor storage + SS")
        print("fmt,ppl_multiformat,ppl_anchor_ss,rel_gap")
        for r in rows:
            gap = abs(r["ppl_anchor_ss"] - r["ppl_multiformat"]) \
                / r["ppl_multiformat"]
            worst = max(worst, gap)
            print(f'{r["fmt"]},{r["ppl_multiformat"]:.3f},'
                  f'{r["ppl_anchor_ss"]:.3f},{gap:.4f}')
    print(f"fig4_anchor_pipeline,{(time.time() - t0) * 1e6:.0f},"
          f"worst_rel_gap={worst:.4f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
