"""Tables 1-2 analog: downstream-task grid (QAT precision x PTQ precision).

No MMLU/MathQA/HellaSwag offline — the stand-in downstream metric is
held-out next-token top-1 accuracy on the synthetic corpus (its copy-motif
structure makes accuracy a meaningful skill metric, not just inverted PPL).
Rows: FP FT, each single-format QAT, multi-format QAT. Columns: every eval
format (starred = unseen during training). Claim: MF-QAT within ~1 point of
the best row per column (3 points at 2-bit), mirroring the paper.
"""
import time

from benchmarks._qat_harness import (EVAL_MXINT, HarnessConfig,
                                     eval_accuracy, train_variant)


def run(hc: HarnessConfig = None):
    hc = hc or HarnessConfig(arch="qwen3-4b")
    variants = {"fp_ft": "fp", "multiformat": "multiformat"}
    for i, f in enumerate(hc.train_formats):
        variants[f"single_{f}"] = f"single:{i}"
    table = {}
    models = {}
    for vname, sched in variants.items():
        out = train_variant(hc, sched)
        models[vname] = out
        table[vname] = {
            ef: eval_accuracy(out["cfg"], out["api"], out["params"], ef, hc)
            for ef in EVAL_MXINT}
    return table


def main():
    t0 = time.time()
    table = run()
    unseen = {"mxint3", "mxint5", "mxint7"}
    print("# table12: accuracy (x100) by QAT variant x PTQ precision "
          "(* = unseen)")
    hdr = "variant," + ",".join(
        (f + "*" if f in unseen else f) for f in EVAL_MXINT)
    print(hdr)
    for v, row in table.items():
        print(v + "," + ",".join(f"{row[f] * 100:.1f}" for f in EVAL_MXINT))
    # claim check: multiformat within margin of best per column
    ok, margin = True, 0.0
    for ef in EVAL_MXINT:
        best = max(table[v][ef] for v in table)
        gap = best - table["multiformat"][ef]
        margin = max(margin, gap)
        tol = 0.05 if ef == "mxint2" else 0.03
        ok &= gap <= tol
    print(f"table12_downstream,{(time.time() - t0) * 1e6:.0f},"
          f"multi_within_margin={ok}:max_gap={margin * 100:.1f}pts")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
