"""Figures 2-3: end-to-end perplexity, direct quantization vs Slice-and-Scale.

Llama-3.2-1B in the paper -> reduced llama-family (smollm) model here,
briefly fine-tuned, then evaluated with (i) direct PTQ to each format and
(ii) SS conversion from the 8-bit anchor. Sweeps: bits at block 64; block
size at 4 bits. Claim C2: the two curves are nearly identical.
"""
import time

from benchmarks._qat_harness import HarnessConfig, eval_ppl, train_variant


def run():
    hc = HarnessConfig(arch="smollm-135m", train_formats=("mxint8",),
                       block_size=64, epochs_per_format=2)
    out = train_variant(hc, "fp")      # plain fine-tune, like the paper's base
    cfg, api, params = out["cfg"], out["api"], out["params"]

    rows = []
    for kind, bits in (("int", range(2, 9)), ("fp", range(4, 9))):
        for b in bits:
            fmt = f"mx{kind}{b}"
            hcb = HarnessConfig(**{**hc.__dict__,
                                   "anchor": f"mx{kind}8"})
            direct = eval_ppl(cfg, api, params, fmt, hcb)
            ss = eval_ppl(cfg, api, params, fmt, hcb, use_anchor_ss=True)
            rows.append({"sweep": "bits@bs64", "fmt": fmt,
                         "block_size": 64, "ppl_direct": direct,
                         "ppl_ss": ss})
    for kind in ("int", "fp"):
        for bs in (16, 32, 64, 128):
            fmt = f"mx{kind}4"
            hcb = HarnessConfig(**{**hc.__dict__, "block_size": bs,
                                   "anchor": f"mx{kind}8"})
            direct = eval_ppl(cfg, api, params, fmt, hcb)
            ss = eval_ppl(cfg, api, params, fmt, hcb, use_anchor_ss=True)
            rows.append({"sweep": "bs@4bit", "fmt": fmt, "block_size": bs,
                         "ppl_direct": direct, "ppl_ss": ss})
    base = eval_ppl(cfg, api, params, None, hc)
    return rows, base


def main():
    t0 = time.time()
    rows, base = run()
    print("# fig23: direct PTQ vs SS-from-anchor perplexity "
          f"(fp baseline ppl={base:.2f})")
    print("sweep,fmt,block_size,ppl_direct,ppl_ss,rel_gap")
    worst = 0.0
    for r in rows:
        gap = abs(r["ppl_ss"] - r["ppl_direct"]) / r["ppl_direct"]
        # only down-conversions are SS'd; 8-bit rows are identical by constr.
        if not r["fmt"].endswith("8"):
            worst = max(worst, gap)
        print(f'{r["sweep"]},{r["fmt"]},{r["block_size"]},'
              f'{r["ppl_direct"]:.3f},{r["ppl_ss"]:.3f},{gap:.4f}')
    print(f"fig23_ss_ppl,{(time.time() - t0) * 1e6:.0f},"
          f"worst_rel_gap={worst:.4f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
