"""§Roofline: combine the dry-run artifacts with the analytic cost model.

Per (arch x shape) on the single-pod 16x16 mesh:
  compute term    = FLOPs / (chips x 197 TFLOP/s)
  memory term     = HBM bytes / (chips-local x 819 GB/s)
  collective term = per-chip collective bytes / 50 GB/s link
plus MODEL_FLOPS = 6·N·D (train) / 2·N·D (serve), the useful-compute ratio,
the dominant term, and the compile-verified memory footprint from the
dry-run JSON. Writes a markdown table for EXPERIMENTS.md.
"""
import argparse
import glob
import json
import os

from repro.configs import SHAPES, applicable, get_config, list_archs
from repro.launch import costmodel as cm

MESH = cm.MeshDesc(pod=1, data=16, model=16)


def load_dryrun(out_dir, arch, shape, mesh="16x16", variant="baseline"):
    path = os.path.join(out_dir, f"{arch}__{shape}__{mesh}__{variant}.json")
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return None


def cell(arch, shape_name, out_dir, weight_bits=16):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if not applicable(cfg, shape):
        return None
    r = cm.roofline(cfg, shape, MESH, weight_bits_decode=weight_bits)
    dr = load_dryrun(out_dir, arch, shape_name)
    if dr and dr.get("status") == "ok":
        r["compiled"] = True
        r["temp_gib"] = dr["memory"]["temp_size_in_bytes"] / 2 ** 30
        r["arg_gib"] = dr["memory"]["argument_size_in_bytes"] / 2 ** 30
        r["hlo_collectives"] = {k: v for k, v in dr["collectives"].items()
                                if v > 0 and k != "total_weighted"}
    else:
        r["compiled"] = bool(dr)
        r["temp_gib"] = r["arg_gib"] = float("nan")
    return r


def fmt_s(x):
    if x >= 1:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x * 1e3:.2f}ms"
    return f"{x * 1e6:.0f}us"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="out/dryrun")
    ap.add_argument("--md", default="out/roofline.md")
    args = ap.parse_args()

    lines = ["| arch | shape | t_comp | t_mem | t_coll | dominant | "
             "roofline_frac | useful(6ND/HLO) | temp GiB/dev | args GiB/dev |",
             "|---|---|---|---|---|---|---|---|---|---|"]
    print("name,us_per_call,derived")
    for arch in list_archs():
        for shape_name in SHAPES:
            r = cell(arch, shape_name, args.out)
            if r is None:
                lines.append(f"| {arch} | {shape_name} | — | — | — | "
                             f"skipped (full attn @500k) | — | — | — | — |")
                continue
            lines.append(
                f"| {arch} | {shape_name} | {fmt_s(r['t_compute'])} | "
                f"{fmt_s(r['t_memory'])} | {fmt_s(r['t_collective'])} | "
                f"{r['dominant']} | {r['roofline_fraction']:.2f} | "
                f"{min(r['useful_ratio'], 9.99):.2f} | "
                f"{r['temp_gib']:.1f} | {r['arg_gib']:.2f} |")
            print(f"roofline_{arch}_{shape_name},"
                  f"{r['step_time_lower_bound'] * 1e6:.0f},"
                  f"dom={r['dominant']}:frac={r['roofline_fraction']:.2f}")
    os.makedirs(os.path.dirname(args.md), exist_ok=True)
    with open(args.md, "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"# wrote {args.md}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
