"""§Perf ladder: before/after tables for the three hillclimb cells.

Reads every variant JSON the dry-run wrote for the hillclimb cells and
prints compile-verified deltas (temp memory, HLO collective bytes) next to
the analytic roofline terms for the matching configuration.
"""
import glob
import json
import os

from repro.configs import SHAPES, get_config
from repro.launch import costmodel as cm

MESH = cm.MeshDesc(1, 16, 16)

CELLS = [
    ("jamba-1.5-large-398b", "train_4k",
     ["novjp", "baseline", "sp", "inner", "inner_mb4", "sp_mb4"]),
    ("qwen2-72b", "train_4k",
     ["novjp", "baseline", "sp", "sp_mb4"]),
    ("mixtral-8x22b", "decode_32k",
     ["baseline", "w8", "w4", "w16tp", "w8tp", "w4tp", "w8scan", "w4scan"]),
]

ANALYTIC_DECODE = {
    "baseline": dict(weight_bits_decode=16, weight_stationary=False),
    "w8": dict(weight_bits_decode=8, weight_stationary=False),
    "w4": dict(weight_bits_decode=4, weight_stationary=False),
    "w16tp": dict(weight_bits_decode=16, weight_stationary=True),
    "w8tp": dict(weight_bits_decode=8, weight_stationary=True),
    "w4tp": dict(weight_bits_decode=4, weight_stationary=True),
    "w8scan": dict(weight_bits_decode=8, weight_stationary=True),
    "w4scan": dict(weight_bits_decode=4, weight_stationary=True),
}


def main(out_dir="out/dryrun"):
    print("name,us_per_call,derived")
    for arch, shape_name, variants in CELLS:
        cfg = get_config(arch)
        shape = SHAPES[shape_name]
        print(f"\n# {arch} x {shape_name}")
        print("variant,temp_GiB/dev,args_GiB/dev,hlo_coll_GB/dev,"
              "analytic_t_mem_ms,analytic_t_coll_ms,dominant")
        for v in variants:
            path = os.path.join(out_dir,
                                f"{arch}__{shape_name}__16x16__{v}.json")
            if not os.path.exists(path):
                print(f"{v},pending,,,,")
                continue
            r = json.load(open(path))
            if r.get("status") != "ok":
                print(f"{v},ERROR:{r.get('error', '')[:60]},,,,")
                continue
            temp = r["memory"]["temp_size_in_bytes"] / 2 ** 30
            args = r["memory"]["argument_size_in_bytes"] / 2 ** 30
            coll = r["collectives"]["total_weighted"] / 1e9
            kw = ANALYTIC_DECODE.get(v, {}) if shape.kind == "decode" else {}
            ra = cm.roofline(cfg, shape, MESH, **kw)
            print(f"{v},{temp:.1f},{args:.1f},{coll:.2f},"
                  f"{ra['t_memory'] * 1e3:.2f},"
                  f"{ra['t_collective'] * 1e3:.2f},{ra['dominant']}")
            print(f"perf_{arch}_{shape_name}_{v},"
                  f"{ra['step_time_lower_bound'] * 1e6:.0f},"
                  f"temp={temp:.1f}GiB")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
