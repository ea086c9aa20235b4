"""Serving launcher: load (or synthesize) an anchor checkpoint and serve
batched requests with elastic precision selection."""
from __future__ import annotations

import argparse
import os

import jax
import numpy as np

from repro.checkpoint.anchor_ckpt import load_anchor, save_anchor
from repro.configs import get_config, get_reduced, list_archs
from repro.core import get_format, make_anchor
from repro.core.qat import QATConfig
from repro.models import get_model
from repro.serve.engine import ElasticEngine, Request
from repro.serve.policy import FormatPolicy


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--anchor-ckpt", default=None)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--fmt", default=None,
                    help="pin a format instead of the load policy")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="published widths instead of the reduced config")
    args = ap.parse_args()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    api = get_model(cfg, None)
    params = api.init_params(jax.random.PRNGKey(0))
    qat = QATConfig(formats=("mxint4", "mxint8"), anchor="mxint8",
                    block_size=32)

    if args.anchor_ckpt and os.path.isdir(args.anchor_ckpt):
        anchor = load_anchor(args.anchor_ckpt)
        print(f"loaded anchor checkpoint {args.anchor_ckpt} "
              f"({anchor.fmt_name})")
    else:
        anchor = make_anchor(params, qat, get_format("mxint8", 32))
        if args.anchor_ckpt:
            n = save_anchor(args.anchor_ckpt, anchor)
            print(f"wrote anchor checkpoint ({n / 1e6:.1f} MB)")

    eng = ElasticEngine(api, anchor, batch_slots=args.slots, max_len=96,
                        policy=FormatPolicy(anchor="mxint8"),
                        param_template=params)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, 8).astype(np.int32),
                    max_new=args.max_new)
            for i in range(args.requests)]
    eng.generate(reqs, fmt_override=args.fmt)
    for r in reqs[:4]:
        print(f"req {r.rid}: fmt={r.fmt_used} out={r.out_tokens}")
    print("engine:", eng.stats)


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
