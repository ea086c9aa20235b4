"""Production training launcher: ``--arch <id> --shape train_4k`` etc.

On this CPU container it runs reduced configs for real; on a TPU fleet the
same entry point builds the sharded step over the production mesh (the
dry-run proves those lower+compile). Auto-resumes from the latest checkpoint.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_reduced, list_archs
from repro.core.formats import TRAIN_FORMATS_MXFP, TRAIN_FORMATS_MXINT
from repro.core.qat import QATConfig
from repro.data.pipeline import DataConfig, LMDataset
from repro.models import get_model
from repro.optim.adamw import AdamWConfig
from repro.train.loop import LoopConfig, run_training


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--formats", default="mxint",
                    choices=["mxint", "mxfp", "none"])
    ap.add_argument("--schedule", default="multiformat")
    ap.add_argument("--anchor", default=None,
                    help="anchor format for §3.5 training (e.g. mxint8)")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--moment-dtype", default="f32", choices=["f32", "bf16"])
    args = ap.parse_args()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    fmts = {"mxint": TRAIN_FORMATS_MXINT, "mxfp": TRAIN_FORMATS_MXFP,
            "none": ()}[args.formats]
    qat = QATConfig(formats=fmts, anchor=args.anchor, block_size=32) \
        if fmts else None
    api = get_model(cfg, qat)
    data = LMDataset(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                global_batch=args.batch))
    opt = AdamWConfig(lr=args.lr,
                      moment_dtype=jnp.bfloat16
                      if args.moment_dtype == "bf16" else jnp.float32)
    out = run_training(
        api, data, opt,
        LoopConfig(total_steps=args.steps,
                   schedule=args.schedule if fmts else "fp",
                   ckpt_dir=args.ckpt),
        on_step=lambda s, m: print(
            f"step {s} fmt={m['fmt_idx']} loss={m['loss']:.4f}")
        if s % 10 == 0 else None)
    h = out["history"]
    print(f"finished at step {out['last_step']}; "
          f"loss {h[0]['loss']:.3f} -> {h[-1]['loss']:.3f}" if h else "noop")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
