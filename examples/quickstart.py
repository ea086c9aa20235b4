"""Quickstart: the MF-QAT pipeline end-to-end on a toy model, in one file.

  1. multi-format QAT train a small LM (paper §3.2 schedule),
  2. quantize to the MXINT8 anchor and write the packed checkpoint (§3.5),
  3. Slice-and-Scale to lower formats at 'runtime' and evaluate each (§3.3).

Runs in ~2 minutes on CPU.
"""
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "src")

from repro.checkpoint.anchor_ckpt import load_anchor, save_anchor
from repro.configs import get_reduced
from repro.core import (convert, dequantize, get_format, make_anchor,
                        storage_bytes)
from repro.core.anchor import materialize
from repro.core.qat import QATConfig
from repro.data.pipeline import DataConfig, LMDataset, eval_batches
from repro.models import get_model
from repro.optim.adamw import AdamWConfig
from repro.train.loop import LoopConfig, run_training


def main():
    # 1. ---- multi-format QAT -----------------------------------------------
    cfg = get_reduced("qwen3-4b")
    qat = QATConfig(formats=("mxint2", "mxint4", "mxint6", "mxint8"),
                    block_size=32)
    api = get_model(cfg, qat)
    data = LMDataset(DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=8,
                                n_examples=128))   # paper: 128 examples
    total = data.epoch_steps() * len(qat.formats)  # 1 epoch per format
    print(f"training {cfg.name}-reduced, {total} steps, "
          f"schedule 2->4->6->8 ...")
    out = run_training(api, data, AdamWConfig(lr=3e-3),
                       LoopConfig(total_steps=total, schedule="multiformat"),
                       on_step=lambda s, m: print(
                           f"  step {s:3d} fmt={m['fmt_idx']} "
                           f"loss={m['loss']:.3f}") if s % 16 == 0 else None)
    params = out["state"].params

    # 2. ---- anchor checkpoint ---------------------------------------------
    anchor = make_anchor(params, qat, get_format("mxint8", 32))
    nbytes = save_anchor("out/quickstart_anchor", anchor)
    f32_bytes = sum(x.size * 4 for x in jax.tree_util.tree_leaves(params))
    print(f"anchor checkpoint: {nbytes / 1e3:.0f} kB "
          f"(f32 master: {f32_bytes / 1e3:.0f} kB, "
          f"{f32_bytes / nbytes:.1f}x smaller)")

    # 3. ---- elastic inference: SS to each format, evaluate -----------------
    anchor = load_anchor("out/quickstart_anchor")
    batches = eval_batches(DataConfig(vocab=cfg.vocab, seq_len=64,
                                      global_batch=8), 4)
    loss_fn = jax.jit(lambda p, b: api.train_loss(p, b, None)[1]["ce"])
    print("format  eval_ppl   (from ONE stored anchor, no retraining)")
    for b in (8, 6, 5, 4, 3, 2):
        low = convert(anchor, get_format(f"mxint{b}", 32))
        p_low = materialize(low, params, dtype=jnp.float32)
        losses = [float(loss_fn(p_low, jax.tree_util.tree_map(
            jnp.asarray, bb))) for bb in batches]
        print(f"mxint{b}  {np.exp(np.mean(losses)):8.2f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
