"""Long-context flash block-geometry sweep, in-net and in-process.

Round-3 tuned the packed flash kernel only at S=1024/D=64; at S=4096+
attention grows to ~half the model FLOPs and the net MFU slid to
0.375/0.317.  This sweeps (block_q, block_k) at the long sequence
lengths ON THE TRAIN STEP (not a standalone microbench — those get
const-hoisted or measure the wrong layout), same-process so chip drift
cancels.

    python tools/longctx_sweep.py [--seq 4096] [--batch 8] [--iters 10]
        [--reps 3] [--blocks 512x512,1024x512,...]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def measure(seq_len, batch, iters, reps, bq, bk, split=False,
            head_dim=64):
    import jax

    from singa_tpu.core.trainer import Trainer
    from singa_tpu.models.transformer import (synthetic_token_batches,
                                              transformer_lm)
    from singa_tpu.ops import attention
    from singa_tpu.utils.flops import mfu, net_train_flops
    from singa_tpu.utils.profiler import hard_sync

    attention.set_flash_blocks((bq, bk))
    prev_split = attention.MASK_SPLIT
    attention.MASK_SPLIT = split
    try:
        # heads scale inversely with head_dim so every sweep point keeps
        # the same 768-wide attention (12x64 default, 6x128 for the
        # D=128 floor-proof measurement)
        if 768 % head_dim:
            raise ValueError(f"--head_dim must divide 768, got {head_dim}")
        cfg = transformer_lm(vocab_size=32768, num_layers=12,
                             embed_dim=768, num_heads=768 // head_dim,
                             head_dim=head_dim,
                             seq_len=seq_len, batchsize=batch)
        cfg.precision = "bfloat16"
        trainer = Trainer(cfg, {"data": {"input": (seq_len,),
                                         "target": (seq_len,)}},
                          log_fn=lambda s: None)
        params, opt = trainer.init(seed=0)
        bt = next(synthetic_token_batches(batch, seq_len, 32768))
        bt = jax.tree_util.tree_map(jax.device_put, bt)
        key = jax.random.PRNGKey(0)
        params, opt, _ = trainer.train_steps(params, opt, bt, 0, key,
                                             iters)
        hard_sync(params)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            params, opt, _ = trainer.train_steps(params, opt, bt, iters,
                                                 key, iters)
            hard_sync(params)
            best = min(best, (time.perf_counter() - t0) / iters)
        flops = net_train_flops(trainer.train_net)
        return best, mfu(flops, best), flops
    finally:
        attention.set_flash_blocks(None)
        attention.MASK_SPLIT = prev_split


def main():
    from singa_tpu.utils import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--blocks", default="512x512,1024x512,512x1024,"
                                        "1024x1024,2048x512,256x512")
    ap.add_argument("--head_dim", type=int, default=64)
    args = ap.parse_args()
    batch = args.batch or max(32 * 1024 // args.seq, 1)
    print(f"# S={args.seq} batch={batch} head_dim={args.head_dim} "
          f"iters={args.iters} reps={args.reps} (best-of)")
    base = None
    for spec in args.blocks.split(","):
        # production runs MASK_SPLIT=False (BASELINE: -55% at 512x1024);
        # ':split' opts a sweep point into the A/B variant
        split = spec.endswith(":split")
        bq, bk = (int(x) for x in spec.split(":")[0].split("x"))
        tag = " split" if split else ""
        try:
            step, util, flops = measure(args.seq, batch, args.iters,
                                        args.reps, bq, bk, split,
                                        args.head_dim)
        except Exception as e:
            print(f"bq={bq:5d} bk={bk:5d}{tag}  FAILED: "
                  f"{type(e).__name__}: {str(e)[:110]}", flush=True)
            continue
        base = base or step
        print(f"bq={bq:5d} bk={bk:5d}{tag}  {step * 1e3:8.2f} ms/step  "
              f"MFU {util:.4f}  ({(step - base) / base * 100:+.1f}% vs "
              f"first)", flush=True)


if __name__ == "__main__":
    main()
