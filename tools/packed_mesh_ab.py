"""In-process A/B: packed flash path direct (mesh=None) vs through the
round-5 shard_map wrapper (1-device mesh) on the bench transformer
stack.  Proves un-fencing the packed kernels for mesh runs costs
nothing at mesh=1 — the same kernel, same layout, one shard_map
boundary added.  Chip drift cancels in-process (best-of scan windows,
same rules as bench.py).

    python tools/packed_mesh_ab.py [--seq 1024] [--batch 32]
        [--iters 30] [--reps 3] [--kv_heads 0 (=heads)]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def measure(seq_len, batch, iters, reps, kv_heads, use_mesh):
    import jax

    from singa_tpu.core.trainer import Trainer
    from singa_tpu.models.transformer import (synthetic_token_batches,
                                              transformer_lm)
    from singa_tpu.parallel import make_mesh
    from singa_tpu.utils.flops import mfu, net_train_flops
    from singa_tpu.utils.profiler import hard_sync

    mesh = make_mesh(jax.devices()[:1]) if use_mesh else None
    cfg = transformer_lm(vocab_size=32768, num_layers=12, embed_dim=768,
                         num_heads=12, head_dim=64, seq_len=seq_len,
                         batchsize=batch,
                         num_kv_heads=kv_heads or None)
    cfg.precision = "bfloat16"
    trainer = Trainer(cfg, {"data": {"input": (seq_len,),
                                     "target": (seq_len,)}},
                      log_fn=lambda s: None, mesh=mesh)
    params, opt = trainer.init(seed=0)
    bt = next(synthetic_token_batches(batch, seq_len, 32768))
    bt = jax.tree_util.tree_map(jax.device_put, bt)
    key = jax.random.PRNGKey(0)
    params, opt, _ = trainer.train_steps(params, opt, bt, 0, key, iters)
    hard_sync(params)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        params, opt, _ = trainer.train_steps(params, opt, bt, iters, key,
                                             iters)
        hard_sync(params)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best, mfu(net_train_flops(trainer.train_net), best)


def main():
    from singa_tpu.utils import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--kv_heads", type=int, default=0)
    args = ap.parse_args()
    print(f"# S={args.seq} batch={args.batch} kv_heads="
          f"{args.kv_heads or 12} iters={args.iters} reps={args.reps}")
    base = None
    for name, use_mesh in (("direct", False), ("mesh1", True)):
        step, util = measure(args.seq, args.batch, args.iters, args.reps,
                             args.kv_heads, use_mesh)
        base = base or step
        print(f"{name:8s} {step * 1e3:8.2f} ms/step  MFU {util:.4f}  "
              f"({(step - base) / base * 100:+.2f}% vs direct)",
              flush=True)


if __name__ == "__main__":
    main()
