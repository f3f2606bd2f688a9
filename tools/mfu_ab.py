"""In-process A/B of AlexNet MFU levers on the chip: runs each
variant's best-of scan windows back to back in one process (one chip
call) and prints deltas vs the first (baseline) variant.

    python tools/mfu_ab.py [--batch 8192] [--iters 10] [--reps 4]
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def measure(batch_size, iters, reps, vmem=None, unroll=1):
    import jax

    from singa_tpu.core.trainer import Trainer
    from singa_tpu.models.vision import alexnet_cifar10_full
    from singa_tpu.utils.profiler import hard_sync
    import time

    os.environ["SINGA_TPU_SCAN_UNROLL"] = str(unroll)
    old = Trainer.TPU_CONV_COMPILER_OPTIONS
    if vmem is not None:
        Trainer.TPU_CONV_COMPILER_OPTIONS = {
            "xla_tpu_scoped_vmem_limit_kib": str(vmem)}
    try:
        cfg = alexnet_cifar10_full(batchsize=batch_size)
        cfg.precision = "bfloat16"
        trainer = Trainer(cfg, {"data": {"pixel": (3, 32, 32),
                                         "label": ()}},
                          log_fn=lambda s: None)
        params, opt_state = trainer.init(seed=0)
        rng = np.random.default_rng(0)
        batch = {"data": {
            "pixel": jax.device_put(rng.standard_normal(
                (batch_size, 3, 32, 32)).astype(np.float32)),
            "label": jax.device_put(rng.integers(
                0, 10, (batch_size,)).astype(np.int32))}}
        key = jax.random.PRNGKey(0)
        params, opt_state, _ = trainer.train_steps(
            params, opt_state, batch, 0, key, iters)
        hard_sync(params)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            params, opt_state, _ = trainer.train_steps(
                params, opt_state, batch, iters, key, iters)
            hard_sync(params)
            best = min(best, (time.perf_counter() - t0) / iters)
        return best * 1e3
    finally:
        Trainer.TPU_CONV_COMPILER_OPTIONS = old
        os.environ.pop("SINGA_TPU_SCAN_UNROLL", None)


def main():
    from singa_tpu.utils import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8192)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--reps", type=int, default=4)
    ap.add_argument("--variants", default="base,vmem112,vmem104,unroll2,"
                                          "batch12288")
    args = ap.parse_args()
    variants = {
        "base": {},
        "vmem112": {"vmem": 114688},
        "vmem104": {"vmem": 106496},
        "vmem90": {"vmem": 92160},
        "unroll2": {"unroll": 2},
        "unroll5": {"unroll": 5},
        "batch12288": {"batch": 12288},
        "batch16384": {"batch": 16384},
    }
    base_ms = None
    for name in args.variants.split(","):
        kw = dict(variants[name])
        b = kw.pop("batch", args.batch)
        try:
            ms = measure(b, args.iters, args.reps, **kw)
        except Exception as e:
            print(f"{name:12s} FAILED {type(e).__name__}: "
                  f"{str(e)[:100]}", flush=True)
            continue
        per_img = ms / b * 8192     # normalize to img-time at batch 8192
        if base_ms is None:
            base_ms = per_img
        print(f"{name:12s} {ms:8.3f} ms/step  ({per_img:8.3f} ms per "
              f"8192 imgs, {per_img - base_ms:+7.3f} vs base)",
              flush=True)


if __name__ == "__main__":
    main()
