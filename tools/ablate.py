"""Ablation timing for the AlexNet MFU gate: strip one component at a
time from alexnet_cifar10_full and report step-time deltas, so MFU work
targets the real cost centers instead of guesses.  Run on the chip:

    python tools/ablate.py [--batch 8192]
"""

from __future__ import annotations

import argparse
import copy
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def strip(cfg, names):
    """Remove layers by name, rewiring each consumer to the removed
    layer's first source."""
    cfg = copy.deepcopy(cfg)
    layers = cfg.neuralnet.layer
    redirect = {}
    for l in layers:
        if l.name in names:
            redirect[l.name] = l.srclayers[0]
    kept = [l for l in layers if l.name not in names]
    for l in kept:
        l.srclayers = [redirect.get(s, s) for s in l.srclayers]
    cfg.neuralnet.layer = kept
    return cfg


def measure(cfg, batch_size, iters=10, reps=3, fwd_only=False):
    import jax

    from singa_tpu.core.trainer import Trainer
    from singa_tpu.utils.profiler import hard_sync

    cfg.precision = "bfloat16"
    trainer = Trainer(cfg, {"data": {"pixel": (3, 32, 32), "label": ()}},
                      log_fn=lambda s: None)
    params, opt_state = trainer.init(seed=0)
    rng = np.random.default_rng(0)
    batch = {"data": {
        "pixel": jax.device_put(
            rng.standard_normal((batch_size, 3, 32, 32)).astype(np.float32)),
        "label": jax.device_put(
            rng.integers(0, 10, (batch_size,)).astype(np.int32)),
    }}
    key = jax.random.PRNGKey(0)
    if fwd_only:
        net = trainer.train_net

        def fwd_scan(p, b, k, n):
            def body(carry, step):
                loss, _, _ = net.apply(p, b, rng=k, train=True,
                                       compute_dtype=trainer.compute_dtype,
                                       step=step)
                return carry + loss.astype(np.float32), None
            tot, _ = jax.lax.scan(body, 0.0, np.arange(n))
            return tot
        run = jax.jit(fwd_scan, static_argnums=(3,))
        run(params, batch, key, iters).block_until_ready()
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            hard_sync(run(params, batch, key, iters))
            best = min(best, (time.perf_counter() - t0) / iters)
        return best
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
    return best


def main():
    from singa_tpu.utils import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8192)
    ap.add_argument("--fwd", action="store_true")
    args = ap.parse_args()

    from singa_tpu.models.vision import alexnet_cifar10_full

    base_cfg = alexnet_cifar10_full(batchsize=args.batch)
    ave_cfg = copy.deepcopy(base_cfg)
    for l in ave_cfg.neuralnet.layer:
        if l.pooling_param:
            l.pooling_param.pool = "AVE"
    variants = {
        "full": base_cfg,
        "pools-ave": ave_cfg,
        "no-lrn": strip(base_cfg, {"norm1", "norm2"}),
        "no-lrn-ave": strip(ave_cfg, {"norm1", "norm2"}),
    }
    base_ms = None
    for name, cfg in variants.items():
        try:
            s = measure(copy.deepcopy(cfg), args.batch, fwd_only=args.fwd)
        except Exception as e:
            print(f"{name:12s} FAILED: {e!r}")
            continue
        ms = s * 1e3
        if name == "full":
            base_ms = ms
        delta = f"  delta {ms - base_ms:+8.2f}ms" if base_ms else ""
        print(f"{name:12s} {ms:8.2f}ms{delta}", flush=True)


if __name__ == "__main__":
    main()
