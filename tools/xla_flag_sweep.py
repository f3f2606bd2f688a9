"""Sweep TPU compiler options over the AlexNet gate workload.

Options travel per compile through
`jit(...).lower(...).compile(compiler_options=...)` — the mechanism
Trainer.TPU_CONV_COMPILER_OPTIONS uses in production.

Measured on a v5e chip (2026-07-30, an earlier installation — not
re-measured on this one), best of 3-4 windows, AlexNet-full batch
8192:

    default (16MB scoped vmem)                      135-136 ms
    xla_tpu_scoped_vmem_limit_kib=98304             127-129 ms  <- adopted
    xla_tpu_scoped_vmem_limit_kib=131072            2811 ms (spills!)
    + xla_tpu_rwb_fusion=false                      127-129 ms (noise)
    + xla_tpu_enable_latency_hiding_scheduler=true  128 ms (noise)
    + xla_tpu_enable_experimental_fusion_cost_model 135 ms (worse)
    + xla_tpu_enable_dot_strength_reduction=false   131 ms (worse)

Usage: python tools/xla_flag_sweep.py  [--batch 8192]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

OPTION_SETS = [
    ("default", None),
    ("vmem96m", {"xla_tpu_scoped_vmem_limit_kib": "98304"}),
    ("vmem96m+rwb-off", {"xla_tpu_scoped_vmem_limit_kib": "98304",
                         "xla_tpu_rwb_fusion": "false"}),
    ("vmem96m+latency-sched",
     {"xla_tpu_scoped_vmem_limit_kib": "98304",
      "xla_tpu_enable_latency_hiding_scheduler": "true"}),
]


def main():
    from singa_tpu.utils import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8192)
    args = ap.parse_args()

    import numpy as np
    import jax

    from singa_tpu.core.trainer import Trainer
    from singa_tpu.models.vision import alexnet_cifar10_full
    from singa_tpu.utils.flops import net_train_flops, peak_flops
    from singa_tpu.utils.profiler import hard_sync

    cfg = alexnet_cifar10_full(batchsize=args.batch)
    cfg.precision = "bfloat16"
    # strip the production default so the 'default' row is a REAL
    # baseline (jit-level compiler options merge into every
    # lowered.compile(), so they must not be baked into the jit here)
    Trainer.TPU_CONV_COMPILER_OPTIONS = {}
    tr = Trainer(cfg, {"data": {"pixel": (3, 32, 32), "label": ()}},
                 log_fn=lambda s: None, donate=False)
    params, opt = tr.init(seed=0)
    rng = np.random.default_rng(0)
    batch = {"data": {
        "pixel": jax.device_put(rng.standard_normal(
            (args.batch, 3, 32, 32)).astype(np.float32)),
        "label": jax.device_put(
            rng.integers(0, 10, (args.batch,)).astype(np.int32))}}
    key = jax.random.PRNGKey(0)
    lowered = tr.train_steps.lower(params, opt, batch, 0, key, 10)
    flops = net_train_flops(tr.train_net)
    peak = peak_flops() or float("nan")
    for name, opts in OPTION_SETS:
        try:
            comp = (lowered.compile(compiler_options=opts) if opts
                    else lowered.compile())
            p, o = params, opt
            p, o, _ = comp(p, o, batch, 0, key)
            hard_sync(p)
            best = 1e9
            for _ in range(4):
                t0 = time.perf_counter()
                p, o, _ = comp(p, o, batch, 10, key)
                hard_sync(p)
                best = min(best, (time.perf_counter() - t0) / 10)
            print(f"{name:24s} step {best*1e3:8.2f} ms  "
                  f"MFU {flops/(best*peak):.4f}", flush=True)
        except Exception as e:
            print(f"{name:24s} FAIL {str(e)[:140]}", flush=True)


if __name__ == "__main__":
    main()
