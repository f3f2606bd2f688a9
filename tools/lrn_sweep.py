"""Sweep LRN Pallas kernel geometry on the chip vs the jnp band-dot
path, standalone, on the AlexNet norm1/norm2 shapes.

    python tools/lrn_sweep.py

Measurement rules (see bench.py): everything scan-wrapped in ONE
compiled program and ended by hard_sync.  Each config times fwd+bwd
together in one compile.  The kernels see
the (H*W, C, N) batch-in-lanes view; in-net boundary-layout effects
are measured separately by the full-step A/B.
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ITERS = 10


def time_scan(body, init, reps):
    """ms per body application, scanned ITERS times in one program."""
    import jax

    from singa_tpu.utils.profiler import hard_sync

    def prog(c):
        out, _ = jax.lax.scan(lambda cc, _: (body(cc), None), c, None,
                              length=ITERS)
        return out
    jfn = jax.jit(prog)
    out = jfn(init)
    hard_sync(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = jfn(init)
        hard_sync(out)
        best = min(best, (time.perf_counter() - t0) / ITERS)
    return best * 1e3


def main():
    from singa_tpu.utils import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=4)
    ap.add_argument("--shapes", default="norm1,norm2")
    args = ap.parse_args()
    import jax.numpy as jnp

    from singa_tpu.ops import lrn_pallas as lp
    from singa_tpu.ops.lrn import _lrn_nhwc_bwd, _lrn_nhwc_fwd

    shapes = {"norm1": (8192, 32, 32, 64, 5, 1e-4),
              "norm2": (8192, 16, 16, 192, 5, 1e-4)}
    rng = np.random.default_rng(0)
    for name in args.shapes.split(","):
        n, h, w, c, lsize, alpha = shapes[name]
        x = jnp.asarray(rng.standard_normal((n, h, w, c)), jnp.bfloat16)
        g = jnp.asarray(rng.standard_normal((n, h, w, c)), jnp.bfloat16)
        xt = jnp.asarray(np.ascontiguousarray(np.transpose(np.asarray(
            x, np.float32), (1, 2, 3, 0)).reshape(h * w, c, n)),
            jnp.bfloat16)
        gt = jnp.asarray(np.ascontiguousarray(np.transpose(np.asarray(
            g, np.float32), (1, 2, 3, 0)).reshape(h * w, c, n)),
            jnp.bfloat16)
        band = jnp.asarray(lp._np_band(c, lsize), jnp.bfloat16)

        def jnp_body(carry):
            xx, gg = carry
            y = _lrn_nhwc_fwd(xx, lsize, alpha, 0.75, 1.0, True, "jnp")[0]
            (dx,) = _lrn_nhwc_bwd(lsize, alpha, 0.75, 1.0, True, "jnp",
                                  xx, gg)
            return (dx, y)
        ms = time_scan(jnp_body, (x, g), args.reps)
        print(f"{name} jnp fwd+bwd                  {ms:7.3f} ms",
              flush=True)

        for n_blk, hw_blk, par in [(256, None, False), (256, None, True),
                                   (512, 8, True), (1024, 1, True),
                                   (1024, 4, True), (2048, 1, True)]:
            fkern = functools.partial(
                lp._fwd_kernel, coef=alpha / lsize, knorm=1.0, beta=0.75,
                relu=True)
            bkern = functools.partial(
                lp._bwd_kernel, coef=alpha / lsize, knorm=1.0, beta=0.75,
                relu=True)

            def pl_body(carry, fk=fkern, bk=bkern, nb=n_blk, hb=hw_blk,
                        pr=par):
                xx, gg = carry
                y = lp._call(fk, [xx], band, jnp.bfloat16, h * w, c, n,
                             nb, False, hb, pr)
                dx = lp._call(bk, [xx, gg], band, jnp.bfloat16, h * w,
                              c, n, nb, False, hb, pr)
                return (dx, y)
            try:
                ms = time_scan(pl_body, (xt, gt), args.reps)
            except Exception as e:
                print(f"{name} pallas n{n_blk} hw{hw_blk} p{int(par)} "
                      f"FAILED {type(e).__name__}: {str(e)[:90]}",
                      flush=True)
                continue
            print(f"{name} pallas n{n_blk:5d} hw{str(hw_blk):>4s} "
                  f"par{int(par)}  fwd+bwd {ms:7.3f} ms", flush=True)


if __name__ == "__main__":
    main()
