#!/usr/bin/env python3
"""KDA's chunked form alone (`ops/kda.py delta_rule_chunked`, jitted),
ms a call on the chip at the geometries of the two cells that run it
(about a minute): a 2,048-row chunk of a long prompt at 64 heads x 128
from a carried state (`serve-solar-docqa-sat`), and the 256- and
1,024-row rungs at 32 heads x 128, right-padded under `valid`
(`serve-kimi-decode-sat`).  `--ops 1` traces each geometry too and
prints its longest ops under their HLO names.

    python3 tools/kda_chunk_profile.py [--reps 20] [--ops 1]

It refuses to run without a TPU: a CPU's time is no reading of this
layer.  `--allow-cpu 1` is the smoke mode at a small geometry, and says
on every line that it measured nothing.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# name, rows, real rows, heads, head dim
GEOMETRIES = (("solar_chunk_2048", 2048, 2048, 64, 128),
              ("kimi_rung_256", 256, 200, 32, 128),
              ("kimi_rung_1024", 1024, 900, 32, 128))
SMOKE = (("smoke_128", 128, 100, 2, 32),)


def inputs(rows, real, heads, dim, seed=0):
    """What `KDALayer._inputs` hands over: q scaled, k of unit norm,
    decays from none to e^-30 a token, a state that is not zero."""
    import numpy as np
    rng = np.random.default_rng(seed)
    q, k, v = (rng.standard_normal((1, rows, heads, dim)).astype(np.float32)
               for _ in range(3))
    q *= dim ** -0.5 / np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -np.exp(rng.uniform(-7, 3.4, (1, rows, heads, dim))).astype(
        np.float32)
    beta = rng.uniform(0, 2, (1, rows, heads)).astype(np.float32)
    state = rng.standard_normal((1, heads, dim, dim)).astype(np.float32)
    valid = (np.arange(rows) < real)[None]
    return q, k, v, g, beta, state, valid


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--ops", type=int, default=0)
    ap.add_argument("--allow-cpu", type=int, default=0)
    args = ap.parse_args()
    import jax
    from singa_tpu.ops import kda as kda_ops
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind}")
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.allow_cpu:
        print("no TPU: nothing measured (--allow-cpu 1 is the smoke mode)",
              file=sys.stderr)
        return 1
    geometries = GEOMETRIES if on_chip else SMOKE
    tag = "" if on_chip else " [platform=cpu: NOT a measurement]"
    call = jax.jit(kda_ops.delta_rule_chunked)
    for name, rows, real, heads, dim in geometries:
        xs = [jax.device_put(a) for a in inputs(rows, real, heads, dim)]
        jax.block_until_ready(call(*xs))

        def run():
            for _ in range(args.reps):
                out = call(*xs)
            jax.block_until_ready(out)
            return args.reps

        t0 = time.perf_counter()
        run()
        ms = 1e3 * (time.perf_counter() - t0) / args.reps
        print(f"{name}: 1 x {rows} ({real} real) x {heads} x {dim}: "
              f"{ms:.3f} ms a call over {args.reps}{tag}")
        if args.ops and on_chip:
            from kimi_step_profile import _trace
            _trace(os.path.join(ROOT, ".bench_trace", "kda_chunk", name), run)
    return 0


if __name__ == "__main__":
    sys.exit(main())
