"""Measure the GPipe pipeline bubble fraction vs the analytic model.

The schedule runs m microbatches over S stages in m + S - 1 ticks, so
the idle ("bubble") fraction of each device is (S-1)/(m+S-1).  This
tool times the forward pipeline on the virtual CPU mesh across m and
compares the measured per-microbatch cost ratio to the model:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/pipeline_bubble.py

Bubble is measured as 1 - t(m_ref)/t(m) * (m/m_ref_ideal...) — more
robustly, per-tick time is estimated from the largest-m run (most
bubble-free), and bubble(m) = 1 - ideal_ticks/actual_ticks where
actual_ticks = t(m)/tick_cost.  The result lands in docs/PARITY.md.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    # force the virtual CPU mesh
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    from jax.extend.backend import clear_backends
    clear_backends()
    import jax.numpy as jnp

    from singa_tpu.parallel.mesh import make_mesh
    from singa_tpu.parallel.pipeline import pipeline_apply

    S = 4
    devs = jax.devices()
    if len(devs) < S:
        raise SystemExit(f"need {S}+ devices "
                         f"(xla_force_host_platform_device_count)")
    mesh = make_mesh(devs[:S], pipe=S)
    d = 256
    w = jnp.stack([jnp.eye(d) * (1 + 0.01 * i) for i in range(S)])

    def stage_fn(params, mb):
        # enough work per tick that schedule overhead doesn't dominate
        h = mb
        for _ in range(4):
            h = jnp.tanh(h @ params)
        return h

    results = {}
    for m in (4, 8, 16, 32, 64):
        x = jnp.ones((m, 16, d), jnp.float32)
        fn = jax.jit(lambda ww, xx: pipeline_apply(
            mesh, stage_fn, ww, xx, axis="pipe"))
        fn(w, x).block_until_ready()
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            fn(w, x).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        results[m] = best
        print(f"m={m:3d}  t={best * 1e3:8.2f} ms  ticks={m + S - 1}",
              flush=True)

    # per-tick cost from consecutive m (the schedule adds exactly
    # (m2 - m1) ticks between runs, bubble-independent)
    ms_sorted = sorted(results)
    ticks = {m: m + S - 1 for m in ms_sorted}
    slopes = [(results[b] - results[a]) / (ticks[b] - ticks[a])
              for a, b in zip(ms_sorted, ms_sorted[1:])]
    tick_cost = float(np.median(slopes))
    print(f"\nper-tick cost (median slope): {tick_cost * 1e3:.3f} ms")
    print(f"{'m':>4s} {'model bubble':>13s} {'measured bubble':>16s}")
    for m in ms_sorted:
        model = (S - 1) / (m + S - 1)
        ideal = m * tick_cost
        measured = 1 - ideal / results[m]
        print(f"{m:4d} {model:13.3f} {measured:16.3f}")

    # ---- circular/interleaved schedule: same S total virtual stages on
    # a P = S/v pipe axis.  Model: ticks = v*m + P - 1 at 1/1 the tick
    # work (the stage slices are the same matrices), so
    # bubble = (P-1)/(v*m+P-1) vs GPipe's (S-1)/(m+S-1) at equal m.
    v = 2
    Pp = S // v
    cmesh = make_mesh(devs[:Pp], pipe=Pp)
    print(f"\ncircular schedule: {S} virtual stages on pipe={Pp} (v={v})")
    cres = {}
    for m in (4, 8, 16, 32, 64):
        x = jnp.ones((m, 16, d), jnp.float32)
        fn = jax.jit(lambda ww, xx: pipeline_apply(
            cmesh, stage_fn, ww, xx, axis="pipe", virtual=v))
        fn(w, x).block_until_ready()
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            fn(w, x).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        cres[m] = best
        print(f"m={m:3d}  t={best * 1e3:8.2f} ms  "
              f"ticks={v * m + Pp - 1}", flush=True)
    # circular tick cost from its OWN slope (the two meshes place
    # different device counts on the host, so GPipe's tick cost does
    # not transfer)
    cms = sorted(cres)
    cticks = {m: v * m + Pp - 1 for m in cms}
    cslopes = [(cres[b] - cres[a]) / (cticks[b] - cticks[a])
               for a, b in zip(cms, cms[1:])]
    ctick = float(np.median(cslopes))
    print(f"per-tick cost (median slope): {ctick * 1e3:.3f} ms")
    print(f"{'m':>4s} {'model bubble':>13s} {'measured bubble':>16s} "
          f"{'gpipe model':>12s}")
    for m in cms:
        ideal = v * m * ctick
        measured = 1 - ideal / cres[m]
        model = (Pp - 1) / (v * m + Pp - 1)
        gpipe = (S - 1) / (m + S - 1)
        print(f"{m:4d} {model:13.3f} {measured:16.3f} {gpipe:12.3f}",
              flush=True)
    print("\nNB: virtual CPU devices share host cores, so an idle "
          "device donates its core to busy ones and measured bubbles "
          "read high/noisy; the tick counts (printed per run) are the "
          "exact schedule lengths, and on real chips the bubble "
          "follows them.")


if __name__ == "__main__":
    main()
