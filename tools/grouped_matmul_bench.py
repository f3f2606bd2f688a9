#!/usr/bin/env python3
"""The grouped experts' product alone (`ops/grouped_matmul.py`, jitted)
beside `jax.lax.ragged_dot`, ms a call on the chip (about three
minutes), at the (X, K, N) of the four sparse configurations' held
experts and the rows their grouped rungs hand: the way up (K = hidden,
N = expert hidden) and the way down, the rows in groups spread evenly
(`balanced`), one favourite expert holding half of them (`skewed`) and
a rung six tenths full (`part`).  The times are the DEVICE's, read from
a trace of the runs (each program's mean over `--reps` runs; the
kernel's includes the few small ops that build its schedule): a call of
0.1-0.4 ms is shorter than its dispatch from this host, so the wall
clock of a loop of calls reads the host there (`wall` on each row).
Each row: both times, the visits and the fill of the kernel's tiles,
and the kernel's share of the product's roofline: what it has to read
and write (the matrices of the groups that hold a row, the rows in
groups, their float32 result) at the chip's HBM bandwidth, over the
time it took.

    python3 tools/grouped_matmul_bench.py [--reps 20] [--only solar]
    python3 tools/grouped_matmul_bench.py --sweep solar   # tilings tried

It refuses to run without a TPU: a CPU's time is no reading of this
kernel.  `--allow-cpu 1` is the smoke mode at a small shape (wall clock
only), and says on every line that it measured nothing.
"""

from __future__ import annotations

import argparse
import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

HBM_BYTES_S = 819e9

# name: experts held, of how many routed, hidden, expert hidden, experts
# a token chooses, the rungs that run grouped
SHAPES = {
    "solar": (40, 320, 4096, 1280, 8, (512, 1024, 2048)),
    "kimi": (32, 256, 2304, 1024, 8, (512, 1024)),
    "zaya": (16, 16, 2048, 2048, 1, (512, 1024)),
    "trinity": (16, 128, 2048, 1024, 8, (512, 1024, 2048)),
}
SMOKE = {"smoke": (4, 8, 256, 128, 2, (512,))}


def sizes_of(kind: str, rows: int, groups: int):
    """`rows` rows on `groups` groups."""
    import numpy as np
    if kind == "skewed":
        rest = (rows - rows // 2) // (groups - 1)
        sizes = np.full(groups, rest)
        sizes[groups // 3] = rows - rest * (groups - 1)
    else:
        sizes = np.full(groups, rows // groups)
        sizes[: rows - sizes.sum()] += 1
    return sizes.astype(np.int32)


def cases(shape):
    """(label, rows handed, group sizes, K, N) of every product a
    configuration's grouped rungs run."""
    held, routed, hidden, inner, k, rungs = shape
    for rung in rungs:
        # `_grouped`'s rule: half of the T k assignments where the
        # groups fit that, all of them past it
        on_held = rung * k * held // routed
        handed = -(-rung * k // 2) if 2 * on_held <= rung * k else rung * k
        for kind, rows in (("balanced", on_held), ("skewed", on_held),
                           ("part", on_held * 6 // 10)):
            sizes = sizes_of(kind, rows, held)
            yield f"{rung:5d} up   {kind:8s}", handed, sizes, hidden, inner
            yield f"{rung:5d} down {kind:8s}", handed, sizes, inner, hidden


def timed(call, args, reps):
    import jax
    jax.block_until_ready(call(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = call(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def device_times(path, runs, reps):
    """`runs`: [(call, args)], each run `reps` times under one trace
    kept at `path`.  Returns the device's mean seconds a run of each,
    in order (the trace's program events, `reps` at a time)."""
    import jax
    from benchmark.trace import reduce as reducer
    shutil.rmtree(path, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(path, profiler_options=opts)
    for call, args in runs:
        for _ in range(reps):
            out = call(*args)
        jax.block_until_ready(out)
    jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(path, "plugins", "profile", "*",
                                          "*.xplane.pb")))[-1]
    dev = [p for p in reducer.read_planes(found)
           if reducer.DEVICE_PLANE.match(p["name"])][0]
    took = [e - s for _, s, e in sorted(
        reducer.line_events(dev, reducer.MODULES_LINE), key=lambda ev: ev[1])]
    if len(took) != reps * len(runs):
        raise RuntimeError(f"{len(took)} program runs in the trace for "
                           f"{len(runs)} x {reps} calls")
    return [sum(took[i * reps:(i + 1) * reps]) / reps
            for i in range(len(runs))]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--only", default="")
    ap.add_argument("--sweep", default="")
    ap.add_argument("--allow-cpu", type=int, default=0)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from singa_tpu.ops import grouped_matmul as gm
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind}")
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.allow_cpu:
        print("no TPU: nothing measured (--allow-cpu 1 is the smoke mode)",
              file=sys.stderr)
        return 1
    shapes = SHAPES if on_chip else SMOKE
    tag = "" if on_chip else " [platform=cpu: NOT a measurement]"
    dtype = jnp.bfloat16
    key = jax.random.PRNGKey(0)

    ragged = jax.jit(lambda a, w, s: jax.lax.ragged_dot(
        a, w, s, preferred_element_type=jnp.float32))
    kernel = jax.jit(gm.grouped_matmul)

    made = {}

    def operands(handed, held, k, n):
        """One array a shape: a configuration's cases share them."""
        if ("lhs", handed, k) not in made:
            made["lhs", handed, k] = jax.random.normal(key, (handed, k),
                                                       dtype)
        if ("rhs", held, k, n) not in made:
            made["rhs", held, k, n] = jax.random.normal(
                jax.random.fold_in(key, 1), (held, k, n), dtype) * 0.02
        return made["lhs", handed, k], made["rhs", held, k, n]

    if args.sweep:
        return sweep(shapes[args.sweep], operands, args.reps, tag)
    for name, shape in shapes.items():
        if args.only and name != args.only:
            continue
        held = shape[0]
        print(f"{name}: {held} groups of ({shape[2]}, {shape[3]}) "
              f"{jnp.dtype(dtype).name}")
        rows_of, runs = [], []
        for label, handed, sizes, k, n in cases(shape):
            lhs, rhs = operands(handed, held, k, n)
            gs = jnp.asarray(sizes)
            rows = int(sizes.sum())
            got, tile_rows = kernel(lhs, rhs, gs)
            want = ragged(lhs, rhs, gs)
            gap = float(jnp.max(jnp.abs(got[:rows] - want[:rows])))
            walls = [timed(f, (lhs, rhs, gs), args.reps)
                     for f in (kernel, ragged)]
            need = ((int(np.count_nonzero(sizes)) * k * n + rows * k) * 2
                    + rows * n * 4) / HBM_BYTES_S
            rows_of.append((label, handed, rows, gm.tiles(handed, k, n, 2),
                            int(tile_rows), need, gap, walls))
            runs += [(kernel, (lhs, rhs, gs)), (ragged, (lhs, rhs, gs))]
        took = (device_times(os.path.join(ROOT, ".bench_trace",
                                          "grouped_matmul", name),
                             runs, args.reps) if on_chip
                else [w for row in rows_of for w in row[-1]])
        made.clear()
        for i, (label, handed, rows, (tm, tk, tn), tile_rows, need, gap,
                walls) in enumerate(rows_of):
            t_k, t_r = took[2 * i], took[2 * i + 1]
            print(f"  {label} handed {handed:6d} in groups {rows:5d}: "
                  f"kernel {t_k * 1e3:6.3f} ms  ragged_dot "
                  f"{t_r * 1e3:6.3f} ms  (wall {walls[0] * 1e3:.3f} / "
                  f"{walls[1] * 1e3:.3f})  tiles ({tm}, {tk}, {tn})  "
                  f"visits {tile_rows // tm:3d}  fill "
                  f"{rows / max(tile_rows, 1):.3f}  roofline "
                  f"{100 * need / t_k:5.1f} %  gap {gap:.1e}{tag}",
                  flush=True)
    return 0


def sweep(shape, operands, reps, tag) -> int:
    """The widest rung's balanced case under every tiling worth trying,
    the rule's among them."""
    import jax
    import jax.numpy as jnp
    from singa_tpu.ops import grouped_matmul as gm
    held = shape[0]
    rung = shape[5][-1]
    for label, handed, sizes, k, n in cases(shape):
        if not label.startswith(f"{rung:5d}") or "balanced" not in label:
            continue
        lhs, rhs = operands(handed, held, k, n)
        gs = jnp.asarray(sizes)
        rule = gm.tiles(handed, k, n, 2)
        tried = {rule}
        for tm in (128, 256, 512):
            for d in (1, 2, 4, 5, 8, 10):
                if n % d == 0 and (n // d) % 128 == 0:
                    tried.add((tm, k, n // d))
            if k % 2 == 0 and (k // 2) % 128 == 0:
                tried.add((tm, k // 2, n))
        calls = []
        for tiling in sorted(tried):
            tm, tk, tn = tiling
            need = 2 * (tk * tn + tm * tk) * 2 + 3 * tm * tn * 4
            gm._VMEM_BYTES = max(32 << 20, need + (8 << 20))

            def call(a, w, s, tiling=tiling):
                plan = gm.schedule(s, a.shape[0], tiling[0])
                return gm.singa_grouped_matmul(a, w, *plan, tiling=tiling,
                                               interpret=bool(tag))
            call = jax.jit(call)
            try:
                wall = timed(call, (lhs, rhs, gs), reps)
                calls.append((tiling, call, wall))
            except Exception as e:  # a tiling Mosaic refuses is a reading
                print(f"  {label} tiles {tiling}: refused "
                      f"({type(e).__name__}: {str(e)[:120]})", flush=True)
        took = (device_times(os.path.join(ROOT, ".bench_trace",
                                          "grouped_matmul", "sweep"),
                             [(c, (lhs, rhs, gs)) for _, c, _ in calls], reps)
                if not tag else [w for _, _, w in calls])
        for (tiling, _, wall), t in zip(calls, took):
            print(f"  {label} tiles {tiling}: {t * 1e3:6.3f} ms  (wall "
                  f"{wall * 1e3:.3f}){'  <- the rule' if tiling == rule else ''}"
                  f"{tag}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
