#!/usr/bin/env python3
"""Where the two cb programs of a `serve_kimi` cell spend their device
time, op by op (chip only, about two minutes): builds the cell's engine
as `benchmark/run.py` does, then traces a few prefills and a few decode
steps with every slot busy, apart, and prints each program's longest ops
under their HLO names; the paged kernel's calls (kMLA's decode step,
`ops/paged_attention.py`) are the kind `singa_paged_decode`.

    python3 tools/kimi_step_profile.py --workload serve-kimi-decode-sat [--plen 256] [--live 450]
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

from benchmark import harness                     # noqa: E402
from benchmark.trace import reduce as reducer     # noqa: E402


def _trace(path, fn):
    import jax
    shutil.rmtree(path, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(path, profiler_options=opts)
    t0 = time.perf_counter()
    n = fn()
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(path, "plugins", "profile", "*",
                                          "*.xplane.pb")))[-1]
    planes = reducer.read_planes(found)
    dev = [p for p in planes if reducer.DEVICE_PLANE.match(p["name"])][0]
    ops = reducer.line_events(dev, reducer.OPS_LINE)
    self_t = reducer.self_times(ops)
    total = sum(self_t.values())
    print(f"  {n} runs, wall {1e3 * wall / n:.2f} ms a run, device ops "
          f"{1e3 * total / n:.2f} ms a run")
    kinds = {}
    for name, sec in self_t.items():
        kinds[reducer.short(name)] = kinds.get(reducer.short(name), 0) + sec
    for k, v in sorted(kinds.items(), key=lambda kv: -kv[1])[:12]:
        print(f"  kind {1e3 * v / n:8.3f} ms  {k}")
    for name, sec in sorted(self_t.items(), key=lambda kv: -kv[1])[:45]:
        print(f"  {1e3 * sec / n:8.3f} ms  {name[:230]}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--plen", type=int, default=256)
    ap.add_argument("--live", type=int, default=450)
    ap.add_argument("--steps", type=int, default=8)
    args = ap.parse_args()
    cell = harness.Cell(args.workload)
    harness.start_jax(cell)
    import jax
    import numpy as np
    runner = cell.load("runners", cell.spec["runner"])
    engine, sched = runner.build(cell, args.seed)
    sched.stop()
    kv, spec = sched.kv, engine.spec
    slots = spec.cb_slots
    for s in range(slots):
        kv.alloc(s, kv.blocks_for(args.live + args.steps + 2))
    params = engine.params
    # the rung a cell's scheduler gives a prompt of this length
    width = spec.cb_prefill_width(args.plen)
    toks = np.zeros((1, width), np.int32)
    toks[0, :args.plen] = np.random.default_rng(0).integers(
        0, cell.config["vocab_size"], args.plen)
    out = os.path.join(ROOT, ".bench_trace", "kimi_step_profile")

    def prefills():
        for s in range(4):
            _, kv.pools = engine.run_cb_prefill(
                params, kv.pools, toks, args.plen,
                kv.prefill_target(s, width // spec.cb_block_len))
        jax.block_until_ready(kv.pools)
        return 4

    def decodes():
        last = np.ones((slots,), np.int32)
        ntoks = np.full((slots,), args.live, np.int32)
        for _ in range(args.steps):
            nxt, kv.pools = engine.run_cb_decode(params, kv.pools, last,
                                                 ntoks, kv.table_array())
            last, ntoks = nxt.astype(np.int32), ntoks + 1
        return args.steps

    prefills(), decodes()                            # warm
    print(f"prefill, {args.plen} real rows of {width} "
          f"(rungs {spec.cb_prefill_widths}):")
    _trace(out, prefills)
    print(f"decode, {slots} busy slots of {args.live} tokens:")
    _trace(out, decodes)
    print("memory_peak_bytes", harness.device_record(1)["memory_peak_bytes"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
