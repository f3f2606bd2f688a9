#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json and print its result line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell.  The workload's files name its configuration,
its traffic mix and its runner; the per-layer metrics it reports are
those of BENCHMARK.json that list it, each read by
`benchmark/layer_metrics/<metric>.py`.  Nothing here names a cell.

`--rehearsal 1` is the benchmark's own CPU mode for its tests: the
files' `tiny` sizes, a device record that says `cpu`, counts only and
no metric.  Without it a run that finds no TPU, or fewer chips than the
cell asks for, exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()        # set-up is counted from here

import argparse                        # noqa: E402
import os                              # noqa: E402
import sys                             # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness          # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.Cell(args.workload, rehearsal=bool(args.rehearsal))
    log = harness.start_jax(cell)
    runner = cell.load("runners", cell.spec["runner"])
    outcome = runner.run(cell, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), t_process=T_PROCESS,
                         compile_log=log)
    print(harness.result_line(cell, bool(args.trace), outcome), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
