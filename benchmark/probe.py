#!/usr/bin/env python3
"""Readings that define a cell, taken once on the chip (never by a
benchmark run): the knee sweep of a serving mix, and the correctness
numbers of sound runs and of the lower-precision control over many
seeds.  One process per call, so set-up is paid once.

    python3 benchmark/probe.py sweep --workload W --rates 2,4,6 --seconds 15 --out F
    python3 benchmark/probe.py seeds --workload W --seeds 11,12 --seconds 6 [--control fp8] --out F
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, stats          # noqa: E402


def sweep(cell, args, log) -> list:
    """One engine, one window per rate: tails, completed tokens/s and
    the backlog left when the window closed."""
    from benchmark.runners import serve_cb
    from benchmark.traffic import open_loop
    clock = serve_cb._TokenClock()
    engine, sched = serve_cb.build(cell, args.seed)
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(cell.traffic, rate_rps=rate)
        reqs = open_loop.generate(mix, args.seed, args.seconds,
                                  cell.config["vocab_size"])
        sent, t0 = serve_cb.drive(sched, reqs, args.seconds)
        t1 = t0 + args.seconds
        backlog = sum(1 for s in sent if not s.ticket.done())
        unstarted = sum(1 for s in sent if not s.times)
        serve_cb.finish(sent, "drain", timeout=300.0)
        ttft = [(s.times[0] - s.due) * 1e3 for s in sent if s.times]
        itl = [(b - a) * 1e3 for s in sent
               for a, b in zip(s.times, s.times[1:]) if b <= t1]
        toks = sum(1 for s in sent for t in s.times if t0 <= t <= t1)
        half = [x for s, x in zip(sent, ttft) if s.due > t0 + args.seconds / 2]
        row = {"rate_rps": rate, "seconds": args.seconds, "sent": len(sent),
               "ttft_p50_ms": stats.median(ttft),
               "ttft_p95_ms": stats.percentile(ttft, 95),
               "ttft_p50_second_half_ms": stats.median(half) if half else None,
               "itl_p50_ms": stats.median(itl),
               "itl_p95_ms": stats.percentile(itl, 95),
               "out_tok_s": toks / args.seconds,
               "offered_tok_s": sum(r.max_new for r in reqs) / args.seconds,
               "backlog_at_close": backlog, "unstarted_at_close": unstarted,
               "late_p95_ms": stats.percentile(
                   [(s.sent - s.due) * 1e3 for s in sent], 95)}
        print(json.dumps(row), flush=True)
        rows.append(row)
    sched.stop()
    clock.close()
    return rows


def seeds(cell, args, log) -> list:
    runner = cell.load("runners", cell.spec["runner"])
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = runner.run(cell, seed=seed, seconds=args.seconds, trace=False,
                         t_process=time.perf_counter(), compile_log=log,
                         control=args.control or None)
        row = {"seed": seed, "correct": out["correct"],
               "compared": out["compared"], "control": out.get("control"),
               "end_to_end": out["end_to_end"]}
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("what", choices=("sweep", "seeds"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=20260927)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--control", default="")
    ap.add_argument("--rehearsal", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    cell = harness.Cell(args.workload, rehearsal=bool(args.rehearsal))
    log = harness.start_jax(cell)
    rows = {"sweep": sweep, "seeds": seeds}[args.what](cell, args, log)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "what": args.what,
                       "device": harness.device_record(cell.chips),
                       "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
