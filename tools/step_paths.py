"""What the scheduler's loop did while the device idled, from a kept
trace (a builder's tool: the eleven metrics of
`benchmark/layer_metrics/_step_paths.py` with what they do not split):

    python tools/step_paths.py --workload serve-kimi-decode-sat
    python tools/step_paths.py --trace some.xplane.pb | fixture.json.gz

reads the trace a `--trace 1` run of the cell kept
(`.bench_trace/<cell>/`; no chip needed to READ it) and prints the
partition of the device's idle time by the innermost span of the loop,
an admission's inside (`engine.cb_prefill` the hand-over,
`scheduler.collect(why=2)` the read of the step in flight,
`engine.cb_prefill_fetch` the wait for the first token), how steps went
out (`scheduler.decode`'s `ahead`, `scheduler.collect`'s `why`), each
span's median wall, an emit loop's cost a slot, and the stall account."""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import stats  # noqa: E402
from benchmark.layer_metrics import _program_spans as P  # noqa: E402
from benchmark.layer_metrics import _step_paths as SP  # noqa: E402

SPANS = ("scheduler.step", "scheduler.admit_pending", "scheduler.prefill",
         "engine.cb_prefill", "engine.cb_prefill_fetch", "scheduler.decode",
         "engine.cb_decode", "engine.upload", "engine.dispatch",
         "engine.fetch", "scheduler.collect", "scheduler.emit",
         "scheduler.wait")


def report(facts):
    tr = SP.of(facts)
    if tr is None:
        return {"error": "no kept trace with a device plane and PR 37's "
                         "spans (`scheduler.emit`) for these facts"}
    parts = SP.idle_partition(facts)
    admit = stats.union(tr.intervals("scheduler.admit_pending"))
    admitting = [(s, e) for s, e, st in tr.spans.get("scheduler.collect", [])
                 if st.get("why") == 2]

    def pct(seconds):
        return round(100.0 * seconds / parts["span"], 4)

    def in_admission(rows):
        return pct(P.overlap(tr.idle, P.intersect(stats.union(rows), admit)))

    def count(name, key):
        values = [st[key] for _, _, st in tr.spans.get(name, []) if key in st]
        return {str(v): values.count(v) for v in sorted(set(values))}

    emits = [(e - s, st["slots"]) for s, e, st in
             tr.spans.get("scheduler.emit", []) if st.get("slots")]
    return {
        "traced_span_s": parts["span"],
        "clock_shift_ms": 1e3 * tr.clock_shift,
        "idle_pct": {k: pct(v) for k, v in parts.items() if k != "span"},
        "idle_pct_sum": pct(sum(v for k, v in parts.items() if k != "span")),
        "idle_admit_pct_inside": {
            "engine.cb_prefill": in_admission(
                tr.intervals("engine.cb_prefill")),
            "scheduler.collect": in_admission(admitting),
            "engine.cb_prefill_fetch": in_admission(
                tr.intervals("engine.cb_prefill_fetch")),
        },
        "step_ahead_share": SP.step_ahead_share(facts),
        "round_trip_host_ms": SP.round_trip_host_ms(facts),
        "stall_s": SP.stall_seconds(facts, "stall_ms"),
        "stall_wait_s": SP.stall_seconds(facts, "stall_wait_ms"),
        "decode_ahead": count("scheduler.decode", "ahead"),
        "collect_why": count("scheduler.collect", "why"),
        "emit_us_a_slot": (1e6 * stats.median([d / n for d, n in emits])
                           if emits else None),
        "median_ms": {n: round(1e3 * stats.median(
            [e - s for s, e, _ in tr.spans[n]]), 4)
            for n in SPANS if tr.spans.get(n)},
        "spans": {n: len(tr.spans[n]) for n in SPANS if tr.spans.get(n)},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", help="a cell whose traced run was kept")
    ap.add_argument("--trace", help="an xplane file or a trimmed fixture")
    args = ap.parse_args(argv)
    if bool(args.workload) == bool(args.trace):
        ap.error("one of --workload and --trace")
    if args.trace:
        P.trace_path = lambda facts: args.trace
    facts = {"cell": args.workload, "trace": {"devices": 1}}
    print(json.dumps({"tool": "step_paths", **report(facts)}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
