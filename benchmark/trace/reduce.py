"""From the profiler's xplane file to busy time, kernel time and gaps.

`jax.profiler.ProfileData` reads the file with nothing but JAX.  What a
v5e trace looks like (seen by hand in PR 23, PERF.md section 3): one
plane per chip named `/device:TPU:<n>`, whose line `XLA Ops` holds one
event per executed HLO op (a `while` and the ops of its body both), and
whose line `XLA Modules` holds one event per program run, named
`jit_<function>(<fingerprint>)`; the plane `/host:CPU` holds one line
per host thread, `jax.profiler.TraceAnnotation` spans among them.  All
planes share one clock.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmark import stats

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
Event = Tuple[str, float, float]          # name, start_s, end_s


def read_planes(path: str) -> List[Dict]:
    """[{name, lines: [{name, events: [Event]}]}], times in seconds."""
    if path.endswith(".json.gz"):        # a trimmed fixture of this form
        import gzip
        import json
        with gzip.open(path, "rt") as f:
            return [{"name": p["name"], "lines": [
                {"name": ln["name"],
                 "events": [tuple(e) for e in ln["events"]]}
                for ln in p["lines"]]} for p in json.load(f)]
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = [(ev.name, ev.start_ns * 1e-9,
                       (ev.start_ns + ev.duration_ns) * 1e-9)
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        out.append({"name": plane.name, "lines": lines})
    return out


def line_events(plane: Dict, name: str) -> List[Event]:
    return [e for ln in plane["lines"] if ln["name"] == name
            for e in ln["events"]]


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Seconds per op name, a parent's time less its children's (the
    ops line nests a loop's body inside the loop's own event)."""
    out: Dict[str, float] = {}
    stack: List[List] = []                # [name, end, child_seconds]

    def close(item):
        name, start, end, child = item
        out[name] = out.get(name, 0.0) + max((end - start) - child, 0.0)
        if stack:
            stack[-1][3] += end - start

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and s >= stack[-1][2]:
            close(stack.pop())
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return out


def host_spans(planes: Sequence[Dict], names: Iterable[str]) -> List[Event]:
    names = set(names)
    return [e for p in planes if not DEVICE_PLANE.match(p["name"])
            for ln in p["lines"] for e in ln["events"] if e[0] in names]


def covering(spans: Sequence[Event], t: float) -> Optional[str]:
    """The shortest span that holds time `t`."""
    hit = [(e - s, n) for n, s, e in spans if s <= t <= e]
    return min(hit)[1] if hit else None


def short(name: str) -> str:
    """An op's kind: the trace names an op by its whole HLO line
    (`%fusion.123 = f32[...] fusion(...)`); keep the name before ` = `
    without its `%` and numeric suffix, so that one kind of op adds up."""
    head = name.split(" = ")[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", head) or head


def custom_call_signature(name: str) -> Optional[str]:
    """For a Mosaic kernel's event (`%tpu_custom_call.N = <outputs>
    custom-call(...)`): its output types without layouts, e.g.
    `(bf16[2,4096,4096], f32[2,4096,32])`.  The trace carries no kernel
    name (PERF.md, for the tracing issue), so a reader tells the kernels
    apart by what they return."""
    m = re.match(r"%?tpu_custom_call[.\d]* = (.*?) custom-call\(", name)
    return re.sub(r"\{[^}]*\}", "", m.group(1)) if m else None


def programs_by_span(modules) -> Dict[str, Dict]:
    """For each host span name: the program that took most device time
    under it (`main`: name, seconds, runs) and the others' total.  A
    call of the engine also runs a few one-microsecond conversion
    programs for its arguments; they are not runs of the step."""
    per: Dict[str, Dict[str, List[float]]] = {}
    for name, s, e, who in modules:
        row = per.setdefault(who or "no_span", {}).setdefault(
            name.split("(")[0], [0.0, 0])
        row[0] += e - s
        row[1] += 1
    out = {}
    for who, progs in per.items():
        main = max(progs, key=lambda k: progs[k][0])
        out[who] = {"main": main, "seconds": progs[main][0],
                    "runs": progs[main][1],
                    "other_seconds": sum(v[0] for k, v in progs.items()
                                         if k != main)}
    return out


SPAN_NAMES = ("engine.prefill", "engine.decode", "trainer.chunk",
              "feeder.wait", "host.fetch", "sched.admit")


def reduce(path: str, span_names: Iterable[str] = SPAN_NAMES) -> Dict:
    planes = read_planes(path)
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    if not devices:
        return {"busy_s": 0.0, "window_s": 0.0, "devices": 0}
    spans = host_spans(planes, span_names)
    every = [e for p in devices for ln in p["lines"] for e in ln["events"]]
    every += spans
    lo, hi = min(e[1] for e in every), max(e[2] for e in every)
    busy_each, ops_total, kernels, modules = [], {}, {}, []
    kernel_calls: Dict[str, int] = {}
    gaps_by: Dict[str, float] = {}
    for p in devices:
        ops = line_events(p, OPS_LINE) or line_events(p, MODULES_LINE)
        busy = stats.union((s, e) for _, s, e in ops)
        busy_each.append(stats.union_length(busy))
        for n, sec in self_times(ops).items():
            ops_total[short(n)] = ops_total.get(short(n), 0.0) + sec
        for n, s, e in ops:
            sig = custom_call_signature(n)
            if sig:
                kernels[sig] = kernels.get(sig, 0.0) + e - s
                kernel_calls[sig] = kernel_calls.get(sig, 0) + 1
        modules += [(n, s, e, covering(spans, 0.5 * (s + e)))
                    for n, s, e in line_events(p, MODULES_LINE)]
        for s, e in stats.gaps(busy, lo, hi):
            who = covering(spans, 0.5 * (s + e)) or "no_span"
            gaps_by[who] = gaps_by.get(who, 0.0) + e - s
    n = len(devices)
    top = sorted(ops_total.items(), key=lambda kv: -kv[1])[:10]
    by_span = programs_by_span(modules)
    return {
        "devices": n, "window_s": hi - lo, "busy_s": sum(busy_each) / n,
        "ops": ops_total, "kernels": kernels,
        "kernel_calls": kernel_calls,
        "modules_by_span": by_span,
        "module_names": sorted({short(m[0].split("(")[0]) for m in modules}),
        "breakdown": {
            "device_ops": [[k, v / n] for k, v in top],
            "idle_gaps": [[k, v / n] for k, v in sorted(
                gaps_by.items(), key=lambda kv: -kv[1])[:10]]}}
