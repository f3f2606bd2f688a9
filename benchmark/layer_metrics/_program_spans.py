"""The program's own spans, read back from the kept trace.

Since PR 24 every `obs.span` of the program is a
`jax.profiler.TraceAnnotation`: it lies on `/host:CPU` of the same
xplane file the device's ops are in, and its scalar attributes come
back as the event's `stats`.  A reader gets `facts` and nothing else,
so the file is the carrier: the runner's capture leaves it at
`<root>/.bench_trace/<cell>/plugins/profile/*/*.xplane.pb`, and this
module opens it once a process.

The two planes do NOT share a clock to better than a millisecond or
two: in the traces of PR 24 the device plane lies 1.0 to 1.9 ms EARLY
(a program "starts" before the host has enqueued it).  The TPU runtime
tags its own host events with the program run's `run_id`
(`DoEnqueueProgram` when it hands a run to the chip, `CompleteCallbacks`
when it hears the run is done), and the `XLA Modules` events carry the
same id, so causality bounds the shift from both sides: no run starts
before its enqueue call BEGAN (the chip may start before the call
returns), none ends after its completion was heard.  Each run gives one
bound of each kind, and each is a true bound whatever the host's
threads did meanwhile, so the tightest of each holds.  Device times are
moved later by the least shift the first allows (`clock_shift`); the
second lay 0.05 to 0.3 ms above it (`clock_slack`).  That much the
split below can be off, and only between two parts: a later shift
moves each idle gap later, out of the `engine.fetch` that heard the
last program end and into the `engine.dispatch` (or the head of the
next fetch) in which the next one starts; the upload and the rest lie
wholly inside the gap and keep theirs.  `step_clock_slack_ms.chat`
reports it beside the parts.

Gives: the program's spans by name with their stats, the device's busy
union on the host's clock, and device-idle seconds inside one kind of
span but outside others.  `of(facts)` is None where there is no device
plane (the CPU rehearsal), no kept trace, or no span of the program in
it (a program from before PR 24).
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmark import stats
from benchmark.trace import reduce as reducer

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PROGRAM_PREFIXES = ("scheduler.", "engine.", "trainer.", "feeder.")
Span = Tuple[float, float, Dict]          # start_s, end_s, stats
Interval = Tuple[float, float]


def trace_path(facts: Dict) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        ROOT, ".bench_trace", str(facts.get("cell")), "plugins", "profile",
        "*", "*.xplane.pb")))
    return found[-1] if found else None


RUNTIME_EVENTS = ("DoEnqueueProgram", "CompleteCallbacks")


def _wants_stats(device: bool, line: str, name: str) -> bool:
    if device:
        return line == reducer.MODULES_LINE
    return name.startswith(PROGRAM_PREFIXES) or name in RUNTIME_EVENTS


def read_planes(path: str) -> List[Dict]:
    """As `reduce.read_planes`, each line with a `stats` list beside its
    `events`: one dict for a program span, a runtime event or a program
    run, None for any other event (the device's ops carry only their
    times there; PERF.md, "Reading a v5e trace")."""
    if path.endswith(".json.gz"):        # a trimmed fixture of this form
        import gzip
        import json
        with gzip.open(path, "rt") as f:
            planes = json.load(f)
        for p in planes:
            for ln in p["lines"]:
                ln["events"] = [tuple(e) for e in ln["events"]]
                ln.setdefault("stats", [None] * len(ln["events"]))
        return planes
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(reducer.DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events, st = [], []
            for ev in line.events:
                events.append((ev.name, ev.start_ns * 1e-9,
                               (ev.start_ns + ev.duration_ns) * 1e-9))
                st.append(dict(ev.stats) if _wants_stats(
                    device, line.name, ev.name) else None)
            lines.append({"name": line.name, "events": events, "stats": st})
        out.append({"name": plane.name, "lines": lines})
    return out


class Trace:
    """One kept trace: program spans, and the device's busy time moved
    onto the host's clock."""

    def __init__(self, planes: Sequence[Dict]):
        devices = [p for p in planes if reducer.DEVICE_PLANE.match(p["name"])]
        self.spans: Dict[str, List[Span]] = {}
        runtime: Dict[str, Dict[int, Interval]] = {n: {} for n in
                                                   RUNTIME_EVENTS}
        for p in planes:
            if reducer.DEVICE_PLANE.match(p["name"]):
                continue
            for ln in p["lines"]:
                for (name, s, e), st in zip(ln["events"], ln["stats"]):
                    if name.startswith(PROGRAM_PREFIXES):
                        self.spans.setdefault(name, []).append(
                            (s, e, st or {}))
                    elif name in runtime and st and "run_id" in st:
                        runtime[name][int(st["run_id"])] = (s, e)
        for rows in self.spans.values():
            rows.sort(key=lambda r: r[0])
        # one chip's cells only: the first device plane
        ops: List[Interval] = []
        runs: Dict[int, Interval] = {}
        for ln in (devices[0]["lines"] if devices else []):
            if ln["name"] == reducer.OPS_LINE:
                ops += [(s, e) for _, s, e in ln["events"]]
            elif ln["name"] == reducer.MODULES_LINE:
                for (_, s, e), st in zip(ln["events"], ln["stats"]):
                    if st and "run_id" in st:
                        runs[int(st["run_id"])] = (s, e)
                if not ops:
                    ops = [(s, e) for _, s, e in ln["events"]]
        enq, done = runtime["DoEnqueueProgram"], runtime["CompleteCallbacks"]
        least = [enq[r][0] - runs[r][0] for r in runs if r in enq]
        most = [done[r][0] - runs[r][1] for r in runs if r in done]
        self.clock_shift = max(least) if least else 0.0
        self.clock_slack = (min(most) - self.clock_shift) if most and least \
            else None
        self.busy = stats.union((s + self.clock_shift, e + self.clock_shift)
                                for s, e in ops)
        self.lo, self.hi = ((self.busy[0][0], self.busy[-1][1])
                            if self.busy else (0.0, 0.0))
        self.idle = stats.gaps(self.busy, self.lo, self.hi)

    def intervals(self, name: str, inside: Optional[str] = None
                  ) -> List[Interval]:
        """The spans of `name` that lie within the device's traced
        time (and, with `inside`, within a span of that name)."""
        rows = [(s, e) for s, e, _ in self.spans.get(name, [])
                if s >= self.lo and e <= self.hi]
        if inside is not None:
            outer = self.intervals(inside)
            rows = [(s, e) for s, e in rows
                    if any(a <= s and e <= b for a, b in outer)]
        return rows

    def idle_inside(self, within: Iterable[Interval],
                    without: Iterable[Interval] = ()) -> float:
        """Device-idle seconds inside `within` and outside `without`."""
        within = stats.union(within)
        return (overlap(self.idle, within)
                - overlap(self.idle, intersect(within,
                                               stats.union(without))))


def intersect(a: Sequence[Interval], b: Sequence[Interval]
              ) -> List[Interval]:
    """Intersection of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intersect(a, b))


_CACHE: Dict[str, Optional[Trace]] = {}


def load(path: str) -> Optional[Trace]:
    tr = Trace(read_planes(path))
    return tr if tr.busy and tr.spans else None


def of(facts: Dict) -> Optional[Trace]:
    """The kept trace of this run's cell, or None (see module doc)."""
    if not (facts.get("trace") or {}).get("devices"):
        return None                      # no device plane: the rehearsal
    path = trace_path(facts)
    if path is None:
        return None
    if path not in _CACHE:
        _CACHE[path] = load(path)
    return _CACHE[path]


# -- the per-token path's readers ---------------------------------------------

ENGINE_PARTS = ("engine.upload", "engine.dispatch", "engine.fetch")


def step_host_parts(facts: Dict) -> Optional[Dict[str, float]]:
    """Milliseconds a decode step in which the device waits on the
    host: device-idle time inside `scheduler.step` spans but outside
    `scheduler.admit_pending`, per `scheduler.decode` span; and its
    parts: inside `engine.upload` / `engine.dispatch` / `engine.fetch`
    of `engine.cb_decode`, and the rest (emit, account, expire, the
    loop).  The four parts sum to `host`."""
    tr = of(facts)
    if tr is None:
        return None
    steps = tr.intervals("scheduler.step")
    decodes = len(tr.intervals("scheduler.decode", inside="scheduler.step"))
    if not steps or not decodes:
        return None
    admit = tr.intervals("scheduler.admit_pending")
    out, parts = {}, []
    for name in ENGINE_PARTS:
        rows = tr.intervals(name, inside="engine.cb_decode")
        parts += rows
        out[name.split(".")[1]] = tr.idle_inside(rows, admit)
    out["rest"] = tr.idle_inside(steps, admit + parts)
    out["host"] = tr.idle_inside(steps, admit)
    return {k: 1e3 * v / decodes for k, v in out.items()}


def clock_slack_ms(facts: Dict) -> Optional[float]:
    """How far apart the two causal bounds on the device plane's clock
    shift lie in this trace: `step_dispatch_ms` and `step_fetch_ms` can
    trade that much between them (module doc)."""
    tr = of(facts)
    if tr is None or tr.clock_slack is None or \
            step_host_parts(facts) is None:
        return None                      # no parts to qualify
    return 1e3 * tr.clock_slack


def step_part_ms(facts: Dict, part: str) -> Optional[float]:
    parts = step_host_parts(facts)
    return None if parts is None else parts[part]


def admit_stall_ms(facts: Dict) -> Optional[float]:
    """Mean wall of the traced `scheduler.admit_pending` spans that
    admitted at least one request (hold a `scheduler.prefill`): how
    long an admitting step holds every slot before it decodes."""
    tr = of(facts)
    if tr is None:
        return None
    prefills = tr.intervals("scheduler.prefill")
    walls = [e - s for s, e in tr.intervals("scheduler.admit_pending")
             if any(s <= a and b <= e for a, b in prefills)]
    return 1e3 * sum(walls) / len(walls) if walls else None


def queue_wait_ms(facts: Dict) -> Optional[float]:
    """Median `queue_ms` (submit to admission) of the traced
    `scheduler.prefill` spans: a dozen requests in a 4 s trace."""
    tr = of(facts)
    if tr is None:
        return None
    waits = [st["queue_ms"] for s, e, st in tr.spans.get(
        "scheduler.prefill", []) if "queue_ms" in st]
    return stats.median(waits) if waits else None
