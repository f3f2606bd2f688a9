"""The serving step's paths, read back from the kept trace (PR 37).

Since PR 37 the scheduler's loop thread spans every path a step can
take, not the round trip alone: `scheduler.decode` says whether the
step went to the device AHEAD of the read of the step before it,
`scheduler.collect` is that read (and says why it happened where it
did), `scheduler.emit` the per-slot loop on either path,
`engine.cb_prefill` / `engine.cb_prefill_fetch` the two engine calls of
an admission, `scheduler.wait` the loop with nothing to do
(docs/OBSERVABILITY.md, the per-token path).  On
`_program_spans.Trace` (the device plane moved onto the host's clock by
the `run_id` causality bound) this module gives:

* ONE partition of the traced span's device-idle seconds by the
  innermost span of the program, seven parts that sum exactly to the
  trace's own idle time (gaps of the shifted busy union between the
  first and the last device op; `device_idle.<cell>` is `reduce.py`'s,
  unshifted and over every event's extent: the two differ at the
  span's two edges only);
* the share of decode steps that went ahead, and what a step that did
  NOT costs the device in idle time (ROADMAP S3's yardstick);
* the stall account's running values, which `scheduler.step` carries
  as attributes: seconds of the whole run, not of the traced span.

Every reader gives None where `_program_spans.of` does (no device
plane, no kept trace, no span of the program) and where the trace
holds no `scheduler.emit`: a program from before PR 37, the parent's
side of that PR's check.  (`scheduler.collect` would not do as the
mark: a house that never fills, as `serve-code-sat`'s, has none.)
"""

from __future__ import annotations

from typing import Dict, List, Optional

from benchmark import stats
from benchmark.layer_metrics import _program_spans as P

MARK = "scheduler.emit"
# innermost first: a part takes what the parts before it left
PARTS = (
    ("admit", ("scheduler.admit_pending",)),
    ("emit", ("scheduler.emit",)),
    ("decode_wait", ("engine.fetch",)),
    ("decode_handover", ("engine.upload", "engine.dispatch")),
    ("step_rest", ("scheduler.step",)),
    ("no_work", ("scheduler.wait",)),
)


def of(facts: Dict) -> Optional[P.Trace]:
    tr = P.of(facts)
    return tr if tr is not None and tr.spans.get(MARK) else None


def _clipped(tr: P.Trace, names) -> List[P.Interval]:
    """The merged spans of `names`, cut to the device's traced time (a
    span that straddles an edge keeps its part inside)."""
    return stats.union(stats.clip(
        ((s, e) for n in names for s, e, _ in tr.spans.get(n, [])),
        tr.lo, tr.hi))


def idle_partition(facts: Dict) -> Optional[Dict[str, float]]:
    """Device-idle seconds of the traced span by the innermost program
    span: `admit` (all of `scheduler.admit_pending`: the hand-over, the
    collect inside it, the first-token fetch, alloc), `emit`,
    `decode_wait` (`engine.fetch`, inside `engine.cb_decode` or bare),
    `decode_handover` (`engine.upload` + `engine.dispatch`),
    `step_rest` (the rest of `scheduler.step`: expire, the walk's
    counts, the table, the account), `no_work` (`scheduler.wait`),
    `unnamed` (under no span of the loop: its lock, another thread, the
    edges); and `span`, the seconds they are shares of."""
    tr = of(facts)
    if tr is None or tr.hi <= tr.lo:
        return None
    left, out = tr.idle, {}
    for part, names in PARTS:
        spans = _clipped(tr, names)
        out[part] = P.overlap(left, spans)
        left = P.intersect(left, stats.gaps(spans, tr.lo, tr.hi))
    out["unnamed"] = sum(e - s for s, e in left)
    out["span"] = tr.hi - tr.lo
    return out


def idle_share(facts: Dict, part: str) -> Optional[float]:
    """One part of the partition, in % of the traced span."""
    parts = idle_partition(facts)
    return None if parts is None else 100.0 * parts[part] / parts["span"]


def _decodes(tr: P.Trace) -> List[P.Span]:
    """The traced decode steps that say how they went out."""
    return [(s, e, st) for s, e, st in tr.spans.get("scheduler.decode", [])
            if s >= tr.lo and e <= tr.hi and "ahead" in st]


def step_ahead_share(facts: Dict) -> Optional[float]:
    """Of the traced `scheduler.decode` spans, the % with `ahead` = 1:
    steps whose hand-over the device did not wait for."""
    tr = of(facts)
    rows = _decodes(tr) if tr is not None else []
    if not rows:
        return None
    return 100.0 * sum(int(st["ahead"]) for _, _, st in rows) / len(rows)


def round_trip_host_ms(facts: Dict) -> Optional[float]:
    """Milliseconds the device idles in a step that did NOT go ahead (a
    round trip, or the first step of a full house after an admission or
    a drain): idle time inside the `scheduler.step` spans whose decode
    has `ahead` = 0, outside `scheduler.admit_pending`, per such step."""
    tr = of(facts)
    if tr is None:
        return None
    exposed = [(s, e) for s, e, st in _decodes(tr) if not int(st["ahead"])]
    steps = [(a, b) for a, b in tr.intervals("scheduler.step")
             if any(a <= s and e <= b for s, e in exposed)]
    if not steps:
        return None
    idle = tr.idle_inside(steps, tr.intervals("scheduler.admit_pending"))
    return 1e3 * idle / len(steps)


def stall_seconds(facts: Dict, key: str) -> Optional[float]:
    """`stall_ms` or `stall_wait_ms` of the last traced `scheduler.step`,
    in seconds: the stall account since the scheduler started
    (`ServeStats.cb_stall_seconds` / `cb_stall_wait_seconds`), pre-roll
    and window, whatever stretch of it the trace holds."""
    tr = of(facts)
    if tr is None:
        return None
    rows = [st for _, _, st in tr.spans.get("scheduler.step", [])
            if key in st]
    return float(rows[-1][key]) / 1e3 if rows else None
