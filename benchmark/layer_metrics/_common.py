"""Shared arithmetic of the per-layer readers.  A reader is
`read(facts) -> number or None`; `facts` is what the cell's runner
gathered (spans, counters, samples, the reduced trace, the
configuration and the device's peaks).  None means nothing to read."""

from __future__ import annotations

from typing import Dict, List, Optional

from benchmark import opcount, stats


def span_median_ms(facts: Dict, name: str) -> Optional[float]:
    rows = [r for r in facts.get("spans", []) if r[0] == name]
    if not rows:
        return None
    return stats.median([(r[2] - r[1]) * 1e3 for r in rows])


def sample_p95(facts: Dict, name: str) -> Optional[float]:
    xs = facts.get("samples", {}).get(name) or []
    return stats.percentile(xs, 95) if xs else None


def device_idle(facts: Dict) -> Optional[float]:
    tr = facts.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def _traced_rows(facts: Dict, name: str) -> List[tuple]:
    """The host spans of `name` that lie inside the traced span."""
    span = facts.get("trace_span")
    if not span:
        return []
    return [r for r in facts.get("spans", [])
            if r[0] == name and r[1] >= span[0] and r[2] <= span[1]]


def _module_seconds(facts: Dict, name: str):
    row = ((facts.get("trace") or {}).get("modules_by_span") or {}).get(name)
    return (row["seconds"], row["runs"]) if row and row["runs"] else (0.0, 0)


def decode_roofline(facts: Dict) -> Optional[float]:
    """Least time of the traced decode steps (weights once plus every
    live cached token's keys and values, over the memory bandwidth; or
    their operations over the peak, whichever is longer) over the
    decode program's device time."""
    rows = _traced_rows(facts, "engine.decode")
    seconds, runs = _module_seconds(facts, "engine.decode")
    peaks = facts.get("peaks")
    if not rows or not runs or not peaks:
        return None
    cfg, slots = facts["config"], facts["counters"]["cb_slots"]
    live = sum(r[3] for r in rows) / len(rows)
    need_b = opcount.decode_step_needed_bytes(cfg, live, facts["itemsize"])
    need_f = opcount.decode_step_flops(cfg, slots, live)
    return stats.roofline_share(need_f, need_b, seconds / runs,
                                peaks["bf16_flops_per_s"],
                                peaks["hbm_bytes_per_s"])


def prefill_roofline(facts: Dict) -> Optional[float]:
    """Needed operations of the traced prefills at their REAL prompt
    lengths (the head over the last row only) over the prefill
    program's device time."""
    rows = _traced_rows(facts, "engine.prefill")
    seconds, runs = _module_seconds(facts, "engine.prefill")
    peaks = facts.get("peaks")
    if not rows or not runs or not peaks:
        return None
    cfg = facts["config"]
    need = sum(opcount.prefill_needed_flops(cfg, r[3]) for r in rows)
    need_b = len(rows) * opcount.weight_bytes(cfg, facts["itemsize"])
    # spans and program runs are the same calls; scale if the trace cut one
    scale = runs / len(rows)
    return stats.roofline_share(need * scale, need_b * scale, seconds,
                                peaks["bf16_flops_per_s"],
                                peaks["hbm_bytes_per_s"])
