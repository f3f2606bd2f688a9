"""Readers of the Trinity cells: the decode program and its paged-kernel
calls against the bytes a step cannot avoid
(`benchmark/trinity_opcount.py`) and what of a context the windowed
layers read (the load on the held experts and its balance are read as
the ZAYA cell's, `_zaya.py`).
`facts["spans"]` rows of `engine.decode` are the runner's
(`runners/serve_kimi.py:_Spans`): (name, t0, t1, live tokens, busy
slots, experts touched, assignments); rows of `engine.window`
(`runners/serve_trinity.py:_Spans`) carry, a step, the sum over slots
of min(context, window).  None where there is nothing to read: no
trace, or a program without those counters."""

from __future__ import annotations

from typing import Dict, Optional

from benchmark import stats, trinity_opcount
from benchmark.layer_metrics._common import _module_seconds, _traced_rows


def _step(facts: Dict):
    """The traced decode steps' means (live tokens, busy slots, experts
    touched, assignments, window tokens), the decode program's device
    seconds and its runs; None without them."""
    rows = [r for r in _traced_rows(facts, "engine.decode") if len(r) >= 7]
    win = _traced_rows(facts, "engine.window")
    seconds, runs = _module_seconds(facts, "engine.decode")
    if not rows or not win or not runs or not facts.get("peaks"):
        return None
    mean = lambda rs, i: sum(r[i] for r in rs) / len(rs)     # noqa: E731
    return {"live": mean(rows, 3), "busy": mean(rows, 4),
            "touched": mean(rows, 5), "assigned": mean(rows, 6),
            "window": mean(win, 3), "seconds": seconds, "runs": runs}


def decode_roofline(facts: Dict) -> Optional[float]:
    st = _step(facts)
    if st is None:
        return None
    cfg, peaks = facts["config"], facts["peaks"]
    need_b = trinity_opcount.decode_step_needed_bytes(
        cfg, st["busy"], st["live"], st["window"], st["touched"],
        facts["itemsize"])
    need_f = trinity_opcount.decode_step_flops(
        cfg, st["busy"], st["live"], st["window"], st["assigned"])
    return stats.roofline_share(need_f, need_b, st["seconds"] / st["runs"],
                                peaks["bf16_flops_per_s"],
                                peaks["hbm_bytes_per_s"])


def paged_roofline(facts: Dict) -> Optional[float]:
    """The K and V rows the traced steps' `singa_paged_decode` calls had
    to read (full layers every live token, windowed the window's) over
    the memory bandwidth, over the device time of the trace's
    `singa_paged_decode` rows.  The rows hold EVERY run of the decode
    program in the traced span, also those that lie outside the
    runner's annotation (in a full house it lies around the fetch, and
    a step may run after it): all of them are counted, at the annotated
    steps' mean sizes."""
    st = _step(facts)
    trace = facts.get("trace") or {}
    took = (trace.get("ops") or {}).get("singa_paged_decode")
    if st is None or not took:
        return None
    by_span = trace["modules_by_span"]
    program = by_span["engine.decode"].get("main")
    runs = sum(row["runs"] for row in by_span.values()
               if program and row.get("main") == program) or st["runs"]
    need = runs * trinity_opcount.paged_step_bytes(
        facts["config"], st["live"], st["window"], facts["itemsize"])
    return 100.0 * need / facts["peaks"]["hbm_bytes_per_s"] / took


def window_block_share(facts: Dict) -> Optional[float]:
    """Ring blocks the windowed layers' walk read over table blocks the
    full layers' walk read, the window's decode steps summed: percent."""
    c = facts.get("counters") or {}
    if not c.get("cb_live_block_steps") or "cb_window_block_steps" not in c:
        return None
    return 100.0 * c["cb_window_block_steps"] / c["cb_live_block_steps"]
