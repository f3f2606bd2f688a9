"""Readers of the openPangu cells: the verify-and-draft step and its
paged-kernel calls against the bytes and operations a step cannot avoid
(`benchmark/pangu_opcount.py`), the load on the held experts, what the
drafts yield, and the module's share of the step's device time.
`facts["spans"]` rows of `engine.decode` are the runner's
(`runners/serve_kimi.py:_Spans`): (name, t0, t1, live tokens, busy
slots, experts touched, assignments), and behind them this cell's
runner puts the drafts the step accepted.  None where there is nothing to
read: no trace, or a program without those counters."""

from __future__ import annotations

from typing import Dict, Optional

from benchmark import pangu_opcount, stats
from benchmark.layer_metrics import _program_spans, _zaya
from benchmark.layer_metrics._common import _module_seconds, _traced_rows
from benchmark.trace import reduce as reducer


def _step(facts: Dict):
    """The traced steps' means (live tokens, busy slots, experts
    touched, assignments), the decode program's device seconds and its
    runs; None without them."""
    rows = [r for r in _traced_rows(facts, "engine.decode") if len(r) >= 7]
    seconds, runs = _module_seconds(facts, "engine.decode")
    if not rows or not runs or not facts.get("peaks"):
        return None
    mean = lambda i: sum(r[i] for r in rows) / len(rows)     # noqa: E731
    return {"live": mean(3), "busy": mean(4), "touched": mean(5),
            "assigned": mean(6), "seconds": seconds, "runs": runs}


def decode_roofline(facts: Dict) -> Optional[float]:
    st = _step(facts)
    if st is None:
        return None
    cfg, peaks = facts["config"], facts["peaks"]
    need_b = pangu_opcount.decode_step_needed_bytes(
        cfg, st["busy"], st["live"], st["touched"], facts["itemsize"])
    need_f = pangu_opcount.decode_step_flops(
        cfg, st["busy"], st["live"], st["assigned"])
    return stats.roofline_share(need_f, need_b, st["seconds"] / st["runs"],
                                peaks["bf16_flops_per_s"],
                                peaks["hbm_bytes_per_s"])


def _program_runs(facts: Dict, st: Dict) -> int:
    """Every run of the decode program in the traced span, also those
    outside the runner's annotation (`_trinity.paged_roofline`)."""
    by_span = facts["trace"]["modules_by_span"]
    program = by_span["engine.decode"].get("main")
    return sum(row["runs"] for row in by_span.values()
               if program and row.get("main") == program) or st["runs"]


def paged_roofline(facts: Dict) -> Optional[float]:
    """The least time of the traced steps' `singa_paged_decode` calls
    (one a latent layer: the longer of their operations over the peak
    and their 576-value rows over the bandwidth; this geometry sits on
    the ridge) over the device time of the trace's
    `singa_paged_decode` rows, at the annotated steps' mean sizes."""
    st = _step(facts)
    trace = facts.get("trace") or {}
    took = (trace.get("ops") or {}).get("singa_paged_decode")
    if st is None or not took:
        return None
    cfg, peaks = facts["config"], facts["peaks"]
    calls = _program_runs(facts, st) * pangu_opcount.latent_layers(cfg)
    least = max(pangu_opcount.paged_call_flops(cfg, st["live"])
                / peaks["bf16_flops_per_s"],
                pangu_opcount.paged_call_bytes(cfg, st["live"],
                                               facts["itemsize"])
                / peaks["hbm_bytes_per_s"])
    return 100.0 * calls * least / took


def _as_zaya(facts: Dict) -> Dict:
    """The facts with the experts held under the key `_zaya`'s readers
    read them from (this configuration counts them in
    `n_routed_experts`)."""
    cfg = facts["config"]
    return {**facts, "config": {**cfg, "num_experts": cfg["n_routed_experts"]}}


def expert_tokens_per_step(facts: Dict) -> Optional[float]:
    return _zaya.expert_tokens_per_step(_as_zaya(facts))


def expert_max_load(facts: Dict) -> Optional[float]:
    return _zaya.expert_max_load(_as_zaya(facts))


def mtp_accept_share(facts: Dict) -> Optional[float]:
    c = facts.get("counters") or {}
    if not c.get("cb_drafts_made"):
        return None
    return 100.0 * c["cb_drafts_accepted"] / c["cb_drafts_made"]


def tokens_per_step(facts: Dict) -> Optional[float]:
    c = facts.get("counters") or {}
    if not c.get("cb_emit_slot_steps"):
        return None
    return c["cb_tokens_emitted"] / c["cb_emit_slot_steps"]


def module_entry_marker(cfg: Dict) -> str:
    """What tells the module's first matmul from every other op of the
    step in a device trace: an op is named by its whole HLO line, and
    only that one reads W_eh, (2 x hidden, hidden)."""
    return f"[{2 * cfg['hidden_size']},{cfg['hidden_size']}]"


def mtp_step_share(facts: Dict, planes=None) -> Optional[float]:
    """The module's device time over the step's, percent: in every run
    of the decode program in the kept trace, from the start of the op
    that reads W_eh (the module's entry; the device runs a program's
    ops one after another, the main stack's and the verification before
    it, the module's block, its head and the draft's sampling after) to
    the run's end, over the run.  The trace names no op by the layer it
    came from (PERF.md, "Reading a v5e trace"), so the entry's shape is
    the mark: ONE op a run carries it, and a run in which more do (a
    copy of W_eh ahead of its use, another operand of that shape) would
    move the mark without a sign, so that raises.  A run the trace cut
    before its entry is left out."""
    by_span = (facts.get("trace") or {}).get("modules_by_span") or {}
    program = (by_span.get("engine.decode") or {}).get("main")
    if planes is None:
        path = _program_spans.trace_path(facts)
        planes = reducer.read_planes(path) if path else []
    mark = module_entry_marker(facts["config"])
    inside = whole = 0.0
    for plane in planes:
        if not reducer.DEVICE_PLANE.match(plane["name"]) or not program:
            continue
        entries = sorted(s for n, s, _ in reducer.line_events(
            plane, reducer.OPS_LINE) if mark in n)
        for name, s, e in reducer.line_events(plane, reducer.MODULES_LINE):
            if name.split("(")[0] != program:
                continue
            at = [t for t in entries if s <= t < e]
            if len(at) > 1:
                raise ValueError(
                    f"mtp_step_share: {len(at)} ops of one run of {program} "
                    f"carry the module's mark {mark}; it has to name one")
            if at:
                inside += e - at[0]
                whole += e - s
    return 100.0 * inside / whole if whole else None
