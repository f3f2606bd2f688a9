"""Readers of the Kimi-Linear cells: the decode program against the
bytes a step cannot avoid (`benchmark/kimi_opcount.py`), and the load on
the held experts.  `facts["spans"]` rows of `engine.decode` are the
runner's (`runners/serve_kimi.py`): (name, t0, t1, live tokens, busy
slots, held experts touched, assignments on held experts), the last two
from the program's routing counters.  None where there is nothing to
read: no trace, or a program without those counters."""

from __future__ import annotations

from typing import Dict, Optional

from benchmark import kimi_opcount, stats
from benchmark.layer_metrics._common import _module_seconds, _traced_rows


def decode_roofline(facts: Dict) -> Optional[float]:
    rows = [r for r in _traced_rows(facts, "engine.decode") if len(r) >= 7]
    seconds, runs = _module_seconds(facts, "engine.decode")
    peaks = facts.get("peaks")
    if not rows or not runs or not peaks:
        return None
    mean = lambda i: sum(r[i] for r in rows) / len(rows)     # noqa: E731
    live, busy, touched, assigned = mean(3), mean(4), mean(5), mean(6)
    cfg = facts["config"]
    need_b = kimi_opcount.decode_step_needed_bytes(
        cfg, busy, live, touched, facts["itemsize"])
    need_f = kimi_opcount.decode_step_flops(cfg, busy, live, assigned)
    return stats.roofline_share(need_f, need_b, seconds / runs,
                                peaks["bf16_flops_per_s"],
                                peaks["hbm_bytes_per_s"])


def expert_tokens_per_step(facts: Dict) -> Optional[float]:
    c = facts.get("counters") or {}
    steps = c.get("cb_routed_layer_steps")
    if not steps:
        return None
    return c["cb_routed_assignments"] / (steps * facts["config"]["num_experts"])
