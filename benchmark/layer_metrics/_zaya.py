"""Readers of the ZAYA1 cells: the decode program against the bytes a
step cannot avoid (`benchmark/zaya_opcount.py`), the load on the experts
and its balance, and the share of the block table the paged kernel
walks.  `facts["spans"]` rows of `engine.decode` are the runner's
(`runners/serve_kimi.py:_Spans`): (name, t0, t1, live tokens, busy
slots, experts touched, assignments), the last two from the program's
routing counters.  None where there is nothing to read: no trace, or a
program without those counters."""

from __future__ import annotations

from typing import Dict, Optional

from benchmark import stats, zaya_opcount
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
    need_b = zaya_opcount.decode_step_needed_bytes(
        cfg, busy, live, touched, facts["itemsize"])
    need_f = zaya_opcount.decode_step_flops(cfg, busy, live, assigned)
    return stats.roofline_share(need_f, need_b, seconds / runs,
                                peaks["bf16_flops_per_s"],
                                peaks["hbm_bytes_per_s"])


def _routing(facts: Dict):
    c = facts.get("counters") or {}
    return c if c.get("cb_routed_layer_steps") else None


def expert_tokens_per_step(facts: Dict) -> Optional[float]:
    c = _routing(facts)
    if c is None:
        return None
    return c["cb_routed_assignments"] / (
        c["cb_routed_layer_steps"] * facts["config"]["num_experts"])


def expert_max_load(facts: Dict) -> Optional[float]:
    """The busiest expert's assignments over the mean expert's, layer
    steps summed: 1.0 is a router that spreads a step's tokens evenly."""
    c = _routing(facts)
    if c is None or not c.get("cb_routed_max_load") \
            or not c["cb_routed_assignments"]:
        return None
    return (c["cb_routed_max_load"] * facts["config"]["num_experts"]
            / c["cb_routed_assignments"])


def live_block_share(facts: Dict) -> Optional[float]:
    """Blocks the paged kernel walked over slots x blocks a slot, the
    window's decode steps summed (`ServeStats.cb_live_block_share` over
    the window): percent."""
    c = facts.get("counters") or {}
    if not c.get("cb_decode_steps") or "cb_live_block_steps" not in c:
        return None
    sv = facts["config"]["serve"]
    per_slot = -(-(sv["cb_prompt_cap"] + sv["max_new_tokens"])
                 // sv["cb_block_len"])
    return 100.0 * c["cb_live_block_steps"] / (
        c["cb_decode_steps"] * c["cb_slots"] * per_slot)
