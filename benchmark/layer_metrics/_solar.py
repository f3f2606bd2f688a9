"""Readers of the Solar-Open2 cells: a prompt's chunks and the decode
step between them, each against what it cannot avoid
(`benchmark/solar_opcount.py`), the load on the held experts, and how
the grouped form and the scheduler's one-chunk-one-step rule came out.

The device's time is read BY PROGRAM NAME from the kept trace's `XLA
Modules` line (`jit_cb_chunk`, `jit_cb_decode`: an engine that chunks
sends every prompt through the chunk programs and has no others), not
by the host annotation a run lies under: between two chunks the decode
step is handed over while the chunk before it still runs, so a chunk's
run lies under the step's `engine.decode` annotation as often as not.

`facts["spans"]` rows: `engine.decode` as `runners/serve_kimi.py:_Spans`
makes them (name, t0, t1, live tokens, busy slots, experts touched,
assignments); `engine.chunk` as `runners/serve_solar.py:_Spans` does
(name, t0, t1, real rows, start, width, last, assignments on held
experts).  None where there is nothing to read: no trace, no such
program in it, or a program without those counters."""

from __future__ import annotations

from typing import Dict, List, Optional

from benchmark import solar_opcount, stats
from benchmark.layer_metrics import _program_spans as P
from benchmark.layer_metrics._common import _traced_rows
from benchmark.trace import reduce as reducer

CHUNK, DECODE = "jit_cb_chunk", "jit_cb_decode"
_CACHE: Dict[str, Dict[str, List[float]]] = {}


def programs(facts: Dict) -> Optional[Dict[str, List[float]]]:
    """Seconds of every run of every program in the kept trace, by the
    program's name without its fingerprint."""
    if not (facts.get("trace") or {}).get("devices"):
        return None
    path = P.trace_path(facts)
    if path is None:
        return None
    if path not in _CACHE:
        out: Dict[str, List[float]] = {}
        for plane in reducer.read_planes(path):
            if reducer.DEVICE_PLANE.match(plane["name"]):
                for name, s, e in reducer.line_events(plane,
                                                      reducer.MODULES_LINE):
                    out.setdefault(name.split("(")[0], []).append(e - s)
                break                    # one chip's cells
        _CACHE[path] = out
    return _CACHE[path]


def _runs(facts: Dict, name: str) -> List[float]:
    return (programs(facts) or {}).get(name, [])


def decode_step_ms(facts: Dict) -> Optional[float]:
    """Median device time of a decode step in the traced span."""
    runs = _runs(facts, DECODE)
    return 1e3 * stats.median(runs) if runs else None


def prefill_chunk_ms(facts: Dict) -> Optional[float]:
    """Median device time of one chunk in the traced span."""
    runs = _runs(facts, CHUNK)
    return 1e3 * stats.median(runs) if runs else None


def prefill_ms(facts: Dict) -> Optional[float]:
    """A whole prompt, the sum of its chunks: the traced chunks' mean
    device time by the window's chunks a prompt."""
    runs, c = _runs(facts, CHUNK), facts.get("counters") or {}
    if not runs or not c.get("cb_chunked_prompts"):
        return None
    return (1e3 * sum(runs) / len(runs)
            * c["cb_prefill_chunks"] / c["cb_chunked_prompts"])


def prefill_share(facts: Dict) -> Optional[float]:
    """The chunk programs' share of the device's busy time."""
    busy = (facts.get("trace") or {}).get("busy_s")
    runs = _runs(facts, CHUNK)
    return 100.0 * sum(runs) / busy if runs and busy else None


def prefill_roofline(facts: Dict) -> Optional[float]:
    """What the traced chunks' mathematics needs over the peak, over
    the chunk programs' device time.  Rows and runs are the same calls;
    scaled by their counts where the trace's edge cut one."""
    rows = [r for r in _traced_rows(facts, "engine.chunk") if len(r) >= 8]
    runs, peaks = _runs(facts, CHUNK), facts.get("peaks")
    if not rows or not runs or not peaks:
        return None
    need = sum(solar_opcount.chunk_needed_flops(
        facts["config"], r[3], r[4], r[7], r[6]) for r in rows)
    return (100.0 * need * len(runs) / len(rows)
            / peaks["bf16_flops_per_s"] / sum(runs))


def decode_roofline(facts: Dict) -> Optional[float]:
    rows = [r for r in _traced_rows(facts, "engine.decode") if len(r) >= 7]
    runs, peaks = _runs(facts, DECODE), facts.get("peaks")
    if not rows or not runs or not peaks:
        return None
    mean = lambda i: sum(r[i] for r in rows) / len(rows)     # noqa: E731
    live, busy, touched, assigned = mean(3), mean(4), mean(5), mean(6)
    cfg = facts["config"]
    need_b = solar_opcount.decode_step_needed_bytes(
        cfg, busy, live, touched, facts["itemsize"])
    need_f = solar_opcount.decode_step_flops(cfg, busy, live, assigned)
    return stats.roofline_share(need_f, need_b, sum(runs) / len(runs),
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
        c["cb_routed_layer_steps"] * facts["config"]["n_routed_experts"])


def expert_max_load(facts: Dict) -> Optional[float]:
    c = _routing(facts)
    if c is None or not c.get("cb_routed_max_load") \
            or not c["cb_routed_assignments"]:
        return None
    return (c["cb_routed_max_load"] * facts["config"]["n_routed_experts"]
            / c["cb_routed_assignments"])


def grouped_row_share(facts: Dict) -> Optional[float]:
    """Expert products the chunks' grouped form made over those the
    dense walk would have: percent."""
    c = facts.get("counters") or {}
    if not c.get("cb_grouped_row_slots"):
        return None
    return 100.0 * c["cb_grouped_rows"] / c["cb_grouped_row_slots"]


def steps_between_chunks(facts: Dict) -> Optional[float]:
    """Decode steps that went out between two chunks of one prompt, a
    gap: 1 where slots were running, as the scheduler's rule has it."""
    c = facts.get("counters") or {}
    gaps = c.get("cb_prefill_chunks", 0) - c.get("cb_chunked_prompts", 0)
    return c["cb_steps_between_chunks"] / gaps if gaps > 0 else None
