"""Prompt tokens over compiled rows (sum of `plen` / sum of `width`) of the traced `scheduler.prefill` spans; None where a span has no `width`."""
from benchmark.layer_metrics import _program_spans


def read(facts):
    tr = _program_spans.of(facts)
    if tr is None:
        return None
    rows = [st for _, _, st in tr.spans.get("scheduler.prefill", [])]
    if not rows or any("width" not in st or "plen" not in st for st in rows):
        return None
    return 100.0 * sum(st["plen"] for st in rows) / sum(
        st["width"] for st in rows)
