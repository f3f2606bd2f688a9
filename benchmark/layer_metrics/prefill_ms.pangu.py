"""Median wall time of `engine.run_cb_prefill` (both caches filled, the first token and the first draft fetched; behind a step in a full house); the benchmark's span."""
from benchmark.layer_metrics._common import span_median_ms


def read(facts):
    return span_median_ms(facts, "engine.prefill")
