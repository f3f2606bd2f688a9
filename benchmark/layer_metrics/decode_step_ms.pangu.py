"""Median wall time of a verify-and-draft step (`engine.run_cb_decode`: dispatch to fetched tokens; in a full house a step's period); the benchmark's span."""
from benchmark.layer_metrics._common import span_median_ms


def read(facts):
    return span_median_ms(facts, "engine.decode")
