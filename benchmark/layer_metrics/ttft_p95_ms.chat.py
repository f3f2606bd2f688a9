"""The tail of the time to first token below the knee: recorded, not judged (130 requests a window leave it a spread of 21 %, PERF.md 2)."""
from benchmark.layer_metrics._common import sample_p95


def read(facts):
    return sample_p95(facts, "ttft_ms")
