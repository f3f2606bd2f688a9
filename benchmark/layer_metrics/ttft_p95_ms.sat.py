"""Time to first token above the knee: recorded, not judged."""
from benchmark.layer_metrics._common import sample_p95


def read(facts):
    return sample_p95(facts, "ttft_ms")
