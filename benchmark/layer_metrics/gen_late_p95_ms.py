"""How late the generator sent a request after it was due (p95)."""
from benchmark.layer_metrics._common import sample_p95


def read(facts):
    v = sample_p95(facts, "late_ms")
    return None if v is None else max(v, 1e-6)
