"""Median device time of one chunk program's run in the traced span."""
from benchmark.layer_metrics._solar import prefill_chunk_ms as read  # noqa: F401
