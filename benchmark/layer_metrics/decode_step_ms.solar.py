"""Median device time of the decode program's runs in the traced span (by program name: between two chunks the host's span of a step holds the chunk before it too)."""
from benchmark.layer_metrics._solar import decode_step_ms as read  # noqa: F401
