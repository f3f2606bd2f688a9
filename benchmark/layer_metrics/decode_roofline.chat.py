"""The decode program against its roofline (memory-bound: weights and live KV)."""
from benchmark.layer_metrics._common import decode_roofline as read  # noqa: F401
