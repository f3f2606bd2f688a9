"""The prefill program against its roofline, at the real prompt lengths."""
from benchmark.layer_metrics._common import prefill_roofline as read  # noqa: F401
