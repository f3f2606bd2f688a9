"""The prefill programs' (chunks and whole prompts) share of the device's busy time in the traced span."""
from benchmark.layer_metrics._solar import prefill_share as read  # noqa: F401
