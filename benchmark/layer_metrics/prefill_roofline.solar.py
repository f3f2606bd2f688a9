"""The chunk programs against the peak: the operations the traced chunks' mathematics needs (real rows, routed pairs on held experts, the causal half of a chunk and its live prefix, the head on one row of a last chunk; `solar_opcount.chunk_needed_flops`) over 197 TFLOP/s, over their device time."""
from benchmark.layer_metrics._solar import prefill_roofline as read  # noqa: F401
