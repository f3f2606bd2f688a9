"""Median `queue_ms` (submit to admission) of the traced `scheduler.prefill` spans; about a dozen requests in the 4 s trace."""
from benchmark.layer_metrics._program_spans import queue_wait_ms as read  # noqa: F401
