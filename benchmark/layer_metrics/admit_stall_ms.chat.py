"""Mean wall of the traced `scheduler.admit_pending` spans that admitted at least one request: how long an admitting step holds all slots before it decodes."""
from benchmark.layer_metrics._program_spans import admit_stall_ms as read  # noqa: F401
