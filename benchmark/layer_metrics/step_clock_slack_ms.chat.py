"""How far `step_dispatch_ms.chat` and `step_fetch_ms.chat` can be off: the distance between the two causal bounds on the device plane's clock shift in the kept trace (no run starts before its enqueue began, none ends after its completion was heard)."""
from benchmark.layer_metrics._program_spans import clock_slack_ms as read  # noqa: F401
