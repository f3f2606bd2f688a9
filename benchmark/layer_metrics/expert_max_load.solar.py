"""The busiest held expert's assignments over the mean held expert's in a decode step, layer steps summed: 1.0 is balanced routing."""
from benchmark.layer_metrics._solar import expert_max_load as read  # noqa: F401
