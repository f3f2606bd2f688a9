"""The busiest held expert's assignments over the mean held expert's in a step, layer steps summed (`cb_routed_max_load` x experts held / `cb_routed_assignments`): 1.0 is balanced routing."""
from benchmark.layer_metrics._pangu import expert_max_load as read  # noqa: F401
