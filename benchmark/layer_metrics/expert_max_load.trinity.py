"""The busiest held expert's assignments over the mean held expert's in a decode step, layer steps summed (`cb_routed_max_load` x experts held / `cb_routed_assignments`): 1.0 is balanced routing; how far a seed's router is from the case the step's cost must not depend on."""
from benchmark.layer_metrics._zaya import expert_max_load as read  # noqa: F401
