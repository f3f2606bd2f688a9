"""Mean tokens a held expert sees in a decode step (assignments on held experts over routed layers x steps x experts held), from `ServeStats` routing counters: 4.0 at 64 busy slots and top-8 of 128 is the deployment's load."""
from benchmark.layer_metrics._zaya import expert_tokens_per_step as read  # noqa: F401
