"""Mean tokens a held expert sees in a decode step (assignments on held experts over routed layers x steps x experts held), from `ServeStats` routing counters: 1.2 at 48 busy slots and top-8 of 320 is the deployment's load."""
from benchmark.layer_metrics._solar import expert_tokens_per_step as read  # noqa: F401
