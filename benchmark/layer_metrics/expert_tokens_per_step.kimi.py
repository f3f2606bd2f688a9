"""Mean tokens a held expert sees in a decode step (assignments on held experts over routed layers x steps x experts held), from `ServeStats` routing counters: 3.0 at 96 busy slots is the deployment's load."""
from benchmark.layer_metrics._kimi import expert_tokens_per_step as read  # noqa: F401
