"""Mean tokens an expert sees in a decode step (assignments over layers x steps x experts), from `ServeStats` routing counters: 4.0 at 64 busy slots and top-1 of 16 is the deployment's load."""
from benchmark.layer_metrics._zaya import expert_tokens_per_step as read  # noqa: F401
