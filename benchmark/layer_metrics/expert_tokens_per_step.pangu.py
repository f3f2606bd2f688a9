"""Mean rows a held expert sees in a verify-and-draft step (assignments on held experts over routed layers x steps x experts held), from `ServeStats` routing counters: 4.0 at 64 busy slots x 2 rows and top-8 of 256 is the deployment's load."""
from benchmark.layer_metrics._pangu import expert_tokens_per_step as read  # noqa: F401
