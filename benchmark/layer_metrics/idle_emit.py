"""Share of the traced span in which the device idles under `scheduler.emit` (outside an admission): the per-slot emit-and-retire loop, exposed."""
from benchmark.layer_metrics._step_paths import idle_share


def read(facts):
    return idle_share(facts, "emit")
