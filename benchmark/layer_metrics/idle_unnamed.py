"""Share of the traced span in which the device idles under no span of the scheduler's loop: its lock, another thread, the trace's edges."""
from benchmark.layer_metrics._step_paths import idle_share


def read(facts):
    return idle_share(facts, "unnamed")
