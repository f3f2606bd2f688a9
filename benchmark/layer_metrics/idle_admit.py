"""Share of the traced span in which the device idles under `scheduler.admit_pending`: the prefill's hand-over, the collect inside it, the first-token fetch, alloc."""
from benchmark.layer_metrics._step_paths import idle_share


def read(facts):
    return idle_share(facts, "admit")
