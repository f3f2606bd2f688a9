"""Share of the traced span in which the device idles under `scheduler.wait`: nothing pending, nothing active, nothing in flight."""
from benchmark.layer_metrics._step_paths import idle_share


def read(facts):
    return idle_share(facts, "no_work")
