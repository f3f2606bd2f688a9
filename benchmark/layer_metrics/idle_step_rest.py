"""Share of the traced span in which the device idles under `scheduler.step` and no span inside it: expire, the walk's counts, the table, the account."""
from benchmark.layer_metrics._step_paths import idle_share


def read(facts):
    return idle_share(facts, "step_rest")
