"""Share of the traced span in which the device idles under `engine.fetch` (inside `engine.cb_decode` or bare, outside an admission and the emit loop): the host still waiting for tokens the device has made."""
from benchmark.layer_metrics._step_paths import idle_share


def read(facts):
    return idle_share(facts, "decode_wait")
