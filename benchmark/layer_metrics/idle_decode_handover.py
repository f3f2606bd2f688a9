"""Share of the traced span in which the device idles under `engine.upload` + `engine.dispatch` of `engine.cb_decode` (outside an admission): a decode step's hand-over, exposed."""
from benchmark.layer_metrics._step_paths import idle_share


def read(facts):
    return idle_share(facts, "decode_handover")
