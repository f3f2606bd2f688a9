"""Of the traced `scheduler.decode` spans, the share with `ahead` = 1: steps handed to the device before the step before them was read."""
from benchmark.layer_metrics._step_paths import step_ahead_share


def read(facts):
    return step_ahead_share(facts)
