"""The part of `stall_s` in which the host waited on the device or the runtime: the last traced `scheduler.step`'s `stall_wait_ms` (counter `cb_stall_wait_seconds`); the rest is the host's own."""
from benchmark.layer_metrics._step_paths import stall_seconds


def read(facts):
    return stall_seconds(facts, "stall_wait_ms")
