"""Seconds of the whole run (pre-roll and window) inside laps of the scheduler's loop over 0.5 s: the last traced `scheduler.step`'s `stall_ms` (counter `cb_stall_seconds`)."""
from benchmark.layer_metrics._step_paths import stall_seconds


def read(facts):
    return stall_seconds(facts, "stall_ms")
