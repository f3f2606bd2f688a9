"""Milliseconds the device idles in a decode step that did not go ahead (`ahead` = 0), outside `scheduler.admit_pending`, per such step: what a step ahead for a house that is not full would hide."""
from benchmark.layer_metrics._step_paths import round_trip_host_ms


def read(facts):
    return round_trip_host_ms(facts)
