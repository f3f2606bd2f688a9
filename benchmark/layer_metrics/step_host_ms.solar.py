"""Milliseconds of a decode step in which the device waits on the host: device-idle time inside `scheduler.step` spans but outside `scheduler.admit_pending`, per `scheduler.decode` span (the program's own spans, from the kept trace)."""
from benchmark.layer_metrics._program_spans import step_part_ms


def read(facts):
    return step_part_ms(facts, "host")
