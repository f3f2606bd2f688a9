"""The part of `step_host_ms.chat` that lies inside the `engine.fetch` spans of `engine.cb_decode`."""
from benchmark.layer_metrics._program_spans import step_part_ms


def read(facts):
    return step_part_ms(facts, "fetch")
