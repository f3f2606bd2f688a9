"""The part of `step_host_ms.chat` outside the engine's upload, dispatch and fetch: the emit loop, account, expire, the scheduler's loop.  With those three it sums to `step_host_ms.chat`."""
from benchmark.layer_metrics._program_spans import step_part_ms


def read(facts):
    return step_part_ms(facts, "rest")
