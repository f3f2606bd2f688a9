"""Decode steps that went out between two chunks of one prompt, a gap (`cb_steps_between_chunks` over chunks less prompts): 1 where slots were running, as the scheduler's rule has it."""
from benchmark.layer_metrics._solar import steps_between_chunks as read  # noqa: F401
