"""Percent of the block table the paged kernel walks in a decode step (the `cb_live_block_steps` counter over decode steps x slots x blocks a slot): what of the K/V pool a step reads."""
from benchmark.layer_metrics._zaya import live_block_share as read  # noqa: F401
