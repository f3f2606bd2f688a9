"""Percent: ring blocks the windowed layers' kernel walks over table blocks the full layers' walks (`cb_window_block_steps` / `cb_live_block_steps`, once a step per kind): what of a context the window still reads; 100 means no context has passed it."""
from benchmark.layer_metrics._trinity import window_block_share as read  # noqa: F401
