"""Tokens a busy slot is handed a fetched step (`cb_tokens_emitted` / `cb_emit_slot_steps`): 1 + the acceptance, less the second tokens a retiring request drops."""
from benchmark.layer_metrics._pangu import tokens_per_step as read  # noqa: F401
