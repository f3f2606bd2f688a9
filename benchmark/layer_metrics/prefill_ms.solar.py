"""Device time of a whole prompt's prefill, the sum of its chunks: the traced chunks' mean by the window's chunks a prompt (`cb_prefill_chunks` / `cb_chunked_prompts`)."""
from benchmark.layer_metrics._solar import prefill_ms as read  # noqa: F401
