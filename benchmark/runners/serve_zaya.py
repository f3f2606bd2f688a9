"""Runner: open-loop traffic through the continuous-batching engine, for
the ZAYA1 configurations (compressed convolutional attention, top-1
experts under an MLP router whose state runs from layer to layer,
scaled residuals, a tied head).

The same path as `serve_cb` and `serve_kimi`: `hybrid_lm(...)` ->
`NeuralNet` -> `InferenceEngine(net, spec, params=<the seed's tree>)` ->
`ContinuousScheduler(engine).start()`.  The run itself IS
`serve_kimi.run` (the pre-roll onto a full house, the spans of a step
handed over ahead, the routing counts in a decode row, the sampled
teacher-forced check): that function is written against five names of
its own module, and `run` here binds them to this configuration's for
the length of the call: the weights (`benchmark/zaya_weights.py`, with
the program's name for each leaf), the reference
(`benchmark/reference/zaya1.py`), the model's builder and the counters
read back.  Nothing of either runner is copied or edited.

The program's new layers are imported first, at the top: a program that
lacks them fails there, before anything is put on the device.
"""

from __future__ import annotations

from singa_tpu.core.hybrid_layers import (CCALayer,    # noqa: F401, I001
                                          ZayaMoELayer)  # — first

from contextlib import contextmanager
from typing import Dict

from singa_tpu.models.transformer import hybrid_lm

from benchmark import zaya_weights
from benchmark.reference import zaya1
from benchmark.runners import serve_kimi

COUNTERS = ("cb_steps", "cb_active_slot_steps", "cb_decode_steps",
            "cb_live_block_steps", "cb_routed_max_load"
            ) + serve_kimi.ROUTING_COUNTERS


def model_config(cfg: Dict, seq_len: int):
    """The program's ModelConfig for the benchmark's configuration."""
    cca = {"num_heads": cfg["num_attention_heads"],
           "num_kv_heads": cfg["num_key_value_heads"],
           "head_dim": cfg["head_dim"],
           "conv_kernel0": cfg["cca_time0"],
           "conv_kernel1": cfg["cca_time1"],
           "rotary_factor": cfg["partial_rotary_factor"],
           "rope_theta": cfg["rope_parameters"]["hybrid"]["rope_theta"]}
    moe = {"num_routed": cfg["num_experts"],
           "experts_per_token": cfg["num_experts_per_tok"],
           "expert_hidden": cfg["moe_intermediate_size"],
           "router_hidden": cfg["router_hidden_size"],
           "epsilon": cfg["rms_norm_eps"]}
    n = cfg["num_hidden_layers"]
    return hybrid_lm(
        vocab_size=cfg["vocab_size"], embed_dim=cfg["hidden_size"],
        mixers=[{"cca": cca}] * n, ffns=[{"zaya_moe": moe}] * n,
        seq_len=seq_len, epsilon=cfg["rms_norm_eps"],
        tie_head=cfg["tie_word_embeddings"], scaled_residual=True)


def _counters(engine) -> Dict[str, int]:
    return {k: getattr(engine.stats, k) for k in COUNTERS}


@contextmanager
def _bound():
    """`serve_kimi`'s build, run and check_sample under this
    configuration's weights, reference, builder and counters."""
    mine = {"kimi_weights": zaya_weights, "kimi_linear": zaya1,
            "model_config": model_config,
            "program_name": zaya_weights.program_name,
            "_counters": _counters}
    theirs = {k: getattr(serve_kimi, k) for k in mine}
    for k, v in mine.items():
        setattr(serve_kimi, k, v)
    try:
        yield
    finally:
        for k, v in theirs.items():
            setattr(serve_kimi, k, v)


def build(cell, seed: int):
    """The engine and scheduler over the seed's weights, warmed."""
    with _bound():
        return serve_kimi.build(cell, seed)


def run(cell, **kw) -> Dict:
    with _bound():
        return serve_kimi.run(cell, **kw)
