"""Runner: open-loop traffic through the continuous-batching engine, for
the Trinity (`model_type: afmoe`) configurations (window and full
attention in one model, gated QK-normed attention, a norm before and
after every sublayer, sparse experts of which this chip holds a share).

The same path as `serve_cb`, `serve_kimi` and `serve_zaya`:
`hybrid_lm(...)` -> `NeuralNet` -> `InferenceEngine(net, spec,
params=<the seed's tree>)` -> `ContinuousScheduler(engine).start()`.
The run itself IS `serve_kimi.run`, bound to this configuration's names
for the length of the call as `serve_zaya` binds it (that module's
`_bound`, with another table of names): the weights
(`benchmark/trinity_weights.py`, with the program's name for each
leaf), the reference (`benchmark/reference/trinity.py`), the model's
builder and the counters read back.  Nothing of either runner is copied
or edited.

A name the windowed serving state brought to the program is imported
first, at the top: a program that lacks it fails there, before anything
is put on the device.
"""

from __future__ import annotations

from singa_tpu.ops.paged_attention import ring_blocks  # noqa: I001 — first

import time
from contextlib import contextmanager
from typing import Dict

import numpy as np

from singa_tpu.models.transformer import hybrid_lm

from benchmark import trinity_weights
from benchmark.reference import trinity
from benchmark.runners import serve_kimi

COUNTERS = ("cb_steps", "cb_active_slot_steps", "cb_decode_steps",
            "cb_live_block_steps", "cb_window_block_steps",
            "cb_routed_max_load") + serve_kimi.ROUTING_COUNTERS


def model_config(cfg: Dict, seq_len: int):
    """The program's ModelConfig for the benchmark's configuration."""
    attention = {"num_heads": cfg["num_attention_heads"],
                 "num_kv_heads": cfg["num_key_value_heads"],
                 "head_dim": cfg["head_dim"], "qk_norm": True, "gate": True,
                 "norm_epsilon": cfg["rms_norm_eps"],
                 "rope_theta": cfg["rope_theta"]}
    mixer = {"sliding": {**attention, "rope": True,
                         "window": cfg["sliding_window"]},
             "full": {**attention, "rope": False}}
    moe = {"num_routed": cfg["n_routed_experts"],
           "experts_per_token": cfg["num_experts_per_tok"],
           "num_held": cfg["num_experts"],
           "first_held": cfg["first_held_expert"],
           "expert_hidden": cfg["moe_intermediate_size"],
           "shared_hidden": (cfg["moe_intermediate_size"]
                             * cfg["num_shared_experts"]),
           "renormalize": cfg["route_norm"],
           "routed_scale": cfg["route_scale"]}
    dense = {"hidden_dim": cfg["intermediate_size"],
             "activation": cfg["hidden_act"]}
    kinds = trinity.layer_kinds(cfg)
    return hybrid_lm(
        vocab_size=cfg["vocab_size"], embed_dim=cfg["hidden_size"],
        mixers=[{"attention": mixer[m]} for m, _ in kinds],
        ffns=[{f: dense if f == "dense" else moe} for _, f in kinds],
        seq_len=seq_len, epsilon=cfg["rms_norm_eps"], post_norm=True,
        embed_scale=trinity.embed_scale(cfg))


def resident_bytes(cfg: Dict) -> Dict[str, int]:
    """What the chip holds before a request arrives, reckoned from the
    configuration: the weights, the growing blocks of the full layers,
    the rings of the windowed ones."""
    sv = cfg["serve"]
    item = 2 if sv["dtype"] == "bfloat16" else 4
    bl, slots = sv["cb_block_len"], sv["cb_slots"]
    block = (2 * cfg["num_key_value_heads"] * bl * cfg["head_dim"] * item)
    kinds = [m for m, _ in trinity.layer_kinds(cfg)]
    table = -(-(sv["cb_prompt_cap"] + sv["max_new_tokens"]) // bl)
    ring = ring_blocks(cfg["sliding_window"], bl)
    return {"params": trinity_weights.param_count(cfg),
            "weights": trinity_weights.param_count(cfg) * item,
            "full_blocks": kinds.count("full") * (slots * table + 1) * block,
            "window_rings": (kinds.count("sliding")
                             * (slots * ring + 1) * block)}


def _counters(engine) -> Dict[str, int]:
    return {k: getattr(engine.stats, k) for k in COUNTERS}


class _Spans(serve_kimi._Spans):
    """`serve_kimi._Spans`, and a row a decode step handed to the device
    of what its windowed layers read: ("engine.window", t, t, the sum
    over slots of min(context, window)), beside the step's
    `engine.decode` row and its sum of contexts."""

    def __init__(self, engine):
        from singa_tpu.serve.kvcache import window_of
        window = window_of(engine.net)

        def noting(call):
            def handed(params, pools, tokens, ntoks, tables):
                now = time.perf_counter()
                self.rows.append(("engine.window", now, now, int(np.sum(
                    np.minimum(ntoks, window)))))
                return call(params, pools, tokens, ntoks, tables)
            return handed

        engine.run_cb_decode = noting(engine.run_cb_decode)
        engine.dispatch_cb_decode = noting(engine.dispatch_cb_decode)
        super().__init__(engine)


@contextmanager
def _bound():
    """`serve_kimi`'s build, run and check_sample under this
    configuration's weights, reference, builder and counters."""
    mine = {"kimi_weights": trinity_weights, "kimi_linear": trinity,
            "model_config": model_config,
            "program_name": trinity_weights.program_name,
            "_counters": _counters, "_Spans": _Spans}
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
    print(f"resident: {resident_bytes(cell.config)}", flush=True)
    with _bound():
        return serve_kimi.build(cell, seed)


def run(cell, **kw) -> Dict:
    print(f"resident: {resident_bytes(cell.config)}", flush=True)
    with _bound():
        return serve_kimi.run(cell, **kw)
