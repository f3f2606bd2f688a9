"""Bytes and operations a verify-and-draft step of an openPangu-Ultra-MoE
configuration needs, and those of one paged-kernel call at its
geometry, from shapes and the step's own counts.  Beside `opcount.py`,
`kimi_opcount.py`, `zaya_opcount.py` and `trinity_opcount.py`, with
their conventions (a multiply-add is 2 operations), and kept with the
benchmark so that no PR that claims a gain can change them.

A step runs TWO rows a busy slot (the last token and the draft) through
the main stack, and two through the module.  What it cannot avoid
reading, for `busy` slots in use:
  - every matrix outside the routed experts, once: the attention's six
    projections of every main layer and of the module's block, the
    routers, the shared experts, the dense layer, W_eh, the head's slice
    (the embedding is a gather of a few rows; norms are counted, they
    are there);
  - the held experts some row of the step chose, once each: from the
    program's routing counter, NOT all the held ones;
  - the latent row (kv_lora_rank + qk_rope_head_dim values; the pool
    pads it to whole lane tiles, the padding is not needed) of every
    live cached token, in every main layer and in the module (the new
    rows' writes are a few KB).
"""

from __future__ import annotations

import math
from typing import Dict

from benchmark import pangu_weights


def _sizes(cfg: Dict) -> Dict[str, int]:
    return {n: math.prod(shape)
            for n, shape, _ in pangu_weights.leaf_table(cfg)}


def expert_params(cfg: Dict) -> int:
    """Parameters of ONE routed expert."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def latent_layers(cfg: Dict) -> int:
    """Layers that keep a latent pool: the main stack's and the module's."""
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def moe_layers(cfg: Dict) -> int:
    return (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
            + cfg["num_nextn_predict_layers"])


def fixed_params(cfg: Dict) -> int:
    """Everything a step reads whatever it routes: all leaves but the
    embedding and the held routed experts."""
    size = _sizes(cfg)
    held = moe_layers(cfg) * cfg["n_routed_experts"] * expert_params(cfg)
    return sum(size.values()) - size["embed"] - held


def latent_row_bytes(cfg: Dict, itemsize: int) -> int:
    """A cached token's latent row in ONE layer, as it is needed."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize


def paged_call_bytes(cfg: Dict, tokens: float, itemsize: int) -> float:
    """What one `singa_paged_decode` call needs to read: the latent rows
    of the `tokens` positions it attends over all slots, once (both
    query rows of a slot share them)."""
    return tokens * latent_row_bytes(cfg, itemsize)


def paged_call_flops(cfg: Dict, tokens: float, rows: int = 2) -> float:
    """The absorbed form: every query row of every head scores a latent
    row over rank + rope values and sums it over rank."""
    width = 2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return rows * tokens * 2.0 * cfg["num_attention_heads"] * width


def decode_step_needed_bytes(cfg: Dict, busy: float, live_tokens: float,
                             experts_touched: float, itemsize: int) -> float:
    """`experts_touched`: held experts some busy row chose, summed over
    the routed layers of one step."""
    return (itemsize * (fixed_params(cfg)
                        + experts_touched * expert_params(cfg))
            + latent_layers(cfg) * paged_call_bytes(cfg, live_tokens,
                                                    itemsize))


def decode_step_flops(cfg: Dict, busy: float, live_tokens: float,
                      assignments: float) -> float:
    """`assignments`: (row, held expert) pairs of one step, summed over
    the routed layers.  Two rows a busy slot through every fixed matrix
    (the head's slice twice over: the main stack's rows and the
    module's)."""
    head = _sizes(cfg)["head"]
    return (2.0 * (2 * busy * (fixed_params(cfg) + head)
                   + assignments * expert_params(cfg))
            + latent_layers(cfg) * paged_call_flops(cfg, live_tokens))
