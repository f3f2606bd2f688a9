"""Bytes and operations a decode step of a Trinity configuration needs,
and those of one paged-kernel call at its geometry, from shapes and the
step's own counts.  Beside `opcount.py`, `kimi_opcount.py` and
`zaya_opcount.py`, with their conventions (a multiply-add is 2
operations), and kept with the benchmark so that no PR that claims a
gain can change them.

What a step cannot avoid reading, for `busy` slots in use:
  - every matrix outside the routed experts, once: the attention's five
    projections, the router, the shared expert, the dense layers, the
    head's slice (the embedding is a gather of a few rows; norms are
    counted, they are there);
  - the held experts some token of the step chose, once each: from the
    program's routing counter, NOT all the held ones;
  - the K and V rows of every live cached token in the full layers, and
    in the windowed layers of the last `sliding_window` of them at most:
    `window_tokens` is the step's sum over slots of min(context,
    window) (the new rows' writes are a few KB).
"""

from __future__ import annotations

import math
from typing import Dict

from benchmark import trinity_weights
from benchmark.reference import trinity


def _sizes(cfg: Dict) -> Dict[str, int]:
    return {n: math.prod(shape)
            for n, shape, _ in trinity_weights.leaf_table(cfg)}


def expert_params(cfg: Dict) -> int:
    """Parameters of ONE routed expert."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_counts(cfg: Dict) -> Dict[str, int]:
    kinds = trinity.layer_kinds(cfg)
    return {"full": sum(m == "full" for m, _ in kinds),
            "sliding": sum(m == "sliding" for m, _ in kinds),
            "moe": sum(f == "moe" for _, f in kinds)}


def fixed_params(cfg: Dict) -> int:
    """Everything a decode step reads whatever it routes: all leaves but
    the embedding and the held routed experts."""
    size = _sizes(cfg)
    held = layer_counts(cfg)["moe"] * cfg["num_experts"] * expert_params(cfg)
    return sum(size.values()) - size["embed"] - held


def kv_row_bytes(cfg: Dict, itemsize: int) -> int:
    """A cached token's K and V rows in ONE layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def paged_call_bytes(cfg: Dict, tokens: float, itemsize: int) -> float:
    """What one `singa_paged_decode` call needs at this geometry (two
    pools of (Hkv, block, D)): the K and V rows of the `tokens` positions
    it attends over all slots (the queries and the output are a few
    hundred KB)."""
    return tokens * kv_row_bytes(cfg, itemsize)


def paged_step_bytes(cfg: Dict, live_tokens: float, window_tokens: float,
                     itemsize: int) -> float:
    """All of a decode step's paged-kernel calls: one a layer, the full
    layers over every live token, the windowed over the window's."""
    n = layer_counts(cfg)
    return (n["full"] * paged_call_bytes(cfg, live_tokens, itemsize)
            + n["sliding"] * paged_call_bytes(cfg, window_tokens, itemsize))


def decode_step_needed_bytes(cfg: Dict, busy: float, live_tokens: float,
                             window_tokens: float, experts_touched: float,
                             itemsize: int) -> float:
    """`experts_touched`: held experts some busy token chose, summed
    over the routed layers of one step."""
    return (itemsize * (fixed_params(cfg)
                        + experts_touched * expert_params(cfg))
            + paged_step_bytes(cfg, live_tokens, window_tokens, itemsize))


def decode_step_flops(cfg: Dict, busy: float, live_tokens: float,
                      window_tokens: float, assignments: float) -> float:
    """`assignments`: (token, held expert) pairs of one step, summed
    over the routed layers.  Attention: a key and a value of head_dim a
    query head and attended token."""
    n = layer_counts(cfg)
    attended = n["full"] * live_tokens + n["sliding"] * window_tokens
    return (2.0 * (busy * fixed_params(cfg)
                   + assignments * expert_params(cfg))
            + attended * cfg["num_attention_heads"] * 4.0 * cfg["head_dim"])
