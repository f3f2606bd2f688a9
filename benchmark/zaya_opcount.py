"""Bytes and operations a decode step of a ZAYA1 configuration needs,
from shapes and the step's own counts.  Beside `opcount.py` and
`kimi_opcount.py`, with their conventions (a multiply-add is 2
operations), and kept with the benchmark so that no PR that claims a
gain can change them.

What a step cannot avoid reading or writing, for `busy` slots in use:
  - every matrix outside the experts, once: CCA's projections and
    convolutions, the router, the residuals' vectors, the norms, and the
    embedding ONCE, as the head it also is (as an embedding it is a
    gather of a few rows);
  - the experts some token of the step chose, once each: from the
    program's routing counter, NOT all sixteen a layer;
  - the K and V rows of every live cached token, read (the new rows'
    writes are a few KB);
  - each busy slot's tails (the two convolutions' and the shifted value
    half), read and written.
"""

from __future__ import annotations

import math
from typing import Dict

from benchmark import zaya_weights


def _sizes(cfg: Dict) -> Dict[str, int]:
    return {n: math.prod(shape)
            for n, shape, _ in zaya_weights.leaf_table(cfg)}


def expert_params(cfg: Dict) -> int:
    """Parameters of ONE expert."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def fixed_params(cfg: Dict) -> int:
    """Everything a decode step reads whatever it routes: all leaves but
    the experts (the embedding among them, read as the head)."""
    experts = (cfg["num_hidden_layers"] * cfg["num_experts"]
               * expert_params(cfg))
    return sum(_sizes(cfg).values()) - experts


def kv_bytes_per_token(cfg: Dict, itemsize: int) -> int:
    """A cached token's K and V rows over all layers."""
    return (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * itemsize)


def slot_tail_bytes(cfg: Dict, itemsize: int) -> int:
    """One slot's tails over all layers: K0 - 1 rows of [q~ ; k~],
    K1 - 1 rows of the depthwise convolution's output, and the last
    token's half of the value."""
    d, hk = cfg["head_dim"], cfg["num_key_value_heads"]
    c = (cfg["num_attention_heads"] + hk) * d
    rows = (cfg["cca_time0"] - 1) * c + (cfg["cca_time1"] - 1) * c \
        + hk // 2 * d
    return cfg["num_hidden_layers"] * rows * itemsize


def decode_step_needed_bytes(cfg: Dict, busy: float, live_tokens: float,
                             experts_touched: float, itemsize: int) -> float:
    """`experts_touched`: experts some busy token chose, summed over the
    layers of one step."""
    return (itemsize * (fixed_params(cfg)
                        + experts_touched * expert_params(cfg))
            + live_tokens * kv_bytes_per_token(cfg, itemsize)
            + 2.0 * busy * slot_tail_bytes(cfg, itemsize))


def decode_step_flops(cfg: Dict, busy: float, live_tokens: float,
                      assignments: float) -> float:
    """`assignments`: (token, expert) pairs of one step, summed over the
    layers.  Attention: a key and a value of head_dim a query head and
    live token."""
    attn = (cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * 4.0 * cfg["head_dim"])
    return (2.0 * (busy * fixed_params(cfg)
                   + assignments * expert_params(cfg))
            + live_tokens * attn)


def resident_bytes(cfg: Dict, slots: int, blocks: int, block_len: int,
                   itemsize: int) -> Dict[str, int]:
    """What the engine keeps on the device: weights, the K/V pool of
    `blocks` blocks (the null block among them), the slots' tails."""
    out = {"weights": itemsize * sum(_sizes(cfg).values()),
           "kv_pool": blocks * block_len * kv_bytes_per_token(cfg, itemsize),
           "tails": slots * slot_tail_bytes(cfg, itemsize)}
    out["total"] = sum(out.values())
    return out
