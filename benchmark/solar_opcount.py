"""Bytes and operations a decode step and a prefill chunk of a
Solar-Open2 configuration need, from shapes and the program's own
counts.  Beside `opcount.py`, with its conventions (a multiply-add is 2
operations), and kept with the benchmark so that no PR that claims a
gain can change them.

A DECODE STEP cannot avoid reading or writing, for `busy` slots in use:
  - every matrix outside the routed experts, once: both kinds of mixer,
    router, shared expert, the head's slice (the embedding is a gather
    of a few rows; norms and biases are counted, they are there);
  - the held experts some token of the step chose, once each: from the
    program's routing counter, NOT all the held ones;
  - each busy slot's recurrent state and conv tails, read and written;
  - the K and V rows of every live cached token of the attention
    layers, read (the new rows' writes are a few KB).

A PREFILL CHUNK is bound by its operations, and `chunk_needed_flops`
counts what the mathematics needs and no more: the projections of its
REAL rows, the recurrence a token at a time (the chunked form does
more), attention over the causal half of the chunk and over its live
prefix, the (row, held expert) pairs the router chose (not every held
expert a row), the head on ONE row and only in a prompt's last chunk.
Whatever implements the chunk does at least this, so the share of the
peak it gives cannot pass 100 %.
"""

from __future__ import annotations

import math
from typing import Dict

from benchmark import solar_weights
from benchmark.reference import solar_open2


def _sizes(cfg: Dict) -> Dict[str, int]:
    return {n: math.prod(shape)
            for n, shape, _ in solar_weights.leaf_table(cfg)}


def expert_params(cfg: Dict) -> int:
    """Parameters of ONE routed expert."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _layers(cfg: Dict, mixer: str) -> int:
    return solar_open2.layer_kinds(cfg).count(mixer)


def fixed_params(cfg: Dict) -> int:
    """Everything a decode step reads whatever it routes: all leaves but
    the embedding and the held routed experts."""
    size = _sizes(cfg)
    held = (cfg["num_hidden_layers"] * cfg["n_routed_experts"]
            * expert_params(cfg))
    return sum(size.values()) - size["embed"] - held


def row_params(cfg: Dict) -> int:
    """The matrices every row of a chunk is multiplied by: `fixed_params`
    less the head (one row a prompt)."""
    return fixed_params(cfg) - _sizes(cfg)["head"]


def slot_state_bytes(cfg: Dict, itemsize: int) -> int:
    """One slot's fixed state over all KDA layers: S (H, D, D) float32
    and the K-1 pre-convolution rows of q~, k~, v~ in the serving
    dtype."""
    lin = cfg["linear_attn_config"]
    h, d, k = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    return _layers(cfg, "kda") * (h * d * d * 4
                                  + (k - 1) * 3 * h * d * itemsize)


def kv_bytes_per_token(cfg: Dict, itemsize: int) -> int:
    return (_layers(cfg, "gqa") * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * itemsize)


def _state_flops(cfg: Dict) -> float:
    """The recurrence, a token: decay, two reads and the rank-one update
    of an (H, D, D) state, 7 operations an entry, every KDA layer."""
    lin = cfg["linear_attn_config"]
    return _layers(cfg, "kda") * lin["num_heads"] * lin["head_dim"] ** 2 * 7.0


def _pair_flops(cfg: Dict) -> float:
    """One query against one key and its value, every head, every
    attention layer."""
    return (_layers(cfg, "gqa") * cfg["num_attention_heads"] * 4.0
            * cfg["head_dim"])


def decode_step_needed_bytes(cfg: Dict, busy: float, live_tokens: float,
                             experts_touched: float, itemsize: int) -> float:
    """`experts_touched`: held experts some busy token chose, summed
    over the routed layers of one step."""
    return (itemsize * (fixed_params(cfg)
                        + experts_touched * expert_params(cfg))
            + 2.0 * busy * slot_state_bytes(cfg, itemsize)
            + live_tokens * kv_bytes_per_token(cfg, itemsize))


def decode_step_flops(cfg: Dict, busy: float, live_tokens: float,
                      assignments: float) -> float:
    """`assignments`: (token, held expert) pairs of one step, summed
    over the routed layers."""
    return (2.0 * (busy * fixed_params(cfg)
                   + assignments * expert_params(cfg))
            + busy * _state_flops(cfg) + live_tokens * _pair_flops(cfg))


def chunk_needed_flops(cfg: Dict, rows: int, start: int, assignments: float,
                       last: bool) -> float:
    """A chunk of `rows` real rows at positions start .. start + rows -
    1, `assignments` (row, held expert) pairs over its routed layers."""
    pairs = rows * start + rows * (rows + 1) / 2.0
    head = 2.0 * _sizes(cfg)["head"] if last else 0.0
    return (2.0 * (rows * row_params(cfg)
                   + assignments * expert_params(cfg))
            + rows * _state_flops(cfg) + pairs * _pair_flops(cfg) + head)


def prompt_needed_flops(cfg: Dict, plen: int) -> float:
    """A whole prompt under a balanced router (`num_experts_per_tok` x
    held / routed pairs a row and layer): the forecast's arithmetic."""
    pairs = (plen * cfg["num_hidden_layers"] * cfg["num_experts_per_tok"]
             * cfg["n_routed_experts"] / cfg["router_width"])
    return chunk_needed_flops(cfg, plen, 0, pairs, True)
