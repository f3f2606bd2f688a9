"""Bytes and operations a decode step of a Kimi-Linear configuration
needs, from shapes and the step's own counts.  Beside `opcount.py`,
with its conventions (a multiply-add is 2 operations), and kept with
the benchmark so that no PR that claims a gain can change them.

What a step cannot avoid reading or writing, for `busy` slots in use:
  - every matrix outside the routed experts, once: mixers, router,
    shared expert, the dense layer, the head's slice (the embedding is a
    gather of a few rows; norms and biases are counted, they are there);
  - the held experts some token of the step chose, once each: from the
    program's routing counter, NOT all the held ones;
  - each busy slot's recurrent state and conv tail, read and written;
  - the latent row of every live cached token, read (the new rows'
    writes are a few KB).
"""

from __future__ import annotations

import math
from typing import Dict

from benchmark import kimi_weights
from benchmark.reference import kimi_linear


def _sizes(cfg: Dict) -> Dict[str, int]:
    return {n: math.prod(shape)
            for n, shape, _ in kimi_weights.leaf_table(cfg)}


def expert_params(cfg: Dict) -> int:
    """Parameters of ONE routed expert."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def routed_layers(cfg: Dict) -> int:
    return sum(f == "moe" for _, f in kimi_linear.layer_kinds(cfg))


def fixed_params(cfg: Dict) -> int:
    """Everything a decode step reads whatever it routes: all leaves but
    the embedding and the held routed experts."""
    size = _sizes(cfg)
    held = routed_layers(cfg) * cfg["num_experts"] * expert_params(cfg)
    return sum(size.values()) - size["embed"] - held


def slot_state_bytes(cfg: Dict, itemsize: int) -> int:
    """One slot's fixed state over all KDA layers: S (H, D, D) float32
    and the K-1 pre-convolution rows of q~, k~, v~ in the serving
    dtype."""
    lin = cfg["linear_attn_config"]
    h, d, k = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    n = sum(m == "kda" for m, _ in kimi_linear.layer_kinds(cfg))
    return n * (h * d * d * 4 + (k - 1) * 3 * h * d * itemsize)


def latent_bytes_per_token(cfg: Dict, itemsize: int) -> int:
    n = sum(m == "mla" for m, _ in kimi_linear.layer_kinds(cfg))
    return n * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize


def decode_step_needed_bytes(cfg: Dict, busy: float, live_tokens: float,
                             experts_touched: float, itemsize: int) -> float:
    """`experts_touched`: held experts some busy token chose, summed
    over the routed layers of one step."""
    return (itemsize * (fixed_params(cfg)
                        + experts_touched * expert_params(cfg))
            + 2.0 * busy * slot_state_bytes(cfg, itemsize)
            + live_tokens * latent_bytes_per_token(cfg, itemsize))


def decode_step_flops(cfg: Dict, busy: float, live_tokens: float,
                      assignments: float) -> float:
    """`assignments`: (token, held expert) pairs of one step, summed
    over the routed layers.  The recurrence: decay, two reads and the
    rank-one update of an (H, D, D) state, 7 operations an entry; the
    absorbed latent attention: a (rank + rope) key and a rank value a
    head and live token."""
    lin = cfg["linear_attn_config"]
    kinds = kimi_linear.layer_kinds(cfg)
    n_kda = sum(m == "kda" for m, _ in kinds)
    n_mla = len(kinds) - n_kda
    state = n_kda * lin["num_heads"] * lin["head_dim"] ** 2 * 7.0
    latent = n_mla * cfg["num_attention_heads"] * 2.0 * (
        2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    return (2.0 * (busy * fixed_params(cfg)
                   + assignments * expert_params(cfg))
            + busy * state + live_tokens * latent)


def resident_bytes(cfg: Dict, slots: int, blocks: int, block_len: int,
                   itemsize: int) -> Dict[str, int]:
    """What the engine keeps on the device: weights, the slots' states,
    the latent pool of `blocks` blocks (the null block among them)."""
    size = _sizes(cfg)
    out = {"weights": itemsize * sum(size.values()),
           "state": slots * slot_state_bytes(cfg, itemsize),
           "latent_pool": blocks * block_len
           * latent_bytes_per_token(cfg, itemsize)}
    out["total"] = sum(out.values())
    return out
