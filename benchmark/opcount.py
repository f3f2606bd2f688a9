"""Operations and bytes that the algorithm needs, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can change
them.  Conventions: a multiply-add is 2 operations; causal attention
counts half the score matrix (the live half), the convention of
`singa_tpu/utils/flops.py:_attention_flops`, whose arithmetic this copies
(PERF.md lists the original for a later PR to delete); a training step
is 3 x forward (d-input and d-weight for every matmul), recomputation
not counted.
"""

from __future__ import annotations

from typing import Dict


def _dims(cfg: Dict):
    e, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    return e, f, v, h, kv, d, cfg["num_hidden_layers"]


def layer_matmul_params(cfg: Dict) -> int:
    """Parameters of one block's matrices (norm scales left out)."""
    e, f, _, h, kv, d, _ = _dims(cfg)
    return e * h * d + 2 * e * kv * d + h * d * e + 3 * e * f


def head_params(cfg: Dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def forward_flops(cfg: Dict, tokens: int, seq: int,
                  head_rows: int) -> float:
    """Forward pass over `tokens` positions in sequences of length
    `seq`, the head projecting `head_rows` of them."""
    _, _, _, h, _, d, n = _dims(cfg)
    mats = 2.0 * tokens * layer_matmul_params(cfg) * n
    scores = n * tokens * (4.0 * seq * h * d) / 2.0       # causal half
    return mats + scores + 2.0 * head_rows * head_params(cfg)


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    """Model FLOPs of one trained token: 3 x forward, every position
    through the head."""
    return 3.0 * forward_flops(cfg, 1, seq, 1)


def prefill_needed_flops(cfg: Dict, plen: int) -> float:
    """What a prompt of `plen` real tokens needs: every block over
    `plen` positions, the head over the last one.  Padding to the
    compiled width and the head over every row are the program's
    choices and do not count."""
    return forward_flops(cfg, plen, plen, 1)


def weight_bytes(cfg: Dict, itemsize: int) -> float:
    """Bytes of the matrices one decode step has to read: every block
    and the head (the embedding is a gather of a few rows)."""
    n = cfg["num_hidden_layers"]
    return itemsize * (layer_matmul_params(cfg) * n + head_params(cfg))


def kv_bytes_per_token(cfg: Dict, itemsize: int) -> float:
    _, _, _, _, kv, d, n = _dims(cfg)
    return 2.0 * kv * d * n * itemsize


def decode_step_needed_bytes(cfg: Dict, live_tokens: float,
                             itemsize: int) -> float:
    """One decode step for all slots: the weights once, and the keys and
    values of every live cached token."""
    return (weight_bytes(cfg, itemsize)
            + live_tokens * kv_bytes_per_token(cfg, itemsize))


def decode_step_flops(cfg: Dict, slots: int, live_tokens: float) -> float:
    _, _, _, h, _, d, n = _dims(cfg)
    return (2.0 * slots * (layer_matmul_params(cfg) * n + head_params(cfg))
            + n * 4.0 * live_tokens * h * d)


# -- flash attention kernels (per call, causal) -----------------------------
# matmuls of (S x D) by (D x S) shape each kernel computes: forward QK^T
# and PV; dq recomputes QK^T, forms dP = dO V^T and dQ = dS K; dkv
# recomputes QK^T, forms dV = P^T dO, dP and dK = dS^T Q.
FLASH_MATMULS = {"singa_flash_fwd": 2, "singa_flash_dq": 3,
                 "singa_flash_dkv": 4}


def flash_call_flops(kernel: str, batch: int, heads: int, seq: int,
                     head_dim: int) -> float:
    per = 2.0 * seq * seq * head_dim / 2.0               # causal half
    return FLASH_MATMULS[kernel] * per * batch * heads


def flash_call_bytes(kernel: str, batch: int, heads: int, kv_heads: int,
                     seq: int, head_dim: int, itemsize: int) -> float:
    """Least traffic: each operand read once, each result written once."""
    q = batch * seq * heads * head_dim * itemsize
    kv = batch * seq * kv_heads * head_dim * itemsize
    reads = {"singa_flash_fwd": q + 2 * kv,               # q, k, v
             "singa_flash_dq": 3 * q + 2 * kv,            # q, o/do, k, v
             "singa_flash_dkv": 3 * q + 2 * kv}[kernel]
    writes = {"singa_flash_fwd": q, "singa_flash_dq": q,
              "singa_flash_dkv": 2 * kv}[kernel]
    return float(reads + writes)
