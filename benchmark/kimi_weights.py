"""Seeded weights of the Kimi-Linear configurations, made on the device.

As `benchmark/weights.py` for the dense LM: `leaf_table` names every
leaf of a configuration with its shape and how it is drawn, `leaf`
makes one (what the plain reference asks for, layer by layer), and
`tree` makes them all through the same jitted `leaf`, so that the two
agree bit for bit.

A leaf is drawn as `weights.leaf` draws it (uniform in [-a, a], a = std
sqrt(3); std 0: ones; std 1/sqrt(fan_in) for a matrix stored (in, out)),
except three kinds the configuration file lists under `assumed`, drawn
from a uniform u in [-1, 1] of their own:

  a_log        log A, A = 1 + 15 (u + 1) / 2: uniform in [1, 16]
  dt_bias      softplus^-1(dt), dt log-uniform in [1e-3, 1e-1]
  router_bias  0.05 u: small, not zero, so that the experts chosen (by
               score + bias) and their weights (by score) differ

as the public implementation initialises the first two, so that the
state's horizons span one to about a thousand tokens.  These three are
made in float32 and cast to the asked dtype last.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights
from benchmark.reference import kimi_linear

Leaf = Tuple[str, Tuple[int, ...], Union[float, str]]   # name, shape, draw


def leaf_table(cfg: Dict) -> List[Leaf]:
    """Every leaf, in a fixed order.  Matrices are stored (in, out);
    the held experts are stacked on a leading axis."""
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    lin = cfg["linear_attn_config"]
    kh, kd = lin["num_heads"], lin["head_dim"]
    kk = lin["short_conv_kernel_size"]
    h = cfg["num_attention_heads"]
    nope, rope, vd, rank = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                            cfg["v_head_dim"], cfg["kv_lora_rank"])
    held, routed = cfg["num_experts"], cfg["n_routed_experts"]
    f, fd = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    fs = f * cfg["num_shared_experts"]
    s = lambda n: 1.0 / math.sqrt(n)                         # noqa: E731
    out: List[Leaf] = [("embed", (v, e), s(e))]
    for i, (mixer, ffn) in enumerate(kimi_linear.layer_kinds(cfg)):
        p = f"L{i}."
        out += [(p + "mix_norm", (e,), 0.0), (p + "ffn_norm", (e,), 0.0)]
        if mixer == "kda":
            m, hd = p + "kda.", kh * kd
            out += [(m + "wq", (e, hd), s(e)), (m + "wk", (e, hd), s(e)),
                    (m + "wv", (e, hd), s(e)),
                    (m + "conv_q", (hd, kk), s(kk)),
                    (m + "conv_k", (hd, kk), s(kk)),
                    (m + "conv_v", (hd, kk), s(kk)),
                    (m + "w_beta", (e, kh), s(e)),
                    (m + "w_fa", (e, kd), s(e)), (m + "w_fb", (kd, hd), s(kd)),
                    (m + "a_log", (kh,), "a_log"),
                    (m + "dt_bias", (hd,), "dt_bias"),
                    (m + "w_ga", (e, kd), s(e)), (m + "w_gb", (kd, hd), s(kd)),
                    (m + "o_norm", (kd,), 0.0), (m + "wo", (hd, e), s(hd))]
        else:
            m = p + "mla."
            out += [(m + "wq", (e, h * (nope + rope)), s(e)),
                    (m + "w_kva", (e, rank + rope), s(e)),
                    (m + "kv_norm", (rank,), 0.0),
                    (m + "w_kvb", (rank, h * (nope + vd)), s(rank)),
                    (m + "wo", (h * vd, e), s(h * vd))]
        if ffn == "dense":
            m = p + "ffn."
            out += [(m + "w_gate", (e, fd), s(e)), (m + "w_up", (e, fd), s(e)),
                    (m + "w_down", (fd, e), s(fd))]
        else:
            m = p + "moe."
            out += [(m + "router", (e, routed), s(e)),
                    (m + "router_bias", (routed,), "router_bias"),
                    (m + "w_gate", (held, e, f), s(e)),
                    (m + "w_up", (held, e, f), s(e)),
                    (m + "w_down", (held, f, e), s(f)),
                    (m + "shared_gate", (e, fs), s(e)),
                    (m + "shared_up", (e, fs), s(e)),
                    (m + "shared_down", (fs, e), s(fs))]
    out += [("final_norm", (e,), 0.0), ("head", (e, v), s(e))]
    return out


def param_count(cfg: Dict) -> int:
    return sum(int(np.prod(s)) for _, s, _ in leaf_table(cfg))


def counts_by_part(cfg: Dict) -> Dict[str, int]:
    """Parameters of layer 0 (the dense one), of the first sparse KDA
    and MLA layers, of one layer's held experts, of embedding + head."""
    kinds = kimi_linear.layer_kinds(cfg)
    size = {n: int(np.prod(s)) for n, s, _ in leaf_table(cfg)}
    layer = lambda i: sum(c for n, c in size.items()         # noqa: E731
                          if n.startswith(f"L{i}."))
    first = lambda kind: next(i for i, k in enumerate(kinds)  # noqa: E731
                              if k == kind)
    i_moe = first(("kda", "moe"))
    mixer = lambda i, m: sum(c for n, c in size.items()      # noqa: E731
                             if n.startswith(f"L{i}.{m}."))
    return {"dense_layer": layer(0), "kda_moe_layer": layer(i_moe),
            "mla_moe_layer": layer(first(("mla", "moe"))),
            "kda": mixer(i_moe, "kda"),
            "mla": mixer(first(("mla", "moe")), "mla"),
            "held_experts": sum(size[f"L{i_moe}.moe.{n}"]
                                for n in ("w_gate", "w_up", "w_down")),
            "embed_and_head": size["embed"] + size["head"]}


@partial(jax.jit, static_argnums=(2, 3, 4))
def _special(key, name_id, shape, kind: str, dtype):
    u = jax.random.uniform(jax.random.fold_in(key, name_id), shape,
                           jnp.float32, -1.0, 1.0)
    if kind == "a_log":
        x = jnp.log(1.0 + 15.0 * (u + 1.0) / 2.0)
    elif kind == "dt_bias":
        dt = jnp.exp(math.log(1e-3)
                     + (u + 1.0) / 2.0 * (math.log(1e-1) - math.log(1e-3)))
        x = dt + jnp.log(-jnp.expm1(-dt))                    # softplus^-1
    elif kind == "router_bias":
        x = 0.05 * u
    else:
        raise ValueError(f"unknown kind of leaf {kind!r}")
    return x.astype(dtype)


def leaf(key, name: str, shape, draw, dtype):
    """One leaf; one compiled program per shape and kind."""
    if isinstance(draw, str):
        return _special(key, weights._name_id(name), tuple(shape), draw,
                        dtype)
    return weights.leaf(key, name, shape, draw, dtype)


def tree(cfg: Dict, seed: int, dtype) -> Dict[str, jax.Array]:
    """All leaves in `dtype`, each through `leaf`."""
    key = weights.seed_key(seed)
    return {n: leaf(key, n, s, d, dtype) for n, s, d in leaf_table(cfg)}
