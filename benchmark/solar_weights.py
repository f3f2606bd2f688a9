"""Seeded weights of the Solar-Open2 (`model_type: solar_open2`)
configurations, made on the device, and the program's name for each.

As `benchmark/kimi_weights.py`: `leaf_table` names every leaf of a
configuration with its shape and how it is drawn, `leaf` makes one
(what the plain reference asks for, layer by layer), and `tree` makes
them all through the same jitted `leaf`, so that the two agree bit for
bit.

A leaf is drawn as `weights.leaf` draws it (uniform in [-a, a], a = std
sqrt(3); std 0: ones; std 1/sqrt(fan_in) for a matrix stored (in, out)),
except the three kinds the configuration file lists under `assumed`
(KDA's `a_log` and `dt_bias`, the router's selection bias), which are
drawn as `kimi_weights` draws them: the same layers, the same reasons.
"""

from __future__ import annotations

import math
from typing import Dict, List

import jax
import numpy as np

from benchmark import kimi_weights, weights
from benchmark.kimi_weights import Leaf, leaf  # noqa: F401 — the same draws
from benchmark.reference import solar_open2

PROGRAM_NAMES = {"embed": "embed/embedding", "final_norm": "ln_f/scale",
                 "head": "loss/w", "mix_norm": "ln{i}a/scale",
                 "ffn_norm": "ln{i}b/scale"}


def program_name(name: str) -> str:
    """The program's name for one of the benchmark's leaves:
    `L0.attention.wq` is `attention0/wq`, `L3.kda.wq` `kda3/wq`,
    `L3.moe.router` `moe3/router`."""
    if not name.startswith("L"):
        return PROGRAM_NAMES[name]
    i, part = name[1:].split(".", 1)
    if part in PROGRAM_NAMES:
        return PROGRAM_NAMES[part].format(i=i)
    kind, leaf_name = part.split(".", 1)
    return f"{kind}{i}/{leaf_name}"


def leaf_table(cfg: Dict) -> List[Leaf]:
    """Every leaf, in a fixed order.  Matrices are stored (in, out);
    the held experts are stacked on a leading axis."""
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    lin = cfg["linear_attn_config"]
    kh, kd = lin["num_heads"], lin["head_dim"]
    kk = lin["short_conv_kernel_size"]
    hd = cfg["num_attention_heads"] * cfg["head_dim"]
    kvd = cfg["num_key_value_heads"] * cfg["head_dim"]
    held, routed = cfg["n_routed_experts"], cfg["router_width"]
    f = cfg["moe_intermediate_size"]
    fs = f * cfg["n_shared_experts"]
    s = lambda n: 1.0 / math.sqrt(n)                         # noqa: E731
    out: List[Leaf] = [("embed", (v, e), s(e))]
    for i, mixer in enumerate(solar_open2.layer_kinds(cfg)):
        p = f"L{i}."
        out += [(p + "mix_norm", (e,), 0.0), (p + "ffn_norm", (e,), 0.0)]
        if mixer == "kda":
            m, khd = p + "kda.", kh * kd
            out += [(m + "wq", (e, khd), s(e)), (m + "wk", (e, khd), s(e)),
                    (m + "wv", (e, khd), s(e)),
                    (m + "conv_q", (khd, kk), s(kk)),
                    (m + "conv_k", (khd, kk), s(kk)),
                    (m + "conv_v", (khd, kk), s(kk)),
                    (m + "w_beta", (e, kh), s(e)),
                    (m + "w_fa", (e, kd), s(e)),
                    (m + "w_fb", (kd, khd), s(kd)),
                    (m + "a_log", (kh,), "a_log"),
                    (m + "dt_bias", (khd,), "dt_bias"),
                    (m + "w_ga", (e, kd), s(e)),
                    (m + "w_gb", (kd, khd), s(kd)),
                    (m + "o_norm", (kd,), 0.0), (m + "wo", (khd, e), s(khd))]
        else:
            m = p + "attention."
            out += [(m + "wq", (e, hd), s(e)), (m + "wk", (e, kvd), s(e)),
                    (m + "wv", (e, kvd), s(e)), (m + "wg", (e, hd), s(e)),
                    (m + "wo", (hd, e), s(hd))]
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
    """Parameters of the GQA mixer, of one KDA mixer, of one layer's
    sparse part (router, shared expert, held experts), of its held
    experts alone, and of embedding + head."""
    size = {n: int(np.prod(s)) for n, s, _ in leaf_table(cfg)}
    kinds = solar_open2.layer_kinds(cfg)
    part = lambda i, m: sum(c for n, c in size.items()       # noqa: E731
                            if n.startswith(f"L{i}.{m}."))
    i_kda = kinds.index("kda")
    return {"gqa": part(kinds.index("gqa"), "attention"),
            "kda": part(i_kda, "kda"), "sparse": part(i_kda, "moe"),
            "held_experts": sum(size[f"L{i_kda}.moe.{n}"]
                                for n in ("w_gate", "w_up", "w_down")),
            "embed_and_head": size["embed"] + size["head"]}


def tree(cfg: Dict, seed: int, dtype) -> Dict[str, jax.Array]:
    """All leaves in `dtype`, each through `leaf`."""
    key = weights.seed_key(seed)
    return {n: kimi_weights.leaf(key, n, s, d, dtype)
            for n, s, d in leaf_table(cfg)}
