"""Seeded weights of the ZAYA1 configurations, made on the device, and
the program's name for each.

As `benchmark/kimi_weights.py`: `leaf_table` names every leaf of a
configuration with its shape and how it is drawn, `leaf` makes one
(what the plain reference asks for, layer by layer), and `tree` makes
them all through the same jitted `leaf`, so that the two agree bit for
bit.  There is no head leaf: the head is the embedding
(`tie_word_embeddings`).

A leaf is drawn as `weights.leaf` draws it (uniform in [-a, a], a = std
sqrt(3); std 0: ones; std 1/sqrt(fan_in) for a matrix stored (in, out)),
except the parameters the configuration file lists under `assumed`
(config.json has no key for them), each from a uniform u in [-1, 1] of
its own, made in float32 and cast to the asked dtype last:

  near_one   1 + 0.1 u     tau (the key's temperature), the residual's
                           scales a and c
  near_zero  0.05 u        the convolutions' biases, the residual's
                           biases b and d, the router's three biases
  balance    0.01 u        the balancing bias: p is near 1/16 = 0.06 a
                           expert, so a hundredth moves some choices and
                           does not make them
  gamma      0.5 + 0.4 u   the depth average's weight, in (0.1, 0.9)
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights

Leaf = Tuple[str, Tuple[int, ...], Union[float, str]]   # name, shape, draw

DRAWS = {"near_one": (1.0, 0.1), "near_zero": (0.0, 0.05),
         "balance": (0.0, 0.01), "gamma": (0.5, 0.4)}

PROGRAM_NAMES = {"embed": "embed/embedding", "final_norm": "ln_f/scale",
                 "mix_norm": "ln{i}a/scale", "ffn_norm": "ln{i}b/scale"}


def program_name(leaf: str) -> str:
    """The program's name for one of the benchmark's leaves:
    `L3.cca.wq` is `cca3/wq`, `L3.zaya_moe.gamma` `zaya_moe3/gamma`,
    `L3.res_a.c` `res3a/c`."""
    if not leaf.startswith("L"):
        return PROGRAM_NAMES[leaf]
    i, part = leaf[1:].split(".", 1)
    if part in PROGRAM_NAMES:
        return PROGRAM_NAMES[part].format(i=i)
    kind, name = part.split(".", 1)
    if kind.startswith("res_"):
        return f"res{i}{kind[-1]}/{name}"
    return f"{kind}{i}/{name}"


def leaf_table(cfg: Dict) -> List[Leaf]:
    """Every leaf, in a fixed order.  Matrices are stored (in, out);
    the held experts are stacked on a leading axis, the per-head mix
    is (heads, taps, in, out)."""
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    h, hk, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    k0, k1 = cfg["cca_time0"], cfg["cca_time1"]
    n, f, r = (cfg["num_experts"], cfg["moe_intermediate_size"],
               cfg["router_hidden_size"])
    v_now = (hk + 1) // 2 * d
    s = lambda x: 1.0 / math.sqrt(x)                         # noqa: E731
    out: List[Leaf] = [("embed", (v, e), s(e))]
    for i in range(cfg["num_hidden_layers"]):
        p = f"L{i}."
        out += [(p + "mix_norm", (e,), 0.0), (p + "ffn_norm", (e,), 0.0)]
        m = p + "cca."
        out += [(m + "wq", (e, h * d), s(e)), (m + "wk", (e, hk * d), s(e)),
                (m + "wv1", (e, v_now), s(e)),
                (m + "wv2", (e, hk * d - v_now), s(e)),
                (m + "conv0", ((h + hk) * d, k0), s(k0)),
                (m + "bias0", ((h + hk) * d,), "near_zero"),
                (m + "conv1", (h + hk, k1, d, d), s(k1 * d)),
                (m + "bias1", (h + hk, d), "near_zero"),
                (m + "tau", (hk,), "near_one"),
                (m + "wo", (h * d, e), s(h * d))]
        for half in ("res_a.", "res_b."):
            out += [(p + half + "a", (e,), "near_one"),
                    (p + half + "b", (e,), "near_zero"),
                    (p + half + "c", (e,), "near_one"),
                    (p + half + "d", (e,), "near_zero")]
        m = p + "zaya_moe."
        out += [(m + "router_down", (e, r), s(e)),
                (m + "router_down_bias", (r,), "near_zero"),
                (m + "router_norm", (r,), 0.0),
                (m + "router_w1", (r, r), s(r)),
                (m + "router_b1", (r,), "near_zero"),
                (m + "router_w2", (r, r), s(r)),
                (m + "router_b2", (r,), "near_zero"),
                (m + "router_w3", (r, n), s(r)),
                (m + "router_bias", (n,), "balance")]
        if i:
            out.append((m + "gamma", (r,), "gamma"))
        out += [(m + "w_gate", (n, e, f), s(e)), (m + "w_up", (n, e, f), s(e)),
                (m + "w_down", (n, f, e), s(f))]
    out.append(("final_norm", (e,), 0.0))
    return out


def param_count(cfg: Dict) -> int:
    return sum(int(np.prod(s)) for _, s, _ in leaf_table(cfg))


def counts_by_part(cfg: Dict) -> Dict[str, int]:
    """Parameters of layer 1's parts (a layer with a gamma), of one
    layer, and of the embedding that is also the head."""
    size = {n: int(np.prod(s)) for n, s, _ in leaf_table(cfg)}
    part = lambda pre: sum(c for n, c in size.items()        # noqa: E731
                           if n.startswith(pre))
    experts = sum(size[f"L1.zaya_moe.{n}"]
                  for n in ("w_gate", "w_up", "w_down"))
    return {"cca": part("L1.cca."), "experts": experts,
            "router": part("L1.zaya_moe.") - experts,
            "residual_and_norms": part("L1.res_") + 2 * size["L1.mix_norm"],
            "layer": part("L1."), "embed_and_head": size["embed"]}


@partial(jax.jit, static_argnums=(2, 3, 4))
def _special(key, name_id, shape, kind: str, dtype):
    u = jax.random.uniform(jax.random.fold_in(key, name_id), shape,
                           jnp.float32, -1.0, 1.0)
    centre, width = DRAWS[kind]
    return (centre + width * u).astype(dtype)


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
