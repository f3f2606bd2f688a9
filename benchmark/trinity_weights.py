"""Seeded weights of the Trinity (`model_type: afmoe`) configurations,
made on the device, and the program's name for each.

As `benchmark/kimi_weights.py`: `leaf_table` names every leaf of a
configuration with its shape and how it is drawn, `leaf` makes one
(what the plain reference asks for, layer by layer), and `tree` makes
them all through the same jitted `leaf`, so that the two agree bit for
bit.

A leaf is drawn as `weights.leaf` draws it (uniform in [-a, a], a = std
sqrt(3); std 0: ones; std 1/sqrt(fan_in) for a matrix stored (in, out)),
except the parameters the configuration file lists under `assumed`
(config.json has no key for them), each from a uniform u in [-1, 1] of
its own, made in float32 and cast to the asked dtype last:

  near_one     1 + 0.1 u   the q and k norms' scales and the scales of
                           the two norms after the sublayers: not ones,
                           so that a norm left out or a scale not
                           applied shows
  router_bias  0.05 u      the balancing bias: small, not zero, so that
                           the experts chosen (by score + bias) and
                           their weights (by score) differ
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights
from benchmark.reference import trinity

Leaf = Tuple[str, Tuple[int, ...], Union[float, str]]   # name, shape, draw

DRAWS = {"near_one": (1.0, 0.1), "router_bias": (0.0, 0.05)}

PROGRAM_NAMES = {"embed": "embed/embedding", "final_norm": "ln_f/scale",
                 "head": "loss/w", "mix_norm": "ln{i}a/scale",
                 "mix_post_norm": "pn{i}a/scale",
                 "ffn_norm": "ln{i}b/scale",
                 "ffn_post_norm": "pn{i}b/scale"}


def program_name(leaf: str) -> str:
    """The program's name for one of the benchmark's leaves:
    `L3.attention.wq` is `attention3/wq`, `L3.moe.router` `moe3/router`,
    `L0.ffn.w_gate` `ffn0/w1`, `L3.mix_post_norm` `pn3a/scale`."""
    if not leaf.startswith("L"):
        return PROGRAM_NAMES[leaf]
    i, part = leaf[1:].split(".", 1)
    if part in PROGRAM_NAMES:
        return PROGRAM_NAMES[part].format(i=i)
    kind, name = part.split(".", 1)
    if kind == "ffn":
        name = {"w_gate": "w1", "w_up": "w3", "w_down": "w2"}[name]
    return f"{kind}{i}/{name}"


def leaf_table(cfg: Dict) -> List[Leaf]:
    """Every leaf, in a fixed order.  Matrices are stored (in, out);
    the held experts are stacked on a leading axis."""
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    h, hk, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    held, routed = cfg["num_experts"], cfg["n_routed_experts"]
    f, fd = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    fs = f * cfg["num_shared_experts"]
    s = lambda n: 1.0 / math.sqrt(n)                         # noqa: E731
    out: List[Leaf] = [("embed", (v, e), s(e))]
    for i, (_, ffn) in enumerate(trinity.layer_kinds(cfg)):
        p = f"L{i}."
        out += [(p + "mix_norm", (e,), 0.0),
                (p + "mix_post_norm", (e,), "near_one"),
                (p + "ffn_norm", (e,), 0.0),
                (p + "ffn_post_norm", (e,), "near_one")]
        m = p + "attention."
        out += [(m + "wq", (e, h * d), s(e)), (m + "wk", (e, hk * d), s(e)),
                (m + "wv", (e, hk * d), s(e)), (m + "wg", (e, h * d), s(e)),
                (m + "q_norm", (d,), "near_one"),
                (m + "k_norm", (d,), "near_one"),
                (m + "wo", (h * d, e), s(h * d))]
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
    """Parameters of a dense layer, of a sparse layer and of its parts,
    of embedding + head."""
    kinds = trinity.layer_kinds(cfg)
    size = {n: int(np.prod(s)) for n, s, _ in leaf_table(cfg)}
    part = lambda pre: sum(c for n, c in size.items()        # noqa: E731
                           if n.startswith(pre))
    i_dense = next(i for i, k in enumerate(kinds) if k[1] == "dense")
    i_moe = next(i for i, k in enumerate(kinds) if k[1] == "moe")
    experts = sum(size[f"L{i_moe}.moe.{n}"]
                  for n in ("w_gate", "w_up", "w_down"))
    return {"dense_layer": part(f"L{i_dense}."),
            "moe_layer": part(f"L{i_moe}."),
            "attention": part(f"L{i_moe}.attention."),
            "held_experts": experts,
            "router_and_shared": part(f"L{i_moe}.moe.") - experts,
            "embed_and_head": size["embed"] + size["head"]}


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
