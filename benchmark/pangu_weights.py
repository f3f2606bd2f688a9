"""Seeded weights of the openPangu-Ultra-MoE (`model_type:
pangu_ultra_moe`) configurations, made on the device, and the program's
name for each.

As `benchmark/trinity_weights.py`: `leaf_table` names every leaf of a
configuration with its shape and how it is drawn, `leaf` makes one
(what the plain reference asks for, layer by layer), and `tree` makes
them all through the same jitted `leaf`, so that the two agree bit for
bit.  The multi-token prediction module's block is leaf `L<n>`, n the
number of main layers, and its own parts are `mtp.*`.

A leaf is drawn as `weights.leaf` draws it (uniform in [-a, a], a = std
sqrt(3); std 0: ones; std 1/sqrt(fan_in) for a matrix stored (in, out)),
except the two kinds the configuration file lists under `assumed`:

  near_one  1 + 0.1 u, u uniform in [-1, 1]: the scales of the two
            norms after the sublayers and of the module's two input
            norms: not ones, so that a norm left out or a scale not
            applied shows
  zero      the router's selection bias the program's layer has and
            this model has not
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights
from benchmark.reference import pangu

Leaf = Tuple[str, Tuple[int, ...], Union[float, str]]   # name, shape, draw

DRAWS = {"near_one": (1.0, 0.1), "zero": (0.0, 0.0)}

PROGRAM_NAMES = {"embed": "embed/embedding", "final_norm": "ln_f/scale",
                 "head": "loss/w", "mix_norm": "ln{i}a/scale",
                 "mix_post_norm": "pn{i}a/scale",
                 "ffn_norm": "ln{i}b/scale",
                 "ffn_post_norm": "pn{i}b/scale",
                 "mtp.e_norm": "mtp/e_norm", "mtp.h_norm": "mtp/h_norm",
                 "mtp.w_eh": "mtp/w_eh", "mtp.final_norm": "mtp_ln_f/scale"}


def program_name(leaf: str) -> str:
    """The program's name for one of the benchmark's leaves:
    `L3.mla.wq_a` is `mla3/wq_a`, `L3.moe.router` `moe3/router`,
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
    h, qr, rank = (cfg["num_attention_heads"], cfg["q_lora_rank"],
                   cfg["kv_lora_rank"])
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    held, routed = cfg["n_routed_experts"], cfg["router_width"]
    f, fd = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    fs = f * cfg["n_shared_experts"]
    s = lambda n: 1.0 / math.sqrt(n)                         # noqa: E731
    out: List[Leaf] = [("embed", (v, e), s(e))]
    for i, ffn in enumerate(pangu.layer_kinds(cfg) + pangu.module_kinds(cfg)):
        p = f"L{i}."
        if i == cfg["num_hidden_layers"]:    # the module's own, before its block
            out += [("mtp.e_norm", (e,), "near_one"),
                    ("mtp.h_norm", (e,), "near_one"),
                    ("mtp.w_eh", (2 * e, e), s(2 * e))]
        out += [(p + "mix_norm", (e,), 0.0),
                (p + "mix_post_norm", (e,), "near_one"),
                (p + "ffn_norm", (e,), 0.0),
                (p + "ffn_post_norm", (e,), "near_one")]
        m = p + "mla."
        out += [(m + "wq_a", (e, qr), s(e)), (m + "q_norm", (qr,), 0.0),
                (m + "wq", (qr, h * (nope + rope)), s(qr)),
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
                    (m + "router_bias", (routed,), "zero"),
                    (m + "w_gate", (held, e, f), s(e)),
                    (m + "w_up", (held, e, f), s(e)),
                    (m + "w_down", (held, f, e), s(f)),
                    (m + "shared_gate", (e, fs), s(e)),
                    (m + "shared_up", (e, fs), s(e)),
                    (m + "shared_down", (fs, e), s(fs))]
    if cfg["num_nextn_predict_layers"]:
        out.append(("mtp.final_norm", (e,), 0.0))
    out += [("final_norm", (e,), 0.0), ("head", (e, v), s(e))]
    return out


def param_count(cfg: Dict) -> int:
    return sum(int(np.prod(s)) for _, s, _ in leaf_table(cfg))


def counts_by_part(cfg: Dict) -> Dict[str, int]:
    """Parameters of the dense layer, of an expert layer and of its
    parts, of the module, of embedding + head."""
    size = {n: int(np.prod(s)) for n, s, _ in leaf_table(cfg)}
    part = lambda pre: sum(c for n, c in size.items()        # noqa: E731
                           if n.startswith(pre))
    kinds = pangu.layer_kinds(cfg)
    i_moe, n = kinds.index("moe"), cfg["num_hidden_layers"]
    experts = sum(size[f"L{i_moe}.moe.{k}"]
                  for k in ("w_gate", "w_up", "w_down"))
    return {"dense_layer": part(f"L{kinds.index('dense')}."),
            "moe_layer": part(f"L{i_moe}."),
            "attention": part(f"L{i_moe}.mla."),
            "held_experts": experts,
            "router_and_shared": part(f"L{i_moe}.moe.") - experts,
            "module": part(f"L{n}.") + part("mtp."),
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
