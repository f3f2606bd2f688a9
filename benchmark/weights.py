"""Seeded weights of the dense LM, made on the device.

The benchmark owns the weights: `leaf_table` names every leaf of a
configuration with its shape and scale, `tree` makes them all in one
jitted call (what the system under test is handed), and `leaf` makes
one (what the plain reference asks for, layer by layer, so that it
never holds the whole model).  Both go through `_values` with the same
key, so they agree bit for bit.

Values are uniform in [-a, a] with a = std * sqrt(3): cheap to make,
and the standard deviations are the program's own defaults
(1/sqrt(fan_in)), so random-weight logits have unit scale.
"""

from __future__ import annotations

import math
import zlib
from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Leaf = Tuple[str, Tuple[int, ...], float]     # name, shape, std (0: ones)


def leaf_table(cfg: Dict) -> List[Leaf]:
    """Every leaf of the configuration, in a fixed order.  Matrices are
    stored (in, out), as the program's einsums contract them."""
    e, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd = cfg["num_attention_heads"] * cfg["head_dim"]
    kvd = cfg["num_key_value_heads"] * cfg["head_dim"]
    se, sf = 1.0 / math.sqrt(e), 1.0 / math.sqrt(f)
    out: List[Leaf] = [("embed", (v, e), se)]
    for i in range(cfg["num_hidden_layers"]):
        out += [(f"L{i}.attn_norm", (e,), 0.0),
                (f"L{i}.wq", (e, hd), se), (f"L{i}.wk", (e, kvd), se),
                (f"L{i}.wv", (e, kvd), se), (f"L{i}.wo", (hd, e), se),
                (f"L{i}.ffn_norm", (e,), 0.0),
                (f"L{i}.w_gate", (e, f), se), (f"L{i}.w_up", (e, f), se),
                (f"L{i}.w_down", (f, e), sf)]
    out += [("final_norm", (e,), 0.0), ("head", (e, v), se)]
    return out


def param_count(cfg: Dict) -> int:
    return sum(int(np.prod(s)) for _, s, _ in leaf_table(cfg))


def seed_key(seed: int):
    """A key from any non-negative whole number (the driver's seeds
    pass 2**31): the two 32-bit halves are folded in apart."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(0),
                             np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def _values(key, name_id, shape, std: float, dtype):
    if std == 0.0:
        return jnp.ones(shape, dtype)
    a = std * math.sqrt(3.0)
    return jax.random.uniform(jax.random.fold_in(key, name_id), shape, dtype,
                              -a, a)


def _name_id(name: str):
    return np.uint32(zlib.crc32(name.encode()))


@partial(jax.jit, static_argnums=(2, 3, 4))
def _leaf(key, name_id, shape, std: float, dtype):
    return _values(key, name_id, shape, std, dtype)


def leaf(key, name: str, shape, std: float, dtype):
    """One leaf; one compiled program per shape, whatever the name."""
    return _leaf(key, _name_id(name), tuple(shape), std, dtype)


def tree(cfg: Dict, seed: int, dtype, shardings=None) -> Dict[str, jax.Array]:
    """All leaves in one jitted call, in `dtype`."""
    table = leaf_table(cfg)

    def make(key):
        return {n: _values(key, _name_id(n), s, std, dtype)
                for n, s, std in table}

    return jax.jit(make, out_shardings=shardings)(seed_key(seed))
