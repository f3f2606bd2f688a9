"""Plain formulations kept only for the tests to hold the program's
kernels to.  Nothing under `singa_tpu/` imports this file."""

import math

import jax
import jax.numpy as jnp

from singa_tpu.ops.attention import NEG_INF


def attend_absorbed(layer, params, q, lat, allowed):
    """A `kMLA` decode step's sums over a dense table, what
    `MLALayer.apply_paged` ran before the paged kernel took the middle:
    q (N, H, nope + rope) one token a row against its latent rows lat
    (N, L, rank + rope, or wider with zeros behind); allowed (N, L)
    bool.  Built from the layer's own two halves of Wkvb
    (`_absorb_query`, `_expand_output`); returns (N, H * vdim)."""
    sc = jnp.einsum("nhr,nlr->nhl",
                    layer._absorb_query(params, q, lat.shape[-1]), lat,
                    preferred_element_type=jnp.float32)
    sc = sc / math.sqrt(layer.nope + layer.rope)
    sc = jnp.where(allowed[:, None, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    # 0 * (inf | nan) is nan: rows the mask hides may hold anything
    c = jnp.where(allowed[:, :, None], lat[..., :layer.rank], 0)
    o_lat = jnp.einsum("nhl,nlr->nhr", p.astype(c.dtype), c,
                       preferred_element_type=jnp.float32)
    return layer._expand_output(params, o_lat.astype(q.dtype))
