"""Ring attention on the 8-device CPU mesh: the gradient of its default path.

Cases of `tests/test_sequence.py` in a file of their own: the driver's
`--dist loadfile` gives a file to ONE worker, and these interpret the
flash kernel through eight rotations, forward and backward (75 s of
that file's 822 in the driver's run at PR 45, the suite's longest)."""

import jax
import jax.numpy as jnp
import numpy as np

from singa_tpu.ops.attention import attention_reference
from singa_tpu.parallel import make_mesh, ring_attention

RNG = np.random.default_rng(0)


def _qkv(b=2, h=8, s=256, d=32):
    return tuple(jnp.asarray(RNG.standard_normal((b, h, s, d))
                             .astype(np.float32)) for _ in range(3))


def test_ring_attention_grad():
    q, k, v = _qkv(1, 4, 128, 16)
    mesh = make_mesh(seq=8)
    g1 = jax.grad(lambda q: ring_attention(q, k, v, mesh, "seq", True).sum())(q)
    g2 = jax.grad(lambda q: attention_reference(q, k, v, True).sum())(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-5)
