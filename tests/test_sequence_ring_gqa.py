"""Ring attention on the 8-device CPU mesh: its unexpanded key / value heads.

Cases of `tests/test_sequence.py` in a file of their own: the driver's
`--dist loadfile` gives a file to ONE worker, and these interpret the
flash kernel through eight rotations, forward and backward (260 s of
that file's 822 in the driver's run at PR 45, the suite's longest)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu.ops.attention import attention_reference, expand_kv_heads
from singa_tpu.parallel import make_mesh, ring_attention

RNG = np.random.default_rng(0)


def _gqa_qkv(b=2, h=8, hkv=2, s=256, d=16):
    q = jnp.asarray(RNG.standard_normal((b, h, s, d)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((b, hkv, s, d)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((b, hkv, s, d)).astype(np.float32))
    return q, k, v


def _gqa_ref(q, k, v, causal):
    return attention_reference(q, expand_kv_heads(k, q.shape[1]),
                               expand_kv_heads(v, q.shape[1]), causal)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_gqa_unexpanded_kv(causal):
    """Ring accepts (B, Hkv, S, D) k/v directly: forward parity vs the
    dense reference on expanded heads, plus q AND k gradients (the k
    grad flows through ppermute rotations at Hkv width)."""
    q, k, v = _gqa_qkv()
    mesh = make_mesh(seq=8)
    out = ring_attention(q, k, v, mesh, "seq", causal)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_gqa_ref(q, k, v, causal)),
                               rtol=1e-4, atol=1e-5)
    g1 = jax.grad(lambda q, k: ring_attention(
        q, k, v, mesh, "seq", causal).sum(), argnums=(0, 1))(q, k)
    g2 = jax.grad(lambda q, k: _gqa_ref(q, k, v, causal).sum(),
                  argnums=(0, 1))(q, k)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
