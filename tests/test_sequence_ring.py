"""Ring attention on the 8-device CPU mesh: its two local-step paths.

Cases of `tests/test_sequence.py` in a file of their own: the driver's
`--dist loadfile` gives a file to ONE worker, and these interpret the
flash kernel through eight rotations, forward and backward (288 s of
that file's 822 in the driver's run at PR 45, the suite's longest)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu.ops.attention import attention_reference
from singa_tpu.parallel import make_mesh, ring_attention

RNG = np.random.default_rng(0)


def _qkv(b=2, h=8, s=256, d=32):
    return tuple(jnp.asarray(RNG.standard_normal((b, h, s, d))
                             .astype(np.float32)) for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_and_blockwise_paths_agree(causal):
    """Both ring local-step implementations — the Pallas flash unrolled
    rotation (use_flash=True) and the XLA blockwise scan fallback — must
    match the dense reference and each other, gradients included."""
    q, k, v = _qkv(1, 4, 256, 16)
    mesh = make_mesh(seq=8)
    of = ring_attention(q, k, v, mesh, "seq", causal, use_flash=True)
    ob = ring_attention(q, k, v, mesh, "seq", causal, use_flash=False)
    ref = attention_reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(of), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(of), np.asarray(ob),
                               rtol=1e-4, atol=1e-5)
    gf = jax.grad(lambda k: ring_attention(
        q, k, v, mesh, "seq", causal, use_flash=True).sum())(k)
    gr = jax.grad(lambda k: attention_reference(
        q, k, v, causal).sum())(k)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                               rtol=1e-4, atol=1e-5)
