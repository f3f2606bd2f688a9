"""Fused Pallas LM-head forward (ops/head_loss.py) vs the chunked XLA
path and the dense softmax_loss_metrics oracle — loss, top-1
precision, argmax tie semantics, and gradients."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu.ops.head_loss import fused_lm_xent
from singa_tpu.ops.loss import chunked_lm_xent, softmax_loss_metrics

N, E, V = 64, 128, 512
BN, BV = 16, 128


def _data(seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.standard_normal((N, E)), dtype)
    w_vE = jnp.asarray(rng.standard_normal((V, E)) * 0.05, dtype)
    labels = jnp.asarray(rng.integers(0, V, (N,)), jnp.int32)
    return h, w_vE, labels


def _fused(h, w, labels, scale=1.0):
    return fused_lm_xent(h, w, labels, scale, 4096, BN, BV, True)


def test_fused_matches_dense_oracle():
    h, w, labels = _data()
    loss_f, prec_f = _fused(h, w, labels)
    logits = (h @ w.T).astype(jnp.float32)
    loss_d, prec_d = softmax_loss_metrics(logits, labels)
    np.testing.assert_allclose(float(loss_f), float(loss_d), rtol=1e-5)
    np.testing.assert_allclose(float(prec_f), float(prec_d), rtol=1e-6)


def test_fused_matches_chunked():
    h, w, labels = _data(1)
    loss_f, prec_f = _fused(h, w, labels, scale=2.0)
    loss_c, prec_c = chunked_lm_xent(h, w, labels, chunk_size=16,
                                     scale=2.0, w_is_vE=True)
    np.testing.assert_allclose(float(loss_f), float(loss_c), rtol=1e-5)
    np.testing.assert_allclose(float(prec_f), float(prec_c), rtol=1e-6)


def test_argmax_tie_lowest_index_wins():
    h = jnp.zeros((N, E), jnp.float32)      # all logits identical (0)
    _, w, _ = _data(2)
    w = jnp.zeros_like(w)
    labels = jnp.zeros((N,), jnp.int32)     # label 0 == argmax 0
    _, prec = _fused(h, w, labels)
    assert float(prec) == 1.0               # every row ties; idx 0 wins
    labels2 = jnp.ones((N,), jnp.int32)
    _, prec2 = _fused(h, w, labels2)
    assert float(prec2) == 0.0


def test_gradients_match_chunked():
    h, w, labels = _data(3)

    def f_fused(hh, ww):
        loss, _ = _fused(hh, ww, labels, scale=1.5)
        return loss

    def f_chunk(hh, ww):
        loss, _ = chunked_lm_xent(hh, ww, labels, chunk_size=16,
                                  scale=1.5, w_is_vE=True)
        return loss

    (lf, (dh_f, dw_f)) = jax.value_and_grad(f_fused, (0, 1))(h, w)
    (lc, (dh_c, dw_c)) = jax.value_and_grad(f_chunk, (0, 1))(h, w)
    np.testing.assert_allclose(float(lf), float(lc), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(dh_f), np.asarray(dh_c),
                               rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dw_f), np.asarray(dw_c),
                               rtol=2e-4, atol=1e-6)


def test_label_logit_exact():
    """The online pass must pick the label's exact f32 logit, not an
    approximation — loss for a one-hot-certain row is ~0."""
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.standard_normal((N, E)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((V, E)) * 0.05, jnp.float32)
    logits = h @ w.T
    labels = jnp.argmax(logits, axis=1).astype(jnp.int32)
    loss_f, prec_f = _fused(h, w, labels)
    loss_d, _ = softmax_loss_metrics(logits.astype(jnp.float32), labels)
    assert float(prec_f) == 1.0
    np.testing.assert_allclose(float(loss_f), float(loss_d), rtol=1e-5)


def test_layer_gating():
    """The LMHeadLoss layer selects the fused kernel exactly when the
    head is tied, top-1, kernel-legal and unsharded — whatever the
    platform (off-TPU the kernel runs interpreted)."""
    import types

    from jax.sharding import Mesh

    from singa_tpu.core.net import build_net
    from singa_tpu.models.transformer import transformer_lm

    cfg = transformer_lm(vocab_size=2048, num_layers=1, embed_dim=128,
                         num_heads=2, head_dim=64, seq_len=128,
                         batchsize=4)
    net = build_net(cfg, "kTrain", {"data": {"input": (128,),
                                             "target": (128,)}})
    layer = net.layers["loss"]
    h2 = jnp.zeros((4 * 128, 128), jnp.bfloat16)      # N=512, E=128
    w = jnp.zeros((2048, 128), jnp.bfloat16)          # (V, E)
    ctx = types.SimpleNamespace(mesh=None)

    assert layer._use_fused(h2, w, True, ctx)
    assert not layer._use_fused(h2, w, False, ctx)     # untied (E,V)
    layer.topk = 5
    assert not layer._use_fused(h2, w, True, ctx)      # top-k > 1
    layer.topk = 1
    # shape-illegal: N not a multiple of the token block
    assert not layer._use_fused(h2[:100], w, True, ctx)
    # under a mesh GSPMD cannot partition the custom call: chunked head
    meshed = types.SimpleNamespace(
        mesh=Mesh(np.array(jax.devices()[:2]), ("data",)))
    assert not layer._use_fused(h2, w, True, meshed)


def test_layer_walks_the_fused_branch_off_tpu():
    """Through the layer, on CPU: kernel-legal shapes take the fused
    (interpreted) head and match the chunked head the same net takes
    when the kernel is not legal for it."""
    from singa_tpu.core.net import build_net
    from singa_tpu.models.transformer import (synthetic_token_batches,
                                              transformer_lm)
    from singa_tpu.ops import head_loss

    cfg = transformer_lm(vocab_size=2048, num_layers=1, embed_dim=128,
                         num_heads=2, head_dim=64, seq_len=128,
                         batchsize=4)
    net = build_net(cfg, "kTrain", {"data": {"input": (128,),
                                             "target": (128,)}})
    params = net.init_params(jax.random.PRNGKey(0))
    batch = next(synthetic_token_batches(4, 128, 2048))
    calls = []
    real = head_loss._head_stats_pallas

    def spy(h, w, labels, bn, bv, interpret):
        calls.append(interpret)
        return real(h, w, labels, bn, bv, interpret)

    head_loss._head_stats_pallas = spy
    try:
        fused, _, _ = net.apply(params, batch, train=False)
    finally:
        head_loss._head_stats_pallas = real
    assert calls == [True]              # fused kernel, interpreted
    net.layers["loss"].topk = 2         # not kernel-legal -> chunked
    chunked, _, _ = net.apply(params, batch, train=False)
    np.testing.assert_allclose(float(fused), float(chunked), rtol=1e-5)
