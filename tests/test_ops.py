"""Golden numeric tests for singa_tpu.ops vs NumPy oracles implementing
the reference math (mshadow expressions, layer.cc compute paths)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu import ops

RNG = np.random.default_rng(0)


def np_conv2d(x, w, b, kernel, stride, pad):
    """Direct-loop conv oracle over the reference weight layout
    (num_filters, C*k*k), layer.cc:63-83."""
    n, c, h, w_ = x.shape
    nf = w.shape[0]
    oh = (h + 2 * pad - kernel) // stride + 1
    ow = (w_ + 2 * pad - kernel) // stride + 1
    xp = np.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    wk = w.reshape(nf, c, kernel, kernel)
    out = np.zeros((n, nf, oh, ow), np.float32)
    for ni in range(n):
        for f in range(nf):
            for i in range(oh):
                for j in range(ow):
                    patch = xp[ni, :, i * stride:i * stride + kernel,
                               j * stride:j * stride + kernel]
                    out[ni, f, i, j] = np.sum(patch * wk[f]) + b[f]
    return out


@pytest.mark.parametrize("pad,stride", [(0, 1), (2, 2), (1, 3)])
def test_conv2d_golden(pad, stride):
    x = RNG.standard_normal((2, 3, 9, 9)).astype(np.float32)
    w = RNG.standard_normal((4, 3 * 3 * 3)).astype(np.float32)
    b = RNG.standard_normal((4,)).astype(np.float32)
    got = ops.conv2d(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b),
                     kernel=3, stride=stride, pad=pad)
    want = np_conv2d(x, w, b, 3, stride, pad)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


def test_im2col_matches_conv():
    """weight @ im2col(x) == conv2d(x) — the reference's own identity
    (layer.cc:75-82)."""
    x = RNG.standard_normal((1, 2, 6, 6)).astype(np.float32)
    w = RNG.standard_normal((3, 2 * 3 * 3)).astype(np.float32)
    col = ops.im2col(jnp.asarray(x[0]), kernel=3, stride=1)
    via_col = (jnp.asarray(w) @ col).reshape(1, 3, 4, 4)
    direct = ops.conv2d(jnp.asarray(x), jnp.asarray(w), None, kernel=3, stride=1)
    np.testing.assert_allclose(np.asarray(via_col), np.asarray(direct),
                               rtol=1e-4, atol=1e-4)


def np_pool(x, kernel, stride, mode):
    n, c, h, w = x.shape
    oh = int(np.ceil((h - kernel) / stride)) + 1
    ow = int(np.ceil((w - kernel) / stride)) + 1
    out = np.zeros((n, c, oh, ow), np.float32)
    for i in range(oh):
        for j in range(ow):
            hs, ws = i * stride, j * stride
            win = x[:, :, hs:min(hs + kernel, h), ws:min(ws + kernel, w)]
            if mode == "max":
                out[:, :, i, j] = win.max(axis=(2, 3))
            else:
                # reference AVE divides by k*k always (layer.cc:513-515)
                out[:, :, i, j] = win.sum(axis=(2, 3)) / (kernel * kernel)
    return out


@pytest.mark.parametrize("h,k,s", [(6, 2, 2), (7, 3, 2), (5, 2, 3)])
def test_pool_golden(h, k, s):
    x = RNG.standard_normal((2, 3, h, h)).astype(np.float32)
    got_max = ops.max_pool2d(jnp.asarray(x), k, s)
    got_avg = ops.avg_pool2d(jnp.asarray(x), k, s)
    np.testing.assert_allclose(np.asarray(got_max), np_pool(x, k, s, "max"),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_avg), np_pool(x, k, s, "avg"),
                               rtol=1e-5, atol=1e-5)


def test_maxpool_grad_routes_to_argmax():
    """unpool<red::maximum> semantics: grad flows only to the max cell."""
    x = jnp.array([[[[1., 2.], [3., 4.]]]])
    g = jax.grad(lambda t: ops.max_pool2d(t, 2, 2).sum())(x)
    np.testing.assert_allclose(np.asarray(g),
                               [[[[0., 0.], [0., 1.]]]])


def np_lrn(x, lsize, alpha, beta, knorm):
    n, c, h, w = x.shape
    half = lsize // 2
    sq = x * x
    norm = np.zeros_like(x)
    for ci in range(c):
        lo, hi = max(0, ci - half), min(c, ci + half + 1)
        norm[:, ci] = sq[:, lo:hi].sum(axis=1)
    norm = norm * (alpha / lsize) + knorm
    return x * norm ** (-beta)


def test_lrn_golden():
    x = RNG.standard_normal((2, 8, 4, 4)).astype(np.float32)
    got = ops.lrn(jnp.asarray(x), 5, 1e-4, 0.75, 1.0)
    np.testing.assert_allclose(np.asarray(got), np_lrn(x, 5, 1e-4, 0.75, 1.0),
                               rtol=1e-5, atol=1e-6)


def test_lrn_grad_matches_reference_formula():
    """layer.cc:366-377: gsrc = g*norm^-b - 2*b*salpha*chpool(g*x*norm^(-b-1))*x"""
    lsize, alpha, beta, knorm = 5, 1e-2, 0.75, 1.0
    x = RNG.standard_normal((1, 7, 3, 3)).astype(np.float32)
    gout = RNG.standard_normal(x.shape).astype(np.float32)
    _, vjp = jax.vjp(lambda t: ops.lrn(t, lsize, alpha, beta, knorm),
                     jnp.asarray(x))
    got = np.asarray(vjp(jnp.asarray(gout))[0])

    salpha = alpha / lsize
    half = lsize // 2
    sq = x * x
    norm = np.zeros_like(x)
    for ci in range(x.shape[1]):
        lo, hi = max(0, ci - half), min(x.shape[1], ci + half + 1)
        norm[:, ci] = sq[:, lo:hi].sum(axis=1)
    norm = norm * salpha + knorm
    inner = gout * x * norm ** (-beta - 1.0)
    ch = np.zeros_like(x)
    for ci in range(x.shape[1]):
        lo, hi = max(0, ci - half), min(x.shape[1], ci + half + 1)
        ch[:, ci] = inner[:, lo:hi].sum(axis=1)
    want = gout * norm ** (-beta) - 2.0 * beta * salpha * ch * x
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_fused_relu_lrn_matches_relu_then_lrn():
    """relu_lrn(relu=True) == lrn(relu(x)) in fwd AND bwd — the fused
    conv→relu→lrn path NeuralNet._fuse_relu_lrn selects (custom_vjp
    with in-vjp relu and x>0 gradient masking, ops/lrn.py)."""
    lsize, alpha, beta, knorm = 5, 1e-2, 0.75, 1.0
    x = jnp.asarray(RNG.standard_normal((2, 4, 3, 16)).astype(np.float32))
    g = jnp.asarray(RNG.standard_normal(x.shape).astype(np.float32))

    def fused(t):
        return ops.relu_lrn(t, lsize, alpha, beta, knorm, relu=True,
                            layout="NHWC")

    def unfused(t):
        # autodiff oracle: separate relu, then the NCHW reduce_window
        # LRN (no custom_vjp on either piece)
        a = jnp.maximum(t, 0.0)
        return ops.lrn(jnp.transpose(a, (0, 3, 1, 2)), lsize, alpha,
                       beta, knorm, layout="NCHW").transpose(0, 2, 3, 1)

    y1, vjp1 = jax.vjp(fused, x)
    y2, vjp2 = jax.vjp(unfused, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(vjp1(g)[0]),
                               np.asarray(vjp2(g)[0]),
                               rtol=1e-4, atol=1e-5)


def test_stanh_constants():
    x = jnp.array([0.5, -1.0, 2.0])
    np.testing.assert_allclose(
        np.asarray(ops.stanh(x)),
        1.7159047 * np.tanh(0.66666667 * np.asarray(x)), rtol=1e-6)
    # grad-from-output identity: stanh'(x) = B*A - (B/A) * y^2
    g = jax.grad(lambda t: ops.stanh(t).sum())(x)
    y = np.asarray(ops.stanh(x))
    want = 0.66666667 * 1.7159047 - 0.66666667 / 1.7159047 * y * y
    np.testing.assert_allclose(np.asarray(g), want, rtol=1e-5)


def test_nhwc_ops_match_nchw_oracles():
    """The NHWC code paths (the production layout for every vision net)
    must agree numerically with the NCHW golden-oracle paths: conv's
    HWIO weight transpose, pool's window tuples, and LRN's banded-matmul
    channel window."""
    x = RNG.standard_normal((2, 5, 7, 7)).astype(np.float32)  # NCHW
    xh = jnp.asarray(np.moveaxis(x, 1, -1))                   # NHWC
    xc = jnp.asarray(x)

    w = RNG.standard_normal((6, 5 * 3 * 3)).astype(np.float32)
    b = RNG.standard_normal((6,)).astype(np.float32)
    conv_c = ops.conv2d(xc, jnp.asarray(w), jnp.asarray(b), kernel=3,
                        stride=2, pad=1)
    conv_h = ops.conv2d(xh, jnp.asarray(w), jnp.asarray(b), kernel=3,
                        stride=2, pad=1, layout="NHWC")
    np.testing.assert_allclose(np.moveaxis(np.asarray(conv_h), -1, 1),
                               np.asarray(conv_c), rtol=1e-5, atol=1e-5)

    for f in (ops.max_pool2d, ops.avg_pool2d):
        pc = f(xc, 3, 2)
        ph = f(xh, 3, 2, layout="NHWC")
        np.testing.assert_allclose(np.moveaxis(np.asarray(ph), -1, 1),
                                   np.asarray(pc), rtol=1e-6)

    lc = ops.lrn(xc, 3, 5e-5, 0.75, 1.0)
    lh = ops.lrn(xh, 3, 5e-5, 0.75, 1.0, layout="NHWC")
    np.testing.assert_allclose(np.moveaxis(np.asarray(lh), -1, 1),
                               np.asarray(lc), rtol=1e-5, atol=1e-6)
    # gradients too (banded matmul backward vs reduce_window backward)
    gc = jax.grad(lambda t: (ops.lrn(t, 3, 5e-5, 0.75, 1.0) ** 2).sum())(xc)
    gh = jax.grad(lambda t: (ops.lrn(t, 3, 5e-5, 0.75, 1.0,
                                     layout="NHWC") ** 2).sum())(xh)
    np.testing.assert_allclose(np.moveaxis(np.asarray(gh), -1, 1),
                               np.asarray(gc), rtol=1e-4, atol=1e-5)


def test_binary_op_structs():
    """square/threshold/power/sqrtop vs cxxnet_op.h:71-113 oracles."""
    a = jnp.array([0.25, 4.0, 0.5, 2.0])
    b = jnp.array([0.5, 0.5, 3.0, 2.0])
    np.testing.assert_allclose(np.asarray(ops.square(a)),
                               np.asarray(a) ** 2, rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(ops.threshold(a, b)),
        (np.asarray(a) < np.asarray(b)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(ops.power(a, b)),
                               np.asarray(a) ** np.asarray(b), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ops.sqrtop(a, b)),
                               np.sqrt(np.asarray(a) + np.asarray(b)),
                               rtol=1e-6)


def test_relu_and_leaky():
    x = jnp.array([-2.0, 0.0, 3.0])
    np.testing.assert_allclose(np.asarray(ops.relu(x)), [0, 0, 3])
    np.testing.assert_allclose(np.asarray(ops.relu(x, 0.1)),
                               [-0.2, 0, 3], rtol=1e-6)


def test_softmax_loss_golden():
    logits = RNG.standard_normal((8, 10)).astype(np.float32)
    labels = RNG.integers(0, 10, 8)
    loss, prec = ops.softmax_loss_metrics(
        jnp.asarray(logits), jnp.asarray(labels), topk=3, scale=1.0)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want_loss = -np.mean(np.log(p[np.arange(8), labels]))
    top3 = np.argsort(-logits, axis=-1)[:, :3]
    want_prec = np.mean([labels[i] in top3[i] for i in range(8)])
    np.testing.assert_allclose(float(loss), want_loss, rtol=1e-5)
    np.testing.assert_allclose(float(prec), want_prec, rtol=1e-6)


def test_softmax_loss_grad_is_prob_minus_onehot():
    """layer.cc:756-765: gsrc = (prob - onehot) * scale / batch."""
    logits = RNG.standard_normal((4, 5)).astype(np.float32)
    labels = np.array([1, 0, 4, 2])
    scale = 2.0
    g = jax.grad(lambda t: ops.softmax_cross_entropy(
        t, jnp.asarray(labels), scale))(jnp.asarray(logits))
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    onehot = np.eye(5, dtype=np.float32)[labels]
    np.testing.assert_allclose(np.asarray(g), (p - onehot) * scale / 4,
                               rtol=1e-5, atol=1e-6)


def test_dropout_mask_and_scale():
    x = jnp.ones((1000,))
    y = ops.dropout(x, 0.4, jax.random.PRNGKey(0), train=True)
    kept = np.asarray(y) > 0
    assert abs(kept.mean() - 0.6) < 0.06
    np.testing.assert_allclose(np.asarray(y)[kept], 1.0 / 0.6, rtol=1e-6)
    y_eval = ops.dropout(x, 0.4, jax.random.PRNGKey(0), train=False)
    np.testing.assert_allclose(np.asarray(y_eval), np.asarray(x))


def test_linear_golden():
    x = RNG.standard_normal((3, 4, 2)).astype(np.float32)  # flattened to (3,8)
    w = RNG.standard_normal((8, 5)).astype(np.float32)
    b = RNG.standard_normal((5,)).astype(np.float32)
    got = ops.linear(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b))
    want = x.reshape(3, 8) @ w + b
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)


def test_elastic_deform_identity_and_transforms():
    """ops/augment: zero strengths = identity; rotation/scale/elastic move
    pixels as expected; deterministic under a fixed key."""
    import jax
    from singa_tpu.ops.augment import elastic_deform
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((3, 17, 17)).astype(np.float32))
    key = jax.random.PRNGKey(0)

    out = elastic_deform(x, key)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=1e-5)

    # the rotation center is a fixed point of a pure rotation
    delta = jnp.zeros((1, 17, 17)).at[0, 8, 8].set(1.0)
    rot = elastic_deform(delta, key, beta=45.0)
    assert float(rot[0, 8, 8]) > 0.99

    # elastic displacement changes the image but is deterministic
    e1 = elastic_deform(x, key, kernel=5, sigma=2.0, alpha=3.0)
    e2 = elastic_deform(x, key, kernel=5, sigma=2.0, alpha=3.0)
    e3 = elastic_deform(x, jax.random.PRNGKey(1), kernel=5, sigma=2.0,
                        alpha=3.0)
    np.testing.assert_allclose(np.asarray(e1), np.asarray(e2))
    assert float(jnp.max(jnp.abs(e1 - x))) > 1e-3
    assert float(jnp.max(jnp.abs(e1 - e3))) > 1e-3


def test_mnist_layer_applies_distortion_only_in_train():
    """kMnistImage runs the declared-but-unimplemented reference
    distortion surface (MnistProto) on-device in the train phase only."""
    import jax
    from singa_tpu.config import model_config_from_text
    from singa_tpu.core import build_net
    text = """
    neuralnet {
      layer { name: "data" type: "kShardData" data_param { batchsize: 4 } }
      layer { name: "mnist" type: "kMnistImage" srclayers: "data"
              mnist_param { kernel: 5 sigma: 2.0 alpha: 4.0 beta: 10.0
                            norm_a: 255.0 } }
      layer { name: "lab" type: "kLabel" srclayers: "data" }
      layer { name: "fc" type: "kInnerProduct" srclayers: "mnist"
              inner_product_param { num_output: 10 }
              param { name: "weight" init_method: kUniform }
              param { name: "bias" init_method: kConstant value: 0 } }
      layer { name: "loss" type: "kSoftmaxLoss" srclayers: "fc"
              srclayers: "lab" }
    }
    """
    cfg = model_config_from_text(text)
    net = build_net(cfg, "kTrain", {"data": {"pixel": (28, 28),
                                             "label": ()}})
    params = net.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    batch = {"data": {
        "pixel": jnp.asarray(rng.integers(0, 256, (4, 28, 28))
                             .astype(np.uint8)),
        "label": jnp.asarray(rng.integers(0, 10, (4,)))}}
    _, _, out_train = net.apply(params, batch, rng=jax.random.PRNGKey(3),
                                train=True)
    _, _, out_eval = net.apply(params, batch, train=False)
    plain = np.asarray(batch["data"]["pixel"], np.float32) / 255.0
    np.testing.assert_allclose(np.asarray(out_eval["mnist"]), plain,
                               atol=1e-6)
    assert float(jnp.max(jnp.abs(out_train["mnist"] - plain))) > 1e-4


def test_maxpool_equality_mask_vjp_ties_match_reference():
    """_max_pool_nhwc routes gradient to EVERY tied max (mshadow
    unpool<red::maximum> semantics, tensor_expr_ext.h:148-163): with a
    constant input, every window position compares equal to the max and
    receives the window's full cotangent — unlike select-and-scatter,
    which picks a single winner."""
    from singa_tpu.ops.pool import _max_pool_nhwc

    x = jnp.ones((1, 4, 4, 1), np.float32)
    y, vjp = jax.vjp(lambda t: _max_pool_nhwc(t, 2, 2), x)
    (dx,) = vjp(jnp.ones_like(y))
    # 2x2 stride-2 windows: every input position ties -> grad 1 each
    np.testing.assert_allclose(dx, np.ones((1, 4, 4, 1)))
    # and on untied data it matches autodiff of the NCHW path
    xr = jnp.asarray(RNG.standard_normal((2, 8, 8, 3)).astype(np.float32))
    cot = jnp.asarray(RNG.standard_normal((2, 4, 4, 3)).astype(np.float32))
    _, vjp_em = jax.vjp(lambda t: _max_pool_nhwc(t, 3, 2), xr)
    _, vjp_ad = jax.vjp(
        lambda t: ops.max_pool2d(t.transpose(0, 3, 1, 2), 3, 2,
                                 "NCHW").transpose(0, 2, 3, 1), xr)
    np.testing.assert_allclose(vjp_em(cot)[0], vjp_ad(cot)[0], atol=1e-6)
