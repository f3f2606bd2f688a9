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


# -- the cb prefill's attention through the flash forward kernel ---------------
# (`core.seq_layers.attend_cache`: a whole chunk at position 0 takes
# `ops.attention.flash_prefill`; everything else the dense scores)

def _chunk(heads, kv_heads, d, p, dtype, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((1, n, p, d)), dtype)
                 for n in (heads, kv_heads, kv_heads))


@pytest.fixture()
def blocks_128(monkeypatch):
    """(128, 128) blocks, so that a chunk of 512 rows is a 4 x 4 grid
    with blocks to skip and blocks to mask."""
    from singa_tpu.ops import attention
    monkeypatch.setattr(attention, "prefill_blocks", lambda *a: (128, 128))


# (heads, kv heads, head_dim, rows, window): groups of 1 / 4 / 8, two
# head widths; no window; a window under the chunk (block (3, 0) is
# skipped and (3, 1) masked in part at 200; 129 leaves one row of a
# block); a window that holds every key
PREFILL_CASES = [(4, 4, 16, 256, 0), (8, 2, 16, 512, 0), (8, 1, 32, 512, 0),
                 (8, 2, 16, 512, 200), (8, 1, 32, 512, 129),
                 (4, 4, 16, 512, 384), (8, 2, 16, 256, 256),
                 (8, 2, 32, 256, 1000),
                 # heads of 128: a grid step holds a kv head's group
                 (2, 2, 128, 256, 0), (8, 2, 128, 256, 0),
                 (8, 1, 128, 512, 200)]


@pytest.mark.parametrize("heads,kv_heads,d,p,window", PREFILL_CASES)
def test_prefill_dispatch_equals_the_dense_scores(blocks_128, heads,
                                                  kv_heads, d, p, window):
    from singa_tpu.core import seq_layers
    q, k, v = _chunk(heads, kv_heads, d, p, jnp.float32, seed=p + window)
    text = str(jax.make_jaxpr(
        lambda *a: seq_layers.attend_cache(*a, 0, None, window))(q, k, v))
    assert "singa_flash_fwd" in text and "softmax" not in text
    got = seq_layers.attend_cache(q, k, v, 0, None, window)
    want = seq_layers._attend_dense(q, k, v, 0, None, window)
    assert got.shape == want.shape == (1, p, heads * d)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5


def test_prefill_dispatch_at_the_blocks_it_picks_itself():
    from singa_tpu.core import seq_layers
    q, k, v = _chunk(8, 2, 16, 1024, jnp.float32, seed=5)
    for window in (0, 300):
        got = seq_layers.attend_cache(q, k, v, 0, None, window)
        want = seq_layers._attend_dense(q, k, v, 0, None, window)
        assert float(jnp.max(jnp.abs(got - want))) < 2e-5


def test_a_block_before_the_window_is_not_computed(blocks_128):
    """Keys 0..127 hold infinities.  Under a window of 200 the queries
    of rows 384.. start at key 185: a visited block (3, 0) would put
    0 x inf = NaN into them through the value matmul; a skipped one
    leaves them what they are without those keys."""
    from singa_tpu.core import seq_layers
    q, k, v = _chunk(8, 2, 16, 512, jnp.float32, seed=9)
    bad_k, bad_v = k.at[:, :, :128].set(jnp.inf), v.at[:, :, :128].set(jnp.inf)
    got = seq_layers.attend_cache(q, bad_k, bad_v, 0, None, 200)[:, 384:]
    want = seq_layers._attend_dense(q, k, v, 0, None, 200)[:, 384:]
    assert bool(jnp.all(jnp.isfinite(got)))
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5


@pytest.mark.parametrize("heads,kv_heads,d,p,window", [
    (4, 1, 128, 256, 0), (4, 2, 64, 256, 100), (4, 4, 128, 512, 0)])
def test_prefill_dispatch_at_bf16_is_no_further_from_float32_than_dense(
        heads, kv_heads, d, p, window):
    """bf16 operands, f32 scores scaled in f32, f32 max / sum /
    accumulator, probabilities rounded to bf16 for the value matmul: the
    dense path's precision, so no wider a gap to float32 than its own."""
    from singa_tpu.core import seq_layers
    q, k, v = _chunk(heads, kv_heads, d, p, jnp.bfloat16, seed=1)
    exact = seq_layers._attend_dense(
        *(a.astype(jnp.float32) for a in (q, k, v)), 0, None, window)

    def gaps(out):
        gap = jnp.abs(out.astype(jnp.float32) - exact)
        return float(jnp.max(gap)), float(jnp.mean(gap))
    got = gaps(seq_layers.attend_cache(q, k, v, 0, None, window))
    dense = gaps(seq_layers._attend_dense(q, k, v, 0, None, window))
    assert got[0] <= dense[0] and got[1] <= dense[1], (got, dense)


@pytest.mark.parametrize("real", [1, 255])
@pytest.mark.parametrize("window", [0, 100])
def test_a_right_padded_chunk_keeps_its_real_rows_and_stays_finite(real,
                                                                   window):
    """The cb prefill pads a prompt to its rung: the pad rows' K and V
    (here 50 x the real rows' size) lie after every real query, so the
    real rows are the unpadded run's; a pad query's row is finite."""
    from singa_tpu.core import seq_layers
    q, k, v = _chunk(8, 2, 16, 256, jnp.float32, seed=real)
    pad = jnp.arange(256)[None, None, :, None] >= real
    q, k, v = (jnp.where(pad, 50.0 * a, a) for a in (q, k, v))
    got = seq_layers.attend_cache(q, k, v, 0, None, window)
    assert bool(jnp.all(jnp.isfinite(got)))
    alone = seq_layers.attend_cache(
        *(a[:, :, :real] for a in (q, k, v)), 0, None, window)
    assert float(jnp.max(jnp.abs(got[:, :real] - alone))) < 2e-5


def test_a_gradient_through_the_prefill_dispatch_is_the_dense_scores():
    """The kernel is forward only; `CCALayer.apply` reaches the dispatch
    from a training step."""
    from singa_tpu.core import seq_layers
    q, k, v = _chunk(4, 2, 16, 128, jnp.float32, seed=4)

    def loss(fn):
        return lambda *a: jnp.sum(jnp.square(fn(*a, 0, None, 50)))
    got = jax.grad(loss(seq_layers.attend_cache), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(seq_layers._attend_dense), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert float(jnp.max(jnp.abs(g - w))) < 1e-4


def _digest(fn, *shapes):
    import hashlib
    return hashlib.sha256(
        str(jax.make_jaxpr(fn)(*shapes)).encode()).hexdigest()[:16]


def _bf16(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16)


# The programs that must not feel the prefill's kernel, by the digest of
# their jaxpr's text at commit bc48cc4 (PR 35), before the dispatch and
# the kernel's window existed: a later change to one of these is a
# change to the trainer's kernels or to the dense path, and says so here.
def _trainer_forward(q, k, v):
    from singa_tpu.ops.attention import flash_attention_packed
    return flash_attention_packed(q, k, v, 32, True, 256, 512, None, 8)


UNTOUCHED = {
    # the training cell: 32 / 8 heads x 128, S 4096, blocks (256, 512)
    "trainer_forward": ("e759643c6c983a67", _trainer_forward,
                        (_bf16(2, 4096, 4096),) + (_bf16(2, 4096, 1024),) * 2),
    "trainer_backward": (
        "237e666a2dc56db6",
        jax.grad(lambda *a: _trainer_forward(*a).astype(jnp.float32).sum(),
                 argnums=(0, 1, 2)),
        (_bf16(2, 4096, 4096),) + (_bf16(2, 4096, 1024),) * 2),
    # `attend_cache`: a decode token of the static batcher, a chunk at a
    # traced position, the left-padded batch's mask, a chunk under a
    # lane tile, a chunk that is not the whole cache
    "decode_token": (
        "d678aa0dc867eb8b", lambda q, k, v, pos: _attend(q, k, v, pos),
        (_bf16(2, 8, 1, 128),) + (_bf16(2, 2, 256, 128),) * 2
        + (jax.ShapeDtypeStruct((), jnp.int32),)),
    "traced_pos": (
        "cab326db8b5925da", lambda q, k, v, pos: _attend(q, k, v, pos),
        (_bf16(1, 8, 256, 128),) + (_bf16(1, 2, 256, 128),) * 2
        + (jax.ShapeDtypeStruct((), jnp.int32),)),
    "kmask": (
        "68571f57c7b81b84", lambda q, k, v, m: _attend(q, k, v, 0, m),
        (_bf16(1, 8, 256, 128),) + (_bf16(1, 2, 256, 128),) * 2
        + (jax.ShapeDtypeStruct((1, 256), jnp.bool_),)),
    "under_a_lane_tile": (
        "f1bad6a8ca4454ad", lambda q, k, v: _attend(q, k, v, 0),
        (_bf16(1, 8, 64, 128),) + (_bf16(1, 2, 64, 128),) * 2),
    "part_of_the_cache": (
        "0228ebadd6ea89eb", lambda q, k, v: _attend(q, k, v, 0),
        (_bf16(1, 8, 128, 128),) + (_bf16(1, 2, 256, 128),) * 2),
}


def _attend(*args):
    from singa_tpu.core.seq_layers import attend_cache
    return attend_cache(*args)


@pytest.mark.parametrize("name", sorted(UNTOUCHED))
def test_programs_beside_the_prefill_trace_as_before_it(name):
    want, fn, shapes = UNTOUCHED[name]
    assert _digest(fn, *shapes) == want


def test_a_window_that_holds_every_key_is_no_window():
    """`window` is static: 0, and one of at least the chunk's length,
    trace to the kernel as it was before it knew a window; a window
    under the chunk adds its compares."""
    import functools
    from singa_tpu.ops.attention import _packed_forward
    q, kv = _bf16(1, 512, 1024), _bf16(1, 512, 256)

    def text(**kw):
        return str(jax.make_jaxpr(functools.partial(
            _packed_forward, num_heads=8, causal=True, block_q=128,
            block_k=128, interpret=True, num_kv_heads=2, **kw))(q, kv, kv))
    plain = text()
    assert text(window=0) == plain == text(window=512) == text(window=4096)
    assert text(window=200).count("gt") > plain.count("gt")


# (rows, query columns, key columns a grid step holds) at bf16 -> blocks
@pytest.mark.parametrize("shape,want", [
    ((2048, 128, 128), (512, 1024)),      # every serving cell: one head
    ((512, 128, 128), (512, 512)),
    ((256, 128, 128), (256, 256)),
    ((2048, 4096, 1024), (128, 256)),     # 64 / 16 heads of 64, all held
])
def test_prefill_blocks_follow_the_geometry_not_the_length_alone(shape, want):
    from singa_tpu.ops.attention import flash_blocks, prefill_blocks
    assert prefill_blocks(*shape, 2) == want
    assert flash_blocks(shape[0]) == ((512, 1024) if shape[0] >= 1024
                                      else (512, 512))   # the trainer's


@pytest.mark.parametrize("d,grid0,cols", [
    (128, 2 * 8, 128),            # whole lane tiles: one head a step
    (64, 2, 8 * 64)])             # under a lane tile: all of them
def test_a_grid_step_holds_one_head_where_a_head_is_whole_lane_tiles(
        d, grid0, cols):
    """Batch 2, 8 / 2 heads: at heads of 128 the grid's first axis
    walks (batch, head) and a q block is the head's columns; at 64 a
    step holds every head, as the trainer's does."""
    from singa_tpu.ops.attention import flash_prefill
    q, kv = _bf16(2, 256, 8 * d), _bf16(2, 256, 2 * d)
    text = str(jax.make_jaxpr(
        lambda q, k, v: flash_prefill(q, k, v, 8, 2))(q, kv, kv))
    assert f"grid=({grid0}, 1, 1)" in text
    assert f"Blocked(block_size={cols})" in text


@pytest.mark.parametrize("per_step", [1, 2, 4])
def test_heads_a_step_are_the_same_attention(per_step):
    """8 / 2 heads x 128: whatever run of a kv head's group a grid step
    holds, out and lse are those of all heads a step."""
    from singa_tpu.ops.attention import _packed_forward
    rng = np.random.default_rng(per_step)
    q = jnp.asarray(rng.standard_normal((2, 256, 8 * 128)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((2, 256, 2 * 128)), jnp.float32)
            for _ in range(2))
    args = (q, k, v, 8, True, 128, 128, True, 2, 100)
    want = _packed_forward(*args)
    got = _packed_forward(*args, heads_per_step=per_step)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        assert float(jnp.max(jnp.abs(g - w))) < 1e-6


def test_layers_with_a_window_that_holds_the_rung_share_the_lowering():
    """A window of at least the chunk is dropped before the jitted
    call: Trinity's 12 windowed and 4 full layers are one trace and one
    Mosaic lowering a rung."""
    from singa_tpu.ops.attention import flash_prefill, singa_flash_prefill
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 128, 256)), jnp.float32)
    kv = jnp.asarray(rng.standard_normal((1, 128, 128)), jnp.float32)
    flash_prefill(q, kv, kv, 2, 1, 0)
    before = singa_flash_prefill._cache_size()
    flash_prefill(q, kv, kv, 2, 1, 128)
    flash_prefill(q, kv, kv, 2, 1, 4096)
    assert singa_flash_prefill._cache_size() == before
    flash_prefill(q, kv, kv, 2, 1, 64)
    assert singa_flash_prefill._cache_size() == before + 1
