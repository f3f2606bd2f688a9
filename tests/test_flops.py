"""FLOPs accounting + MFU (utils/flops.py).

The analytic counter is the oracle for the XLA cost-analysis path: on
a matmul/conv-dominated net the two must agree to within the share of
elementwise work XLA additionally counts.
"""

import os

import jax
import numpy as np
import pytest

from singa_tpu.config import load_model_config
from singa_tpu.core.net import build_net
from singa_tpu.utils.flops import (compiled_flops, mfu, net_forward_flops,
                                   net_train_flops, peak_flops)

MNIST_SHAPES = {"data": {"pixel": (28, 28), "label": ()}}


def _lenet_net(bs=64):
    cfg = load_model_config(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "examples/mnist/conv.conf"))
    return build_net(cfg, "kTrain", MNIST_SHAPES, batchsize=bs)


def test_analytic_lenet_flops_formula():
    net = _lenet_net(bs=1)
    # conv1: 2*20*24*24*5*5*1 + conv2: 2*50*8*8*5*5*20 + ip1: 2*800*500
    # + ip2: 2*500*10 (per sample, 2*MACs)
    conv1 = 2 * 20 * 24 * 24 * 25
    conv2 = 2 * 50 * 8 * 8 * 25 * 20
    shapes = {s.name: s.shape for s in net.param_specs.values()}
    ip1 = 2 * int(np.prod(shapes["ip1/weight"]))
    ip2 = 2 * int(np.prod(shapes["ip2/weight"]))
    assert net_forward_flops(net) == conv1 + conv2 + ip1 + ip2
    assert net_train_flops(net) == 3 * net_forward_flops(net)


def test_analytic_scales_linearly_with_batch():
    assert net_forward_flops(_lenet_net(8)) * 8 == \
        net_forward_flops(_lenet_net(64))


def test_compiled_flops_close_to_analytic():
    bs = 32
    net = _lenet_net(bs)
    params = net.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"data": {
        "pixel": rng.integers(0, 256, (bs, 28, 28)).astype(np.uint8),
        "label": rng.integers(0, 10, (bs,)).astype(np.int32)}}

    def fwd(p, b):
        loss, _, _ = net.apply(p, b, train=False)
        return loss

    got = compiled_flops(jax.jit(fwd), params, batch)
    if got is None:
        pytest.skip("backend reports no flops")
    analytic = net_forward_flops(net)
    # XLA adds elementwise/softmax flops on top of the matmul/conv core
    assert analytic <= got <= 1.5 * analytic


def test_mfu_and_peak_lookup():
    class FakeDev:
        device_kind = "TPU v5 lite"
    assert peak_flops(FakeDev()) == 197e12
    # 197e12 flops done in 2s on a 197e12-peak chip → 50% MFU
    assert mfu(197e12, 2.0, FakeDev()) == pytest.approx(0.5)

    class Cpu:
        device_kind = "cpu"
        platform = "cpu"
    assert peak_flops(Cpu()) is None
    assert mfu(1e9, 1.0, Cpu()) is None
    assert peak_flops() is None          # the test platform itself

    class Unknown:
        device_kind = "TPU v99"
        platform = "tpu"
    # an accelerator with no peak on record is an error, never a
    # silently absent (or guessed) utilization
    with pytest.raises(ValueError, match="TPU v99"):
        peak_flops(Unknown())
    with pytest.raises(ValueError, match="TPU v99"):
        mfu(1e9, 1.0, Unknown())
