"""Model zoo + checkpoint/resume + RBM tests."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu.core.trainer import Trainer
from singa_tpu.models import (alexnet_cifar10, alexnet_imagenet, lenet_mnist,
                              mlp_mnist, rbm)
from singa_tpu.utils.checkpoint import CheckpointManager

CIFAR_SHAPES = {"data": {"pixel": (3, 32, 32), "label": ()}}
MNIST_SHAPES = {"data": {"pixel": (28, 28), "label": ()}}
# the repo's shipped copies of the reference's mnist configs
MNIST = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples", "mnist")


def _cifar_batch(bs, seed=0):
    rng = np.random.default_rng(seed)
    return {"data": {
        "pixel": rng.integers(0, 256, (bs, 3, 32, 32)).astype(np.uint8),
        "label": rng.integers(0, 10, (bs,)).astype(np.int32)}}


def _mnist_batch(bs, seed=0):
    rng = np.random.default_rng(seed)
    return {"data": {
        "pixel": rng.integers(0, 256, (bs, 28, 28)).astype(np.uint8),
        "label": rng.integers(0, 10, (bs,)).astype(np.int32)}}


def test_alexnet_cifar10_builds_and_steps():
    cfg = alexnet_cifar10(batchsize=8, train_steps=2)
    trainer = Trainer(cfg, CIFAR_SHAPES, donate=False)
    net = trainer.train_net
    assert net.shapes["conv1"] == (8, 32, 32, 32)  # NHWC (h=w=c=32)
    assert net.shapes["pool1"] == (8, 16, 16, 32)
    assert net.shapes["pool3"] == (8, 4, 4, 64)
    assert net.shapes["ip1"] == (8, 10)
    params, opt = trainer.init(0)
    p, o, m = trainer.train_step(params, opt, _cifar_batch(8), 0,
                                 jax.random.PRNGKey(0))
    assert np.isfinite(float(m["loss"]))


def test_alexnet_imagenet_shapes():
    cfg = alexnet_imagenet(batchsize=2, nclass=100)
    shapes = {"data": {"pixel": (3, 256, 256), "label": ()}}
    trainer = Trainer(cfg, shapes, donate=False)
    net = trainer.train_net
    assert net.shapes["rgb"] == (2, 227, 227, 3)  # NHWC
    assert net.shapes["conv1"] == (2, 55, 55, 96)
    assert net.shapes["pool5"] == (2, 6, 6, 256)
    assert net.shapes["fc6"] == (2, 4096)
    assert net.shapes["fc8"] == (2, 100)


def test_programmatic_lenet_matches_conf_lenet():
    from singa_tpu.config import load_model_config
    from singa_tpu.core import build_net
    a = build_net(lenet_mnist(batchsize=4), "kTrain", MNIST_SHAPES)
    b = build_net(load_model_config(
        f"{MNIST}/conv.conf"), "kTrain",
        MNIST_SHAPES, batchsize=4)
    for k in ("conv1", "pool1", "conv2", "pool2", "ip1", "ip2"):
        assert a.shapes[k] == b.shapes[k]


def test_checkpoint_roundtrip(tmp_path):
    cfg = lenet_mnist(batchsize=4, train_steps=2)
    trainer = Trainer(cfg, MNIST_SHAPES, donate=False)
    params, opt = trainer.init(0)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(7, params, opt)
    assert mgr.latest_step() == 7
    rp, ro, step = mgr.restore(template={"params": params, "opt_state": opt})
    assert step == 7
    np.testing.assert_allclose(np.asarray(rp["conv1/weight"]),
                               np.asarray(params["conv1/weight"]))
    np.testing.assert_allclose(
        np.asarray(ro["history"]["ip1/weight"]),
        np.asarray(opt["history"]["ip1/weight"]))


def test_trainer_checkpoint_and_resume(tmp_path):
    cfg = lenet_mnist(batchsize=4, train_steps=4)
    cfg.checkpoint_frequency = 2
    trainer = Trainer(cfg, MNIST_SHAPES, donate=False)
    params, opt = trainer.init(0)
    batches = iter(lambda: _mnist_batch(4), None)
    p2, o2, _ = trainer.run(params, opt, batches, workspace=str(tmp_path))
    rp, ro, step = trainer.resume(params, opt, str(tmp_path))
    assert step == 4
    np.testing.assert_allclose(np.asarray(rp["ip2/weight"]),
                               np.asarray(p2["ip2/weight"]))
    # resume from a fresh trainer continues without error
    p3, o3, _ = trainer.run(rp, ro, batches, start_step=step,
                            workspace=str(tmp_path))


def test_rbm_cd_learns_reconstruction():
    """CD-1 on a toy two-mode binary dataset must cut reconstruction
    error substantially."""
    rng = np.random.default_rng(0)
    modes = (rng.random((2, 16)) > 0.5).astype(np.float32)

    def data_factory():
        while True:
            idx = rng.integers(0, 2, 32)
            noise = rng.random((32, 16)) < 0.05
            yield jnp.asarray(np.logical_xor(modes[idx], noise)
                              .astype(np.float32))

    it = data_factory()
    params = rbm.init_rbm(jax.random.PRNGKey(0), 16, 8)
    _, recon0, _ = rbm.cd_grads(params, next(it), jax.random.PRNGKey(1))
    trained = rbm.pretrain_rbm(jax.random.PRNGKey(0), it, 16, 8,
                               steps=200, lr=0.1)
    _, recon1, _ = rbm.cd_grads(trained, next(it), jax.random.PRNGKey(2))
    assert float(recon1) < float(recon0) * 0.6, (float(recon0), float(recon1))


def test_rbm_greedy_stack_and_unroll():
    rng = np.random.default_rng(1)

    def data_factory():
        while True:
            yield jnp.asarray((rng.random((16, 20)) > 0.7).astype(np.float32))

    rbms = rbm.greedy_pretrain(jax.random.PRNGKey(0), data_factory,
                               widths=[12, 6], nvis=20, steps_per_layer=20,
                               log_fn=lambda s: None)
    assert rbms[0]["W"].shape == (20, 12)
    assert rbms[1]["W"].shape == (12, 6)
    params = rbm.unroll_autoencoder(rbms)
    v = jnp.asarray((rng.random((4, 20)) > 0.5).astype(np.float32))
    out = rbm.autoencoder_apply(params, v, nlayers=2)
    assert out.shape == (4, 20)
    # differentiable for fine-tuning
    g = jax.grad(lambda p: jnp.mean(
        (rbm.autoencoder_apply(p, v, 2) - v) ** 2))(params)
    assert np.isfinite(float(jnp.sum(jnp.abs(g["enc0/weight"]))))
