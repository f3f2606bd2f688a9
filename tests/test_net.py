"""NeuralNet builder tests: reference configs → compiled train steps."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu.config import load_model_config, model_config_from_text
from singa_tpu.core import build_net, Trainer
from singa_tpu.core.graph import Graph, GraphError

MNIST_SHAPES = {"data": {"pixel": (28, 28), "label": ()}}
# the repo's shipped copies of the reference's mnist configs
MNIST = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples", "mnist")


def _mnist_batch(bs, rng, size=28, nclass=10):
    return {"data": {
        "pixel": jnp.asarray(
            rng.integers(0, 256, (bs, size, size)).astype(np.uint8)),
        "label": jnp.asarray(rng.integers(0, nclass, (bs,))),
    }}


def test_graph_topo_and_cycle():
    g = Graph()
    for n in "abc":
        g.add_node(n)
    g.add_edge("a", "b")
    g.add_edge("b", "c")
    assert g.topo_sort() == ["a", "b", "c"]
    g.add_edge("c", "a")
    with pytest.raises(GraphError):
        g.topo_sort()


def test_build_mlp_from_reference_conf():
    cfg = load_model_config(f"{MNIST}/mlp.conf")
    net = build_net(cfg, "kTrain", MNIST_SHAPES, batchsize=8)
    # phase filtering: only one data layer remains
    assert [n for n in net.topo if n == "data"] == ["data"]
    # shapes through the stack
    assert net.shapes["mnist"] == (8, 28, 28)
    assert net.shapes["fc1"] == (8, 2500)
    assert net.shapes["fc6"] == (8, 10)
    # 6 fc layers × (weight+bias)
    assert len(net.param_specs) == 12
    assert net.param_specs["fc1/weight"].shape == (784, 2500)

    rng = np.random.default_rng(0)
    params = net.init_params(jax.random.PRNGKey(0))
    loss, metrics, outputs = net.apply(params, _mnist_batch(8, rng))
    assert np.isfinite(float(loss))
    assert 0.0 <= float(metrics["precision"]) <= 1.0
    # uniform(-0.05, 0.05) init → initial loss near log(10)
    assert abs(float(loss) - np.log(10)) < 0.5


def test_build_lenet_from_reference_conf():
    cfg = load_model_config(f"{MNIST}/conv.conf")
    net = build_net(cfg, "kTrain", MNIST_SHAPES, batchsize=4)
    # NHWC runtime layout (same geometry as the reference's NCHW shapes)
    assert net.shapes["conv1"] == (4, 24, 24, 20)
    assert net.shapes["pool1"] == (4, 12, 12, 20)
    assert net.shapes["conv2"] == (4, 8, 8, 50)
    assert net.shapes["pool2"] == (4, 4, 4, 50)
    assert net.shapes["ip1"] == (4, 500)
    assert net.shapes["ip2"] == (4, 10)
    assert net.param_specs["conv1/weight"].shape == (20, 25)
    assert net.param_specs["conv2/weight"].shape == (50, 20 * 25)

    rng = np.random.default_rng(1)
    params = net.init_params(jax.random.PRNGKey(1))
    loss, metrics, _ = net.apply(params, _mnist_batch(4, rng))
    assert np.isfinite(float(loss))


def test_test_phase_net_shares_params():
    cfg = load_model_config(f"{MNIST}/mlp.conf")
    train_net = build_net(cfg, "kTrain", MNIST_SHAPES, batchsize=8)
    test_net = build_net(cfg, "kTest", MNIST_SHAPES, batchsize=8)
    # same param specs → same pytree works for both (ShareWeights parity)
    assert set(train_net.param_specs) == set(test_net.param_specs)
    params = train_net.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    loss, _, _ = test_net.apply(params, _mnist_batch(8, rng), train=False)
    assert np.isfinite(float(loss))


def test_trainer_loss_decreases_on_fixed_batch():
    """End-to-end smoke: jitted train step memorizes one batch."""
    cfg = load_model_config(f"{MNIST}/conv.conf")
    cfg.train_steps = 30
    cfg.test_frequency = 0
    cfg.display_frequency = 0
    trainer = Trainer(cfg, MNIST_SHAPES)
    params, opt_state = trainer.init(seed=0)
    rng = np.random.default_rng(3)
    batch = _mnist_batch(16, rng)

    losses = []
    for step in range(60):
        params, opt_state, metrics = trainer.train_step(
            params, opt_state, batch, step, jax.random.PRNGKey(step))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.7, losses[::10]


def test_share_param_aliasing():
    text = """
    neuralnet {
      layer { name: "data" type: "kShardData"
              data_param { batchsize: 4 } }
      layer { name: "img" type: "kMnistImage" srclayers: "data" }
      layer { name: "lab" type: "kLabel" srclayers: "data" }
      layer { name: "fc1" type: "kInnerProduct" srclayers: "img"
              inner_product_param { num_output: 784 }
              param { name: "w" init_method: kUniform low: -0.1 high: 0.1 }
              param { name: "b" init_method: kConstant value: 0 } }
      layer { name: "fc2" type: "kInnerProduct" srclayers: "fc1"
              inner_product_param { num_output: 784 }
              share_param: "fc1/w"
              param { name: "w2" }
              param { name: "b2" init_method: kConstant value: 0 } }
      layer { name: "loss" type: "kSoftmaxLoss"
              srclayers: "fc2" srclayers: "lab" }
    }
    """
    cfg = model_config_from_text(text)
    net = build_net(cfg, "kTrain", MNIST_SHAPES)
    assert "fc2/w2" not in net.param_specs
    assert net.param_aliases == {"fc2/w2": "fc1/w"}
    params = net.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(4)
    loss, _, _ = net.apply(params, _mnist_batch(4, rng))
    assert np.isfinite(float(loss))


def test_connector_layers_concate_slice_split():
    text = """
    neuralnet {
      layer { name: "data" type: "kShardData"
              data_param { batchsize: 6 } }
      layer { name: "img" type: "kMnistImage" srclayers: "data" }
      layer { name: "lab" type: "kLabel" srclayers: "data" }
      layer { name: "split" type: "kSplit" srclayers: "img"
              split_param { num_splits: 2 } }
      layer { name: "fc_a" type: "kInnerProduct" srclayers: "split"
              inner_product_param { num_output: 8 }
              param { name: "weight" init_method: kUniform }
              param { name: "bias" init_method: kConstant value: 0 } }
      layer { name: "fc_b" type: "kInnerProduct" srclayers: "split"
              inner_product_param { num_output: 8 }
              param { name: "weight" init_method: kUniform }
              param { name: "bias" init_method: kConstant value: 0 } }
      layer { name: "cat" type: "kConcate"
              srclayers: "fc_a" srclayers: "fc_b"
              concate_param { concate_dimension: 1 } }
      layer { name: "slice" type: "kSlice" srclayers: "cat"
              slice_param { slice_dimension: 1 slice_num: 2 } }
      layer { name: "out_a" type: "kReLU" srclayers: "slice" }
      layer { name: "out_b" type: "kReLU" srclayers: "slice" }
      layer { name: "cat2" type: "kConcate"
              srclayers: "out_a" srclayers: "out_b"
              concate_param { concate_dimension: 1 } }
      layer { name: "loss" type: "kSoftmaxLoss"
              srclayers: "cat2" srclayers: "lab" }
    }
    """
    cfg = model_config_from_text(text)
    net = build_net(cfg, "kTrain", MNIST_SHAPES)
    assert net.shapes["cat"] == (6, 16)
    assert net.shapes["slice"] == ((6, 8), (6, 8))
    assert net.shapes["cat2"] == (6, 16)
    params = net.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    loss, _, outputs = net.apply(params, _mnist_batch(6, rng))
    np.testing.assert_allclose(
        np.asarray(outputs["cat2"]),
        np.maximum(np.asarray(outputs["cat"]), 0), rtol=1e-6)


def test_uneven_slice_remainder_to_last():
    """neuralnet.cc:160-162: remainder goes to the last partition."""
    text = """
    neuralnet {
      layer { name: "data" type: "kShardData" data_param { batchsize: 2 } }
      layer { name: "img" type: "kMnistImage" srclayers: "data" }
      layer { name: "lab" type: "kLabel" srclayers: "data" }
      layer { name: "fc" type: "kInnerProduct" srclayers: "img"
              inner_product_param { num_output: 10 }
              param { name: "weight" } param { name: "bias" } }
      layer { name: "slice" type: "kSlice" srclayers: "fc"
              slice_param { slice_dimension: 1 slice_num: 3 } }
      layer { name: "a" type: "kReLU" srclayers: "slice" }
      layer { name: "b" type: "kReLU" srclayers: "slice" }
      layer { name: "c" type: "kReLU" srclayers: "slice" }
      layer { name: "cat" type: "kConcate"
              srclayers: "a" srclayers: "b" srclayers: "c"
              concate_param { concate_dimension: 1 } }
      layer { name: "loss" type: "kSoftmaxLoss"
              srclayers: "cat" srclayers: "lab" }
    }
    """
    cfg = model_config_from_text(text)
    net = build_net(cfg, "kTrain", MNIST_SHAPES)
    assert net.shapes["slice"] == ((2, 3), (2, 3), (2, 4))


def test_fused_relu_lrn_net_matches_unfused():
    """A conv→relu→lrn net produces identical loss and grads whether
    the relu is fused into the LRN custom_vjp (fuse_from, the default
    the builder picks) or the layers run separately."""
    import numpy as np

    from singa_tpu.models.vision import alexnet_cifar10
    from singa_tpu.core.net import build_net

    cfg = alexnet_cifar10(batchsize=4)
    shapes = {"data": {"pixel": (3, 8, 8), "label": ()}}
    rng = np.random.default_rng(3)
    batch = {"data": {
        "pixel": jnp.asarray(rng.standard_normal((4, 3, 8, 8)),
                             jnp.float32),
        "label": jnp.asarray(rng.integers(0, 10, (4,)))}}

    fused = build_net(cfg, "kTrain", shapes)
    assert any(getattr(l, "fuse_from", "") for l in fused.layers.values())
    unfused = build_net(cfg, "kTrain", shapes)
    for l in unfused.layers.values():
        if hasattr(l, "fuse_from"):
            l.fuse_from = ""
    params = fused.init_params(jax.random.PRNGKey(0))

    def loss_of(net):
        # rng: the kRGBImage per-image mirror (train-time) draws it;
        # same key both nets → identical flips → comparable grads
        return jax.value_and_grad(
            lambda p: net.apply(p, batch, rng=jax.random.PRNGKey(1),
                                train=True)[0])(params)

    l1, g1 = loss_of(fused)
    l2, g2 = loss_of(unfused)
    assert np.allclose(float(l1), float(l2), rtol=1e-5)
    for k in g1:
        np.testing.assert_allclose(np.asarray(g1[k]), np.asarray(g2[k]),
                                   rtol=1e-4, atol=1e-5)


def test_debug_info_and_json():
    cfg = load_model_config(f"{MNIST}/conv.conf")
    net = build_net(cfg, "kTrain", MNIST_SHAPES, batchsize=2)
    params = net.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(6)
    _, _, outputs = net.apply(params, _mnist_batch(2, rng))
    info = net.debug_info(params, outputs)
    assert "conv1" in info and "param" in info
    j = net.to_json()
    assert '"nodes"' in j and '"links"' in j


def test_train_steps_scan_matches_per_step_calls():
    """trainer.train_steps (one lax.scan program) must reproduce n
    individual train_step calls exactly — same params, same metrics."""
    cfg = load_model_config(f"{MNIST}/conv.conf")
    cfg.display_frequency = 0
    trainer = Trainer(cfg, MNIST_SHAPES, donate=False)
    params, opt_state = trainer.init(seed=0)
    rng = np.random.default_rng(7)
    key = jax.random.PRNGKey(9)
    n = 4

    # reused fixed batch
    batch = _mnist_batch(8, rng)
    p_scan, o_scan, metrics = trainer.train_steps(
        params, opt_state, batch, 0, key, n)
    assert metrics["loss"].shape == (n,)
    p_ref, o_ref = params, opt_state
    for step in range(n):
        p_ref, o_ref, m = trainer.train_step(
            p_ref, o_ref, batch, step, jax.random.fold_in(key, step))
        np.testing.assert_allclose(float(metrics["loss"][step]),
                                   float(m["loss"]), rtol=1e-5)
    for k in p_ref:
        np.testing.assert_allclose(np.asarray(p_scan[k]),
                                   np.asarray(p_ref[k]), atol=1e-5)

    # stacked per-step batches (leading axis n) are scanned over
    batches = [_mnist_batch(8, rng) for _ in range(n)]
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *batches)
    p_scan2, _, metrics2 = trainer.train_steps(
        params, opt_state, stacked, 0, key, n, True)
    p_ref2, o_ref2 = params, opt_state
    for step in range(n):
        p_ref2, o_ref2, m = trainer.train_step(
            p_ref2, o_ref2, batches[step], step,
            jax.random.fold_in(key, step))
        np.testing.assert_allclose(float(metrics2["loss"][step]),
                                   float(m["loss"]), rtol=1e-5)


def test_run_scan_chunk_matches_per_step_run():
    """run(scan_chunk=N) must produce the same params, display logs, and
    test history as the per-step loop, with cadence at the same steps."""
    cfg = load_model_config(f"{MNIST}/conv.conf")
    cfg.train_steps = 11
    cfg.display_frequency = 3
    cfg.test_frequency = 5
    cfg.test_steps = 2
    rng = np.random.default_rng(11)
    train_batches = [_mnist_batch(8, rng) for _ in range(cfg.train_steps)]
    test_batches = [_mnist_batch(8, rng) for _ in range(cfg.test_steps)]

    def run_with(chunk):
        logs = []
        tr = Trainer(cfg, MNIST_SHAPES, log_fn=logs.append, donate=False)
        p, o = tr.init(seed=0)
        p, o, hist = tr.run(p, o, iter(train_batches),
                            test_iter_factory=lambda: iter(test_batches),
                            seed=0, scan_chunk=chunk)
        return p, hist, logs

    p1, hist1, logs1 = run_with(0)
    p4, hist4, logs4 = run_with(4)
    for k in p1:
        np.testing.assert_allclose(np.asarray(p4[k]), np.asarray(p1[k]),
                                   atol=2e-5)
    assert [h["step"] for h in hist1] == [h["step"] for h in hist4]
    for h1, h4 in zip(hist1, hist4):
        assert abs(h1["loss"] - h4["loss"]) < 1e-4
    # same display steps (log lines starting with "step-N:"); DebugInfo
    # lines are excluded — they print at chunk granularity by design
    # (labeled with the chunk's last step, whose params they reflect)
    steps1 = [l.split(":")[0] for l in logs1
              if l.startswith("step-") and " debug" not in l]
    steps4 = [l.split(":")[0] for l in logs4
              if l.startswith("step-") and " debug" not in l]
    assert steps1 == steps4


def test_preemption_signal_checkpoints_and_resumes(tmp_path):
    """SIGTERM mid-run -> snapshot at the current step + clean stop;
    resume() continues from there (the recovery story the reference
    lacks: a killed worker hung the whole job)."""
    import os
    import signal

    cfg = load_model_config(f"{MNIST}/conv.conf")
    cfg.train_steps = 50
    cfg.test_frequency = 0
    cfg.display_frequency = 0
    cfg.checkpoint_frequency = 1000   # cadence would never fire
    trainer = Trainer(cfg, MNIST_SHAPES, log_fn=lambda s: None,
                      donate=False)
    params, opt_state = trainer.init(seed=0)
    rng = np.random.default_rng(21)
    batches = [_mnist_batch(8, rng) for _ in range(50)]

    def self_sigterm(step, metrics):
        if step == 4:
            os.kill(os.getpid(), signal.SIGTERM)

    p, o, _ = trainer.run(params, opt_state, iter(batches),
                          hooks=[self_sigterm], workspace=str(tmp_path))
    p2, o2, start = trainer.resume(params, opt_state, str(tmp_path))
    assert start == 5                      # stopped after finishing step 4
    for k in p:
        np.testing.assert_allclose(np.asarray(p2[k]), np.asarray(p[k]))
    # handler restored: SIGTERM must not be swallowed anymore
    assert signal.getsignal(signal.SIGTERM) in (
        signal.SIG_DFL, signal.default_int_handler) or callable(
        signal.getsignal(signal.SIGTERM))


def test_checkpoint_layout_version_mismatch_refuses(tmp_path):
    """A checkpoint written under a different parameter layout version
    (or a pre-versioning one) must refuse to restore instead of loading
    permuted weights (ADVICE r1: the NCHW->NHWC vdim reorder)."""
    import os
    import pytest
    from singa_tpu.utils.checkpoint import (CheckpointManager,
                                            LayoutMismatchError)

    mgr = CheckpointManager(str(tmp_path))
    params = {"w": jnp.ones((2, 2))}
    opt = {"history": {"w": jnp.zeros((2, 2))}}
    mgr.save(3, params, opt)
    restored = mgr.restore(template={"params": params, "opt_state": opt})
    assert restored is not None and restored[2] == 3

    # simulate an old checkpoint: version marker absent
    os.remove(os.path.join(mgr.dir, "LAYOUT_VERSION"))
    with pytest.raises(LayoutMismatchError, match="layout version 1"):
        CheckpointManager(str(tmp_path)).restore(
            template={"params": params, "opt_state": opt})


def test_maybe_net_raises_on_broken_eval_phase():
    """A typo'd srclayer in the test phase must FAIL Trainer
    construction, not silently disable evaluation (round-1 review: the
    old bare `except Exception` in _maybe_net swallowed real config
    errors)."""
    from singa_tpu.core.layers import LayerError
    from singa_tpu.core.trainer import Trainer
    from singa_tpu.models.vision import lenet_mnist

    cfg = lenet_mnist(batchsize=4)
    # an extra kTest-only layer pointing at a layer that doesn't exist
    from singa_tpu.config.schema import model_config_from_dict
    d = {"name": "broken", "train_steps": 1, "test_steps": 5,
         "test_frequency": 1,
         "updater": {"type": "kSGD", "base_learning_rate": 0.01},
         "neuralnet": {"layer": [
             {"name": "data", "type": "kShardData",
              "data_param": {"batchsize": 4}},
             {"name": "mnist", "type": "kMnistImage", "srclayers": "data"},
             {"name": "label", "type": "kLabel", "srclayers": "data"},
             {"name": "ip", "type": "kInnerProduct", "srclayers": "mnist",
              "inner_product_param": {"num_output": 10},
              "param": [{"name": "weight", "init_method": "kUniform",
                         "low": -0.1, "high": 0.1},
                        {"name": "bias", "init_method": "kConstant"}]},
             {"name": "bad", "type": "kReLU", "srclayers": "nope",
              "exclude": ["kTrain", "kValidation"]},
             {"name": "loss", "type": "kSoftmaxLoss",
              "srclayers": ["ip", "label"]},
         ]}}
    with pytest.raises(LayerError, match="nope"):
        Trainer(model_config_from_dict(d),
                {"data": {"pixel": (28, 28), "label": ()}},
                log_fn=lambda s: None)
    # sanity: the clean config (with test cadence on) still builds
    cfg.test_steps = 10
    tr = Trainer(cfg, {"data": {"pixel": (28, 28), "label": ()}},
                 log_fn=lambda s: None)
    assert tr.test_step is not None


def test_maybe_net_none_when_phase_has_no_loss():
    """A phase whose filtered layers lack a loss layer is legitimately
    absent — Trainer builds no eval step and raises nothing."""
    from singa_tpu.core.trainer import Trainer
    from singa_tpu.models.vision import lenet_mnist

    cfg = lenet_mnist(batchsize=4)
    cfg.test_steps = 10
    cfg.validation_steps = 10
    for l in cfg.neuralnet.layer:
        if l.type == "kSoftmaxLoss":
            l.exclude = ["kTest", "kValidation"]
    tr = Trainer(cfg, {"data": {"pixel": (28, 28), "label": ()}},
                 log_fn=lambda s: None)
    assert tr.test_step is None and tr.val_step is None
