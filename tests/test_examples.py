"""The shipped example configs must load, build, and train end to end —
the examples ARE the integration suite, as in the reference (SURVEY §4).
"""

import glob
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from singa_tpu.config import load_cluster_config, load_model_config
from singa_tpu.core.trainer import Trainer
from singa_tpu.data import resolve_data_source
from singa_tpu.parallel import mesh_from_cluster

LM_CONF = "examples/transformer/lm.conf"
CLUSTER_CONF = "examples/transformer/cluster.conf"
REF = "/root/reference/examples/mnist"


def test_lm_conf_loads_and_matches_builder_idiom():
    cfg = load_model_config(LM_CONF)
    types = {l.type for l in cfg.neuralnet.layer}
    assert {"kSequenceData", "kEmbed", "kAttention", "kMoE",
            "kFeedForward", "kLMHead", "kRMSNorm"} <= types
    attn = next(l for l in cfg.neuralnet.layer if l.type == "kAttention")
    assert attn.attention_param.seq_parallel == "ring"
    assert cfg.precision == "bfloat16"
    # tied embeddings via share_param, as the builder emits them
    head = next(l for l in cfg.neuralnet.layer if l.type == "kLMHead")
    assert head.share_param == ["embed/embedding"]


def test_cluster_conf_mesh_axes():
    cluster = load_cluster_config(CLUSTER_CONF)
    mesh = mesh_from_cluster(cluster)
    assert dict(mesh.shape) == {"data": 2, "model": 2, "pipe": 1,
                                "seq": 2, "expert": 1}


def test_lm_conf_trains_a_step():
    cfg = load_model_config(LM_CONF)
    # shrink for test speed; keep the layer graph identical
    sd = next(l for l in cfg.neuralnet.layer if l.type == "kSequenceData")
    sd.seqdata_param.batchsize, sd.seqdata_param.seq_len = 4, 64
    cfg.precision = "float32"
    s = sd.seqdata_param.seq_len
    trainer = Trainer(cfg, {"data": {"input": (s,), "target": (s,)}},
                      donate=False, log_fn=lambda _: None)
    params, opt = trainer.init(0)
    train_iter, _ = resolve_data_source(cfg, 4)
    batch = next(train_iter)
    assert batch["data"]["input"].shape == (4, 64)
    p, o, m = trainer.train_step(params, opt, batch, 0, jax.random.PRNGKey(0))
    assert np.isfinite(float(m["loss"]))


def test_cli_runs_example_end_to_end():
    out = subprocess.run(
        [sys.executable, "-m", "singa_tpu.main",
         "-model_conf", LM_CONF, "-cluster_conf", CLUSTER_CONF,
         "--synthetic", "--steps", "2"],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": ".", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu", "HOME": "/tmp",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert "mesh: " in out.stdout and "training done" in out.stdout


# every shipped model conf — cluster.conf is a ClusterProto, not a model
MODEL_CONFS = sorted(
    c for c in glob.glob("examples/**/*.conf", recursive=True)
    if not c.endswith("cluster.conf"))


def test_conf_glob_finds_the_expected_families():
    fams = {c.split("/")[1] for c in MODEL_CONFS}
    assert {"mnist", "cifar10", "imagenet", "transformer"} <= fams


@pytest.mark.parametrize("conf", MODEL_CONFS)
def test_every_shipped_conf_trains_through_cli(conf):
    """conf + binary is the whole interface (main.cc:34-58): every conf
    we ship must run end to end through the CLI, with input geometry
    discovered from the net (data/discovery.py), not hardcoded.
    --batchsize shrinks compute for CPU CI; the layer graph and the
    discovered shapes are identical to a full run."""
    out = subprocess.run(
        [sys.executable, "-m", "singa_tpu.main", "-model_conf", conf,
         "--synthetic", "--steps", "2", "--batchsize", "8"],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": ".", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu", "HOME": "/tmp"})
    assert out.returncode == 0, (conf, out.stderr[-2000:])
    assert "training done" in out.stdout, (conf, out.stdout[-500:])


def test_discovered_shapes_follow_parser_geometry():
    from singa_tpu.data import discover_input_shapes

    cases = {"examples/cifar10/quick.conf": (3, 32, 32),
             "examples/imagenet/alexnet.conf": (3, 256, 256),
             "examples/mnist/conv.conf": (28, 28)}
    for conf, want in cases.items():
        shapes = discover_input_shapes(load_model_config(conf),
                                       force_synthetic=True)
        got = next(iter(shapes.values()))["pixel"]
        assert got == want, (conf, got)


def test_discovery_peeks_a_real_shard(tmp_path):
    """A live source wins over parser inference: the record IS the
    schema (layer.cc:388-392 reads a sample record in Setup)."""
    from singa_tpu.data import (Record, Shard, SingleLabelImageRecord,
                                discover_input_shapes)

    folder = str(tmp_path)
    with Shard(folder, Shard.KCREATE) as sh:
        rec = Record(image=SingleLabelImageRecord(
            shape=[3, 40, 40], label=1, pixel=b"\x00" * (3 * 40 * 40)))
        sh.insert(b"k0", rec.encode())
    cfg = load_model_config("examples/cifar10/quick.conf")
    data = next(l for l in cfg.neuralnet.layer if l.type == "kShardData")
    data.data_param.path = folder
    shapes = discover_input_shapes(cfg)
    assert shapes[data.name]["pixel"] == (3, 40, 40)


def test_shipped_example_confs_match_zoo():
    """examples/{mnist,cifar10,imagenet}/*.conf are generated from the
    model zoo (tools/export_examples); they must load back equal to the
    zoo configs."""
    from singa_tpu.models import vision
    from singa_tpu.tools.export_examples import EXAMPLES

    for rel, build in EXAMPLES.items():
        assert load_model_config(f"examples/{rel}") == build(), rel
    assert vision.mlp_mnist() == load_model_config(
        "examples/mnist/mlp.conf")


@pytest.mark.skipif(not os.path.exists(f"{REF}/conv.conf"),
                    reason="reference tree not mounted at /root/reference")
def test_shipped_mnist_confs_match_reference():
    """The shipped mnist pair must describe the same nets as the
    reference's hand-written mlp.conf/conv.conf."""
    ours = load_model_config("examples/mnist/conv.conf")
    ref = load_model_config(f"{REF}/conv.conf")
    # data source differs by design (kShardData here vs the reference's
    # phase-excluded kLMDBData pair); the neuron-layer graph must match.
    skip = {"kShardData", "kLMDBData"}
    assert ([(l.name, l.type) for l in ours.neuralnet.layer
             if l.type not in skip]
            == [(l.name, l.type) for l in ref.neuralnet.layer
                if l.type not in skip])
    assert ours.updater.base_learning_rate == ref.updater.base_learning_rate

    mlp_ours = load_model_config("examples/mnist/mlp.conf")
    mlp_ref = load_model_config(f"{REF}/mlp.conf")
    assert ([(l.type,
              l.inner_product_param.num_output if l.inner_product_param
              else None) for l in mlp_ours.neuralnet.layer
             if l.type not in skip]
            == [(l.type,
                 l.inner_product_param.num_output if l.inner_product_param
                 else None) for l in mlp_ref.neuralnet.layer
                if l.type not in skip])


def test_viz_dot_and_log_plot(tmp_path):
    """tools/viz: net JSON -> dot (script/graph.py role) and training-log
    -> curves (script/draw.py role)."""
    from singa_tpu.config import load_model_config
    from singa_tpu.core import build_net
    from singa_tpu.tools.viz import (json_to_dot, parse_training_log,
                                     plot_training_log)

    cfg = load_model_config("examples/mnist/conv.conf")
    net = build_net(cfg, "kTrain", {"data": {"pixel": (28, 28),
                                             "label": ()}}, batchsize=2)
    dot = json_to_dot(net.to_json())
    assert dot.startswith("digraph")
    for name in net.topo:
        assert f'"{name}"' in dot
    assert '"conv1" -> "pool1";' in dot

    log = ("step-0: loss : 2.301234, precision : 0.101562\n"
           "junk line\n"
           "step-30 test: loss : 2.100000, precision : 0.301000\n"
           "step-30: loss : 1.900111, precision : 0.401222\n")
    series = parse_training_log(log)
    assert series["train"]["step"] == [0, 30]
    assert series["test"]["precision"] == [0.301]
    out = tmp_path / "curves.png"
    metrics = plot_training_log(log, str(out))
    assert "loss" in metrics and out.exists() and out.stat().st_size > 0
