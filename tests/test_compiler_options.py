"""The scoped-VMEM compiler-option knob (VERDICT r2 item 9).

Policy: ModelProto `scoped_vmem` (auto|on|off).  `auto` applies the
raised budget only to conv stacks whose widest conv has >= 96 filters
(smaller nets gain nothing from it); the field beats its verdict either
way.
"""

import pytest

import singa_tpu.ops.attention as attention
from singa_tpu.config.schema import ConfigError, model_config_from_dict
from singa_tpu.core.trainer import Trainer
from singa_tpu.models.vision import alexnet_cifar10_full, lenet_mnist

ALEX_SHAPES = {"data": {"pixel": (3, 32, 32), "label": ()}}
LENET_SHAPES = {"data": {"pixel": (28, 28), "label": ()}}


def _opts(cfg, shapes, monkeypatch):
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    t = Trainer(cfg, shapes, log_fn=lambda s: None)
    return t._compiler_options()


def test_auto_picks_option_for_alexnet(monkeypatch):
    opts = _opts(alexnet_cifar10_full(batchsize=8), ALEX_SHAPES,
                 monkeypatch)
    assert opts == Trainer.TPU_CONV_COMPILER_OPTIONS


def test_auto_skips_lenet(monkeypatch):
    assert _opts(lenet_mnist(batchsize=8), LENET_SHAPES,
                 monkeypatch) is None


@pytest.mark.parametrize("mode", ["on", "off"])
@pytest.mark.parametrize("net", ["alexnet", "lenet"])
def test_field_beats_autos_verdict(net, mode, monkeypatch):
    """`auto` raises the budget for AlexNet and not for LeNet; the
    field forces it or drops it for either."""
    cfg, shapes = {
        "alexnet": (alexnet_cifar10_full(batchsize=8), ALEX_SHAPES),
        "lenet": (lenet_mnist(batchsize=8), LENET_SHAPES)}[net]
    cfg.scoped_vmem = mode
    want = Trainer.TPU_CONV_COMPILER_OPTIONS if mode == "on" else None
    assert _opts(cfg, shapes, monkeypatch) == want


def test_bad_field_fails_loud(monkeypatch):
    with pytest.raises(ConfigError, match="scoped_vmem"):
        model_config_from_dict({"name": "x", "scoped_vmem": "maybe"})
    # a value assigned behind the schema's back fails where it is read
    cfg = lenet_mnist(batchsize=8)
    cfg.scoped_vmem = "sometimes"
    with pytest.raises(ValueError, match="scoped_vmem must be"):
        _opts(cfg, LENET_SHAPES, monkeypatch)


def test_textproto_field_parses():
    cfg = model_config_from_dict({"name": "x", "scoped_vmem": "on"})
    assert cfg.scoped_vmem == "on"


def test_attention_family_gets_modest_budget(monkeypatch):
    from singa_tpu.models.transformer import transformer_lm
    cfg = transformer_lm(vocab_size=64, num_layers=1, embed_dim=32,
                         num_heads=2, head_dim=16, seq_len=32,
                         batchsize=4)
    shapes = {"data": {"input": (32,), "target": (32,)}}
    assert _opts(cfg, shapes,
                 monkeypatch) == Trainer.TPU_ATTN_COMPILER_OPTIONS
    # "on" must force the FAMILY budget, never the conv-sized one
    # (which starves the flash kernels)
    cfg2 = transformer_lm(vocab_size=64, num_layers=1, embed_dim=32,
                          num_heads=2, head_dim=16, seq_len=32,
                          batchsize=4)
    cfg2.scoped_vmem = "on"
    assert _opts(cfg2, shapes,
                 monkeypatch) == Trainer.TPU_ATTN_COMPILER_OPTIONS


def test_on_tpu_is_the_default_backend(monkeypatch):
    import jax
    assert attention._on_tpu() is False            # pytest runs on cpu
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attention._on_tpu() is True


def test_on_tpu_propagates_a_backend_error(monkeypatch):
    """A chip that cannot be reached must fail the run, not select the
    interpreter (and drop the compiler options) behind its back."""
    import jax

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        attention._on_tpu()
