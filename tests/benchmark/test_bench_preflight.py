"""Pre-flight compiles at real widths for a described v5e (no chip):
the two continuous-batching programs of `mistral7b-serve-l16`, the scan
step of `mistral7b-train-l2`, and the plain reference's training step,
each on one described device, with XLA's memory analysis under the
chip's 16 GB.  They say "it compiles and fits", never "how fast".

The topology is described inside a module fixture, after a test of
this file has started, and every test of it lives in this one file
(only one process at a time may load the TPU's library).
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

HBM = 16e9
OUT = os.environ.get("BENCH_PREFLIGHT_OUT")     # write the bytes found


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def kernels_for_tpu(monkeypatch):
    """The Pallas kernels ask `_on_tpu()` whether to interpret; compile
    them for the described chip."""
    import jax
    from singa_tpu.ops import attention
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _on(tree, sharding):
    import jax
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _bytes(compiled):
    m = compiled.memory_analysis()
    return {"arguments": int(m.argument_size_in_bytes),
            "outputs": int(m.output_size_in_bytes),
            "aliased": int(m.alias_size_in_bytes),
            "temporaries": int(m.temp_size_in_bytes),
            "total": int(m.argument_size_in_bytes + m.output_size_in_bytes
                         - m.alias_size_in_bytes + m.temp_size_in_bytes)}


def _note(name, found):
    print(name, json.dumps(found))
    if OUT:
        with open(OUT, "a") as f:
            f.write(json.dumps({name: found}) + "\n")


def _cell(name):
    from benchmark import harness
    return harness.Cell(name)


@pytest.mark.parametrize("which", ["prefill", "decode"])
def test_serve_programs_compile_and_fit(which, one_chip, kernels_for_tpu):
    import jax
    import jax.numpy as jnp
    from benchmark import weights
    from benchmark.runners import serve_cb
    from singa_tpu.core.net import build_net
    from singa_tpu.data import discover_input_shapes
    from singa_tpu.serve.engine import InferenceEngine, ServeSpec
    from singa_tpu.serve.kvcache import init_pools

    cell = _cell("serve-chat-r80")
    cfg, sv = cell.config, cell.config["serve"]
    model = serve_cb.model_config(cfg, sv["cb_prompt_cap"], 1, "float32")
    net = build_net(model, "kTrain",
                    discover_input_shapes(model, force_synthetic=True))
    spec = ServeSpec(buckets=((1, sv["cb_prompt_cap"]),),
                     max_new_tokens=sv["max_new_tokens"], cb="on",
                     cb_slots=sv["cb_slots"], cb_block_len=sv["cb_block_len"],
                     cb_prompt_cap=sv["cb_prompt_cap"])
    engine = InferenceEngine(net, spec, params={}, log_fn=lambda *a: None)
    params = _on({serve_cb.program_name(n): jax.ShapeDtypeStruct(
        s, jnp.bfloat16) for n, s, _ in weights.leaf_table(cfg)}, one_chip)
    pools = _on(jax.eval_shape(lambda: init_pools(
        net, spec.cb_pool_blocks, spec.cb_block_len, jnp.bfloat16)), one_chip)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,   # noqa: E731
                                          sharding=one_chip)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    if which == "prefill":
        args = (params, pools, i32(1, spec.cb_prefill_len), i32(),
                i32(spec.cb_prefill_len // spec.cb_block_len), rng)
        fn = engine._build_cb_prefill()
    else:
        s = spec.cb_slots
        args = (params, pools, i32(s), i32(s),
                i32(s, spec.cb_blocks_per_slot), rng)
        fn = engine._build_cb_decode()
    found = _bytes(jax.jit(fn, donate_argnums=(1,)).lower(*args).compile())
    _note(f"cb_{which}", found)
    assert found["total"] < HBM


def test_train_scan_compiles_and_fits(one_chip, kernels_for_tpu, monkeypatch):
    import jax
    import jax.numpy as jnp
    from benchmark import weights
    from benchmark.runners import serve_cb
    from singa_tpu.core.trainer import Trainer
    from singa_tpu.data import discover_input_shapes
    from singa_tpu.ops.attention import KERNEL_NAMES

    cell = _cell("train-s4096-1chip")
    cfg, tc = cell.config, cell.config["train"]
    monkeypatch.setattr("singa_tpu.ops.attention._FLASH_BLOCK_OVERRIDE",
                        tuple(tc["flash_blocks"]))
    seq, b, k = cell.traffic["seq_len"], tc["batch"], tc["scan_chunk"]
    model = serve_cb.model_config(cfg, seq, b, tc["precision"])
    model.updater.type = "kAdam"
    trainer = Trainer(model, discover_input_shapes(model,
                                                   force_synthetic=True),
                      log_fn=lambda *a: None)
    params = _on({serve_cb.program_name(n): jax.ShapeDtypeStruct(
        s, jnp.float32) for n, s, _ in weights.leaf_table(cfg)}, one_chip)
    opt = {"history": params, "update": params}
    tok = jax.ShapeDtypeStruct((k, b, seq), jnp.int32, sharding=one_chip)
    step = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = trainer.train_steps.lower(
        params, opt, {"data": {"input": tok, "target": tok}}, step, rng, k,
        True, None).compile()
    found = _bytes(compiled)
    text = compiled.as_text()
    found["mosaic_calls"] = {n: text.count(n) for n in KERNEL_NAMES}
    _note(f"train_scan_b{b}", found)
    assert found["total"] < HBM
    assert all(found["mosaic_calls"].values()), found["mosaic_calls"]


def test_reference_train_step_fits(one_chip, kernels_for_tpu):
    """The plain reference runs alone on the chip before the program's
    state exists; its gradient program and its state have to fit."""
    import jax
    import jax.numpy as jnp
    from benchmark import weights
    from benchmark.reference import dense_lm

    cell = _cell("train-s4096-1chip")
    cfg, tc = cell.config, cell.config["train"]
    seq, b = cell.traffic["seq_len"], tc["batch"]
    params = _on({n: jax.ShapeDtypeStruct(s, jnp.float32)
                  for n, s, _ in weights.leaf_table(cfg)}, one_chip)
    tok = jax.ShapeDtypeStruct((b, seq), jnp.int32, sharding=one_chip)
    compiled = dense_lm._loss_and_grad.lower(
        params, tok, tok, dense_lm._static(cfg), None).compile()
    found = _bytes(compiled)
    state = 2 * found["arguments"]           # Adam's two moments beside it
    found["with_adam_moments"] = found["total"] + state
    _note(f"reference_grad_b{b}", found)
    assert found["with_adam_moments"] < HBM
