"""The plain reference against the program on the CPU at the tiny
preset: `NeuralNet.apply`'s loss and gradients, prefill + paged decode's
logits; and a bf16 copy of the program failing the same tolerance."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, weights  # noqa: E402
from benchmark.reference import dense_lm  # noqa: E402
from benchmark.runners import serve_cb, train  # noqa: E402

TOL = 1e-4       # float32 against float32; bf16 rounding is ~4e-3 a value


def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(ROOT, "benchmark", "reference",
                            "dense_lm.py")).read()
    assert "import singa_tpu" not in src and "from singa_tpu" not in src
    assert "from benchmark" not in src and "import benchmark" not in src


@pytest.fixture(scope="module")
def tiny():
    cell = harness.Cell("train-s4096-1chip", rehearsal=True)
    cfg = cell.config
    from singa_tpu.core.net import build_net
    from singa_tpu.data import discover_input_shapes
    model = serve_cb.model_config(cfg, 32, 2, "float32")
    net = build_net(model, "kTrain",
                    discover_input_shapes(model, force_synthetic=True))
    made = weights.tree(cfg, 7, jnp.float32)
    return cfg, net, made


def _program(made):
    return {serve_cb.program_name(k): v for k, v in made.items()}


def test_tree_and_leaf_make_the_same_values(tiny):
    cfg, _, made = tiny
    key = weights.seed_key(7)
    for n, shape, std in weights.leaf_table(cfg):
        one = weights.leaf(key, n, tuple(shape), std, jnp.float32)
        assert np.array_equal(np.asarray(one), np.asarray(made[n])), n
    other = weights.tree(cfg, 2 ** 31 + 7, jnp.float32)
    assert not np.array_equal(np.asarray(other["head"]),
                              np.asarray(made["head"]))


def _batch(cfg, seed=0, b=2, s=32):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg["vocab_size"], (b, s + 1)).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]


def test_loss_and_gradients_agree_with_neuralnet_apply(tiny):
    cfg, net, made = tiny
    x, y = _batch(cfg)
    batch = {"data": {"input": jnp.asarray(x), "target": jnp.asarray(y)}}

    def prog_loss(p):
        return net.apply(p, batch, train=True)[0]

    pl, pg = jax.value_and_grad(prog_loss)(_program(made))
    rl, rg = dense_lm._loss_and_grad(made, jnp.asarray(x), jnp.asarray(y),
                                     dense_lm._static(cfg), None)
    assert abs(float(pl) - float(rl)) < TOL
    got = {n: float(jnp.linalg.norm(pg[serve_cb.program_name(n)]))
           for n in made}
    ref = {n: float(jnp.linalg.norm(rg[n])) for n in made}
    assert train.worst_leaf(got, ref) < TOL


def test_a_bf16_program_fails_the_same_tolerance(tiny):
    cfg, net, made = tiny
    x, y = _batch(cfg)
    batch = {"data": {"input": jnp.asarray(x), "target": jnp.asarray(y)}}
    pg = jax.grad(lambda p: net.apply(
        p, batch, train=True, compute_dtype=jnp.bfloat16)[0])(_program(made))
    _, rg = dense_lm._loss_and_grad(made, jnp.asarray(x), jnp.asarray(y),
                                    dense_lm._static(cfg), None)
    got = {n: float(jnp.linalg.norm(pg[serve_cb.program_name(n)]))
           for n in made}
    ref = {n: float(jnp.linalg.norm(rg[n])) for n in made}
    assert train.worst_leaf(got, ref) > 3 * TOL


def test_the_fp8_control_reads_far_above_a_sound_run(tiny):
    cfg, _, made = tiny
    x, y = _batch(cfg)
    args = (made, jnp.asarray(x), jnp.asarray(y), dense_lm._static(cfg))
    _, rg = dense_lm._loss_and_grad(*args, None)
    _, cg = dense_lm._loss_and_grad(*args, "fp8")
    ref = {n: float(jnp.linalg.norm(rg[n])) for n in made}
    ctl = {n: float(jnp.linalg.norm(cg[n])) for n in made}
    assert train.worst_leaf(ctl, ref) > 30 * TOL


def test_prefill_and_paged_decode_agree_with_a_full_forward_pass(tiny):
    from singa_tpu.models.generate import (forward_cached, forward_paged,
                                           init_cache, scatter_prefill)
    from singa_tpu.serve.kvcache import init_pools
    cfg, net, made = tiny
    params = _program(made)
    plen, width, bl, new = 11, 16, 4, 5
    rng = np.random.default_rng(3)
    seq = rng.integers(0, cfg["vocab_size"], plen + new).astype(np.int32)
    hid = dense_lm.hidden_states(jnp.asarray(seq[None]), made.__getitem__,
                                 cfg)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(hid[0] @ made["head"])            # (plen+new, V)

    toks = np.zeros((1, width), np.int32)
    toks[0, :plen] = seq[:plen]
    logits, cache = forward_cached(net, params, jnp.asarray(toks),
                                   init_cache(net, 1, width, jnp.float32), 0)
    assert np.max(np.abs(np.asarray(logits[0, :plen]) - ref[:plen])) < TOL

    table = np.arange(1, (width + 8) // bl + 1, dtype=np.int32)[None]
    pools = scatter_prefill(init_pools(net, table.size + 1, bl, jnp.float32),
                            cache, jnp.asarray(table[0, :width // bl]))
    worst = 0.0
    for k in range(new):
        pos = plen + k
        lg, pools = forward_paged(net, params, jnp.asarray(seq[None, pos:pos + 1]),
                                  pools, jnp.asarray(table),
                                  jnp.asarray([pos], jnp.int32))
        worst = max(worst, float(np.max(np.abs(np.asarray(lg[0, 0]) - ref[pos]))))
    assert worst < TOL

    half = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    lg16, _ = forward_cached(net, half, jnp.asarray(toks),
                             init_cache(net, 1, width, jnp.bfloat16), 0)
    assert np.max(np.abs(np.asarray(lg16[0, :plen], np.float32)
                         - ref[:plen])) > 10 * TOL


def test_served_gaps_are_zero_for_the_references_own_choice(tiny):
    cfg, _, made = tiny
    rng = np.random.default_rng(5)
    toks = rng.integers(0, cfg["vocab_size"], (2, 12)).astype(np.int32)
    hid = dense_lm.hidden_states(jnp.asarray(toks), made.__getitem__, cfg)
    first = np.stack([np.asarray(dense_lm._gap_rows(
        hid[i], made["head"], jnp.zeros((12,), jnp.int32), None)[1])
        for i in range(2)])
    gap, ctl = dense_lm.served_gaps(toks, first, made.__getitem__, cfg,
                                    control="fp8")
    assert np.max(gap) == 0.0
    # fp8 puts other tokens first, far outside the float32 limit
    assert np.max(ctl) > 10 * serve_cb.DEFAULT_LIMITS["served_gap"]
    other = (first + 1) % cfg["vocab_size"]
    assert np.min(dense_lm.served_gaps(toks, other, made.__getitem__,
                                       cfg)) > 0.0
