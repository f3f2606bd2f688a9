"""The ZAYA1 layers (kCCA, kZayaMoE, kScaledResidual, the tied head)
against the plain reference (`benchmark/reference/zaya1.py`) on seeded
random weights at the configuration's tiny size, float32, on the CPU:
each layer alone and the whole model, a prompt cut at every row
(prefill, then decode through `apply_cached`: the value shift and both
convolutions' tails cross the cut), the serving state (paged K and V
rows beside a tail per slot, in one layer's entry), the whole net
through the continuous-batching scheduler, the router's state handed
from layer to layer, and the share of the experts against the uncut
layer."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness, zaya_weights  # noqa: E402
from benchmark.reference import zaya1  # noqa: E402
from benchmark.runners import serve_zaya  # noqa: E402
from singa_tpu.core.net import build_net  # noqa: E402
from singa_tpu.data import discover_input_shapes  # noqa: E402
from singa_tpu.models.generate import (forward_cached, forward_paged,  # noqa: E402
                                       generate, init_cache, scatter_prefill)
from singa_tpu.ops import cca as cca_ops  # noqa: E402
from singa_tpu.ops import moe as moe_ops  # noqa: E402
from singa_tpu.serve.engine import InferenceEngine, ServeSpec  # noqa: E402
from singa_tpu.serve.kvcache import (PagedKVCache, init_pools,  # noqa: E402
                                     pool_bytes, state_bytes)
from singa_tpu.serve.scheduler import ContinuousScheduler  # noqa: E402

CFG = harness._tiny(harness.read_json(
    ROOT, "benchmark", "configs", "zaya1-8b-serve-l16.json"))
CAP, BL = 16, 4
TAILS = ("conv", "mix", "vprev")


@pytest.fixture(scope="module")
def lm():
    model = serve_zaya.model_config(CFG, CAP)
    net = build_net(model, "kTrain",
                    discover_input_shapes(model, force_synthetic=True))
    made = zaya_weights.tree(CFG, 11, jnp.float32)
    params = {zaya_weights.program_name(k): v for k, v in made.items()}
    return net, params, made


@pytest.fixture(autouse=True)
def exact():
    with jax.default_matmul_precision("highest"):
        yield


def _ref_logits(made, toks):
    return np.asarray(zaya1.logits(np.asarray(toks)[None],
                                   lambda n: made[n], CFG)[0])


def _layer_params(made, i, kind):
    return {n: made[f"L{i}.{kind}.{n}"].astype(jnp.float32)
            for n in (zaya1.CCA_LEAVES if kind == "cca" else
                      zaya1.MOE_LEAVES + (("gamma",) if i else ()))}


def _x(seed, *shape):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


# -- the ops -----------------------------------------------------------------

@pytest.mark.parametrize("real", [0, 1, 3, 15, 16])
def test_padded_rows_leave_a_tail_alone(real):
    """Right padding: the tail handed back is the rows up to the last
    real one, whatever the pads hold."""
    rng = np.random.default_rng(real)
    x = rng.standard_normal((1, 16, 6)).astype(np.float32)
    tail0 = rng.standard_normal((1, 2, 6)).astype(np.float32)
    valid = (np.arange(16) < real)[None]
    full, tail = cca_ops.window(x, tail0, valid)
    want_full, want = cca_ops.window(x[:, :real], tail0)
    np.testing.assert_array_equal(full[:, :real + 2], want_full)
    np.testing.assert_array_equal(tail, want)


def test_left_padding_reads_as_the_zeros_before_a_sequence():
    x = np.random.default_rng(5).standard_normal((1, 9, 6)).astype(
        np.float32)
    zeros = np.zeros((1, 1, 6), np.float32)
    full, tail = cca_ops.window(x, zeros, (np.arange(9) >= 4)[None])
    assert not np.any(np.asarray(full[:, :5]))
    np.testing.assert_array_equal(full[:, 5:], x[:, 4:])
    np.testing.assert_array_equal(tail, x[:, -1:])


def test_partial_rope_turns_the_first_dims_only_and_by_position():
    x = _x(0, 2, 5, 3, 16)
    got = cca_ops.partial_rope(x, jnp.arange(5), 8, 5e6)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    np.testing.assert_array_equal(got[:, 0], x[:, 0])       # position 0
    np.testing.assert_allclose(got, zaya1.rope(x, 8, 5e6), rtol=1e-6,
                               atol=1e-6)
    # a slot's own position: (B, T) positions, one token a row
    one = cca_ops.partial_rope(x[:, 3:4], jnp.asarray([[3], [3]]), 8, 5e6)
    np.testing.assert_allclose(one, got[:, 3:4], rtol=1e-6, atol=1e-6)


def test_the_mlp_router_chooses_by_probability_plus_bias_and_weighs_without():
    rng = np.random.default_rng(1)
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731,E501
    state, norm = f32(9, 8), jnp.ones((8,))
    layers = [(f32(8, 8), f32(8)), (f32(8, 8), f32(8)), (f32(8, 5), None)]
    idx0, w0 = moe_ops.route_mlp_softmax(state, norm, 1e-5, layers,
                                         jnp.zeros((5,)), 1)
    bias = jnp.zeros((5,)).at[2].set(10.0)
    idx, w = moe_ops.route_mlp_softmax(state, norm, 1e-5, layers, bias, 1)
    assert np.all(np.asarray(idx) == 2) and not np.all(np.asarray(idx0) == 2)
    assert np.all(np.asarray(w) <= np.asarray(w0) + 1e-7) and np.all(
        np.asarray(w) < 1.0)
    idx2, w2 = moe_ops.route_mlp_softmax(state, norm, 1e-5, layers,
                                         jnp.zeros((5,)), 5)
    np.testing.assert_allclose(np.sum(np.asarray(w2), -1), 1.0, rtol=1e-5)


def test_routing_counts_the_busiest_expert_when_asked():
    idx = jnp.asarray([[0], [2], [2], [3], [2], [9]], jnp.int32)
    x, w = jnp.ones((6, 8)), jnp.ones((6, 1))
    gate, down = jnp.zeros((4, 8, 6)), jnp.zeros((4, 6, 8))
    valid = jnp.asarray([True] * 5 + [False])
    _, counts = moe_ops.held_experts_ffn(x, idx, w, gate, gate, down, 0,
                                         valid, max_load=True)
    assert counts.tolist() == [5, 3, 3]
    _, counts = moe_ops.held_experts_ffn(x, idx, w, gate, gate, down, 0,
                                         valid)
    assert counts.tolist() == [5, 3]


# -- layer by layer -----------------------------------------------------------

@pytest.mark.parametrize("i", range(CFG["num_hidden_layers"]))
def test_cca_layer_equals_the_reference(lm, i):
    net, params, made = lm
    x = _x(i, 2, 12, CFG["hidden_size"])
    got = net.layers[f"cca{i}"].apply(net._resolve_params(params), [x], None)
    want = zaya1.cca(x, _layer_params(made, i, "cca"), CFG)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_cca_over_whole_lane_tiles_equals_the_reference(lm):
    """128 rows at position 0: `attend_cache` takes the flash forward
    kernel for kCCA as for kAttention (no layer kind tests anything of
    its own); at 12 rows, above, the dense scores."""
    net, params, made = lm
    layer, full = net.layers["cca0"], net._resolve_params(params)
    x = _x(3, 1, 128, CFG["hidden_size"])
    text = str(jax.make_jaxpr(lambda x: layer.apply(full, [x], None))(x))
    assert "singa_flash_fwd" in text
    want = zaya1.cca(x, _layer_params(made, 0, "cca"), CFG)
    np.testing.assert_allclose(layer.apply(full, [x], None), want,
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("i", range(CFG["num_hidden_layers"]))
def test_expert_layer_equals_the_reference(lm, i):
    """Its two outputs by name: the experts' and the router's state."""
    net, params, made = lm
    x = _x(i, 2, 12, CFG["hidden_size"])
    state = _x(i + 7, 2, 12, CFG["router_hidden_size"]) if i else None
    srcs = [x] + ([{"router": state}] if i else [])
    got = net.layers[f"zaya_moe{i}"].apply(net._resolve_params(params), srcs,
                                           None)
    want, want_state = zaya1.moe(x, state, _layer_params(made, i, "zaya_moe"),
                                 CFG)
    assert set(got) == {"out", "router"}
    np.testing.assert_allclose(got["out"], want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got["router"], want_state, rtol=1e-5,
                               atol=1e-5)
    assert got["router"].dtype == jnp.float32


def test_scaled_residual_is_the_four_vectors(lm):
    net, params, made = lm
    x, y = _x(1, 2, 5, CFG["hidden_size"]), _x(2, 2, 5, CFG["hidden_size"])
    w = {n: made[f"L1.res_b.{n}"] for n in "abcd"}
    for src in (y, {"out": y, "router": None}):
        got = net.layers["res1b"].apply(net._resolve_params(params),
                                        [x, src], None)
        np.testing.assert_allclose(got, zaya1.residual(x, y, w), rtol=1e-6,
                                   atol=1e-6)
    assert not np.allclose(got, x + y, atol=1e-2)


def test_whole_model_equals_the_reference_and_the_head_is_the_embedding(lm):
    net, params, made = lm
    assert "loss/w" not in params and net.param_aliases  # tied: one matrix
    toks = np.random.default_rng(0).integers(0, CFG["vocab_size"], 14)
    got, _ = forward_cached(net, params, jnp.asarray(toks)[None],
                            init_cache(net, 1, 14, jnp.float32), 0)
    np.testing.assert_allclose(got[0], _ref_logits(made, toks), rtol=2e-4,
                               atol=2e-4)


def test_the_router_state_reaches_the_next_layer(lm):
    """Fails if gamma's term is dropped: with gamma zeroed in layer 1 the
    logits move, and layer 1's own state is its projection alone."""
    net, params, made = lm
    toks = jnp.asarray(np.random.default_rng(3).integers(
        0, CFG["vocab_size"], 10))[None]
    cache = init_cache(net, 1, 10, jnp.float32)
    base, _ = forward_cached(net, params, toks, cache, 0)
    cut = dict(params)
    cut["zaya_moe1/gamma"] = jnp.zeros_like(params["zaya_moe1/gamma"])
    moved, _ = forward_cached(net, cut, toks, cache, 0)
    assert float(jnp.max(jnp.abs(moved - base))) > 1e-3
    assert net.layers["zaya_moe1"].cfg.srclayers == ["ln1b", "zaya_moe0"]
    assert net.layers["zaya_moe0"].cfg.srclayers == ["ln0b"]
    # the edge is in the DAG: layer 1 comes after layer 0 in the order
    assert net.topo.index("zaya_moe0") < net.topo.index("zaya_moe1")
    full = net._resolve_params(params)
    x = _x(4, 1, 6, CFG["hidden_size"])
    state = _x(5, 1, 6, CFG["router_hidden_size"])
    layer = net.layers["zaya_moe1"]
    with_state = layer.apply(full, [x, {"router": state}], None)["router"]
    without = layer.apply(full, [x, {"router": 0 * state}], None)["router"]
    np.testing.assert_allclose(
        with_state - without, made["L1.zaya_moe.gamma"] * state, rtol=1e-5,
        atol=1e-5)


# -- a prompt cut at every row ------------------------------------------------

@pytest.mark.parametrize("cut", range(1, 12))
def test_prefill_then_decode_through_apply_cached_at_every_cut(lm, cut):
    """Rows 0 .. cut-1 as one chunk, then a token at a time: the value's
    shifted half and both tails cross the cut."""
    net, params, made = lm
    toks = np.random.default_rng(12).integers(0, CFG["vocab_size"], 12)
    cache = init_cache(net, 1, 12, jnp.float32)
    out, cache = forward_cached(net, params, jnp.asarray(toks[:cut])[None],
                                cache, 0)
    rows = [np.asarray(out[0])]
    for pos in range(cut, 12):
        out, cache = forward_cached(
            net, params, jnp.asarray(toks[pos:pos + 1])[None], cache, pos)
        rows.append(np.asarray(out[0]))
    np.testing.assert_allclose(np.concatenate(rows), _ref_logits(made, toks),
                               rtol=2e-4, atol=2e-4)


def test_static_path_left_padding_matches_the_unpadded_prompt(lm):
    """The bucket path LEFT-pads: pads enter both convolutions and the
    value shift as the zeros before a sequence's start (the biases do
    not leak in), and are routed nowhere."""
    net, params, made = lm
    seq = np.random.default_rng(2).integers(
        0, CFG["vocab_size"], 6).astype(np.int32)
    toks = np.zeros((1, 10), np.int32)
    toks[0, 4:] = seq
    kmask = jnp.asarray(np.arange(10) >= 4)[None]
    lg, _ = forward_cached(net, params, jnp.asarray(toks),
                           init_cache(net, 1, 10, jnp.float32), 0,
                           kmask=kmask)
    np.testing.assert_allclose(lg[0, 4:], _ref_logits(made, seq),
                               rtol=2e-4, atol=2e-4)


# -- the serving state: rows per token AND a tail per slot --------------------

def test_one_entry_holds_paged_rows_and_slot_tails_and_both_are_counted(lm):
    net, _, _ = lm
    h, hk, d = (CFG["num_attention_heads"], CFG["num_key_value_heads"],
                CFG["head_dim"])
    pools = init_pools(net, 7, BL, jnp.float32, 3)
    entry = pools["cca0"]
    assert entry["kv"].shape == (7, 2 * hk, BL, d)
    assert entry["conv"].shape == entry["mix"].shape == (3, 1, (h + hk) * d)
    assert entry["vprev"].shape == (3, 1, d)
    assert pools["zaya_moe0"]["routed"].shape == (3,)
    layers = CFG["num_hidden_layers"]
    per = state_bytes(net, BL, jnp.float32)
    assert per["block"] == layers * 2 * hk * BL * d * 4
    assert per["block_copy"] == 2 * hk * BL * d * 4     # one layer's block
    assert per["window_block_copy"] == 0
    assert per["slot"] == layers * (2 * (h + hk) * d + d) * 4
    assert pool_bytes(net, 7, BL, jnp.float32, 3) == (
        7 * per["block"] + 3 * per["slot"] + layers * 3 * 4)
    kv = PagedKVCache(net, 3, 4, 7, BL)
    assert kv.per_slot_state
    kv.alloc(2, 3)
    assert kv.prefill_target(2, 4).tolist()[-1] == 2     # the slot behind


def _prefill_then_decode(net, params, seq, plen, slot, nslots=3):
    """Logits at positions plen-1 .. len(seq)-1: the right-padded prefill
    scattered into slot `slot`, then one paged step a token.  Returns
    (logits, the pools before the first step, the pools after the
    last)."""
    nb = -(-len(seq) // BL)
    table = np.zeros((nslots, max(nb, CAP // BL)), np.int32)
    table[slot, :nb] = 1 + np.arange(nb)
    toks = np.zeros((1, CAP), np.int32)
    toks[0, :plen] = seq[:plen]
    toks[0, plen:] = 199                 # pads that are not zeros
    lg, cache = forward_cached(net, params, jnp.asarray(toks),
                               init_cache(net, 1, CAP, jnp.float32), 0,
                               plen=jnp.int32(plen))
    pools = init_pools(net, nb + 1, BL, jnp.float32, nslots)
    # every slot's last tenant left something behind
    pools = {n: {k: (a + 7.0 if k in TAILS else a) for k, a in e.items()}
             for n, e in pools.items()}
    pools = scatter_prefill(pools, cache, jnp.asarray(table[slot, :CAP // BL]),
                            jnp.int32(slot), net)
    first = pools
    out = [np.asarray(lg[0, plen - 1])]
    for pos in range(plen, len(seq)):
        tok = np.zeros((1, nslots), np.int32)
        ntoks = np.zeros((nslots,), np.int32)
        tok[0, slot], ntoks[slot] = seq[pos], pos
        lg, pools = forward_paged(net, params, jnp.asarray(tok), pools,
                                  jnp.asarray(table), jnp.asarray(ntoks))
        out.append(np.asarray(lg[0, slot]))
    return np.stack(out), first, pools


@pytest.mark.parametrize("plen", [1, 2, 3, 7, CAP - 1, CAP])
def test_padded_prefill_then_paged_decode_equals_the_full_forward(lm, plen):
    """Right-padded to the cap: the tails handed to the slot are those
    at the last REAL row, the K and V rows land in the slot's blocks,
    and a slot that is not in use keeps its tails through every step."""
    net, params, made = lm
    seq = np.random.default_rng(plen).integers(0, CFG["vocab_size"],
                                               plen + 5).astype(np.int32)
    got, first, last = _prefill_then_decode(net, params, seq, plen, slot=1)
    np.testing.assert_allclose(got, _ref_logits(made, seq)[plen - 1:],
                               rtol=2e-4, atol=2e-4)
    for i in range(CFG["num_hidden_layers"]):
        for k in TAILS:
            a, b = first[f"cca{i}"][k], last[f"cca{i}"][k]
            np.testing.assert_array_equal(a[0], b[0])       # idle slots
            np.testing.assert_array_equal(a[2], b[2])
            assert np.all(np.asarray(b[0]) == 7.0)
            assert not np.array_equal(a[1], b[1])


def test_the_prefills_tails_are_those_of_the_unpadded_prompt(lm):
    net, params, _ = lm
    seq = np.random.default_rng(6).integers(0, CFG["vocab_size"], 5)
    toks = np.full((1, CAP), 17, np.int32)
    toks[0, :5] = seq
    _, padded = forward_cached(net, params, jnp.asarray(toks),
                               init_cache(net, 1, CAP, jnp.float32), 0,
                               plen=jnp.int32(5))
    _, plain = forward_cached(net, params, jnp.asarray(seq)[None],
                              init_cache(net, 1, 5, jnp.float32), 0)
    for i in range(CFG["num_hidden_layers"]):
        for k in TAILS:
            np.testing.assert_allclose(padded[f"cca{i}"][k],
                                       plain[f"cca{i}"][k], rtol=1e-5,
                                       atol=1e-6)


# -- the whole net through the scheduler --------------------------------------

def _engine(lm, slots):
    net, params, _ = lm
    spec = ServeSpec(buckets=((1, CAP),), max_new_tokens=8, temperature=0.0,
                     eos_id=None, cb="on", cb_slots=slots, cb_block_len=BL,
                     cb_prompt_cap=CAP)
    engine = InferenceEngine(net, spec, params=params,
                             log_fn=lambda *a, **k: None)
    engine.load()
    return engine


@pytest.fixture(scope="module")
def cb_served(lm):
    """Four prompts of different lengths through three slots (one is
    admitted into a slot another left), then one alone in a house with
    two slots idle."""
    with jax.default_matmul_precision("highest"):
        engine = _engine(lm, 3)
        sched = ContinuousScheduler(engine,
                                    log_fn=lambda *a, **k: None).start()
        rng = np.random.default_rng(4)
        prompts = [rng.integers(0, CFG["vocab_size"], p).astype(np.int32)
                   for p in (5, 16, 2, 11, 1)]
        try:
            tickets = [sched.submit(p, max_new=8) for p in prompts[:4]]
            served = [t.wait(timeout=300)["tokens"] for t in tickets]
            served.append(sched.submit(prompts[4], max_new=8).wait(
                timeout=300)["tokens"])
        finally:
            sched.stop()
    st = engine.stats
    return prompts, served, dict(st.snapshot(),
                                 cb_decode_steps=st.cb_decode_steps)


@pytest.mark.parametrize("i", range(5))
def test_cb_greedy_tokens_equal_generates(lm, cb_served, i):
    net, params, made = lm
    prompts, served, _ = cb_served
    want = np.asarray(generate(net, params, prompts[i][None], 8))[0]
    assert list(served[i]) == list(want)
    seq = np.concatenate([prompts[i], np.asarray(served[i][:-1], np.int32)])
    ref = _ref_logits(made, seq)[len(prompts[i]) - 1:]
    assert np.all(ref.max(-1) - ref[np.arange(8), served[i]] < 1e-3)


def test_cb_counts_routing_and_both_kinds_of_state(lm, cb_served):
    net, _, _ = lm
    _, _, snap = cb_served
    layers = CFG["num_hidden_layers"]
    assert snap["cb_routed_layer_steps"] == snap["cb_decode_steps"] * layers
    # top 1 and every expert held: one assignment a busy token and layer
    assert snap["cb_routed_assignments"] % layers == 0
    assert 0 < snap["cb_routed_assignments"] <= (
        snap["cb_decode_steps"] * 3 * layers)
    assert (snap["cb_routed_layer_steps"] <= snap["cb_routed_max_load"]
            <= snap["cb_routed_assignments"])
    assert 0 < snap["cb_routed_experts_touched"] <= snap[
        "cb_routed_assignments"]
    per = state_bytes(net, BL, jnp.float32)
    assert snap["cb_slot_state_bytes"] == per["slot"] > 0
    assert snap["cb_block_bytes"] == per["block"] > 0


def test_metrics_export_the_imbalance_counter():
    from singa_tpu.serve.stats import ServeStats
    st = ServeStats()
    st.observe_routing(64, 12, 16, 130)
    st.observe_routing(60, 11, 16)
    snap = st.snapshot()
    assert snap["cb_routed_max_load"] == 130
    assert snap["cb_routed_assignments"] == 124
    from singa_tpu.obs.metrics import MetricsRegistry
    registry = MetricsRegistry()
    st.register_into(registry)
    assert "singa_serve_cb_routed_max_load_total 130" in registry.render_prometheus()


def test_serving_in_bf16_keeps_the_router_in_float32(lm):
    net, params, _ = lm
    half = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
    x = _x(0, 1, 6, CFG["hidden_size"]).astype(jnp.bfloat16)
    out = net.layers["zaya_moe0"].apply(net._resolve_params(half), [x], None)
    assert out["out"].dtype == jnp.bfloat16
    assert out["router"].dtype == jnp.float32
    pools = init_pools(net, 5, BL, jnp.bfloat16, 2)
    for name, entry in pools.items():
        for key, a in entry.items():
            assert a.dtype == (jnp.int32 if key == "routed"
                               else jnp.bfloat16), (name, key)


# -- the share ties to the model ----------------------------------------------

def _moe_layer(first, held):
    from singa_tpu.config.schema import LayerConfig, ZayaMoEConfig
    from singa_tpu.core.layers import create_layer
    layer = create_layer(LayerConfig(
        name="moe", type="kZayaMoE", zaya_moe_param=ZayaMoEConfig(
            num_routed=16, num_held=held, first_held=first, expert_hidden=24,
            router_hidden=8)))
    layer.setup([(1, 1, 32), {"out": (1, 1, 32), "router": (1, 1, 8)}])
    return layer


@pytest.fixture(scope="module")
def moe_case():
    rng = np.random.default_rng(8)
    f32 = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    w = {"router_down": f32(32, 8), "router_down_bias": 0.1 * f32(8),
         "router_norm": np.ones(8, np.float32), "router_w1": f32(8, 8),
         "router_b1": 0.1 * f32(8), "router_w2": f32(8, 8),
         "router_b2": 0.1 * f32(8), "router_w3": 2 * f32(8, 16),
         "router_bias": 0.05 * f32(16), "gamma": 0.5 + 0.1 * f32(8),
         "w_gate": f32(16, 32, 24) / 6, "w_up": f32(16, 32, 24) / 6,
         "w_down": f32(16, 24, 32) / 5}
    cfg = {"num_experts_per_tok": 1, "rms_norm_eps": 1e-5}
    return w, cfg, f32(1, 150, 32), f32(1, 150, 8)


def _share(w, x, state, first, held):
    layer = _moe_layer(first, held)
    p = {f"moe/{k}": jnp.asarray(v) for k, v in w.items()}
    for k in ("w_gate", "w_up", "w_down"):
        p[f"moe/{k}"] = p[f"moe/{k}"][first:first + held]
    out = layer.apply(p, [jnp.asarray(x), {"router": jnp.asarray(state)}],
                      None)
    return np.asarray(out["out"]), np.asarray(out["router"])


def test_two_shares_of_eight_experts_add_up_to_the_uncut_layer(moe_case):
    """Each of 2 chips holds 8 of the 16 experts; the router, which both
    compute alike, hands on one and the same state."""
    w, cfg, x, state = moe_case
    whole, want_state = zaya1.moe(jnp.asarray(x), jnp.asarray(state), w, cfg)
    (a, ra), (b, rb) = (_share(w, x, state, f, 8) for f in (0, 8))
    np.testing.assert_allclose(a + b, whole, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(ra, want_state, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(ra, rb)
    assert not np.allclose(a, whole, atol=1e-2)
    # top 1: every row is one share's alone
    mine = np.any(a != 0, -1)
    assert np.all(mine != np.any(b != 0, -1)) and 10 < mine.sum() < 140
    # selection is by probability + bias, weighting by probability alone
    plain = dict(w, router_bias=np.zeros(16, np.float32))
    assert not np.allclose(np.asarray(zaya1.moe(
        jnp.asarray(x), jnp.asarray(state), plain, cfg)[0]), whole,
        atol=1e-3)
