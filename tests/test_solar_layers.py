"""The Solar-Open2 layers (gated NoPE kAttention beside kKDA with
negative eigenvalues, kRoutedMoE behind every mixer) and a prompt
prefilled IN CHUNKS against the plain reference
(`benchmark/reference/solar_open2.py`) on seeded random weights at the
configuration's tiny size, float32, on the CPU: chunks then decode
through the serving state against the reference's full forward pass,
chunks against one wide rung, the shares of the experts against the
uncut layer, the grouped form of the held experts against the dense
walk, and the three forms of the delta rule with beta in (0, 2).

Tolerances: float32 under "highest" matmul precision everywhere; what
differs between two paths is the ORDER of float32 sums (a chunk
boundary, a sorted run of rows, a log-sum-exp merge), so logits of
size ~4 agree to 1e-4 absolute and states to 1e-4 relative; where two
paths run the same sums in the same order the comparison is exact."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness, solar_weights  # noqa: E402
from benchmark.reference import solar_open2  # noqa: E402
from benchmark.runners import serve_solar  # noqa: E402
from singa_tpu.config.schema import LayerConfig, KDAConfig  # noqa: E402
from singa_tpu.core.hybrid_layers import KDALayer  # noqa: E402
from singa_tpu.core.net import build_net  # noqa: E402
from singa_tpu.data import discover_input_shapes  # noqa: E402
from singa_tpu.models.generate import (forward_cached, forward_chunk,  # noqa: E402
                                       forward_paged, init_cache,
                                       project_head, scatter_prefill,
                                       unchunked_layers)
from singa_tpu.ops import kda as kda_ops  # noqa: E402
from singa_tpu.ops import moe as moe_ops  # noqa: E402
from singa_tpu.serve.kvcache import init_pools  # noqa: E402

CFG = harness._tiny(harness.read_json(
    ROOT, "benchmark", "configs", "solar-open2-serve-l4-ep8.json"))
CAP, BL, PIECE, SLOTS = 64, 4, 16, 3
TABLE = (CAP + 8) // BL


@pytest.fixture(scope="module")
def lm():
    model = serve_solar.model_config(CFG, CAP)
    net = build_net(model, "kTrain",
                    discover_input_shapes(model, force_synthetic=True))
    made = solar_weights.tree(CFG, 11, jnp.float32)
    params = {solar_weights.program_name(k): v for k, v in made.items()}
    return net, params, made


@pytest.fixture(autouse=True)
def exact():
    with jax.default_matmul_precision("highest"):
        yield


def _ref_logits(made, toks):
    return np.asarray(solar_open2.logits(np.asarray(toks)[None],
                                         lambda n: made[n], CFG)[0])


def _row(slot, first_block=5):
    """A slot's whole table row, every block its own, the slot behind."""
    row = np.arange(first_block, first_block + TABLE, dtype=np.int32)
    return jnp.asarray(row), jnp.int32(slot)


def _chunked(net, params, toks, widths, slot, pools=None):
    """The prompt through chunks of `widths` (the last ragged): logits
    at every real row and the pools after."""
    if pools is None:
        pools = init_pools(net, 5 + TABLE, BL, jnp.float32, SLOTS)
    row, slot = _row(slot)
    logits, start = [], 0
    for width in widths:
        rows = min(width, len(toks) - start)
        chunk = np.zeros((1, width), np.int32)
        chunk[0, :rows] = toks[start:start + rows]
        hid, pools = forward_chunk(net, params, jnp.asarray(chunk), pools,
                                   row, slot, jnp.int32(start),
                                   jnp.int32(rows), PIECE)
        logits.append(np.asarray(project_head(net, params, hid)[0, :rows]))
        start += rows
    return np.concatenate(logits), pools


# -- (a): chunks, then decode, against the reference's full forward pass ------

@pytest.mark.parametrize("plen,widths,grouped", [
    (41, (16, 16, 16), False), (41, (16, 16, 16), True),
    (33, (16, 16, 8), True), (64, (16, 16, 16, 16), False)])
def test_chunks_then_decode_equal_the_references_full_forward(
        lm, monkeypatch, plen, widths, grouped):
    """A prompt in three or four chunks, the last ragged (9 real rows of
    16, or 1 of 8), then six tokens through the paged decode step: every
    position's logits are the reference's, whose forward pass knows no
    chunk and no cache.  With `grouped` the held experts take every
    chunk in their sorted form."""
    net, params, made = lm
    if grouped:
        monkeypatch.setattr(moe_ops, "ROW_BLOCK", 4)
    rng = np.random.default_rng(plen)
    seq = rng.integers(0, CFG["vocab_size"], plen + 6)
    want = _ref_logits(made, seq)
    got, pools = _chunked(net, params, seq[:plen], widths, slot=1)
    np.testing.assert_allclose(got, want[:plen], rtol=1e-4, atol=1e-4)
    row, _ = _row(1)
    tables = np.zeros((SLOTS, TABLE), np.int32)
    tables[1] = np.asarray(row)
    for p in range(plen, plen + 6):
        ntoks = np.zeros((SLOTS,), np.int32)
        ntoks[1] = p
        toks = np.zeros((1, SLOTS), np.int32)
        toks[0, 1] = seq[p]
        lg, pools = forward_paged(net, params, jnp.asarray(toks), pools,
                                  jnp.asarray(tables), jnp.asarray(ntoks))
        np.testing.assert_allclose(lg[0, 1], want[p], rtol=1e-4, atol=1e-4)


# -- (b): chunks against the same prompt through one wide rung ----------------

def test_chunks_leave_what_one_wide_rung_leaves(lm):
    """State, conv tails, K/V rows and the first token's logits after
    three chunks (16, 16, 9 of 16) are those of the whole-prompt prefill
    at a rung of 48, scattered into the same slot."""
    net, params, _ = lm
    plen, slot = 41, 2
    toks = np.random.default_rng(3).integers(0, CFG["vocab_size"], plen)
    got, pools = _chunked(net, params, toks, (16, 16, 16), slot)
    padded = np.zeros((1, 48), np.int32)
    padded[0, :plen] = toks
    logits, cache = forward_cached(net, params, jnp.asarray(padded),
                                   init_cache(net, 1, 48, jnp.float32), 0,
                                   plen=jnp.int32(plen))
    row, _ = _row(slot)
    whole = scatter_prefill(init_pools(net, 5 + TABLE, BL, jnp.float32,
                                       SLOTS), cache, row[:48 // BL],
                            jnp.int32(slot), net)
    np.testing.assert_allclose(got[plen - 1], logits[0, plen - 1],
                               rtol=1e-4, atol=1e-4)
    kinds = solar_open2.layer_kinds(CFG)
    for i, mixer in enumerate(kinds):
        if mixer == "kda":
            a, b = pools[f"kda{i}"], whole[f"kda{i}"]
            np.testing.assert_allclose(a["S"][slot], b["S"][slot],
                                       rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(a["conv"][slot], b["conv"][slot],
                                       rtol=1e-4, atol=1e-5)
            # no other slot's state was touched
            assert not np.any(np.asarray(a["S"][:slot]))
        else:
            a, b = (np.asarray(p[f"attention{i}"]["kv"])[np.asarray(row)]
                    for p in (pools, whole))
            flat = lambda kv: kv.transpose(1, 0, 2, 3).reshape(  # noqa: E731
                kv.shape[1], -1, kv.shape[3])[:, :plen]
            np.testing.assert_allclose(flat(a), flat(b), rtol=1e-4,
                                       atol=1e-5)


def test_a_first_chunk_starts_from_zeros_whatever_the_slot_held(lm):
    """The slot's last tenant left a state and tails: a chunk at start 0
    reads neither."""
    net, params, _ = lm
    toks = np.random.default_rng(4).integers(0, CFG["vocab_size"], 30)
    clean, _ = _chunked(net, params, toks, (16, 16), slot=0)
    dirty = init_pools(net, 5 + TABLE, BL, jnp.float32, SLOTS)
    dirty = {n: {k: jnp.full_like(v, 0.37) if k in ("S", "conv") else v
                 for k, v in e.items()} for n, e in dirty.items()}
    got, _ = _chunked(net, params, toks, (16, 16), slot=0, pools=dirty)
    np.testing.assert_array_equal(got, clean)


def test_which_layers_keep_a_model_to_one_chunk(lm):
    from benchmark.runners import serve_kimi
    net, _, _ = lm
    assert unchunked_layers(net) == ()
    kimi = harness._tiny(harness.read_json(
        ROOT, "benchmark", "configs", "kimilinear-serve-l17-ep8.json"))
    model = serve_kimi.model_config(kimi, 16)
    latent = build_net(model, "kTrain",
                       discover_input_shapes(model, force_synthetic=True))
    assert unchunked_layers(latent) == ("kMLA",)


# -- (c): the chip's share of the experts --------------------------------------

def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(lm):
    """Four chips hold 4 of the 16 routed experts each (40 of 320 eight
    ways at full size); the shared expert, which every chip computes
    alike, is counted once.  Program layers against the reference's
    layer over ALL experts."""
    net, _, _ = lm
    rng = np.random.default_rng(8)
    f32 = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    e, f, n = CFG["hidden_size"], CFG["moe_intermediate_size"], 16
    w = {"router": f32(e, n), "router_bias": 0.3 * f32(n),
         "w_gate": f32(n, e, f) / 8, "w_up": f32(n, e, f) / 8,
         "w_down": f32(n, f, e) / 6, "shared_gate": f32(e, f) / 8,
         "shared_up": f32(e, f) / 8, "shared_down": f32(f, e) / 6}
    x = f32(1, 50, e)
    whole = np.asarray(solar_open2.moe(jnp.asarray(x), w, CFG, first=0))
    layer = net.layers["moe0"]
    assert (layer.n_routed, layer.n_held, layer.k) == (16, 4, 2)
    parts = []
    for r in range(4):
        ws = {k: (v[4 * r:4 * r + 4] if k in ("w_gate", "w_up", "w_down")
                  else v) for k, v in w.items()}
        parts.append(np.asarray(solar_open2.moe(
            jnp.asarray(x), ws, CFG, first=4 * r, shared=(r == 0))))
        if r == 0:      # the program's layer IS the reference's share
            got = layer.apply({f"moe0/{k}": jnp.asarray(v)
                               for k, v in ws.items()}, [jnp.asarray(x)],
                              None)
            np.testing.assert_allclose(got, parts[0], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-4, atol=1e-4)
    assert not np.allclose(parts[0], whole, atol=1e-2)


# -- (d): the grouped form against the dense walk ------------------------------

def _moe_args(rng, t, e=16, f=12, held=6):
    f32 = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    return (jnp.asarray(f32(t, e)), jnp.asarray(f32(held, e, f) / 4),
            jnp.asarray(f32(held, e, f) / 4), jnp.asarray(f32(held, f, e) / 3))


def _both_forms(monkeypatch, x, idx, weights, wg, wu, wd, first, valid):
    dense = moe_ops.held_experts_ffn(x, idx, weights, wg, wu, wd, first,
                                     valid, max_load=True)
    monkeypatch.setattr(moe_ops, "ROW_BLOCK", 8)
    assert moe_ops.grouped_run(x.shape[0], wg.shape[0], idx.shape[1])
    grouped = moe_ops.held_experts_ffn(x, idx, weights, wg, wu, wd, first,
                                       valid, max_load=True)
    return dense, grouped


@pytest.mark.parametrize("case", ["plain", "pads", "one_expert",
                                  "none_held", "all_held"])
def test_the_grouped_form_is_the_dense_walk(monkeypatch, case):
    """40 rows, top 3 of 20 routed, experts 5..10 held: with pads (rows
    that are routed nowhere), with every assignment on ONE held expert,
    with rows none of whose experts is held, and with every row's every
    expert held (past half of the assignments: the grouped form hands
    its matmul all of them, not the first half); outputs and counts."""
    rng = np.random.default_rng(len(case))
    t, first = 40, 5
    x, wg, wu, wd = _moe_args(rng, t)
    idx = np.stack([rng.permutation(20)[:3] for _ in range(t)])
    valid = None
    if case == "pads":
        valid = jnp.arange(t) < 27
    elif case == "one_expert":
        idx = np.tile(np.array([[7, 0, 19]]), (t, 1))
    elif case == "none_held":
        idx[::3] = np.array([0, 1, 15])
    elif case == "all_held":
        idx = np.stack([5 + rng.permutation(6)[:3] for _ in range(t)])
    weights = jnp.asarray(rng.uniform(0.1, 1, (t, 3)).astype(np.float32))
    idx = jnp.asarray(idx, jnp.int32)
    (y0, c0), (y1, c1) = _both_forms(monkeypatch, x, idx, weights, wg, wu,
                                     wd, first, valid)
    np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(c1, c0)
    if case == "pads":
        assert not np.any(np.asarray(y1[27:]))
    if case == "one_expert":
        assert list(np.asarray(c1)) == [t, 1, t]
    if case == "all_held":
        assert int(c1[0]) == 3 * t > t * 3 // 2
    if case == "none_held":
        assert not np.any(np.asarray(y1[::3])) and np.any(np.asarray(y1[1]))


def test_which_form_a_run_takes_follows_from_sizes_alone():
    """Past ROW_BLOCK rows, and only where more experts are held than a
    token chooses: 40 / 32 / 16 held against 8 are grouped, Pangu's 8
    against 8 and every decode step stay the dense walk."""
    block = moe_ops.ROW_BLOCK
    assert [moe_ops.grouped_run(2048, held, 8)
            for held in (40, 32, 16, 8)] == [True, True, True, False]
    assert not moe_ops.grouped_run(block, 40, 8)
    assert moe_ops.grouped_run(block + 1, 40, 8)
    assert not moe_ops.grouped_run(96, 40, 8)


# -- (e): the three forms of the delta rule with beta in (0, 2) ---------------

def _kda_inputs(rng, b, t, h, d, top):
    q, k, v = (rng.standard_normal((b, t, h, d)).astype(np.float32)
               for _ in range(3))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -np.exp(rng.uniform(-7, 1, (b, t, h, d))).astype(np.float32)
    beta = rng.uniform(0, top, (b, t, h)).astype(np.float32)
    s0 = rng.standard_normal((b, h, d, d)).astype(np.float32)
    return q, k, v, g, beta, s0


@pytest.mark.parametrize("t,chunk", [(1, 8), (9, 8), (40, 8), (40, 64),
                                     (64, 64), (130, 64), (64, 32), (48, 24)])
def test_step_scan_and_chunked_forms_agree_with_beta_to_two(t, chunk):
    """beta up to 2: the transition I - beta k k^T flips k's direction,
    and the chunked form's triangular solve sees entries past 1.  Past
    16 rows a chunk's A and B come from sub-chunks ((48, 24): from
    one)."""
    args = _kda_inputs(np.random.default_rng(t), 2, t, 2, 16, top=2.0)
    assert args[4].max() > 1.5
    o_ref, s_ref = kda_ops.delta_rule_scan(*map(jnp.asarray, args))
    o, s = kda_ops.delta_rule_chunked(*args, chunk=chunk)
    np.testing.assert_allclose(o, o_ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s, s_ref, rtol=1e-4, atol=1e-4)
    q, k, v, g, beta, state = map(jnp.asarray, args)
    outs = []
    for i in range(t):
        o1, state = kda_ops.delta_rule_step(q[:, i], k[:, i], v[:, i],
                                            g[:, i], beta[:, i], state)
        outs.append(o1)
    np.testing.assert_allclose(jnp.stack(outs, 1), o_ref, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(state, s_ref, rtol=1e-5, atol=1e-5)


def _kda_layer(neg):
    layer = KDALayer(LayerConfig(name="kda", type="kKDA", kda_param=KDAConfig(
        num_heads=2, head_dim=16, conv_kernel=4, neg_eigval=neg)))
    layer.setup([(1, 1, 24)])
    return layer


def test_the_layers_switch_doubles_beta_in_every_form():
    """`neg_eigval` through the layer: the chunk of 12 tokens, twelve
    one-token steps through the cache and the paged step give one
    output; without the switch the same weights give another."""
    rng = np.random.default_rng(5)
    neg, pos = _kda_layer(True), _kda_layer(False)
    params = {spec.name: jnp.asarray(
        rng.standard_normal(spec.shape).astype(np.float32) * 0.3)
        for spec in neg.param_specs}
    x = jnp.asarray(rng.standard_normal((1, 12, 24)).astype(np.float32))
    whole = neg.apply(params, [x], None)
    assert not np.allclose(whole, pos.apply(params, [x], None), atol=1e-3)
    entry, pool = neg.init_cache(1, 0, jnp.float32), neg.init_pool(
        2, 1, 4, jnp.float32)
    for i in range(12):
        o, entry = neg.apply_cached(params, x[:, i:i + 1], entry, i)
        np.testing.assert_allclose(o[0, 0], whole[0, i], rtol=1e-4,
                                   atol=1e-5)
        both = jnp.stack([x[0, i], x[0, i]])[None]           # (1, 2, E)
        o, pool = neg.apply_paged(params, both, pool, None,
                                  jnp.asarray([i + 1, 0]))
        np.testing.assert_allclose(o[0, 0], whole[0, i], rtol=1e-4,
                                   atol=1e-5)
    assert not np.any(np.asarray(pool["S"][1]))     # the idle slot's state


def test_the_reference_doubles_beta_and_its_control_does_not(lm):
    _, _, made = lm
    toks = np.random.default_rng(6).integers(0, CFG["vocab_size"], 20)
    ref = _ref_logits(made, toks)
    ctl = np.asarray(solar_open2.logits(toks[None], lambda n: made[n], CFG,
                                        round_to="pos_eig")[0])
    assert np.abs(ref - ctl).max() > 1e-2
