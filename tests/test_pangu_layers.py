"""The openPangu-Ultra-MoE layers (a kMLA with a low-rank query and
rotated rope dimensions, sandwich norms, the multi-token prediction
module) against the plain reference (`benchmark/reference/pangu.py`) on
seeded random weights at the configuration's tiny size, float32, on the
CPU: `apply` = prefill + cached steps = paged steps with one and with
two query rows a slot, rows that straddle a block, the absorbed sums as
oracle, the module, and the chip's share of the experts against the
uncut layer."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from oracles import attend_absorbed  # noqa: E402
from benchmark import harness, pangu_weights  # noqa: E402
from benchmark.reference import pangu  # noqa: E402
from benchmark.runners import serve_pangu  # noqa: E402
from singa_tpu.core.net import build_net  # noqa: E402
from singa_tpu.data import discover_input_shapes  # noqa: E402
from singa_tpu.models.generate import (draft_cached, draft_paged,  # noqa: E402
                                       forward_cached, forward_paged,
                                       init_cache, mtp_module,
                                       scatter_prefill)
from singa_tpu.ops.paged_attention import (paged_attention_reference,  # noqa: E402
                                           paged_decode_attention)
from singa_tpu.serve.kvcache import init_pools, slot_behind_row  # noqa: E402

CFG = harness._tiny(harness.read_json(
    ROOT, "benchmark", "configs", "openpangu-ultra-moe-serve-l5-ep32.json"))
CAP, BL = 16, 4


@pytest.fixture(scope="module")
def lm():
    model = serve_pangu.model_config(CFG, CAP)
    net = build_net(model, "kTrain",
                    discover_input_shapes(model, force_synthetic=True))
    made = pangu_weights.tree(CFG, 39, jnp.float32)
    params = {pangu_weights.program_name(k): v for k, v in made.items()}
    return net, params, made


@pytest.fixture(autouse=True)
def exact():
    with jax.default_matmul_precision("highest"):
        yield


def _ref(made, toks, **kw):
    main, module = pangu.logits(np.asarray(toks)[None], lambda n: made[n],
                                CFG, **kw)
    return np.asarray(main[0]), np.asarray(module[0])


def _seq(seed, n):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], n).astype(np.int32)


# -- the net is the equations --------------------------------------------------

def test_the_nets_layers_are_the_equations(lm):
    net, params, _ = lm
    mla = net.layers["mla0"]
    assert (mla.q_rank, mla.theta) == (CFG["q_lora_rank"], CFG["rope_theta"])
    assert params["mla0/wq"].shape == (
        CFG["q_lora_rank"], CFG["num_attention_heads"]
        * (CFG["qk_nope_head_dim"] + CFG["qk_rope_head_dim"]))
    assert set(params) == {s.name for n in net.topo
                           for s in net.layers[n].param_specs}
    module = mtp_module(net)
    n = CFG["num_hidden_layers"]
    assert module.entry == "mtp" and module.last == "mtp_ln_f"
    assert (module.embed, module.hidden) == ("embed", "ln_f")
    assert {f"mla{n}", f"moe{n}", f"pn{n}a", f"res{n}b"} <= module.layers
    assert not {"ln_f", "embed", "loss", f"mla{n - 1}"} & module.layers
    # the draft per slot is serving state: a prefill is told its slot
    assert slot_behind_row(net)


def test_apply_is_the_reference_and_turns_with_the_position(lm):
    net, params, made = lm
    seq = _seq(4, CAP)
    _, _, outs = net.apply(params, {"data": {
        "input": jnp.asarray(seq[None]), "target": jnp.asarray(seq[None])}},
        train=False)
    get = lambda n: made[n]                                  # noqa: E731
    hid = pangu.hidden_states(jnp.asarray(seq[None]), get, CFG)
    np.testing.assert_allclose(outs["ln_f"], hid, rtol=2e-4, atol=2e-4)
    # `apply` shifts the embedding by a token itself: the module's rows
    # but the last are the reference's
    nxt = np.append(seq[1:], 0)[None]
    mod = pangu.module_states(hid, jnp.asarray(nxt), get, CFG)
    np.testing.assert_allclose(np.asarray(outs["mtp_ln_f"])[0, :-1],
                               np.asarray(mod)[0, :-1], rtol=2e-4, atol=2e-4)
    # the comparison tells a rotation from none
    still = pangu.hidden_states(jnp.asarray(seq[None]), get, CFG,
                                rotate=False)
    np.testing.assert_allclose(still[0, 0], hid[0, 0], atol=1e-5)
    assert np.max(np.abs(np.asarray(still - hid)[0, 4:])) > 0.05


# -- prefill, then decode through the pools ------------------------------------

def _tables(nslots, slot, total):
    nb = -(-total // BL)
    table = np.zeros((nslots, max(nb, CAP // BL)), np.int32)
    table[slot, :nb] = 1 + np.arange(nb)
    return table, nb


def _prefill(net, params, seq, plen, slot, table, nb, nslots):
    """Both caches filled from a right-padded prompt and scattered into
    slot `slot`; returns (the main and the module's logits at the last
    real row, hidden there, pools)."""
    toks = np.zeros((1, CAP), np.int32)
    toks[0, :plen] = seq[:plen]
    lg, cache, hid = forward_cached(
        net, params, jnp.asarray(toks), init_cache(net, 1, CAP, jnp.float32),
        0, plen=jnp.int32(plen), with_hidden=True)
    nxt = np.zeros((1, CAP), np.int32)
    nxt[0, :plen] = seq[1:plen + 1]
    dl, cache = draft_cached(net, params, hid, jnp.asarray(nxt), cache, 0,
                             plen=jnp.int32(plen))
    pools = init_pools(net, nb + 1, BL, jnp.float32, nslots)
    pools = scatter_prefill(pools, cache, jnp.asarray(table[slot, :CAP // BL]),
                            jnp.int32(slot), net)
    return np.asarray(lg[0, plen - 1]), np.asarray(dl[0, plen - 1]), pools


def _decode(net, params, seq, plen, slot, rows, nslots=3, upto=None):
    """Main and module logits at positions plen-1 .. upto-1: the prefill,
    then paged steps of `rows` tokens a slot (teacher-forced; a step of
    two rows advances by two)."""
    upto = len(seq) - 1 if upto is None else upto
    table, nb = _tables(nslots, slot, len(seq) + rows)
    first, dfirst, pools = _prefill(net, params, seq, plen, slot, table, nb,
                                    nslots)

    @jax.jit
    def step(tok, nxt, pools, ntoks):
        lg, pools, hid = forward_paged(net, params, tok, pools,
                                       jnp.asarray(table), ntoks,
                                       with_hidden=True)
        dl, pools = draft_paged(net, params, hid, nxt, pools,
                                jnp.asarray(table), ntoks)
        return lg[0], dl[0], pools

    main, module = [first], [dfirst]
    pos = plen
    while pos < upto:
        tok = np.zeros((1, nslots * rows), np.int32)
        nxt = np.zeros((1, nslots * rows), np.int32)
        ntoks = np.zeros((nslots,), np.int32)
        ntoks[slot] = pos
        tok[0, slot * rows:(slot + 1) * rows] = seq[pos:pos + rows]
        nxt[0, slot * rows:(slot + 1) * rows] = seq[pos + 1:pos + 1 + rows]
        lg, dl, pools = step(jnp.asarray(tok), jnp.asarray(nxt), pools,
                             jnp.asarray(ntoks))
        main += list(np.asarray(lg[slot * rows:(slot + 1) * rows]))
        module += list(np.asarray(dl[slot * rows:(slot + 1) * rows]))
        pos += rows
    return np.stack(main), np.stack(module), pools


@pytest.mark.parametrize("plen,rows", [(1, 1), (5, 1), (CAP, 1), (1, 2),
                                       (2, 2), (3, 2), (CAP - 1, 2),
                                       (CAP, 2)])
def test_prefill_then_paged_steps_equal_the_full_forward(lm, plen, rows):
    """One row a slot a step, and two (a verify step's): with blocks of
    4 a pair of rows starts at every offset of a block, the fourth
    straddling two (prompts of 3 and 15 put the first pair there)."""
    net, params, made = lm
    total = plen + 12 + 1
    seq = _seq(plen * 10 + rows, total)
    main, module, _ = _decode(net, params, seq, plen, slot=1, rows=rows)
    want_main, want_module = _ref(made, seq)
    n = len(main)
    np.testing.assert_allclose(main, want_main[plen - 1:plen - 1 + n],
                               rtol=3e-4, atol=3e-4)
    # module row i stands beside token i + 1 and predicts token i + 2
    np.testing.assert_allclose(module, want_module[plen - 1:plen - 1 + n],
                               rtol=3e-4, atol=3e-4)


def test_a_rejected_row_is_overwritten_and_never_attended(lm):
    """A verify step whose second row (the draft) is NOT what follows:
    the next step starts at that position and writes over it; what the
    later rows read is the sequence's own."""
    net, params, made = lm
    plen, total = 3, 12
    seq = _seq(7, total)
    wrong = seq.copy()
    table, nb = _tables(2, 0, total + 2)
    _, _, pools = _prefill(net, params, seq, plen, 0, table, nb, 2)

    def step(pools, pos, second):
        tok = np.zeros((1, 4), np.int32)
        tok[0, :2] = seq[pos], second
        ntoks = np.array([pos, 0], np.int32)
        lg, pools = forward_paged(net, params, jnp.asarray(tok), pools,
                                  jnp.asarray(table), jnp.asarray(ntoks))
        return np.asarray(lg[0, 0]), pools

    want, _ = _ref(made, seq)
    got = []
    for pos in range(plen, total - 1):
        wrong[pos + 1] = (seq[pos + 1] + 1) % CFG["vocab_size"]
        lg, pools = step(pools, pos, wrong[pos + 1])    # always rejected
        got.append(lg)
    np.testing.assert_allclose(np.stack(got), want[plen:total - 1],
                               rtol=3e-4, atol=3e-4)


# -- the kernel's two rows against the absorbed sums ---------------------------

@pytest.mark.parametrize("ntoks", [[0, 3, 4, 9], [7, 8, 15, 1]])
def test_two_query_rows_a_slot_are_the_absorbed_sums(lm, ntoks):
    """`paged_decode_attention(rows=2)` against `attend_absorbed` over
    the gathered rows, row j up to position ntoks + j, and against the
    gather formulation; lengths that put the pair in one block, at a
    block's end and across two."""
    net, params, _ = lm
    layer = net.layers["mla1"]
    full = net._resolve_params(params)
    rng = np.random.default_rng(1)
    s, t = 4, 5
    pool = jnp.asarray(rng.standard_normal(
        (s * t + 1, BL, layer.pool_row)).astype(np.float32))
    pool = pool.at[..., layer.latent_dim:].set(0)
    tables = jnp.asarray(1 + np.arange(s * t).reshape(s, t), jnp.int32)
    ntoks = jnp.asarray(ntoks, jnp.int32)
    q = jnp.asarray(rng.standard_normal(
        (s, 2, layer.heads, layer.nope + layer.rope)).astype(np.float32))
    absorbed = layer._absorb_query(
        full, q.reshape(s * 2, layer.heads, -1), layer.pool_row)
    kw = dict(value_dim=layer.rank, rows=2,
              scale=1.0 / np.sqrt(layer.nope + layer.rope))
    got = paged_decode_attention(absorbed.reshape(s, 2 * layer.heads, -1),
                                 pool[:, None], tables, ntoks, **kw)
    ref = paged_attention_reference(absorbed.reshape(s, 2 * layer.heads, -1),
                                    pool[:, None], tables, ntoks, **kw)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)
    lat = pool[tables].reshape(s, t * BL, -1)
    for j in range(2):
        allowed = jnp.arange(t * BL)[None, :] <= (ntoks + j)[:, None]
        want = attend_absorbed(layer, full, q[:, j], lat, allowed)
        mine = layer._expand_output(
            full, got.reshape(s, 2, layer.heads, -1)[:, j])
        np.testing.assert_allclose(mine, want, rtol=2e-4, atol=2e-4)


def test_more_heads_than_a_chunk_are_scored_a_chunk_at_a_time(lm,
                                                              monkeypatch):
    from singa_tpu.core import hybrid_layers
    net, params, _ = lm
    seq = jnp.asarray(_seq(2, CAP)[None])
    cache = init_cache(net, 1, CAP, jnp.float32)
    whole, _ = forward_cached(net, params, seq, cache, 0)
    monkeypatch.setattr(hybrid_layers, "_HEAD_CHUNK", 2)   # 4 heads: 2 chunks
    chunked, _ = forward_cached(net, params, seq, cache, 0)
    np.testing.assert_allclose(chunked, whole, rtol=1e-5, atol=1e-5)


# -- the share ties to the model -------------------------------------------------

def _moe_layer(first, held, shared):
    from singa_tpu.config.schema import LayerConfig, RoutedMoEConfig
    from singa_tpu.core.layers import create_layer
    layer = create_layer(LayerConfig(
        name="moe", type="kRoutedMoE", routed_moe_param=RoutedMoEConfig(
            num_routed=256, experts_per_token=8, num_held=held,
            first_held=first, expert_hidden=24,
            shared_hidden=24 if shared else 0, renormalize=True,
            routed_scale=2.5)))
    layer.setup([(1, 1, 32)])
    return layer


def test_the_32_shares_add_up_to_the_uncut_layer():
    """Each of 32 chips holds 8 of the 256 routed experts (`first_held`
    0, 8, ..., 248), the router scoring and choosing among all 256 with
    no selection bias; the shared expert, which every chip computes
    alike, is counted once."""
    rng = np.random.default_rng(8)
    f32 = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    w = {"router": f32(32, 256), "w_gate": f32(256, 32, 24) / 6,
         "w_up": f32(256, 32, 24) / 6, "w_down": f32(256, 24, 32) / 5,
         "shared_gate": f32(32, 24) / 6, "shared_up": f32(32, 24) / 6,
         "shared_down": f32(24, 32) / 5}
    cfg = {"num_experts_per_tok": 8, "norm_topk_prob": True,
           "routed_scaling_factor": 2.5, "first_held_expert": 0,
           "n_shared_experts": 1}
    x = f32(1, 150, 32)

    def share(first, shared):
        layer = _moe_layer(first, 8, shared)
        p = {f"moe/{k}": jnp.asarray(v) for k, v in w.items()
             if shared or not k.startswith("shared")}
        p["moe/router_bias"] = jnp.zeros((256,), jnp.float32)
        for k in ("w_gate", "w_up", "w_down"):
            p[f"moe/{k}"] = p[f"moe/{k}"][first:first + 8]
        return np.asarray(layer.apply(p, [jnp.asarray(x)], None))

    whole = np.asarray(pangu.moe(jnp.asarray(x), w, cfg))
    parts = [share(8 * r, shared=(r == 0)) for r in range(32)]
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-4, atol=1e-4)
    assert not np.allclose(parts[0], whole, atol=1e-2)
    mine = {k: (v[8:16] if k in ("w_gate", "w_up", "w_down") else v)
            for k, v in w.items()}
    np.testing.assert_allclose(
        np.asarray(pangu.moe(jnp.asarray(x), mine, cfg, first=8,
                             shared=False)), parts[1], rtol=1e-4, atol=1e-4)


def test_a_nope_full_rank_layer_is_the_layer_it_was():
    """Without `rope_theta` and `q_lora_rank` kMLA declares the params
    it declared and computes what it computed (the Kimi cells)."""
    from singa_tpu.config.schema import LayerConfig, MLAConfig
    from singa_tpu.core.layers import create_layer
    layer = create_layer(LayerConfig(name="mla", type="kMLA",
                                     mla_param=MLAConfig(num_heads=2)))
    layer.setup([(1, 1, 32)])
    assert [s.name for s in layer.param_specs] == [
        "mla/wq", "mla/w_kva", "mla/w_kvb", "mla/wo", "mla/kv_norm"]
    assert layer.param_specs[0].shape == (32, 2 * (128 + 64))
