"""Window and full attention in one served model (`kAttention`'s window,
q/k norm and output gate, `hybrid_lm`'s norm after a sublayer and scaled
embedding, the per-kind serving cache) against the plain reference
(`benchmark/reference/trinity.py`) on seeded random weights at the
configuration's tiny size, float32, on the CPU: prefill then decode
through a ring that wraps several times, the allocator's two kinds, the
eight shares of a sparse layer against the uncut one, the whole net
through the continuous-batching scheduler, and a `kAttention` without
the new options against the bodies it had before them."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness, trinity_weights, weights  # noqa: E402
from benchmark.reference import trinity  # noqa: E402
from benchmark.runners import serve_cb, serve_trinity  # noqa: E402
from singa_tpu.core.net import build_net  # noqa: E402
from singa_tpu.core.seq_layers import (DECODE_CTX, attend_cache,  # noqa: E402
                                       write_token)
from singa_tpu.data import discover_input_shapes  # noqa: E402
from singa_tpu.models.generate import (forward_cached, forward_paged,  # noqa: E402
                                       generate, init_cache, scatter_prefill)
from singa_tpu.ops.paged_attention import (paged_decode_attention,  # noqa: E402
                                           ring_blocks)
from singa_tpu.serve.engine import InferenceEngine, ServeSpec  # noqa: E402
from singa_tpu.serve.kvcache import (PagedKVCache, init_pools,  # noqa: E402
                                     pool_bytes, state_bytes)
from singa_tpu.serve.scheduler import ContinuousScheduler  # noqa: E402

pytestmark = pytest.mark.serve

CFG = harness._tiny(harness.read_json(
    ROOT, "benchmark", "configs", "trinity-mini-serve-l16-ep8.json"))
CAP, BL, WINDOW = 16, 4, CFG["sliding_window"]
RING = ring_blocks(WINDOW, BL)


@pytest.fixture(scope="module")
def lm():
    model = serve_trinity.model_config(CFG, CAP)
    net = build_net(model, "kTrain",
                    discover_input_shapes(model, force_synthetic=True))
    made = trinity_weights.tree(CFG, 11, jnp.float32)
    params = {trinity_weights.program_name(k): v for k, v in made.items()}
    return net, params, made


@pytest.fixture(autouse=True)
def exact():
    with jax.default_matmul_precision("highest"):
        yield


def _ref_logits(made, toks, round_to=None):
    return np.asarray(trinity.logits(np.asarray(toks)[None],
                                     lambda n: made[n], CFG, round_to)[0])


def test_the_tiny_size_has_both_kinds_and_a_window_its_contexts_pass():
    assert trinity.layer_kinds(CFG) == [
        ("sliding", "dense"), ("full", "moe"), ("sliding", "moe"),
        ("full", "moe")]
    sv = CFG["serve"]
    assert (WINDOW, sv["cb_block_len"], RING) == (8, 4, 3)
    assert sv["cb_prompt_cap"] + sv["max_new_tokens"] > 6 * WINDOW


def test_the_nets_layers_are_the_equations(lm):
    net, params, _ = lm
    kinds = [net.layers[n].cfg.type for n in net.topo]
    assert kinds.count("kRMSNorm") == 4 * 4 + 1          # four a layer
    assert net.layers["embed"].scale == pytest.approx(8.0)   # sqrt(64)
    windowed, full = net.layers["attention0"], net.layers["attention1"]
    assert (windowed.window, windowed.use_rope) == (WINDOW, True)
    assert (full.window, full.use_rope) == (0, False)
    assert windowed.qk_norm and windowed.gate and full.gate
    assert set(params) == {s.name for n in net.topo
                           for s in net.layers[n].param_specs}
    assert params["attention0/q_norm"].shape == (CFG["head_dim"],)


# -- (a): prefill, then decode through the ring and the table -----------------

def _prefill_then_decode(net, params, seq, plen, slot, nslots=3, poison=True):
    """Logits at positions plen-1 .. len(seq)-1: the right-padded prefill
    scattered into slot `slot`, then one paged step a token."""
    nb = -(-len(seq) // BL)
    table = np.zeros((nslots, max(nb, CAP // BL)), np.int32)
    table[slot, :nb] = 1 + np.arange(nb)
    toks = np.zeros((1, CAP), np.int32)
    toks[0, :plen] = seq[:plen]
    lg, cache = forward_cached(net, params, jnp.asarray(toks),
                               init_cache(net, 1, CAP, jnp.float32), 0,
                               plen=jnp.int32(plen))
    pools = init_pools(net, nb + 1, BL, jnp.float32, nslots)
    if poison:
        # the ring's last tenant left garbage behind: every column a
        # request reads it has written itself
        pools = {n: ({k: jnp.full_like(v, jnp.nan) for k, v in e.items()}
                     if getattr(net.layers[n], "window", 0) else e)
                 for n, e in pools.items()}
    pools = scatter_prefill(pools, cache, jnp.asarray(table[slot, :CAP // BL]),
                            jnp.int32(slot), net)
    step = jax.jit(lambda tok, pools, ntoks: forward_paged(
        net, params, tok, pools, jnp.asarray(table), ntoks))
    out = [np.asarray(lg[0, plen - 1])]
    for pos in range(plen, len(seq)):
        tok = np.zeros((1, nslots), np.int32)
        ntoks = np.zeros((nslots,), np.int32)
        tok[0, slot], ntoks[slot] = seq[pos], pos
        lg, pools = step(jnp.asarray(tok), pools, jnp.asarray(ntoks))
        out.append(np.asarray(lg[0, slot]))
    return np.stack(out), pools


@pytest.mark.parametrize("plen", [1, 3, WINDOW, WINDOW + 1, CAP - 1, CAP])
def test_padded_prefill_then_decode_equals_the_full_forward(lm, plen):
    """A context of 44 positions under a window of 8 and a ring of 3
    blocks of 4: the ring wraps three times and more; a prompt longer
    than the window leaves its last blocks in the ring and no pad row."""
    net, params, made = lm
    total = 44
    seq = np.random.default_rng(plen).integers(0, CFG["vocab_size"],
                                               total).astype(np.int32)
    got, _ = _prefill_then_decode(net, params, seq, plen, slot=1)
    want = _ref_logits(made, seq)
    np.testing.assert_allclose(got, want[plen - 1:], rtol=2e-4, atol=2e-4)
    # the comparison tells a window from none: without it the reference
    # reads differently wherever a context has passed the window
    blind = _ref_logits(made, seq, "no_window")
    np.testing.assert_allclose(blind[:WINDOW], want[:WINDOW], atol=1e-5)
    assert np.max(np.abs(blind[WINDOW + 4:] - want[WINDOW + 4:])) > 0.1


def test_the_ring_holds_the_window_and_the_table_every_row(lm):
    net, params, _ = lm
    seq = np.random.default_rng(0).integers(0, CFG["vocab_size"],
                                            30).astype(np.int32)
    _, pools = _prefill_then_decode(net, params, seq, 5, slot=2,
                                    poison=False)
    ring, table = pools["attention0"]["kv"], pools["attention1"]["kv"]
    assert ring.shape[0] == 3 * RING + 1 and table.shape[0] == 8 + 1
    # slot 2's ring is blocks 7..9; slots 0 and 1 decoded nothing but
    # their idle writes at position 0
    used = np.flatnonzero(np.abs(np.asarray(ring)).sum(axis=(1, 2, 3)))
    assert set(used) == {0, 1, 4, 7, 8, 9}       # 0: the pad blocks
    assert np.abs(np.asarray(table)[1:8]).sum(axis=(1, 2, 3)).all()


def test_a_windowed_prefill_without_its_slot_is_refused(lm):
    net, _, _ = lm
    layer = net.layers["attention0"]
    pool = layer.init_pool(2, 9, BL, jnp.float32)
    with pytest.raises(Exception, match="slot"):
        layer.scatter_prefill(pool, layer.init_cache(1, CAP, jnp.float32),
                              jnp.zeros((CAP // BL,), jnp.int32))


def test_apply_equals_the_cached_path_and_the_reference(lm):
    """The training-side `apply` (the window as a mask over dense
    scores) gives the logits of the serving prefill."""
    net, params, made = lm
    seq = np.random.default_rng(4).integers(0, CFG["vocab_size"],
                                            CAP).astype(np.int32)
    _, _, outs = net.apply(params, {"data": {
        "input": jnp.asarray(seq[None]), "target": jnp.asarray(seq[None])}},
        train=False)
    hid = np.asarray(outs["ln_f"])
    want = np.asarray(trinity.hidden_states(jnp.asarray(seq[None]),
                                            lambda n: made[n], CFG))
    np.testing.assert_allclose(hid, want, rtol=2e-4, atol=2e-4)


# -- (c): the allocator's two kinds --------------------------------------------

def test_the_allocator_reserves_per_kind(lm):
    net, _, _ = lm
    kv = PagedKVCache(net, num_slots=3, max_blocks_per_slot=14,
                      num_blocks=3 * 14 + 1, block_len=BL)
    assert (kv.window, kv.ring_blocks, kv.per_slot_state) == (WINDOW, RING,
                                                              True)
    assert kv.pools["attention0"]["kv"].shape[0] == 3 * RING + 1
    assert kv.pools["attention1"]["kv"].shape[0] == 3 * 14 + 1
    # a request of 50 tokens: 13 growing blocks from the free list, and
    # of the ring what it always holds
    assert (kv.blocks_for(50), kv.ring_blocks) == (13, RING)
    free = kv.free_blocks
    row = kv.alloc(1, kv.blocks_for(50))
    assert kv.free_blocks == free - 13 and np.count_nonzero(row) == 13
    target = kv.prefill_target(1, CAP // BL)
    assert target[-1] == 1 and list(target[:-1]) == list(row[:CAP // BL])
    # a ring column is used again every RING blocks, and is the slot's own
    assert kv.ring_block(1, 0) == kv.ring_block(1, RING * BL) == 1 + RING
    assert kv.ring_block(1, BL) == kv.ring_block(1, 4 * RING * BL + BL) \
        == 2 + RING
    assert {kv.ring_block(s, p) for s in range(3) for p in range(200)} \
        == set(range(1, 3 * RING + 1))
    # what a step's kernel walks, once a kind: the table up to the write
    # position, of the ring what the window touches
    # (a block a copy: its blocks hold several heads)
    assert kv.walked_blocks(np.array([0, 5, 40])) == {
        "table": 1 + 2 + 11, "copies": 1 + 2 + 11,
        "window": 1 + 2 + 3}                          # 33..40: 3 blocks
    assert kv.walked_blocks(np.array([0, 43, 0]))["window"] == 1 + 2 + 1
    # retiring returns the growing blocks; the ring was never taken
    kv.free(1)
    assert kv.free_blocks == free and not kv.tables[1].any()
    snap = kv.snapshot()
    assert (snap["window"], snap["ring_blocks"]) == (WINDOW, RING)


def test_state_bytes_by_kind(lm):
    net, _, _ = lm
    per = state_bytes(net, BL, jnp.float32)
    block = 2 * CFG["num_key_value_heads"] * BL * CFG["head_dim"] * 4
    # two layers of each kind; a copy of the kernel moves ONE layer's
    # block, its keys and values together
    assert per == {"slot": 0, "block": 2 * block, "window_block": 2 * block,
                   "block_copy": block, "window_block_copy": block}
    total = pool_bytes(net, 3 * 14 + 1, BL, jnp.float32, 3)
    counts = 3 * 3 * 4                 # three sparse layers' routing counts
    assert total == (2 * block * (3 * 14 + 1) + 2 * block * (3 * RING + 1)
                     + counts)
    want = serve_trinity.resident_bytes(dict(CFG, serve=dict(
        CFG["serve"], cb_slots=3, cb_prompt_cap=16, max_new_tokens=40)))
    assert want["full_blocks"] + want["window_rings"] + counts == total


def test_two_windows_in_one_model_are_refused():
    attention = {"num_heads": 2, "head_dim": 8}
    from singa_tpu.models.transformer import hybrid_lm
    model = hybrid_lm(vocab_size=32, embed_dim=16, seq_len=8, mixers=[
        {"attention": dict(attention, window=4)},
        {"attention": dict(attention, window=8)}],
        ffns=[{"dense": {"hidden_dim": 16}}] * 2)
    net = build_net(model, "kTrain",
                    discover_input_shapes(model, force_synthetic=True))
    with pytest.raises(ValueError, match="one ring geometry"):
        PagedKVCache(net, 2, 4, 9, 4)


# -- (d): the share ties to the model ------------------------------------------

def _moe_layer(first, held, shared):
    from singa_tpu.config.schema import LayerConfig, RoutedMoEConfig
    from singa_tpu.core.layers import create_layer
    layer = create_layer(LayerConfig(
        name="moe", type="kRoutedMoE", routed_moe_param=RoutedMoEConfig(
            num_routed=128, experts_per_token=8, num_held=held,
            first_held=first, expert_hidden=24,
            shared_hidden=24 if shared else 0, renormalize=True,
            routed_scale=2.826)))
    layer.setup([(1, 1, 32)])
    return layer


@pytest.fixture(scope="module")
def moe_case():
    rng = np.random.default_rng(8)
    f32 = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    w = {"router": f32(32, 128), "router_bias": 0.3 * f32(128),
         "w_gate": f32(128, 32, 24) / 6, "w_up": f32(128, 32, 24) / 6,
         "w_down": f32(128, 24, 32) / 5, "shared_gate": f32(32, 24) / 6,
         "shared_up": f32(32, 24) / 6, "shared_down": f32(24, 32) / 5}
    cfg = {"num_experts_per_tok": 8, "route_norm": True,
           "route_scale": 2.826, "first_held_expert": 0,
           "num_shared_experts": 1}
    return w, cfg, f32(1, 150, 32)


def _share(w, x, first, held, shared):
    layer = _moe_layer(first, held, shared)
    p = {f"moe/{k}": jnp.asarray(v) for k, v in w.items()
         if shared or not k.startswith("shared")}
    for k in ("w_gate", "w_up", "w_down"):
        p[f"moe/{k}"] = p[f"moe/{k}"][first:first + held]
    return np.asarray(layer.apply(p, [jnp.asarray(x)], None))


def test_the_eight_shares_add_up_to_the_uncut_layer(moe_case):
    """Each of 8 chips holds 16 of the 128 routed experts (`first_held`
    0, 16, ..., 112), the router scoring and choosing among all 128;
    the shared expert, which every chip computes alike, is counted
    once."""
    w, cfg, x = moe_case
    whole = np.asarray(trinity.moe(jnp.asarray(x), w, cfg))
    parts = [_share(w, x, 16 * r, 16, shared=(r == 0)) for r in range(8)]
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-4, atol=1e-4)
    assert not np.allclose(parts[0], whole, atol=1e-2)
    # one share of the reference is one share of the program
    mine = {k: (v[16:32] if k in ("w_gate", "w_up", "w_down") else v)
            for k, v in w.items()}
    np.testing.assert_allclose(
        np.asarray(trinity.moe(jnp.asarray(x), mine, cfg, first=16,
                               shared=False)), parts[1], rtol=1e-4,
        atol=1e-4)
    # selection is by score + bias, weighting by score alone
    plain = dict(w, router_bias=np.zeros(128, np.float32))
    assert not np.allclose(np.asarray(trinity.moe(jnp.asarray(x), plain,
                                                  cfg)), whole, atol=1e-3)


def test_routing_counts_carry_the_busiest_experts_load(moe_case):
    w, cfg, x = moe_case
    layer = _moe_layer(0, 16, True)
    p = {f"moe/{k}": jnp.asarray(v if k not in ("w_gate", "w_up", "w_down")
                                 else v[:16]) for k, v in w.items()}
    ntoks = jnp.asarray([1] * 100 + [0] * 50, jnp.int32)
    _, entry = layer.apply_paged(p, jnp.asarray(x), layer.init_pool(
        150, 2, BL, jnp.float32), None, ntoks)
    idx, _ = trinity.route(jnp.asarray(x[0, :100]), w, cfg, lambda a: a)
    per = np.bincount(np.asarray(idx).ravel(), minlength=128)[:16]
    assert entry["routed"].tolist() == [per.sum(), (per > 0).sum(), per.max()]


# -- the whole net through the scheduler ---------------------------------------

def test_cb_tokens_are_generates_through_rings_that_wrap(lm):
    """Four slots, ten requests of up to 16 + 40 positions under a
    window of 8: what the scheduler streams is what `generate()` (one
    contiguous cache, the window a mask) produces, slot after slot in
    rings their last tenants wrote."""
    net, params, _ = lm
    sv = CFG["serve"]
    spec = ServeSpec(buckets=((1, CAP),), max_new_tokens=sv["max_new_tokens"],
                     temperature=0.0, eos_id=None, cb="on",
                     cb_slots=sv["cb_slots"], cb_block_len=BL,
                     cb_prompt_cap=CAP, request_timeout_s=600.0)
    engine = InferenceEngine(net, spec, params=params,
                             log_fn=lambda *a, **k: None)
    engine.load()
    sched = ContinuousScheduler(engine, log_fn=lambda *a, **k: None).start()
    rng = np.random.default_rng(6)
    reqs = [(rng.integers(0, CFG["vocab_size"], int(n)).astype(np.int32),
             int(m)) for n, m in zip(rng.integers(1, CAP + 1, 10),
                                     rng.integers(20, 41, 10))]
    try:
        tickets = [sched.submit(t, max_new=m) for t, m in reqs]
        served = [list(t.wait(timeout=600)["tokens"]) for t in tickets]
    finally:
        sched.stop()
    for (toks, m), got in zip(reqs, served):
        want = np.asarray(generate(net, params, toks[None], m))[0]
        assert got == want.tolist()
    snap = engine.stats.snapshot()
    assert snap["cb_ring_blocks"] == RING
    assert snap["cb_window_block_bytes"] == snap["cb_block_bytes"] > 0
    assert (snap["cb_block_copy_bytes"] == snap["cb_window_block_copy_bytes"]
            == snap["cb_block_bytes"] // 2)        # two layers a kind
    assert 0 < snap["cb_window_block_steps"] < snap["cb_live_block_steps"]
    assert 0 < snap["cb_window_block_share"] < 1
    assert snap["cb_routed_max_load"] > 0


def test_serve_stats_exports_the_window_counters():
    from singa_tpu.obs import MetricsRegistry
    from singa_tpu.serve.stats import ServeStats
    st = ServeStats()
    st.gauge("cb_ring_blocks", 129)
    st.gauge("cb_window_block_bytes", 393216)
    st.gauge("cb_block_copy_bytes", 32768)
    st.gauge("cb_window_block_copy_bytes", 32768)
    st.observe_cb_step(64, 1000, 10000, 6400)
    st.observe_cb_step(64, 1000, 10400, 6500)
    st.observe_cb_step(0, 0)                  # a step that decoded nothing
    snap = st.snapshot()
    assert snap["cb_live_block_steps"] == 20400
    assert snap["cb_window_block_steps"] == 12900
    assert snap["cb_window_block_share"] == pytest.approx(0.6324, abs=1e-4)
    registry = MetricsRegistry()
    st.register_into(registry)
    text = registry.render_prometheus()
    assert "singa_serve_cb_window_block_steps_total 12900" in text
    assert "singa_serve_cb_ring_blocks 129" in text
    assert "singa_serve_cb_block_copy_bytes 32768" in text
    assert "singa_serve_cb_window_block_copy_bytes 32768" in text
    plain = ServeStats()
    plain.observe_cb_step(4, 10, 40)
    assert plain.snapshot()["cb_window_block_share"] is None


# -- (e): a kAttention without the options is the layer it was ------------------

def _mistral_tiny():
    cfg = harness._tiny(harness.read_json(
        ROOT, "benchmark", "configs", "mistral7b-serve-l16.json"))
    model = serve_cb.model_config(cfg, 16, 1, "float32")
    net = build_net(model, "kTrain",
                    discover_input_shapes(model, force_synthetic=True))
    made = weights.tree(cfg, 7, jnp.float32)
    return cfg, net, {serve_cb.program_name(k): v for k, v in made.items()}


def _cached_before(layer, params, x, entry, pos):
    """`AttentionLayer.apply_cached` as it stood before the window, the
    q/k norm and the gate, op for op."""
    t = x.shape[1]
    q, k, v = layer.qkv(params, x, pos + jnp.arange(t), DECODE_CTX)
    k_cache = jax.lax.dynamic_update_slice(
        entry["k"], k.astype(entry["k"].dtype), (0, 0, pos, 0))
    v_cache = jax.lax.dynamic_update_slice(
        entry["v"], v.astype(entry["v"].dtype), (0, 0, pos, 0))
    out = attend_cache(q, k_cache, v_cache, pos, None)
    out = layer._proj(params, layer.wo, out.astype(x.dtype), DECODE_CTX)
    return out, {"k": k_cache, "v": v_cache}


def _paged_before(layer, params, x, entry, tables, ntoks):
    """`AttentionLayer.apply_paged` as it stood before them (over the
    one pool of keys and values, PR 38)."""
    _, s, _ = x.shape
    bl = entry["kv"].shape[2]
    q, k, v = layer.qkv(params, x, ntoks, DECODE_CTX)
    bidx = tables[jnp.arange(s), ntoks // bl]
    off = ntoks % bl
    new = jnp.concatenate([k[0], v[0]], 0).transpose(1, 0, 2)
    pool = write_token(entry["kv"], bidx, off, new)
    out = paged_decode_attention(q[0].transpose(1, 0, 2), pool, tables,
                                 ntoks)
    out = out.reshape(1, s, -1)
    out = layer._proj(params, layer.wo, out.astype(x.dtype), DECODE_CTX)
    return out, {"kv": pool}


def test_a_layer_without_the_options_is_bit_identical_to_the_one_before():
    """The Mistral cells' layer: no window, no norm, no gate.  Its
    parameters, its serving state and the outputs of its two serving
    methods are those of the bodies it had before the options existed,
    bit for bit, and so is a whole prefill and decode step."""
    cfg, net, params = _mistral_tiny()
    layer = net.layers["attn0"]
    assert (layer.window, layer.qk_norm, layer.gate) == (0, False, False)
    assert sorted(s.name for s in layer.param_specs) == [
        "attn0/wk", "attn0/wo", "attn0/wq", "attn0/wv"]
    assert set(layer.init_cache(1, 16, jnp.float32)) == {"k", "v"}
    assert layer.init_pool(4, 9, 4, jnp.float32)["kv"].shape[:2] == (
        9, 2 * layer.kv_heads)
    assert state_bytes(net, 4)["window_block"] == 0
    full = net._resolve_params(params)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((1, 16, cfg["hidden_size"])),
                    jnp.float32)
    got, cache = layer.apply_cached(full, x, layer.init_cache(
        1, 16, jnp.float32), 0, plen=jnp.int32(11))
    want, cache0 = _cached_before(layer, full, x, layer.init_cache(
        1, 16, jnp.float32), 0)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(cache["k"]), np.asarray(cache0["k"]))
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8], [0, 0, 0, 0]],
                         jnp.int32)
    ntoks = jnp.asarray([11, 3, 0], jnp.int32)
    pool = layer.scatter_prefill(layer.init_pool(3, 9, 4, jnp.float32),
                                 cache, tables[0])
    xs = x[:, :3]
    got, new = layer.apply_paged(full, xs, pool, tables, ntoks)
    want, new0 = _paged_before(layer, full, xs, pool, tables, ntoks)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert set(new) == set(new0) == {"kv"}
    assert np.array_equal(np.asarray(new["kv"]), np.asarray(new0["kv"]))
    # and the programs: the walkers trace to the same equations with the
    # new options named and switched off as with none named
    named = build_net(_named_off(cfg), "kTrain", discover_input_shapes(
        _named_off(cfg), force_synthetic=True))
    toks = jnp.asarray(rng.integers(0, cfg["vocab_size"], (1, 16)), jnp.int32)
    trace = lambda n: str(jax.make_jaxpr(lambda p, t: forward_cached(  # noqa: E731
        n, p, t, init_cache(n, 1, 16, jnp.float32), 0))(params, toks))
    assert trace(net) == trace(named)


def _named_off(cfg):
    model = serve_cb.model_config(cfg, 16, 1, "float32")
    for layer in model.neuralnet.layer:
        if layer.type == "kAttention":
            layer.attention_param.window = 0
            layer.attention_param.qk_norm = False
            layer.attention_param.gate = False
    return model
