"""The hybrid recurrent / latent / sparse LM (kKDA, kMLA, kRoutedMoE)
against the plain reference (`benchmark/reference/kimi_linear.py`) on
seeded random weights at the configuration's tiny size, float32, on the
CPU: the ops alone, prefill-then-decode through the serving state, the
whole net through the continuous-batching scheduler, and the chip's
share of the experts against the uncut layer."""

import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from oracles import attend_absorbed  # noqa: E402
from benchmark import harness, kimi_weights  # noqa: E402
from benchmark.reference import kimi_linear  # noqa: E402
from benchmark.runners import serve_kimi  # noqa: E402
from singa_tpu.core.net import build_net  # noqa: E402
from singa_tpu.data import discover_input_shapes  # noqa: E402
from singa_tpu.models.generate import (forward_cached, forward_paged,  # noqa: E402
                                       generate, init_cache, scatter_prefill)
from singa_tpu.ops import kda as kda_ops  # noqa: E402
from singa_tpu.ops import moe as moe_ops  # noqa: E402
from singa_tpu.serve.engine import InferenceEngine, ServeSpec  # noqa: E402
from singa_tpu.serve.kvcache import init_pools, state_bytes  # noqa: E402
from singa_tpu.serve.scheduler import ContinuousScheduler  # noqa: E402

CFG = harness._tiny(harness.read_json(
    ROOT, "benchmark", "configs", "kimilinear-serve-l17-ep8.json"))
CAP, BL = 16, 4


@pytest.fixture(scope="module")
def lm():
    model = serve_kimi.model_config(CFG, CAP)
    net = build_net(model, "kTrain",
                    discover_input_shapes(model, force_synthetic=True))
    made = kimi_weights.tree(CFG, 11, jnp.float32)
    params = {serve_kimi.program_name(k): v for k, v in made.items()}
    return net, params, made


@pytest.fixture(autouse=True)
def exact():
    with jax.default_matmul_precision("highest"):
        yield


def _ref_logits(made, toks):
    return np.asarray(kimi_linear.logits(np.asarray(toks)[None],
                                         lambda n: made[n], CFG)[0])


# -- the ops -----------------------------------------------------------------

def _kda_inputs(rng, b, t, h, d):
    q, k, v = (rng.standard_normal((b, t, h, d)).astype(np.float32)
               for _ in range(3))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    # decays from none to e^-30 a token: the fast channels would overflow
    # a factoring that forms exp(-G)
    g = -np.exp(rng.uniform(-7, 3.4, (b, t, h, d))).astype(np.float32)
    beta = rng.uniform(0, 1, (b, t, h)).astype(np.float32)
    s0 = rng.standard_normal((b, h, d, d)).astype(np.float32)
    return q, k, v, g, beta, s0


@pytest.mark.parametrize("t,chunk", [(1, 8), (7, 8), (8, 8), (9, 8),
                                     (40, 8), (40, 64), (64, 64), (130, 64),
                                     (64, 32), (48, 24)])
def test_chunked_delta_rule_is_the_recurrence(t, chunk):
    """(64, 64), (130, 64) and (64, 32) cross sub-chunk borders; a chunk
    of 24 is no multiple of 16: the one-sub-chunk path."""
    args = _kda_inputs(np.random.default_rng(t), 2, t, 2, 16)
    o_ref, s_ref = kda_ops.delta_rule_scan(*map(jnp.asarray, args))
    o, s = kda_ops.delta_rule_chunked(*args, chunk=chunk)
    assert np.isfinite(np.asarray(o)).all()
    # (130, 64) alone reads 1.60 of atol 1e-5 (the rest 0.31-0.72), for
    # the one-sub-chunk form too: the running sum G is float32 and
    # reaches hundreds at these decays, so G_t - G_i is off by its ulp
    # (with G in float64 and all else float32 the case reads 0.03)
    atol = 3e-5 if t == 130 else 1e-5
    np.testing.assert_allclose(o, o_ref, rtol=1e-4, atol=atol)
    np.testing.assert_allclose(s, s_ref, rtol=1e-4, atol=atol)


@pytest.mark.parametrize("t,chunk", [(64, 64), (130, 64), (64, 32)])
def test_sub_chunks_change_nothing_but_the_order_of_the_sums(
        t, chunk, monkeypatch):
    """One sub-chunk a chunk (SUB = the chunk) is the three-index form
    on the whole chunk; sixteen rows a sub-chunk gives the same A and B
    to float32's rounding, a hundred times closer than either is to the
    recurrence."""
    args = _kda_inputs(np.random.default_rng(t), 2, t, 2, 16)
    o, s = kda_ops.delta_rule_chunked(*args, chunk=chunk)
    monkeypatch.setattr(kda_ops, "SUB", chunk)
    o_one, s_one = kda_ops.delta_rule_chunked(*args, chunk=chunk)
    np.testing.assert_allclose(o, o_one, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(s, s_one, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("beta_top", [1.0, 2.0])
def test_a_channel_that_forgets_e30_a_token_stays_finite(beta_top):
    """Every exponent of the factored form is <= 0: a channel whose
    decay over a 64-chunk is e^-1920 (exp(-G_i) would overflow at the
    third token) gives the recurrence's output, and so do its slow
    neighbours in the same products."""
    rng = np.random.default_rng(30)
    q, k, v, g, beta, s0 = _kda_inputs(rng, 1, 64, 2, 16)
    g[..., 0] = -30.0
    g[..., 1] = -88.0
    beta = (beta * beta_top).astype(np.float32)
    o_ref, s_ref = kda_ops.delta_rule_scan(*map(jnp.asarray,
                                                (q, k, v, g, beta, s0)))
    o, s = kda_ops.delta_rule_chunked(q, k, v, g, beta, s0)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(
        np.asarray(s)).all()
    np.testing.assert_allclose(o, o_ref, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(s, s_ref, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("t,chunk", [(40, 64), (64, 64)])
def test_the_chunked_forms_gradient_is_the_recurrences(t, chunk):
    """`KDALayer.apply` trains through the chunked form: the gradient
    of a scalar of (o, state) in every argument, masked exponents
    (-inf) and all, against the gradient through the scan."""
    rng = np.random.default_rng(t)
    args = tuple(map(jnp.asarray, _kda_inputs(rng, 2, t, 2, 16)))
    w_o = jnp.asarray(rng.standard_normal((2, t, 2, 16)), jnp.float32)
    w_s = jnp.asarray(rng.standard_normal((2, 2, 16, 16)), jnp.float32)

    def scalar(form):
        def of(*a):
            o, s = form(*a)
            return jnp.sum(o * w_o) + jnp.sum(s * w_s)
        return jax.grad(of, argnums=tuple(range(6)))(*args)

    want = scalar(kda_ops.delta_rule_scan)
    got = scalar(lambda *a: kda_ops.delta_rule_chunked(*a, chunk=chunk))
    for name, a, b in zip(("q", "k", "v", "g", "beta", "state"), got, want):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(a, b, rtol=1e-3, err_msg=name,
                                   atol=1e-4 * float(jnp.abs(b).max()))


@pytest.mark.parametrize("real,t,chunk", [(0, 16, 8), (1, 16, 8), (3, 16, 8),
                                          (15, 16, 8), (16, 16, 8),
                                          (17, 64, 64), (31, 64, 64),
                                          (33, 64, 64)])
def test_padded_rows_leave_the_state_and_the_conv_tail_alone(real, t, chunk):
    """17, 31 and 33 of 64: `valid` ends INSIDE a sub-chunk."""
    rng = np.random.default_rng(real)
    q, k, v, g, beta, s0 = _kda_inputs(rng, 1, t, 2, 16)
    valid = (np.arange(t) < real)[None]
    _, s = kda_ops.delta_rule_chunked(q, k, v, g, beta, s0, valid,
                                      chunk=chunk)
    _, want = kda_ops.delta_rule_scan(*(jnp.asarray(a[:, :real])
                                        for a in (q, k, v, g, beta)),
                                      jnp.asarray(s0))
    np.testing.assert_allclose(s, want, rtol=1e-4, atol=1e-5)
    x = rng.standard_normal((1, t, 6)).astype(np.float32)
    tail0 = rng.standard_normal((1, 3, 6)).astype(np.float32)
    w = rng.standard_normal((6, 4)).astype(np.float32)
    y, tail = kda_ops.short_conv(x, tail0, w, valid)
    y_want, tail_want = kda_ops.short_conv(x[:, :real], tail0, w)
    np.testing.assert_allclose(y[:, :real], y_want, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(tail, tail_want)


def test_left_padding_reads_as_the_zeros_before_a_sequence():
    rng = np.random.default_rng(5)
    x = rng.standard_normal((1, 9, 6)).astype(np.float32)
    w = rng.standard_normal((6, 4)).astype(np.float32)
    zeros = np.zeros((1, 3, 6), np.float32)
    valid = (np.arange(9) >= 4)[None]
    y, tail = kda_ops.short_conv(x, zeros, w, valid)
    y_want, tail_want = kda_ops.short_conv(x[:, 4:], zeros, w)
    np.testing.assert_allclose(y[:, 4:], y_want, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(tail, tail_want)


# -- (a) + (b): prefill, then decode through the serving state -----------------

def _prefill_then_decode(net, params, seq, plen, slot, nslots=3):
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
    # a pool of whole extents, as the cache's is: the kernel copies the
    # extent that holds a slot's horizon whole
    pools = init_pools(net, table.shape[1] + 1, BL, jnp.float32, nslots)
    # the slot's last tenant left garbage behind
    pools = jax.tree_util.tree_map(
        lambda a: jnp.full_like(a, jnp.nan) if a.dtype == jnp.float32
        and a.shape[0] == nslots else a, pools)
    pools = scatter_prefill(pools, cache, jnp.asarray(table[slot, :CAP // BL]),
                            jnp.int32(slot), net)
    out = [np.asarray(lg[0, plen - 1])]
    for pos in range(plen, len(seq)):
        tok = np.zeros((1, nslots), np.int32)
        ntoks = np.zeros((nslots,), np.int32)
        tok[0, slot], ntoks[slot] = seq[pos], pos
        lg, pools = forward_paged(net, params, jnp.asarray(tok), pools,
                                  jnp.asarray(table), jnp.asarray(ntoks))
        out.append(np.asarray(lg[0, slot]))
    return np.stack(out)


@pytest.mark.parametrize("plen", [1, 3, CAP - 1, CAP])
@pytest.mark.parametrize("chunk", [8, 64])
def test_padded_prefill_then_decode_equals_the_full_forward(
        lm, monkeypatch, plen, chunk):
    """Right-padded to the cap: the state handed to the slot is the one
    after the last REAL token, the conv tail its last three real rows,
    and the latent rows land in the slot's blocks; with chunk 8 the
    16-row prefill crosses a chunk boundary."""
    monkeypatch.setattr(kda_ops, "CHUNK", chunk)
    net, params, made = lm
    seq = np.random.default_rng(plen).integers(0, CFG["vocab_size"],
                                               plen + 5).astype(np.int32)
    got = _prefill_then_decode(net, params, seq, plen, slot=1)
    want = _ref_logits(made, seq)[plen - 1:]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_mla_absorbed_and_expanded_forms_agree(lm):
    net, params, _ = lm
    layer = next(net.layers[n] for n in net.topo
                 if net.layers[n].cfg.type == "kMLA")
    full = net._resolve_params(params)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((3, 9, CFG["hidden_size"])),
                    jnp.float32)
    q, lat = layer._project(full, x)
    allowed = jnp.asarray(rng.uniform(size=(3, 9)) < 0.7).at[:, 0].set(True)
    wide = layer._attend_expanded(full, q[:, -1:], lat, allowed[:, None, :])
    narrow = attend_absorbed(layer, full, q[:, -1], lat, allowed)
    np.testing.assert_allclose(narrow, wide[:, 0], rtol=1e-4, atol=1e-5)


# -- (c) + (d): the whole net through the scheduler ---------------------------

def _engine(lm, slots):
    net, params, _ = lm
    # a queued request waits out the decode program's compile, which a
    # loaded test machine stretches past the default 5 s
    spec = ServeSpec(buckets=((1, CAP),), max_new_tokens=8, temperature=0.0,
                     eos_id=None, cb="on", cb_slots=slots, cb_block_len=BL,
                     cb_prompt_cap=CAP, request_timeout_s=60.0)
    engine = InferenceEngine(net, spec, params=params,
                             log_fn=lambda *a, **k: None)
    engine.load()
    return engine


def test_decode_program_never_materialises_the_gathered_latent_tables(lm):
    """What `MLALayer.apply_paged` used to build per layer, every slot's
    whole table row of latent rows (S, T * bl, row), is in no value of
    the lowered cb decode program: the paged kernel (interpreted here:
    a `while`) reads the pool through the table.  The old formulation's
    lowering, `oracles.attend_absorbed` over the gathered table, is the same
    search's control."""
    net, _, _ = lm
    engine = _engine(lm, 3)
    spec = engine.spec
    s, t, bl = spec.cb_slots, spec.cb_blocks_per_slot, spec.cb_block_len
    layer = next(net.layers[n] for n in net.topo
                 if net.layers[n].cfg.type == "kMLA")
    gathered = f"tensor<{s}x{t * bl}x{layer.pool_row}x"
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.int32)  # noqa: E731
    text = jax.jit(engine._build_cb_decode(), donate_argnums=(1,)).lower(
        engine.params, engine._pools_spec(), shape(s), shape(s),
        shape(s, t), jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text()
    assert "while" in text
    assert gathered not in text

    def gather_formulation(params, q, pool, tables, ntoks):
        mine = pool[tables].reshape(s, t * bl, layer.pool_row)
        allowed = jnp.arange(t * bl)[None, :] <= ntoks[:, None]
        return attend_absorbed(layer, params, q, mine, allowed)

    f32 = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.float32)  # noqa: E731
    control = jax.jit(gather_formulation).lower(
        net._resolve_params(engine.params),
        f32(s, layer.heads, layer.nope + layer.rope),
        f32(spec.cb_pool_blocks, bl, layer.pool_row), shape(s, t),
        shape(s)).as_text()
    assert gathered in control


def test_reused_slots_carry_nothing_of_their_last_tenant(lm):
    """Two slots, seven requests: every slot is retired and re-admitted.
    A freed slot's recurrent state and conv tail are filled with NaN, so
    a tenant that read any of it would serve NaN's argmax."""
    net, params, made = lm
    engine = _engine(lm, 2)
    sched = ContinuousScheduler(engine, log_fn=lambda *a, **k: None)
    retire = sched._retire

    pairs = []

    def poisoned(slot, finish, step_no):
        held = sched.kv.tables[sched.kv.tables != 0]
        pairs.extend(held.reshape(-1, 2).tolist())
        retire(slot, finish, step_no)
        sched.kv.pools = {
            n: ({k: v.at[slot].set(jnp.nan) for k, v in e.items()}
                if "S" in e else e) for n, e in sched.kv.pools.items()}

    sched._retire = poisoned
    sched.start()
    rng = np.random.default_rng(9)
    reqs = [(rng.integers(0, CFG["vocab_size"], int(p)).astype(np.int32),
             int(n)) for p, n in zip([1, 16, 3, 15, 7, 2, 9],
                                     [8, 5, 8, 2, 6, 8, 4])]
    try:
        tickets = [sched.submit(t, max_new=n) for t, n in reqs]
        served = [tk.wait(timeout=300)["tokens"] for tk in tickets]
    finally:
        sched.stop()
    assert engine.stats.cb_prefills == 7
    for (toks, n), out in zip(reqs, served):
        assert len(out) == n
        seq = np.concatenate([toks, np.asarray(out[:-1], np.int32)])
        ref = _ref_logits(made, seq)[len(toks) - 1:]
        gap = ref.max(-1) - ref[np.arange(n), out]
        assert np.all(gap < 1e-3), (gap, out)
    # routing counts rode home with every decode step's tokens
    st = engine.stats
    n_moe = sum(net.layers[n].cfg.type == "kRoutedMoE" for n in net.topo)
    assert st.cb_routed_layer_steps == st.cb_decode_steps * n_moe > 0
    busy_tokens = st.cb_active_slot_steps       # an upper bound
    assert 0 < st.cb_routed_experts_touched <= st.cb_routed_assignments
    assert st.cb_routed_assignments <= (busy_tokens * n_moe
                                        * CFG["num_experts_per_token"])
    per = state_bytes(net, BL, jnp.float32)
    assert st.cb_slot_state_bytes == per["slot"] > 0
    assert st.cb_block_bytes == per["block"] > 0
    # a copy of the paged kernel moves one kMLA layer's block of latent
    # rows; no layer of this model keeps a ring
    mla = [net.layers[n] for n in net.topo if net.layers[n].cfg.type == "kMLA"]
    assert st.cb_block_copy_bytes == per["block_copy"] == (
        BL * mla[0].pool_row * 4) == per["block"] // len(mla)
    assert st.cb_window_block_copy_bytes == per["window_block_copy"] == 0
    # and their blocks come two a copy under this table of 6: every
    # real pair of a row consecutive and aligned while the slots churned
    # (`poisoned` looked after each retirement), a copy an extent
    assert st.cb_extent_blocks == sched.kv.extent_blocks == 2
    assert pairs and all(b == a + 1 and a % 2 == 1 for a, b in pairs)
    assert (st.cb_live_block_steps / 2 <= st.cb_block_copies
            < st.cb_live_block_steps)


def test_cb_greedy_tokens_equal_generates(lm):
    net, params, _ = lm
    engine = _engine(lm, 3)
    sched = ContinuousScheduler(engine, log_fn=lambda *a, **k: None).start()
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, CFG["vocab_size"], p).astype(np.int32)
               for p in (5, 16, 2, 11)]
    try:
        tickets = [sched.submit(p, max_new=8) for p in prompts]
        served = [t.wait(timeout=300)["tokens"] for t in tickets]
    finally:
        sched.stop()
    for p, out in zip(prompts, served):
        want = np.asarray(generate(net, params, p[None], 8))[0]
        assert list(out) == list(want)


RUNG_PLENS = (3, 4, 5, 7, 8, 9)      # rung-1, rung, rung+1 of (4, 8, 16)


@pytest.fixture(scope="module")
def ladder_served(lm):
    """The prefill ladder (serve/engine.py `cb_prefill_widths`) with its
    floor patched down to one block: prompts on both sides of the 4 and
    8 rungs through two slots, so the later ones are admitted behind a
    step in flight.  The floor is put back before any test runs."""
    from singa_tpu.serve import engine as engine_mod
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, CFG["vocab_size"], p).astype(np.int32)
               for p in RUNG_PLENS]
    with pytest.MonkeyPatch.context() as mp, \
            jax.default_matmul_precision("highest"):
        mp.setattr(engine_mod, "CB_PREFILL_FLOOR", BL)
        engine = _engine(lm, 2)
        assert engine.spec.cb_prefill_widths == (4, 8, 16)
        # compiled before anything queues: a request's deadline runs
        # while `start()` compiles and runs the rungs
        assert engine.warmup() == 4
        sched = ContinuousScheduler(engine, log_fn=lambda *a, **k: None)
        tickets = [sched.submit(p, max_new=6) for p in prompts]
        sched.start()
        try:
            served = [t.wait(timeout=300)["tokens"] for t in tickets]
        finally:
            sched.stop()
    return prompts, served, engine.stats.snapshot()


@pytest.mark.parametrize("i", range(len(RUNG_PLENS)),
                         ids=[f"plen{p}" for p in RUNG_PLENS])
def test_ladder_tokens_equal_generates_around_every_rung(lm, ladder_served,
                                                         i):
    """KDA state, MLA latent and routed experts at a prefill width that
    follows the prompt."""
    net, params, _ = lm
    prompts, served, snap = ladder_served
    want = np.asarray(generate(net, params, prompts[i][None], 6))[0]
    assert list(served[i]) == list(want), f"plen={prompts[i].size}"
    assert snap["cb_prefill_width_rows"] == 4 + 4 + 8 + 8 + 8 + 16
    assert snap["cb_prefill_fill_share"] == round(sum(RUNG_PLENS) / 48, 4)


def test_static_path_left_padding_matches_the_unpadded_prompt(lm):
    """The bucket path LEFT-pads: pads leave the recurrence alone and
    read as the zeros before a sequence's start."""
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


def test_serving_in_bf16_keeps_the_recurrent_state_in_float32(lm):
    """The fp8 control fails the cell's limits on the chip; rounding the
    KDA state to bf16 after every token does NOT (PERF.md 2), so the
    state's precision is held here: bf16 weights and latent rows, a
    float32 state that a decode step leaves unrounded."""
    net, params, _ = lm
    half = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
    pools = init_pools(net, 5, BL, jnp.bfloat16, 2)
    kinds = {n: net.layers[n].cfg.type for n in pools}
    for name, entry in pools.items():
        for key, a in entry.items():
            want = {"S": jnp.float32, "routed": jnp.int32}.get(key,
                                                               jnp.bfloat16)
            assert a.dtype == want, (name, key)
    table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    for pos in range(1, 4):
        _, pools = forward_paged(net, half, jnp.asarray([[7, 9]]), pools,
                                 table, jnp.asarray([pos, pos], jnp.int32))
    for name, entry in pools.items():
        if kinds[name] == "kKDA":
            s = np.asarray(entry["S"])
            assert s.dtype == np.float32
            rounded = np.asarray(jnp.asarray(s).astype(jnp.bfloat16)
                                 .astype(jnp.float32))
            assert np.mean(s != rounded) > 0.5


# -- the share ties to the model ----------------------------------------------

def _moe_layer(first, held, shared):
    from singa_tpu.config.schema import LayerConfig, RoutedMoEConfig
    from singa_tpu.core.layers import create_layer
    layer = create_layer(LayerConfig(
        name="moe", type="kRoutedMoE", routed_moe_param=RoutedMoEConfig(
            num_routed=16, experts_per_token=4, num_held=held,
            first_held=first, expert_hidden=24,
            shared_hidden=24 if shared else 0, renormalize=True,
            routed_scale=2.446)))
    layer.setup([(1, 1, 32)])
    return layer


@pytest.fixture(scope="module")
def moe_case():
    rng = np.random.default_rng(8)
    f32 = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    w = {"router": f32(32, 16), "router_bias": 0.3 * f32(16),
         "w_gate": f32(16, 32, 24) / 6, "w_up": f32(16, 32, 24) / 6,
         "w_down": f32(16, 24, 32) / 5, "shared_gate": f32(32, 24) / 6,
         "shared_up": f32(32, 24) / 6, "shared_down": f32(24, 32) / 5}
    cfg = {"num_experts_per_token": 4, "moe_renormalize": True,
           "routed_scaling_factor": 2.446, "first_held_expert": 0,
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
    """Each of 8 chips holds 2 of the 16 routed experts; the shared
    expert, which every chip computes alike, is counted once."""
    w, cfg, x = moe_case
    whole = np.asarray(kimi_linear.moe(jnp.asarray(x), w, cfg))
    parts = [_share(w, x, 2 * r, 2, shared=(r == 0)) for r in range(8)]
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-4, atol=1e-4)
    assert not np.allclose(parts[0], whole, atol=1e-2)
    # selection is by score + bias, weighting by score alone
    plain = dict(w, router_bias=np.zeros(16, np.float32))
    assert not np.allclose(np.asarray(kimi_linear.moe(jnp.asarray(x), plain,
                                                      cfg)), whole, atol=1e-3)


def test_a_token_none_of_whose_experts_is_held_gets_the_shared_expert_alone(
        moe_case):
    w, cfg, x = moe_case
    idx, _ = kimi_linear.route(jnp.asarray(x[0]), w, cfg, lambda a: a)
    lonely = np.flatnonzero(~np.any(np.asarray(idx) < 2, axis=1))
    assert len(lonely) > 10
    got = _share(w, x, 0, 2, shared=True)[0, lonely]
    want = kimi_linear.swiglu(jnp.asarray(x[0, lonely]), w["shared_gate"],
                              w["shared_up"], w["shared_down"], lambda a: a)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("rows", [1, 7, 150])
def test_no_token_is_dropped_and_no_row_depends_on_another(moe_case, rows):
    """A row's output is the same alone and in company, whatever the
    others route to."""
    w, cfg, x = moe_case
    whole = _share(w, x, 0, 4, shared=True)[0]
    alone = _share(w, x[:, :rows], 0, 4, shared=True)[0]
    np.testing.assert_allclose(alone, whole[:rows], rtol=1e-4, atol=1e-5)
    want = np.asarray(kimi_linear.moe(
        jnp.asarray(x), {k: (v[:4] if k in ("w_gate", "w_up", "w_down")
                             else v) for k, v in w.items()}, cfg))[0]
    np.testing.assert_allclose(whole, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("block", [8, 64])
def test_a_long_run_is_walked_in_row_blocks_up_to_its_last_real_row(
        moe_case, monkeypatch, block):
    """150 rows in blocks of 8 or 64; with `valid` only the blocks up
    to the last real row are walked, the rest read zero."""
    w, cfg, x = moe_case
    want = _share(w, x, 0, 4, shared=False)[0]
    monkeypatch.setattr(moe_ops, "ROW_BLOCK", block)
    np.testing.assert_allclose(_share(w, x, 0, 4, shared=False)[0], want,
                               rtol=1e-4, atol=1e-5)
    idx, weights = kimi_linear.route(jnp.asarray(x[0]), w, cfg, lambda a: a)
    valid = jnp.arange(150) < 70
    got, counts = moe_ops.held_experts_ffn(
        jnp.asarray(x[0]), idx.astype(jnp.int32), weights,
        *(jnp.asarray(w[k][:4]) for k in ("w_gate", "w_up", "w_down")), 0,
        valid)
    np.testing.assert_allclose(got[:70], want[:70], rtol=1e-4, atol=1e-5)
    assert not np.any(np.asarray(got[70:]))
    assert int(counts[0]) == int(np.sum(np.asarray(idx)[:70] < 4))


def test_routing_counts_against_a_hand_count():
    idx = jnp.asarray([[0, 5, 9, 3], [7, 8, 1, 2], [4, 5, 6, 7],
                       [1, 0, 15, 14]], jnp.int32)
    x = jnp.ones((4, 8), jnp.float32)
    w = jnp.ones((4, 4), jnp.float32)
    gate = jnp.zeros((4, 8, 6), jnp.float32)
    down = jnp.zeros((4, 6, 8), jnp.float32)
    # experts 4..7 are held; row 3 is a slot not in use
    valid = jnp.asarray([True, True, True, False])
    _, counts = moe_ops.held_experts_ffn(x, idx, w, gate, gate, down, 4,
                                         valid)
    # row 0: 5; row 1: 7; row 2: 4, 5, 6, 7 -> 6 pairs on experts {4,5,6,7}
    assert counts.tolist() == [6, 4]
    _, counts = moe_ops.held_experts_ffn(x, idx, w, gate, gate, down, 12,
                                         valid)
    assert counts.tolist() == [0, 0]


def test_serve_stats_exports_the_routing_and_byte_counters():
    from singa_tpu.serve.stats import ServeStats
    st = ServeStats()
    st.observe_routing(96, 30, 16)
    st.observe_routing(90, 28, 16)
    st.gauge("cb_slot_state_bytes", 2170000)
    st.gauge("cb_block_bytes", 73728)
    snap = st.snapshot()
    assert snap["cb_routed_layer_steps"] == 32
    assert snap["cb_routed_assignments"] == 186
    assert snap["cb_routed_experts_touched"] == 58
    assert snap["cb_slot_state_bytes"] == 2170000
    assert snap["cb_block_bytes"] == 73728
