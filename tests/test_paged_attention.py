"""Paged decode attention (ops/paged_attention.py): the kernel against
the plain gather reference on poisoned pools, the compiled cb decode
program's freedom from the materialised gather, and the scheduler's
`cb_live_block_share` counter.

The kernel runs interpreted here (CPU), at geometries kept tiny: the
Mosaic compile at the serving cell's real geometry is
tests/benchmark/test_bench_preflight.py's."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu.core.net import build_net
from singa_tpu.models.transformer import transformer_lm
from singa_tpu.ops.paged_attention import (paged_attention_reference,
                                           paged_decode_attention)
from singa_tpu.serve import InferenceEngine, InferenceServer, ServeSpec
from singa_tpu.serve.kvcache import NULL_BLOCK

pytestmark = pytest.mark.serve

S, HKV, D, BL, T = 4, 2, 8, 4, 6
FULL = T * BL - 1
# per-slot ntoks; None marks an inactive slot (ntoks 0, null table row)
LENGTHS = {
    "all_inactive": [None, None, None, None],
    "ntoks_0": [0, 0, 0, 0],
    "ntoks_bl-1": [BL - 1] * S,
    "ntoks_bl": [BL] * S,
    "ntoks_bl+1": [BL + 1] * S,
    "ntoks_T*bl-1": [FULL] * S,
    "mixed": [1, BL, 2 * BL + 1, FULL],
    "inactive_among_active": [None, 5, None, 17],
}
_kernel = paged_decode_attention        # jitted inside
_reference = jax.jit(paged_attention_reference)


def _case(lengths, groups, dtype, seed):
    """q, a clean and a poisoned copy of the pools, tables, ntoks.  The
    table is a shuffled (non-monotone) draw of the pool's blocks; a
    slot's reservation ends somewhere at or after its last live block
    and the row's tail is the null block.  In the poisoned copy every
    position no slot may see is nan (K) or inf (V): blocks no live
    table entry names, the tail of each last live block, and the null
    block past its position 0 (which an inactive slot attends)."""
    rng = np.random.default_rng(seed)
    nb = S * T + 1
    q = rng.standard_normal((S, HKV * groups, D)).astype(np.float32)
    k = rng.standard_normal((nb, HKV, BL, D)).astype(np.float32)
    v = rng.standard_normal((nb, HKV, BL, D)).astype(np.float32)
    tables = rng.permutation(np.arange(1, nb)).reshape(S, T).astype(np.int32)
    ntoks = np.zeros((S,), np.int32)
    seen = np.zeros((nb, BL), bool)
    seen[NULL_BLOCK, 0] = True
    for s, n in enumerate(lengths):
        if n is None:
            tables[s] = NULL_BLOCK
            continue
        ntoks[s] = n
        live = n // BL + 1
        tables[s, rng.integers(live, T + 1):] = NULL_BLOCK
        for p in range(n + 1):
            seen[tables[s, p // BL], p % BL] = True
    hide = ~seen[:, None, :, None]
    clean = [np.where(hide, 0.0, a) for a in (k, v)]
    poisoned = [np.where(hide, bad, a)
                for a, bad in ((k, np.nan), (v, np.inf))]
    to = lambda a: jnp.asarray(a, dtype)                      # noqa: E731
    return (to(q), [to(a) for a in clean], [to(a) for a in poisoned],
            jnp.asarray(tables), jnp.asarray(ntoks))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("groups", [1, 4], ids=["mha", "gqa4"])
@pytest.mark.parametrize("name", list(LENGTHS))
def test_kernel_matches_gather_reference_on_poisoned_pools(name, groups,
                                                           dtype, tol):
    q, clean, poisoned, tables, ntoks = _case(
        LENGTHS[name], groups, dtype, seed=len(name) + groups)
    want = np.asarray(_reference(q, *clean, tables, ntoks), np.float32)
    got = np.asarray(_kernel(q, *poisoned, tables, ntoks), np.float32)
    assert np.isfinite(got).all(), "the kernel read past a slot's horizon"
    assert np.max(np.abs(got - want)) <= tol
    # the table's tail and the unseen blocks do not reach the result
    # of the reference either: it is a fair oracle on the same pools
    same = np.asarray(_reference(q, *poisoned, tables, ntoks), np.float32)
    assert np.array_equal(same, want)


def test_kernel_refuses_a_shape_it_cannot_tile_on_the_chip(monkeypatch):
    from singa_tpu.ops import attention
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    q, clean, _, tables, ntoks = _case(LENGTHS["mixed"], 1, jnp.float32, 0)
    with pytest.raises(ValueError, match=r"\(25, 2, 4, 8\)"):
        paged_decode_attention(q, *clean, tables, ntoks)


# -- the compiled decode program ---------------------------------------------

VOCAB, SEQ = 64, 16
LM_HEADS, LM_KV_HEADS, LM_HEAD_DIM = 4, 2, 8


def _engine(**spec):
    cfg = transformer_lm(vocab_size=VOCAB, num_layers=2, embed_dim=32,
                         num_heads=LM_HEADS, num_kv_heads=LM_KV_HEADS,
                         head_dim=LM_HEAD_DIM,
                         seq_len=SEQ, batchsize=2)
    net = build_net(cfg, "kTest",
                    {"data": {"input": (SEQ,), "target": (SEQ,)}})
    params = net.init_params(jax.random.PRNGKey(0))
    spec = ServeSpec(buckets=((2, SEQ),), temperature=0.0,
                     request_timeout_s=30.0, cb="on", cb_block_len=4,
                     **spec)
    return InferenceEngine(net, spec, params=params, log_fn=lambda s: None)


def test_decode_program_never_materialises_the_gathered_tables():
    """What `_attn_paged` used to build per layer and side, (S, T, Hkv,
    bl, D) and its (S, Hkv, T*bl, D) transpose, is in no value of the
    lowered cb decode program; the reference's lowering, the same
    search's control, has both."""
    engine = _engine(max_new_tokens=8, cb_slots=3)
    spec = engine.spec
    s, t, bl = spec.cb_slots, spec.cb_blocks_per_slot, spec.cb_block_len
    hkv, d = LM_KV_HEADS, LM_HEAD_DIM
    gathered = (f"tensor<{s}x{t}x{hkv}x{bl}x{d}x",
                f"tensor<{s}x{hkv}x{t * bl}x{d}x")
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.int32)  # noqa: E731
    text = jax.jit(engine._build_cb_decode(), donate_argnums=(1,)).lower(
        engine.params, engine._pools_spec(), shape(s), shape(s),
        shape(s, t), jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text()
    assert "while" in text                     # the interpreted kernel
    for needle in gathered:
        assert needle not in text, needle
    pool = jax.ShapeDtypeStruct((spec.cb_pool_blocks, hkv, bl, d),
                                jnp.float32)
    control = jax.jit(paged_attention_reference).lower(
        jax.ShapeDtypeStruct((s, LM_HEADS, d), jnp.float32), pool, pool,
        shape(s, t), shape(s)).as_text()
    for needle in gathered:
        assert needle in control, needle


# -- the counter --------------------------------------------------------------

def test_cb_live_block_share_counts_what_the_kernel_walks():
    from singa_tpu.obs.metrics import MetricsRegistry

    engine = _engine(max_new_tokens=8, cb_slots=2)
    spec = engine.spec
    bl, slots, width = spec.cb_block_len, spec.cb_slots, \
        spec.cb_blocks_per_slot
    walked = steps = 0
    with InferenceServer(engine, http=False,
                         log_fn=lambda s: None) as server:
        assert server.snapshot()["cb_live_block_share"] is None
        # one request in flight at a time: prefill emits the first
        # token, then max_new - 1 decode steps see ntoks = plen,
        # plen + 1, ...; the other slot idles on the null block
        for plen, new in ((5, 6), (11, 8), (4, 2)):
            out = server.generate(np.arange(1, plen + 1, dtype=np.int32),
                                  max_new=new)
            assert len(out["tokens"]) == new
            for n in range(plen, plen + new - 1):
                walked += n // bl + 1 + (slots - 1)
                steps += 1
        # a request's last token is out before its step is counted
        deadline = time.monotonic() + 10
        while (engine.stats.cb_decode_steps < steps
               and time.monotonic() < deadline):
            time.sleep(0.01)
        snap = server.snapshot()
        reg = MetricsRegistry()
        engine.stats.register_into(reg)
        text = reg.render_prometheus()
    assert engine.stats.cb_decode_steps == steps
    assert engine.stats.cb_live_block_steps == walked
    want = walked / (steps * slots * width)
    assert snap["cb_live_block_share"] == round(want, 4)
    assert f"singa_serve_cb_live_block_share {round(want, 4)}" in text
