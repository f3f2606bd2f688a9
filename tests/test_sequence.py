"""Sequence/modern-parallelism tests: flash attention, ring, Ulysses,
pipeline, MoE, and the transformer family on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu.core.trainer import Trainer
from singa_tpu.models.transformer import (synthetic_token_batches,
                                          transformer_lm)
from singa_tpu.ops.attention import attention_reference, flash_attention, rope
from singa_tpu.ops.moe import moe_ffn
from singa_tpu.parallel import (make_mesh, param_shardings, pipeline_apply,
                                ring_attention, seq_batch_shardings,
                                stack_stage_params, ulysses_attention)

RNG = np.random.default_rng(0)
SEQ_SHAPES = {"data": {"input": (128,), "target": (128,)}}


def _qkv(b=2, h=8, s=256, d=32):
    return tuple(jnp.asarray(RNG.standard_normal((b, h, s, d))
                             .astype(np.float32)) for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_reference(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal, 128, 128, True)
    ref = attention_reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_flash_attention_grads():
    q, k, v = _qkv(1, 2, 128, 16)
    g = jax.grad(lambda q, k, v: flash_attention(
        q, k, v, True, 128, 128, True).sum(), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: attention_reference(
        q, k, v, True).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(causal):
    q, k, v = _qkv()
    mesh = make_mesh(seq=8)
    out = ring_attention(q, k, v, mesh, "seq", causal)
    ref = attention_reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_reference(causal):
    q, k, v = _qkv()
    mesh = make_mesh(seq=8)
    out = ulysses_attention(q, k, v, mesh, "seq", causal)
    ref = attention_reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_rope_rotation_preserves_norm():
    x = jnp.asarray(RNG.standard_normal((1, 2, 16, 32)).astype(np.float32))
    y = rope(x, jnp.arange(16))
    np.testing.assert_allclose(np.linalg.norm(np.asarray(x), axis=-1),
                               np.linalg.norm(np.asarray(y), axis=-1),
                               rtol=1e-5)
    # position 0 is identity
    np.testing.assert_allclose(np.asarray(y[:, :, 0]), np.asarray(x[:, :, 0]),
                               rtol=1e-6)


def test_pipeline_matches_sequential():
    mesh = make_mesh(pipe=4)
    nstages, nmicro, mb, d = 4, 8, 4, 16
    per_stage = [{"w": jnp.asarray(
        RNG.standard_normal((d, d)).astype(np.float32)) * 0.3}
        for _ in range(nstages)]
    stacked = stack_stage_params(per_stage)
    x = jnp.asarray(RNG.standard_normal((nmicro, mb, d)).astype(np.float32))

    def stage_fn(p, h):
        return jax.nn.relu(h @ p["w"])

    out = pipeline_apply(mesh, stage_fn, stacked, x)
    ref = x
    for p in per_stage:
        ref = jax.vmap(lambda h, p=p: stage_fn(p, h))(ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_pipeline_rejects_underfilled():
    mesh = make_mesh(pipe=4)
    stacked = stack_stage_params(
        [{"w": jnp.eye(4)} for _ in range(4)])
    x = jnp.zeros((2, 2, 4))
    with pytest.raises(ValueError, match="n_micro"):
        pipeline_apply(mesh, lambda p, h: h, stacked, x)


def test_moe_routes_and_balances():
    e, f, n_exp = 16, 32, 4
    x = jnp.asarray(RNG.standard_normal((2, 8, e)).astype(np.float32))
    params = {
        "router": jnp.asarray(RNG.standard_normal((e, n_exp))
                              .astype(np.float32)),
        "w1": jnp.asarray(RNG.standard_normal((n_exp, e, f))
                          .astype(np.float32)) * 0.1,
        "b1": jnp.zeros((n_exp, f)),
        "w2": jnp.asarray(RNG.standard_normal((n_exp, f, e))
                          .astype(np.float32)) * 0.1,
        "b2": jnp.zeros((n_exp, e)),
    }
    out, aux = moe_ffn(x, params, k=2, capacity_factor=2.0)
    assert out.shape == x.shape
    assert float(aux) > 0
    # with generous capacity every token is processed: output nonzero
    assert float(jnp.mean(jnp.abs(out))) > 1e-3
    # differentiable end to end
    g = jax.grad(lambda p: moe_ffn(x, p, 2, 2.0)[0].sum())(params)
    assert np.isfinite(float(jnp.sum(jnp.abs(g["router"]))))


def test_transformer_trains_and_beats_unigram():
    vocab = 32
    cfg = transformer_lm(vocab_size=vocab, num_layers=2, embed_dim=64,
                         num_heads=4, head_dim=16, seq_len=64, batchsize=8,
                         train_steps=5)
    shapes = {"data": {"input": (64,), "target": (64,)}}
    trainer = Trainer(cfg, shapes, donate=False)
    params, opt = trainer.init(0)
    it = synthetic_token_batches(8, 64, vocab, seed=0)
    losses = []
    p, o = params, opt
    for s in range(60):
        p, o, m = trainer.train_step(p, o, next(it), s, jax.random.PRNGKey(s))
        losses.append(float(m["loss"]))
    # unigram floor is log(vocab); Markov structure is learnable below it
    assert losses[-1] < np.log(vocab) - 0.1, losses[::10]


def test_transformer_sharded_step_matches_local():
    """dp×tp×sp mesh with ring attention + MoE == single-device numerics."""
    mesh = make_mesh(data=2, model=2, seq=2)
    common = dict(vocab_size=64, num_layers=2, embed_dim=64, num_heads=4,
                  head_dim=16, seq_len=128, batchsize=8, train_steps=3,
                  moe_every=2, num_experts=4)
    cfg_ring = transformer_lm(seq_parallel="ring", **common)
    cfg_local = transformer_lm(seq_parallel="none", **common)
    tr_ring = Trainer(cfg_ring, SEQ_SHAPES, donate=False, mesh=mesh)
    tr_local = Trainer(cfg_local, SEQ_SHAPES, donate=False)
    params, opt = tr_ring.init(0)
    batch = next(synthetic_token_batches(8, 128, 64))
    rng = jax.random.PRNGKey(0)

    p1, o1, m1 = tr_local.train_step(params, opt, batch, 0, rng)

    p_sh = param_shardings(mesh, tr_ring.train_net)
    sp = {k: jax.device_put(v, p_sh[k]) for k, v in params.items()}
    so = {k: {n: jax.device_put(v, p_sh[n]) for n, v in t.items()}
          for k, t in opt.items()}
    sb = jax.tree_util.tree_map(jax.device_put, batch,
                                seq_batch_shardings(mesh, batch))
    p2, o2, m2 = tr_ring.train_step(sp, so, sb, 0, rng)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-4)
    # Adam's step-0 update is ~lr*sign(g), so reduction-order noise in the
    # sharded grads shows up at ~1e-4 relative — tolerance reflects that.
    np.testing.assert_allclose(np.asarray(p1["attn0/wq"]),
                               np.asarray(p2["attn0/wq"]),
                               rtol=2e-3, atol=1e-5)


def test_expert_parallel_sharding_matches_local():
    """EP on an expert=4 mesh: experts genuinely shard AND the sharded
    step reproduces single-device numerics (not just finite loss)."""
    mesh = make_mesh(data=2, expert=4)
    cfg = transformer_lm(vocab_size=32, num_layers=2, embed_dim=32,
                         num_heads=2, head_dim=16, seq_len=64, batchsize=8,
                         moe_every=1, num_experts=4)
    shapes = {"data": {"input": (64,), "target": (64,)}}
    tr = Trainer(cfg, shapes, donate=False, mesh=mesh)
    tr_local = Trainer(cfg, shapes, donate=False)
    shardings = param_shardings(mesh, tr.train_net)
    from jax.sharding import PartitionSpec as P
    assert shardings["moe0/w1"].spec == P("expert", None, None)
    assert shardings["moe0/b2"].spec == P("expert", None)
    params, opt = tr.init(0)
    batch = next(synthetic_token_batches(8, 64, 32))
    rng = jax.random.PRNGKey(0)
    p1, o1, m1 = tr_local.train_step(params, opt, batch, 0, rng)
    sp = {k: jax.device_put(v, shardings[k]) for k, v in params.items()}
    so = {k: {n: jax.device_put(v, shardings[n]) for n, v in t.items()}
          for k, t in opt.items()}
    sb = jax.tree_util.tree_map(jax.device_put, batch,
                                seq_batch_shardings(mesh, batch))
    p2, o2, m2 = tr.train_step(sp, so, sb, 0, rng)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-4)
    np.testing.assert_allclose(np.asarray(p1["moe0/w1"]),
                               np.asarray(p2["moe0/w1"]),
                               rtol=2e-3, atol=1e-5)
    np.testing.assert_allclose(np.asarray(p1["embed/embedding"]),
                               np.asarray(p2["embed/embedding"]),
                               rtol=2e-3, atol=1e-5)


def test_bfloat16_precision_policy():
    cfg = transformer_lm(vocab_size=32, num_layers=1, embed_dim=32,
                         num_heads=2, head_dim=16, seq_len=64, batchsize=4,
                         precision="bfloat16")
    tr = Trainer(cfg, {"data": {"input": (64,), "target": (64,)}},
                 donate=False)
    params, opt = tr.init(0)
    assert params["attn0/wq"].dtype == jnp.float32  # master weights fp32
    batch = next(synthetic_token_batches(4, 64, 32))
    p, o, m = tr.train_step(params, opt, batch, 0, jax.random.PRNGKey(0))
    assert np.isfinite(float(m["loss"]))


def test_tied_lm_head_with_vocab_equal_embed():
    """Regression: tie orientation must come from config, not shape
    heuristics — ambiguous when vocab_size == embed_dim."""
    vocab = 64
    cfg = transformer_lm(vocab_size=vocab, num_layers=1, embed_dim=vocab,
                         num_heads=4, head_dim=16, seq_len=32, batchsize=4,
                         tie_embeddings=True, fused_head=False)
    tr = Trainer(cfg, {"data": {"input": (32,), "target": (32,)}},
                 donate=False)
    params, opt = tr.init(0)
    assert "lm_head/w" not in params          # aliased to embed/embedding
    net = tr.train_net
    batch = next(synthetic_token_batches(4, 32, vocab))
    _, _, outputs = net.apply(params, batch, rng=jax.random.PRNGKey(0))
    # logits must equal h @ embedding.T (the tied orientation)
    h = np.asarray(outputs["ln_f"])
    emb = np.asarray(params["embed/embedding"])
    np.testing.assert_allclose(np.asarray(outputs["lm_head"]),
                               h @ emb.T, rtol=1e-4, atol=1e-4)


def test_moe_capacity_drops_overflow():
    """With capacity_factor small, overflow tokens are dropped (output 0
    contribution) rather than corrupting other experts' slots."""
    e, f, n_exp = 8, 16, 2
    # router forces ALL tokens to expert 0
    params = {
        "router": jnp.asarray(
            np.stack([np.ones(e) * 5, -np.ones(e) * 5], 1)
            .astype(np.float32)),
        "w1": jnp.ones((n_exp, e, f), jnp.float32) * 0.1,
        "b1": jnp.zeros((n_exp, f)),
        "w2": jnp.ones((n_exp, f, e), jnp.float32) * 0.1,
        "b2": jnp.zeros((n_exp, e)),
    }
    x = jnp.ones((1, 8, e))
    out_full, _ = moe_ffn(x, params, k=1, capacity_factor=2.0)
    out_tight, _ = moe_ffn(x, params, k=1, capacity_factor=0.25)
    # tight capacity: only 1 of 8 tokens served (cap = 0.25*8/2 = 1)
    served_full = int(jnp.sum(jnp.any(jnp.abs(out_full) > 1e-6, -1)))
    served_tight = int(jnp.sum(jnp.any(jnp.abs(out_tight) > 1e-6, -1)))
    assert served_full == 8
    assert served_tight == 1


def test_chunked_lm_xent_matches_naive():
    """Fused chunked head+xent == materialized logits path, including
    gradients (the backward recomputes chunks under jax.checkpoint)."""
    import jax
    from singa_tpu.ops.loss import chunked_lm_xent, softmax_loss_metrics
    rng = np.random.default_rng(0)
    n, e, v = 24, 16, 50
    h = jnp.asarray(rng.standard_normal((n, e)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((e, v)).astype(np.float32)) * 0.1
    labels = jnp.asarray(rng.integers(0, v, (n,)))

    loss_f, prec_f = chunked_lm_xent(h, w, labels, chunk_size=7, topk=2)
    loss_n, prec_n = softmax_loss_metrics(h @ w, labels, topk=2)
    np.testing.assert_allclose(float(loss_f), float(loss_n), rtol=1e-6)
    np.testing.assert_allclose(float(prec_f), float(prec_n), rtol=1e-6)

    gf = jax.grad(lambda h_, w_: chunked_lm_xent(h_, w_, labels, 7)[0],
                  argnums=(0, 1))(h, w)
    gn = jax.grad(lambda h_, w_: softmax_loss_metrics(h_ @ w_, labels)[0],
                  argnums=(0, 1))(h, w)
    np.testing.assert_allclose(np.asarray(gf[0]), np.asarray(gn[0]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(gf[1]), np.asarray(gn[1]),
                               atol=1e-5)


def test_fused_head_model_matches_unfused():
    """transformer_lm(fused_head=True) trains identically to the
    kLMHead+kSoftmaxLoss form (tied embeddings -> same param pytree)."""
    import jax
    from singa_tpu.core.trainer import Trainer
    from singa_tpu.models.transformer import (synthetic_token_batches,
                                              transformer_lm)
    kw = dict(vocab_size=64, num_layers=2, embed_dim=32, num_heads=4,
              head_dim=8, seq_len=32, batchsize=4)
    shapes = {"data": {"input": (32,), "target": (32,)}}
    batch = next(synthetic_token_batches(4, 32, 64))
    out = {}
    for fused in (True, False):
        cfg = transformer_lm(fused_head=fused, **kw)
        tr = Trainer(cfg, shapes, donate=False, log_fn=lambda s: None)
        params, opt = tr.init(0)
        p, o, m = tr.train_step(params, opt, batch, 0, jax.random.PRNGKey(0))
        out[fused] = (set(params), p, m)
    assert out[True][0] == out[False][0]          # same param keys (tied)
    np.testing.assert_allclose(float(out[True][2]["loss"]),
                               float(out[False][2]["loss"]), rtol=1e-5)
    np.testing.assert_allclose(
        float(out[True][2]["precision"]),
        float(out[False][2]["precision"]), rtol=1e-5)
    for k in out[True][1]:
        np.testing.assert_allclose(np.asarray(out[True][1][k]),
                                   np.asarray(out[False][1][k]), atol=2e-5)


def test_flash_backward_kernels_multiblock():
    """The hand-written dq/dkv Pallas backward (interpret mode) across
    MULTIPLE q/kv blocks — exercises the per-block accumulation and the
    causal block-skip guard — against autodiff of the dense reference."""
    q, k, v = _qkv(2, 2, 256, 32)
    cot = jnp.asarray(RNG.standard_normal(q.shape).astype(np.float32))
    for causal in (False, True):
        _, vjp_f = jax.vjp(lambda *a: flash_attention(
            *a, causal, 128, 128, True), q, k, v)
        _, vjp_r = jax.vjp(lambda *a: attention_reference(
            *a, causal), q, k, v)
        for a, b in zip(vjp_f(cot), vjp_r(cot)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)


def test_chunk_attention_blockwise_matches_dense_chunk():
    """The ring local step's chunked-flash form vs the dense
    chunk_attention: same (out, lse) and same gradients, including
    cross-chunk causal offsets."""
    from singa_tpu.ops.attention import (chunk_attention,
                                         chunk_attention_blockwise)

    q, k, v = _qkv(1, 2, 256, 16)
    cot = jnp.asarray(RNG.standard_normal(q.shape).astype(np.float32))
    for (q_off, kv_off) in ((0, 0), (256, 0), (0, 256)):
        (o_d, l_d), vjp_d = jax.vjp(
            lambda *a: chunk_attention(*a, True, q_off, kv_off), q, k, v)
        (o_b, l_b), vjp_b = jax.vjp(
            lambda *a: chunk_attention_blockwise(*a, True, q_off, kv_off,
                                                 block_k=64), q, k, v)
        np.testing.assert_allclose(np.asarray(o_b), np.asarray(o_d),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(l_b), np.asarray(l_d),
                                   rtol=1e-4, atol=1e-4)
        for a, b in zip(vjp_b((cot, jnp.zeros_like(l_b))),
                        vjp_d((cot, jnp.zeros_like(l_d)))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)


def test_attention_layer_packed_path_matches_strided():
    """The zero-transpose packed flash path (AttentionLayer fast path)
    against the strided (B,H,S,D) path, forward AND parameter
    gradients, on the same weights."""
    from singa_tpu.core.net import build_net
    from singa_tpu.models.transformer import (synthetic_token_batches,
                                              transformer_lm)

    cfg = transformer_lm(vocab_size=64, num_layers=1, embed_dim=64,
                         num_heads=4, head_dim=16, seq_len=128,
                         batchsize=2)
    net = build_net(cfg, "kTrain",
                    {"data": {"input": (128,), "target": (128,)}})
    params = net.init_params(jax.random.PRNGKey(0))
    batch = next(synthetic_token_batches(2, 128, 64))
    attn = [l for l in net.layers.values()
            if l.cfg.type == "kAttention"][0]
    assert attn._packed_eligible(2, 128, type("C", (), {"mesh": None})())

    def loss_fn(p):
        loss, _, _ = net.apply(p, batch, rng=jax.random.PRNGKey(1),
                               train=False)
        return loss
    l1, g1 = jax.value_and_grad(loss_fn)(params)
    # force the strided path on the same net/params
    attn._packed_eligible = lambda b, s, ctx: False
    l2, g2 = jax.value_and_grad(loss_fn)(params)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    for k in g1:
        np.testing.assert_allclose(np.asarray(g1[k]), np.asarray(g2[k]),
                                   rtol=1e-3, atol=1e-5, err_msg=k)


def test_packed_flash_gqa_matches_expanded_reference():
    """Native GQA in the packed kernels (q heads read their group's kv
    slice in-kernel): forward and all three input grads vs the dense
    reference on expanded kv heads."""
    from singa_tpu.ops.attention import (expand_kv_heads,
                                         flash_attention_packed)

    b, h, hkv, s, d = 2, 8, 2, 256, 16
    q = jnp.asarray(RNG.standard_normal((b, s, h * d)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((b, s, hkv * d)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((b, s, hkv * d)).astype(np.float32))
    cot = jnp.asarray(RNG.standard_normal(q.shape).astype(np.float32))

    def ref(q, k, v, causal):
        qs = q.reshape(b, s, h, d).transpose(0, 2, 1, 3)
        ks = expand_kv_heads(
            k.reshape(b, s, hkv, d).transpose(0, 2, 1, 3), h)
        vs = expand_kv_heads(
            v.reshape(b, s, hkv, d).transpose(0, 2, 1, 3), h)
        o = attention_reference(qs, ks, vs, causal)
        return o.transpose(0, 2, 1, 3).reshape(b, s, h * d)

    for causal in (False, True):
        out_p, vjp_p = jax.vjp(
            lambda *a: flash_attention_packed(
                *a, h, causal, 128, 128, True, hkv), q, k, v)
        out_r, vjp_r = jax.vjp(lambda *a: ref(*a, causal), q, k, v)
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                                   rtol=1e-3, atol=1e-4)
        for a, r in zip(vjp_p(cot), vjp_r(cot)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=1e-3, atol=1e-4)


def test_attention_layer_gqa_packed_matches_strided():
    """A GQA config now takes the packed path single-device; it must
    reproduce the strided expand_kv_heads path exactly."""
    from singa_tpu.core.net import build_net
    from singa_tpu.models.transformer import (synthetic_token_batches,
                                              transformer_lm)

    cfg = transformer_lm(vocab_size=64, num_layers=1, embed_dim=64,
                         num_heads=4, head_dim=16, num_kv_heads=2,
                         seq_len=128, batchsize=2)
    net = build_net(cfg, "kTrain",
                    {"data": {"input": (128,), "target": (128,)}})
    params = net.init_params(jax.random.PRNGKey(0))
    batch = next(synthetic_token_batches(2, 128, 64))
    attn = [l for l in net.layers.values()
            if l.cfg.type == "kAttention"][0]
    assert attn.kv_heads == 2
    assert attn._packed_eligible(2, 128, type("C", (), {"mesh": None})())

    def loss_fn(p):
        loss, _, _ = net.apply(p, batch, rng=jax.random.PRNGKey(1),
                               train=False)
        return loss
    l1, g1 = jax.value_and_grad(loss_fn)(params)
    attn._packed_eligible = lambda b, s, ctx: False
    l2, g2 = jax.value_and_grad(loss_fn)(params)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    for k in g1:
        np.testing.assert_allclose(np.asarray(g1[k]), np.asarray(g2[k]),
                                   rtol=1e-3, atol=1e-5, err_msg=k)


def _count_packed_traces(monkeypatch):
    """Count traces of the packed forward during jit tracing — proof the
    packed kernel path (not the strided fallback) is the one compiled."""
    from singa_tpu.ops import attention as att
    calls = {"n": 0}
    orig = att._packed_forward

    def counting(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(att, "_packed_forward", counting)
    return calls


@pytest.mark.parametrize("mesh_axes", [dict(data=8), dict(data=4, model=2),
                                       dict(model=2, expert=4)])
def test_packed_path_runs_under_mesh_and_matches_local(monkeypatch,
                                                       mesh_axes):
    """Round-5 un-fencing: DP, DP×TP and TP×EP meshes run the PACKED
    flash path (asserted via a trace counter on the packed forward) and
    reproduce the unsharded step's numerics — loss and updated params."""
    mesh = make_mesh(**mesh_axes)
    cfg = transformer_lm(vocab_size=64, num_layers=2, embed_dim=64,
                         num_heads=4, head_dim=16, num_kv_heads=2,
                         seq_len=128, batchsize=8,
                         moe_every=2, num_experts=4)
    tr = Trainer(cfg, SEQ_SHAPES, donate=False, mesh=mesh)
    tr_local = Trainer(cfg, SEQ_SHAPES, donate=False)
    params, opt = tr.init(0)
    batch = next(synthetic_token_batches(8, 128, 64))
    rng = jax.random.PRNGKey(0)
    p1, o1, m1 = tr_local.train_step(params, opt, batch, 0, rng)

    calls = _count_packed_traces(monkeypatch)
    p_sh = param_shardings(mesh, tr.train_net)
    sp = {k: jax.device_put(v, p_sh[k]) for k, v in params.items()}
    so = {k: {n: jax.device_put(v, p_sh[n]) for n, v in t.items()}
          for k, t in opt.items()}
    sb = jax.tree_util.tree_map(jax.device_put, batch,
                                seq_batch_shardings(mesh, batch))
    p2, o2, m2 = tr.train_step(sp, so, sb, 0, rng)
    assert calls["n"] > 0, "mesh step did not trace the packed kernels"
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-4)
    for k in ("attn0/wq", "attn0/wk", "embed/embedding"):
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]),
                                   rtol=2e-3, atol=1e-5, err_msg=k)


def test_packed_mesh_eligibility_gates():
    """Indivisible head/batch splits and sharded seq/pipe axes fall back
    to the strided path instead of mis-sharding the kernel."""
    from singa_tpu.core.net import build_net

    cfg = transformer_lm(vocab_size=64, num_layers=1, embed_dim=96,
                         num_heads=6, head_dim=16, num_kv_heads=2,
                         seq_len=128, batchsize=2)
    net = build_net(cfg, "kTrain", SEQ_SHAPES)
    attn = [l for l in net.layers.values()
            if l.cfg.type == "kAttention"][0]

    def ctx(mesh):
        return type("C", (), {"mesh": mesh})()

    assert attn._packed_eligible(8, 128, ctx(None))
    assert attn._packed_eligible(8, 128, ctx(make_mesh(data=4, model=2)))
    # kv_heads=2 does not divide model=4
    assert not attn._packed_eligible(8, 128, ctx(make_mesh(data=2,
                                                           model=4)))
    # batch 2 does not divide data=8
    assert not attn._packed_eligible(2, 128, ctx(make_mesh(data=8)))
    # sharded sequence axis is the ring/Ulysses regime, not this one
    assert not attn._packed_eligible(8, 128, ctx(make_mesh(data=4,
                                                           seq=2)))


def test_packed_sharded_helper_matches_reference():
    """packed_attention_sharded == dense reference on expanded KV, for a
    GQA geometry sharded batch-and-heads over data×model."""
    from singa_tpu.ops.attention import expand_kv_heads
    from singa_tpu.parallel.sequence import packed_attention_sharded

    b, h, hkv, s, d = 4, 8, 4, 128, 16
    mesh = make_mesh(data=2, model=4)
    q = jnp.asarray(RNG.standard_normal((b, s, h * d)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((b, s, hkv * d)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((b, s, hkv * d)).astype(np.float32))

    def ref(causal):
        qs = q.reshape(b, s, h, d).transpose(0, 2, 1, 3)
        ks = expand_kv_heads(k.reshape(b, s, hkv, d).transpose(0, 2, 1, 3), h)
        vs = expand_kv_heads(v.reshape(b, s, hkv, d).transpose(0, 2, 1, 3), h)
        o = attention_reference(qs, ks, vs, causal)
        return o.transpose(0, 2, 1, 3).reshape(b, s, h * d)

    for causal in (False, True):
        out = packed_attention_sharded(q, k, v, mesh, h, hkv, causal,
                                       128, 128)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref(causal)),
                                   rtol=1e-3, atol=1e-4)


def _gqa_qkv(b=2, h=8, hkv=2, s=256, d=16):
    q = jnp.asarray(RNG.standard_normal((b, h, s, d)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((b, hkv, s, d)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((b, hkv, s, d)).astype(np.float32))
    return q, k, v


def _gqa_ref(q, k, v, causal):
    from singa_tpu.ops.attention import expand_kv_heads
    return attention_reference(q, expand_kv_heads(k, q.shape[1]),
                               expand_kv_heads(v, q.shape[1]), causal)


@pytest.mark.parametrize("seq_size,native", [(2, True), (8, False)])
def test_ulysses_gqa_kv_width(seq_size, native):
    """Ulysses with GQA: hkv_local % nseq == 0 rides the a2a at Hkv
    width (native); otherwise pre-expands.  Both must match the dense
    reference."""
    q, k, v = _gqa_qkv(b=8)
    axes = dict(seq=seq_size)
    axes["data"] = 8 // seq_size
    mesh = make_mesh(**axes)
    out = ulysses_attention(q, k, v, mesh, "seq", True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_gqa_ref(q, k, v, True)),
                               rtol=1e-4, atol=1e-5)
    # the native case's k/v all-to-alls move Hkv-width arrays
    import re
    txt = jax.jit(lambda q, k, v: ulysses_attention(
        q, k, v, mesh, "seq", True)).lower(q, k, v).compile().as_text()
    a2a = re.findall(r"(?:f32|bf16)\[([0-9,]+)\][^\n]*all-to-all", txt)
    assert a2a, "no all-to-all in the lowered Ulysses step"
    hkv_elems = (8 // axes["data"]) * 2 * (256 // seq_size) * 16
    smallest = min(int(np.prod([int(x) for x in dims.split(",")]))
                   for dims in a2a)
    if native:
        assert smallest <= hkv_elems, (smallest, hkv_elems)
    # non-native: no width claim — XLA may sink the expand broadcast
    # past the a2a on its own; parity above is the contract there


def test_ring_ppermute_rotates_hkv_width():
    """The compiled ring step's collective-permutes move Hkv-head
    chunks, not H-head ones — the round-5 4x ICI saving, asserted in
    lowered HLO so a future re-expansion regression fails loudly."""
    b, h, hkv, s, d = 2, 8, 2, 256, 16
    q, k, v = _gqa_qkv(b, h, hkv, s, d)
    mesh = make_mesh(seq=8)
    chunk = s // 8
    txt = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh, "seq", True)).lower(q, k, v).compile().as_text()
    import re
    perms = re.findall(r"(f32|bf16)\[([0-9,]+)\][^\n]*collective-permute",
                       txt)
    assert perms, "no collective-permute in the lowered ring step"
    shapes = {tuple(int(x) for x in dims.split(",")) for _, dims in perms}
    for shape in shapes:
        assert np.prod(shape) <= b * hkv * chunk * d, (
            f"collective-permute moves {shape}, larger than the "
            f"Hkv-width chunk ({b},{hkv},{chunk},{d})")


def test_gqa_dense_fallback_expands_kv():
    """Non-flash-legal GQA shapes (head_dim % 8 != 0) hit the dense
    fallback, which must expand kv heads — regression for the round-5
    refactor that moved expansion out of the shared path."""
    from singa_tpu.core.net import build_net

    cfg = transformer_lm(vocab_size=32, num_layers=1, embed_dim=48,
                         num_heads=4, head_dim=12, num_kv_heads=2,
                         seq_len=120, batchsize=2)
    shapes = {"data": {"input": (120,), "target": (120,)}}
    net = build_net(cfg, "kTrain", shapes)
    params = net.init_params(jax.random.PRNGKey(0))
    batch = next(synthetic_token_batches(2, 120, 32))
    loss, _, _ = net.apply(params, batch, rng=jax.random.PRNGKey(1),
                           train=False)
    assert np.isfinite(float(loss))
