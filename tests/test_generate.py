"""KV-cache autoregressive generation (models/generate.py).

Correctness anchor: the cached prefill+decode path must produce the
same logits as the full (uncached) forward over the same tokens —
teacher-forcing parity — for both head forms (kLMHead->kSoftmaxLoss and
the fused kLMHeadLoss)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu.core.net import build_net
from singa_tpu.models.generate import forward_cached, generate, init_cache
from singa_tpu.models.transformer import transformer_lm

VOCAB, SEQ, B = 64, 16, 2
SHAPES = {"data": {"input": (SEQ,), "target": (SEQ,)}}


def _net_and_params(fused_head, seed=0, **kw):
    cfg = transformer_lm(vocab_size=VOCAB, num_layers=2, embed_dim=32,
                         num_heads=4, head_dim=8, seq_len=SEQ, batchsize=B,
                         fused_head=fused_head, **kw)
    net = build_net(cfg, "kTest", SHAPES)
    params = net.init_params(jax.random.PRNGKey(seed))
    return net, params


def _full_logits(net, params, toks):
    """Uncached reference logits via the net's ordinary apply."""
    batch = {"data": {"input": toks, "target": toks}}
    if any(l.cfg.type == "kLMHead" for l in net.layers.values()):
        _, _, outputs = net.apply(params, batch, train=False)
        (name,) = [n for n, l in net.layers.items()
                   if l.cfg.type == "kLMHead"]
        return outputs[name].astype(jnp.float32)
    # fused head: replay its projection on the final hidden state
    _, _, outputs = net.apply(params, batch, train=False)
    (name,) = [n for n, l in net.layers.items()
               if l.cfg.type == "kLMHeadLoss"]
    layer = net.layers[name]
    hidden = outputs[layer.cfg.srclayers[0]]
    return layer.project_logits(net._resolve_params(params), hidden)


@pytest.mark.parametrize("fused_head", [False, True])
def test_prefill_matches_full_forward(fused_head):
    net, params = _net_and_params(fused_head)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, VOCAB, (B, SEQ)), jnp.int32)
    cache = init_cache(net, B, SEQ)
    logits, _ = forward_cached(net, params, toks, cache, 0)
    np.testing.assert_allclose(logits, _full_logits(net, params, toks),
                               rtol=2e-4, atol=2e-4)


def test_stepwise_decode_matches_prefill():
    """Feeding tokens one at a time through the cache must equal the
    one-shot prefill (positions, RoPE offsets, masking all line up)."""
    net, params = _net_and_params(fused_head=True)
    toks = jnp.asarray(
        np.random.default_rng(1).integers(0, VOCAB, (B, SEQ)), jnp.int32)
    cache = init_cache(net, B, SEQ)
    ref, _ = forward_cached(net, params, toks, cache, 0)

    cache = init_cache(net, B, SEQ)
    step_logits = []
    for t in range(SEQ):
        lg, cache = forward_cached(net, params, toks[:, t:t + 1], cache,
                                   jnp.int32(t))
        step_logits.append(lg[:, 0])
    got = jnp.stack(step_logits, axis=1)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_generate_greedy_deterministic():
    net, params = _net_and_params(fused_head=True)
    prompt = jnp.asarray(
        np.random.default_rng(2).integers(0, VOCAB, (B, 4)), jnp.int32)
    out1 = generate(net, params, prompt, 8)
    out2 = generate(net, params, prompt, 8)
    assert out1.shape == (B, 8)
    assert out1.dtype == jnp.int32
    np.testing.assert_array_equal(out1, out2)
    assert int(out1.min()) >= 0 and int(out1.max()) < VOCAB


def test_generate_greedy_matches_full_argmax():
    """Greedy decode must pick argmax of the full-forward logits at each
    position (run the uncached forward on the growing sequence)."""
    net, params = _net_and_params(fused_head=False)
    prompt_len, nnew = 4, 4
    prompt = jnp.asarray(
        np.random.default_rng(3).integers(0, VOCAB, (B, prompt_len)),
        jnp.int32)
    got = generate(net, params, prompt, nnew)

    seq = prompt
    for _ in range(nnew):
        logits = _full_logits(net, params, seq)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(got, seq[:, prompt_len:])


def test_generate_sampling_topk_and_eos():
    net, params = _net_and_params(fused_head=True)
    prompt = jnp.asarray(
        np.random.default_rng(4).integers(0, VOCAB, (B, 4)), jnp.int32)
    out = generate(net, params, prompt, 12, key=jax.random.PRNGKey(7),
                   temperature=0.8, top_k=8)
    assert out.shape == (B, 12)
    # eos propagation: once eos appears every later token is eos
    eos = int(out[0, 3])  # pick an id that actually occurs
    out2 = generate(net, params, prompt, 12, key=jax.random.PRNGKey(7),
                    temperature=0.8, top_k=8, eos_id=eos)
    arr = np.asarray(out2)
    for row in arr:
        hits = np.where(row == eos)[0]
        if hits.size:
            assert (row[hits[0]:] == eos).all()


def test_generate_zero_tokens_returns_empty():
    net, params = _net_and_params(fused_head=True)
    prompt = jnp.zeros((B, 4), jnp.int32)
    out = generate(net, params, prompt, 0)
    assert out.shape == (B, 0) and out.dtype == jnp.int32


def test_generate_with_moe_and_gqa():
    """Decode path covers MoE blocks and grouped-query attention."""
    net, params = _net_and_params(fused_head=True, moe_every=2,
                                  num_experts=4, experts_per_token=2,
                                  num_kv_heads=2)
    toks = jnp.asarray(
        np.random.default_rng(5).integers(0, VOCAB, (B, SEQ)), jnp.int32)
    cache = init_cache(net, B, SEQ)
    logits, _ = forward_cached(net, params, toks, cache, 0)
    np.testing.assert_allclose(logits, _full_logits(net, params, toks),
                               rtol=2e-4, atol=2e-4)
    out = generate(net, params, toks[:, :4], 6)
    assert out.shape == (B, 6)


def test_generate_max_len_overallocation_equivalent():
    """An over-allocated KV cache (max_len > prompt+new) must not change
    the tokens: the tail slots are mask-ignored."""
    net, params = _net_and_params(False)
    toks = jnp.asarray(
        np.random.default_rng(5).integers(0, VOCAB, (B, 6)), jnp.int32)
    base = generate(net, params, toks, 8)
    over = generate(net, params, toks, 8, max_len=32)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(over))


def test_generate_max_len_too_small_raises():
    """max_len below prompt+new must fail loudly — silently clamping up
    would recompile a different cache geometry, the drift the pin
    exists to prevent."""
    net, params = _net_and_params(False)
    toks = jnp.zeros((B, 6), jnp.int32)
    with pytest.raises(ValueError, match="max_len"):
        generate(net, params, toks, 8, max_len=10)


def test_sample_top_p_truncates_to_nucleus():
    """Nucleus sampling keeps the smallest descending-prob prefix whose
    mass reaches top_p (top-1 always kept) and masks the rest."""
    from singa_tpu.models.generate import _sample
    logits = jnp.log(jnp.asarray([[0.6, 0.25, 0.1, 0.05]]))
    # top_p=0.5: nucleus is {0} -> deterministic despite temperature 1
    for i in range(5):
        assert int(_sample(logits, jax.random.PRNGKey(i), 1.0, 0, 0.5)[0]) == 0
    # top_p=0.7: before-mass [0, .6, .85, .95] -> nucleus {0, 1}
    toks = {int(_sample(logits, jax.random.PRNGKey(i), 1.0, 0, 0.7)[0])
            for i in range(40)}
    assert toks == {0, 1}
    # top_p=0 disables the filter: every token reachable
    toks = {int(_sample(logits, jax.random.PRNGKey(i), 1.0, 0, 0.0)[0])
            for i in range(120)}
    assert toks == {0, 1, 2, 3}


def test_generate_top_p_smoke():
    net, params = _net_and_params(False)
    toks = jnp.zeros((B, 4), jnp.int32)
    out = generate(net, params, toks, 6, key=jax.random.PRNGKey(1),
                   temperature=0.8, top_p=0.9)
    assert out.shape == (B, 6)
    assert (np.asarray(out) >= 0).all() and (np.asarray(out) < VOCAB).all()


def test_beam_search_finds_global_optimum_small_vocab():
    """Oracle: with num_beams >= V**(T-1) beam search is exhaustive, so
    its winner must equal the argmax-log-prob continuation over ALL
    V**T candidates (scored by teacher-forced forward)."""
    import itertools

    from singa_tpu.models.generate import beam_search
    cfg = transformer_lm(vocab_size=4, num_layers=2, embed_dim=32,
                         num_heads=4, head_dim=8, seq_len=SEQ, batchsize=1)
    net = build_net(cfg, "kTest", SHAPES)
    params = net.init_params(jax.random.PRNGKey(3))
    prompt = jnp.asarray([[1, 2]], jnp.int32)
    T = 3
    toks, score = beam_search(net, params, prompt, T, num_beams=16)

    best_seq, best_lp = None, -np.inf
    for cand in itertools.product(range(4), repeat=T):
        full = jnp.concatenate(
            [prompt, jnp.asarray([cand], jnp.int32)], axis=1)
        cache = init_cache(net, 1, full.shape[1])
        logits, _ = forward_cached(net, params, full, cache, 0)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        total = sum(float(lp[0, prompt.shape[1] - 1 + i, cand[i]])
                    for i in range(T))
        if total > best_lp:
            best_lp, best_seq = total, cand
    assert tuple(np.asarray(toks)[0]) == best_seq
    assert float(score[0]) == pytest.approx(best_lp, abs=1e-3)


def test_beam_search_width_one_is_greedy():
    from singa_tpu.models.generate import beam_search
    net, params = _net_and_params(False)
    prompt = jnp.asarray(
        np.random.default_rng(7).integers(0, VOCAB, (B, 5)), jnp.int32)
    greedy = generate(net, params, prompt, 6)
    beams, _ = beam_search(net, params, prompt, 6, num_beams=1)
    np.testing.assert_array_equal(np.asarray(beams), np.asarray(greedy))


def test_beam_search_eos_freezes_beam():
    from singa_tpu.models.generate import beam_search
    net, params = _net_and_params(False)
    prompt = jnp.zeros((1, 4), jnp.int32)
    eos = int(np.asarray(generate(net, params, prompt, 1))[0, 0])
    toks, _ = beam_search(net, params, prompt, 6, num_beams=2,
                          eos_id=eos)
    row = np.asarray(toks)[0]
    # np.argmax(row == eos) returns 0 on an all-False row, so assert the
    # winner actually emitted eos first — a non-eos winning beam should
    # fail HERE with a clear message, not downstream for the wrong reason
    assert eos in row, (
        f"winning beam never emitted eos={eos} (row={row.tolist()}): the "
        f"greedy next token should make eos the top continuation")
    # once eos appears every later slot is eos (the frozen-beam contract)
    hit = np.argmax(row == eos)
    assert row[hit] == eos and (row[hit:] == eos).all()


def test_beam_search_length_penalty_matches_bruteforce():
    """alpha=1.0 ranking (score/length) against brute force over all
    V**T continuations, with eos-frozen lengths: the winner under the
    penalized objective must match."""
    import itertools

    from singa_tpu.models.generate import beam_search
    cfg = transformer_lm(vocab_size=4, num_layers=2, embed_dim=32,
                         num_heads=4, head_dim=8, seq_len=SEQ, batchsize=1)
    net = build_net(cfg, "kTest", SHAPES)
    params = net.init_params(jax.random.PRNGKey(9))
    prompt = jnp.asarray([[3, 0]], jnp.int32)
    T, EOS = 3, 1
    toks, _ = beam_search(net, params, prompt, T, num_beams=16,
                          length_penalty=1.0, eos_id=EOS,
                          max_len=prompt.shape[1] + T + 2)  # over-alloc ok
    best_seq, best = None, -np.inf
    for cand in itertools.product(range(4), repeat=T):
        # canonical frozen form: after eos, only eos continuations exist
        if EOS in cand:
            cut = cand.index(EOS)
            if any(c != EOS for c in cand[cut:]):
                continue
            length = cut + 1
        else:
            length = T
        full = jnp.concatenate(
            [prompt, jnp.asarray([cand], jnp.int32)], axis=1)
        cache = init_cache(net, 1, full.shape[1])
        logits, _ = forward_cached(net, params, full, cache, 0)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        total = sum(float(lp[0, prompt.shape[1] - 1 + i, cand[i]])
                    for i in range(length))   # frozen tail adds zero
        score = total / length
        if score > best:
            best, best_seq = score, cand
    assert tuple(np.asarray(toks)[0]) == best_seq


def test_decode_with_tp_sharded_params_matches_unsharded():
    """Distributed inference by sharding alone: the SAME compiled
    decode/beam programs run with TP-sharded (partition_dim) params on
    a data x model mesh — GSPMD propagates the shardings through the
    cache loop — and must produce identical tokens."""
    from singa_tpu.models.generate import beam_search
    from singa_tpu.parallel.mesh import make_mesh
    from singa_tpu.parallel.partition import param_shardings

    net, params = _net_and_params(False)
    prompt = jnp.asarray(
        np.random.default_rng(11).integers(0, VOCAB, (B, 5)), jnp.int32)
    base = np.asarray(generate(net, params, prompt, 6))
    bb, bs = beam_search(net, params, prompt, 6, num_beams=4)

    mesh = make_mesh(jax.devices(), data=2, model=4)
    sh = param_shardings(mesh, net)
    # guard against vacuity: the config must actually partition params
    assert any(not s.is_fully_replicated for s in sh.values())
    sp = {k: jax.device_put(v, sh[k]) for k, v in params.items()}
    np.testing.assert_array_equal(np.asarray(generate(net, sp, prompt, 6)),
                                  base)
    tb, ts = beam_search(net, sp, prompt, 6, num_beams=4)
    np.testing.assert_array_equal(np.asarray(tb), np.asarray(bb))
    np.testing.assert_allclose(np.asarray(ts), np.asarray(bs),
                               rtol=1e-4, atol=1e-4)

    # and DP: prompts sharded over the data axis compose with the
    # TP-sharded params in the same programs
    from jax.sharding import NamedSharding, PartitionSpec as P
    dprompt = jax.device_put(prompt, NamedSharding(mesh, P("data")))
    np.testing.assert_array_equal(
        np.asarray(generate(net, sp, dprompt, 6)), base)
