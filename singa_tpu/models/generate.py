"""Autoregressive inference for the transformer LM family: KV-cache
prefill + single-token decode, compiled as two XLA programs.

The reference framework is train/test only (worker.cc Test loop runs
Forward over labelled batches; there is no sampling path) — generation
is a capability the sequence-model family adds.  TPU-first design:

- static shapes everywhere: the cache is allocated at `max_len` up
  front and written with `lax.dynamic_update_slice`; the decode loop is
  one `lax.scan` over the new-token axis, so the whole generation is a
  single compiled program (one dispatch), not a per-token Python loop.
- attention over the cache is a masked dense read of the full cache —
  at decode the query is one token, so the (1, max_len) score row is
  tiny; masking `kpos > pos` makes the static shape exact.
- the same `NeuralNet` (core/net.py) drives decode: position-wise
  layers (embed, rmsnorm, ffn, moe, residual) run their normal
  `apply`; only kAttention (cache read/write + absolute-position RoPE)
  and the heads (emit logits instead of loss) are special-cased.

Works with both head forms emitted by models.transformer.transformer_lm
(kLMHead -> kSoftmaxLoss, and the fused kLMHeadLoss whose loss layer is
re-used here only for its projection weight).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.layers import Context
from ..core.net import NeuralNet
from ..ops.paged_attention import paged_decode_attention

CacheEntry = Dict[str, jnp.ndarray]   # {"k","v"}: (B, Hkv, max_len, D)
Cache = Dict[str, CacheEntry]         # attention-layer name -> entry


def init_cache(net: NeuralNet, batchsize: int, max_len: int,
               dtype=jnp.float32) -> Cache:
    """Zeroed KV cache for every kAttention layer in the net."""
    cache: Cache = {}
    for name in net.topo:
        layer = net.layers[name]
        if layer.cfg.type != "kAttention":
            continue
        shape = (batchsize, layer.kv_heads, max_len, layer.head_dim)
        cache[name] = {"k": jnp.zeros(shape, dtype),
                      "v": jnp.zeros(shape, dtype)}
    return cache


def _attn_cached(layer, params, x, entry: CacheEntry, pos,
                 kmask: Optional[jnp.ndarray] = None
                 ) -> Tuple[jnp.ndarray, CacheEntry]:
    """Attention for a (B, T, E) chunk whose first token sits at absolute
    position `pos` (traced scalar), against the running KV cache.

    `kmask` (B, max_len) bool, optional: per-sequence validity of key
    positions, ANDed with the causal mask.  The serving tier LEFT-pads
    variable-length prompts to a bucket length and masks the pad keys —
    with RoPE's relative rotations, left-padding keeps every attended
    (query, key) distance identical to the unpadded sequence, so a
    padded batched decode matches the unpadded one.

    GQA reads the cache at Hkv width: q is grouped to (B, Hkv, G, T, D)
    and contracted against the (B, Hkv, max_len, D) cache directly — no
    expand_kv_heads copy, so the per-step HBM cache read (the decode
    bottleneck once weights are amortized over batch) scales with Hkv,
    not H."""
    assert layer.causal, f"{layer.name}: decode requires causal attention"
    b, t, e = x.shape
    q, k, v = layer.qkv(params, x, pos + jnp.arange(t), _CTX)

    k_cache = jax.lax.dynamic_update_slice(
        entry["k"], k.astype(entry["k"].dtype), (0, 0, pos, 0))
    v_cache = jax.lax.dynamic_update_slice(
        entry["v"], v.astype(entry["v"].dtype), (0, 0, pos, 0))

    groups = layer.heads // layer.kv_heads
    kk = k_cache.astype(q.dtype)
    vv = v_cache.astype(q.dtype)
    qpos = pos + jnp.arange(t)[:, None]            # (T, 1) absolute
    kpos = jnp.arange(kk.shape[2])[None, :]        # (1, max_len)
    allowed = (kpos <= qpos)[None]                 # (1, T, max_len)
    if kmask is not None:
        allowed = allowed & kmask[:, None, :]      # (B, T, max_len)
    if groups == 1:
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, kk,
                            preferred_element_type=jnp.float32)
        scores = scores / jnp.sqrt(jnp.float32(layer.head_dim))
        scores = jnp.where(allowed[:, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(vv.dtype), vv)
    else:
        qg = q.reshape(b, layer.kv_heads, groups, t, layer.head_dim)
        scores = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kk,
                            preferred_element_type=jnp.float32)
        scores = scores / jnp.sqrt(jnp.float32(layer.head_dim))
        scores = jnp.where(allowed[:, None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhgqk,bhkd->bhgqd", probs.astype(vv.dtype), vv)
        out = out.reshape(b, layer.heads, t, layer.head_dim)
    out = out.transpose(0, 2, 1, 3).reshape(b, t, -1)
    out = layer._proj(params, layer.wo, out.astype(x.dtype), _CTX)
    return out, {"k": k_cache, "v": v_cache}


_CTX = Context(batch={}, train=False, rng=None, layer_index=0, mesh=None,
               compute_dtype=None)


def forward_cached(net: NeuralNet, params, tokens: jnp.ndarray,
                   cache: Cache, pos,
                   kmask: Optional[jnp.ndarray] = None
                   ) -> Tuple[jnp.ndarray, Cache]:
    """Run the LM over a (B, T) token chunk at absolute offset `pos`.
    Returns (logits (B, T, V) float32, updated cache).  `kmask`
    (B, max_len) bool marks per-sequence attendable key positions
    (see `_attn_cached` — the serving tier's left-pad mask); None
    keeps the pure causal mask."""
    full = net._resolve_params(params)
    outputs: Dict[str, Any] = {}
    new_cache: Cache = dict(cache)
    logits = None
    for idx, name in enumerate(net.topo):
        layer = net.layers[name]
        ltype = layer.cfg.type
        srcs = [net._src_out(outputs, s, name) for s in layer.cfg.srclayers]
        if ltype == "kSequenceData":
            outputs[name] = {"input": tokens, "target": tokens}
        elif ltype == "kSeqLabel":
            outputs[name] = tokens
        elif ltype == "kAttention":
            out, new_cache[name] = _attn_cached(
                layer, full, srcs[0], cache[name], pos, kmask=kmask)
            outputs[name] = out
        elif ltype == "kLMHead":
            outputs[name] = layer.apply(full, srcs, _CTX)
            logits = outputs[name]
        elif ltype == "kLMHeadLoss":
            # reuse the fused loss layer's projection to emit logits
            logits = layer.project_logits(full, srcs[0])
            outputs[name] = logits
        elif ltype == "kSoftmaxLoss":
            outputs[name] = None     # no loss at decode
        else:
            ctx = Context(batch={}, train=False, rng=None, layer_index=idx,
                          mesh=None, compute_dtype=None)
            outputs[name] = layer.apply(full, srcs, ctx)
    if logits is None:
        raise ValueError("net has no kLMHead/kLMHeadLoss layer")
    return logits.astype(jnp.float32), new_cache


def _write_token(pool, bidx, off, new):
    """Row `off[s]` of pool block `bidx[s]` becomes `new[s]` (Hkv, D),
    for every slot s; inactive slots all write the null block.

    Whole blocks are read, patched and scattered back, so the scatter's
    window is the pool's trailing (Hkv, block_len, D) dims.  The direct
    form, `pool.at[bidx, :, off].set(new)`, has the window (Hkv, D)
    around the scattered block_len axis; XLA:TPU gives that scatter's
    operand another layout than the pool arrives and leaves in, and
    copies the WHOLE pool there and back, for each side of each layer
    of every decode step."""
    rows = jnp.arange(pool.shape[2])[None, None, :, None]
    blocks = jnp.where(rows == off[:, None, None, None],
                       new.astype(pool.dtype)[:, :, None, :], pool[bidx])
    return pool.at[bidx].set(blocks)


def _attn_paged(layer, params, x, entry: CacheEntry, tables,
                ntoks) -> Tuple[jnp.ndarray, CacheEntry]:
    """Single-token decode attention over a block/paged KV pool.

    `x` is (1, S, E): the serving tier's S decode slots ride the SEQ
    axis of a batch-1 chunk, so every position-wise layer (embed,
    rmsnorm, ffn, lmhead) and `layer.qkv`'s per-position RoPE treat a
    slot exactly like a sequence position — `ntoks` (S,) int32 is both
    the per-slot absolute position vector RoPE rotates by and the
    per-slot key-visibility horizon.  The slots never attend each
    other: attention below is per-slot against that slot's own blocks.

    `entry` holds the layer's {"k","v"} pools, each (num_blocks, Hkv,
    block_len, D); `tables` (S, T) int32 maps slot s's logical block t
    to a pool index (block 0 = null: inactive slots and table tails
    point there).  Token position p of slot s lives at
    pool[tables[s, p // bl], :, p % bl].

    Write-before-read: the new K/V is scattered at position ntoks[s]
    first, then `ops.paged_attention.paged_decode_attention` attends
    positions `<= ntoks[s]` — the same self-inclusive causal horizon as
    `_attn_cached` at T=1 — walking ntoks[s] // bl + 1 blocks of the
    slot's table row and no more (an inactive slot: the null block).
    It is the one formulation on every backend (interpreted off the
    TPU).  Same math as the contiguous read, f32 scores and softmax,
    but summed chunk by chunk: the tests pin greedy-token identity with
    `generate()` and a tolerance against the gather reference, not
    bit-equality."""
    assert layer.causal, f"{layer.name}: decode requires causal attention"
    _, s, _ = x.shape
    bl = entry["k"].shape[2]
    q, k, v = layer.qkv(params, x, ntoks, _CTX)    # (1,H,S,D)/(1,Hkv,S,D)

    bidx = tables[jnp.arange(s), ntoks // bl]      # (S,) pool block
    off = ntoks % bl                               # (S,) offset in block
    k_new = k[0].transpose(1, 0, 2)                # (S, Hkv, D)
    v_new = v[0].transpose(1, 0, 2)
    k_pool = _write_token(entry["k"], bidx, off, k_new)
    v_pool = _write_token(entry["v"], bidx, off, v_new)

    out = paged_decode_attention(q[0].transpose(1, 0, 2), k_pool, v_pool,
                                 tables, ntoks)    # (S, H, D)
    out = out.reshape(1, s, -1)
    out = layer._proj(params, layer.wo, out.astype(x.dtype), _CTX)
    return out, {"k": k_pool, "v": v_pool}


def forward_paged(net: NeuralNet, params, tokens: jnp.ndarray,
                  pools: Cache, tables, ntoks
                  ) -> Tuple[jnp.ndarray, Cache]:
    """One decode step for S slots against the paged KV pool.
    `tokens` (1, S) int32 — slot s's last sampled token on the seq
    axis; `tables` (S, T) int32 block tables; `ntoks` (S,) int32
    tokens already written per slot (= the incoming token's absolute
    position).  Returns (logits (1, S, V) float32, updated pools)."""
    full = net._resolve_params(params)
    outputs: Dict[str, Any] = {}
    new_pools: Cache = dict(pools)
    logits = None
    for idx, name in enumerate(net.topo):
        layer = net.layers[name]
        ltype = layer.cfg.type
        srcs = [net._src_out(outputs, s, name) for s in layer.cfg.srclayers]
        if ltype == "kSequenceData":
            outputs[name] = {"input": tokens, "target": tokens}
        elif ltype == "kSeqLabel":
            outputs[name] = tokens
        elif ltype == "kAttention":
            out, new_pools[name] = _attn_paged(
                layer, full, srcs[0], pools[name], tables, ntoks)
            outputs[name] = out
        elif ltype == "kLMHead":
            outputs[name] = layer.apply(full, srcs, _CTX)
            logits = outputs[name]
        elif ltype == "kLMHeadLoss":
            logits = layer.project_logits(full, srcs[0])
            outputs[name] = logits
        elif ltype == "kSoftmaxLoss":
            outputs[name] = None
        else:
            ctx = Context(batch={}, train=False, rng=None, layer_index=idx,
                          mesh=None, compute_dtype=None)
            outputs[name] = layer.apply(full, srcs, ctx)
    if logits is None:
        raise ValueError("net has no kLMHead/kLMHeadLoss layer")
    return logits.astype(jnp.float32), new_pools


def scatter_prefill(pools: Cache, cache: Cache, table_row) -> Cache:
    """Scatter a batch-1 contiguous prefill cache ((1, Hkv, P, D) per
    layer, P a block_len multiple) into the paged pools at the blocks
    named by `table_row` (P // block_len,) int32.  Table entries
    beyond the slot's real reservation are 0: garbage from pad
    positions lands in the null block, where no mask ever looks."""
    out: Cache = {}
    for name, entry in cache.items():
        bl = pools[name]["k"].shape[2]
        hkv, p, d = entry["k"].shape[1:]
        nb = p // bl
        kb = entry["k"][0].transpose(1, 0, 2).reshape(
            nb, bl, hkv, d).transpose(0, 2, 1, 3)   # (nb, Hkv, bl, D)
        vb = entry["v"][0].transpose(1, 0, 2).reshape(
            nb, bl, hkv, d).transpose(0, 2, 1, 3)
        out[name] = {
            "k": pools[name]["k"].at[table_row].set(
                kb.astype(pools[name]["k"].dtype)),
            "v": pools[name]["v"].at[table_row].set(
                vb.astype(pools[name]["v"].dtype))}
    return out


def _sample(logits: jnp.ndarray, key, temperature: float,
            top_k: int, top_p: float) -> jnp.ndarray:
    """logits: (B, V) -> (B,) int32.  temperature 0 = greedy."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0 and top_k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
        logits = jnp.where(logits < kth, -1e30, logits)
    if 0.0 < top_p < 1.0:
        # nucleus: keep the smallest prefix of descending-prob tokens
        # whose mass reaches top_p.  A token is kept iff the mass
        # STRICTLY BEFORE it is < top_p (the top-1 token is always
        # kept); static shapes — one sort + cumsum over V
        desc = -jnp.sort(-logits, axis=-1)
        probs = jax.nn.softmax(desc, axis=-1)
        before = jnp.cumsum(probs, axis=-1) - probs
        kth = jnp.min(jnp.where(before < top_p, desc, jnp.inf),
                      axis=-1, keepdims=True)
        logits = jnp.where(logits < kth, -1e30, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


@partial(jax.jit, static_argnums=(0, 3, 5, 6, 7, 8, 9))
def _generate_jit(net, params, prompt, max_new_tokens, key,
                  temperature, top_k, eos_id, max_len, top_p):
    b, p = prompt.shape
    if max_len is None:
        max_len = p + max_new_tokens
    elif max_len < p + max_new_tokens:
        # clamping up silently would recompile a different cache
        # geometry — the exact drift max_len exists to prevent
        raise ValueError(f"max_len={max_len} < prompt({p}) + "
                         f"max_new_tokens({max_new_tokens})")
    dtype = jax.tree_util.tree_leaves(params)[0].dtype
    cache = init_cache(net, b, max_len, dtype)

    logits, cache = forward_cached(net, params, prompt, cache, 0)
    keys = jax.random.split(key, max_new_tokens)
    tok0 = _sample(logits[:, -1], keys[0], temperature, top_k,
                   top_p)
    done0 = (jnp.zeros((b,), jnp.bool_) if eos_id is None
             else tok0 == eos_id)

    def step(carry, k):
        tok, cache, pos, done = carry
        logits, cache = forward_cached(net, params, tok[:, None], cache, pos)
        nxt = _sample(logits[:, -1], k, temperature, top_k, top_p)
        if eos_id is not None:
            nxt = jnp.where(done, eos_id, nxt)
            done = done | (nxt == eos_id)
        return (nxt, cache, pos + 1, done), nxt

    (_, _, _, _), rest = jax.lax.scan(
        step, (tok0, cache, jnp.int32(p), done0), keys[1:])
    return jnp.concatenate([tok0[:, None], rest.T], axis=1)


@partial(jax.jit, static_argnums=(0, 3, 4, 5, 6, 7))
def _beam_jit(net, params, prompt, max_new_tokens, num_beams,
              length_penalty, eos_id, max_len):
    b, p = prompt.shape
    w = num_beams
    if max_len is None:
        max_len = p + max_new_tokens
    elif max_len < p + max_new_tokens:
        raise ValueError(f"max_len={max_len} < prompt({p}) + "
                         f"max_new_tokens({max_new_tokens})")
    dtype = jax.tree_util.tree_leaves(params)[0].dtype
    cache = init_cache(net, b, max_len, dtype)

    logits, cache = forward_cached(net, params, prompt, cache, 0)
    lp0 = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), axis=-1)
    vocab = lp0.shape[-1]
    if eos_id is not None and not 0 <= eos_id < vocab:
        # out of range, the frozen-vector .at[eos_id].set() below would
        # silently drop and beam freezing would never engage
        raise ValueError(f"eos_id={eos_id} out of range for vocab size "
                         f"{vocab}")
    # only min(W, V) distinct beams exist after one token; pad the rest
    # with -inf scores so they never outrank a real candidate
    k0 = min(w, vocab)
    scores, tok = jax.lax.top_k(lp0, k0)              # (B, k0) each
    if k0 < w:
        scores = jnp.concatenate(
            [scores, jnp.full((b, w - k0), -1e30, scores.dtype)], axis=1)
        tok = jnp.concatenate(
            [tok, jnp.tile(tok[:, :1], (1, w - k0))], axis=1)
    tok = tok.astype(jnp.int32)
    # beam-expand the cache: beam index varies fastest, so flat row
    # b*W + j is batch b's beam j — matching the take() reorder below
    cache = jax.tree_util.tree_map(
        lambda a: jnp.repeat(a, w, axis=0), cache)
    seqs = jnp.zeros((b, w, max_new_tokens), jnp.int32)
    seqs = jnp.where(jnp.arange(max_new_tokens) == 0, tok[:, :, None],
                     seqs)
    done = (tok == eos_id) if eos_id is not None \
        else jnp.zeros((b, w), jnp.bool_)
    lengths = jnp.ones((b, w), jnp.int32)

    def step(carry, t):
        seqs, scores, cache, done, lengths, last = carry
        logits, cache = forward_cached(
            net, params, last.reshape(b * w, 1), cache, p + t - 1)
        lp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32),
                                axis=-1).reshape(b, w, vocab)
        if eos_id is not None:
            # a finished beam only continues with eos at zero cost, so
            # its score freezes and it cannot spawn siblings
            frozen = jnp.full((vocab,), -1e30,
                              jnp.float32).at[eos_id].set(0.0)
            lp = jnp.where(done[:, :, None], frozen, lp)
        cand = (scores[:, :, None] + lp).reshape(b, w * vocab)
        scores, idx = jax.lax.top_k(cand, w)
        beam = idx // vocab
        tokv = (idx % vocab).astype(jnp.int32)
        seqs = jnp.take_along_axis(seqs, beam[:, :, None], axis=1)
        seqs = jnp.where(jnp.arange(max_new_tokens) == t,
                         tokv[:, :, None], seqs)
        done = jnp.take_along_axis(done, beam, axis=1)
        lengths = jnp.take_along_axis(lengths, beam, axis=1)
        lengths = jnp.where(done, lengths, t + 1)
        if eos_id is not None:
            done = done | (tokv == eos_id)
        flat = (jnp.arange(b)[:, None] * w + beam).reshape(-1)
        cache = jax.tree_util.tree_map(
            lambda a: jnp.take(a, flat, axis=0), cache)
        return (seqs, scores, cache, done, lengths, tokv), None

    if max_new_tokens > 1:
        (seqs, scores, cache, done, lengths, _), _ = jax.lax.scan(
            step, (seqs, scores, cache, done, lengths, tok),
            jnp.arange(1, max_new_tokens))
    if length_penalty:
        ranked = scores / (lengths.astype(jnp.float32) ** length_penalty)
    else:
        ranked = scores
    best = jnp.argmax(ranked, axis=1)
    return (jnp.take_along_axis(seqs, best[:, None, None],
                                axis=1)[:, 0],
            jnp.take_along_axis(scores, best[:, None], axis=1)[:, 0])


def beam_search(net: NeuralNet, params, prompt, max_new_tokens: int,
                num_beams: int = 4, length_penalty: float = 0.0,
                eos_id: Optional[int] = None,
                max_len: Optional[int] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Beam-search decode: returns (tokens (B, max_new_tokens) int32,
    log-prob scores (B,) float32) for the best beam per sequence.  One
    compiled program: prefill at batch B, then a lax.scan decode loop
    at batch B·num_beams with per-step beam reordering of the KV cache
    (static shapes throughout — the top-k over W·V candidates and the
    cache `take` are ordinary XLA ops).  After `eos_id` a beam is
    frozen: it keeps emitting eos at zero added cost and its score
    stops moving.  `length_penalty` alpha divides final scores by
    length**alpha for ranking (0 = rank by raw log-prob).  `max_len`
    over-allocates the KV cache exactly as in generate() — pin it to
    keep one compiled cache geometry across runs of different
    lengths."""
    prompt = jnp.asarray(prompt, jnp.int32)
    if int(num_beams) < 1:
        # num_beams=0 would reach jax.lax.top_k(lp0, 0) and die with a
        # cryptic XLA error deep in the trace
        raise ValueError(f"num_beams must be >= 1, got {num_beams}")
    if int(max_new_tokens) <= 0:
        b = prompt.shape[0]
        return (jnp.zeros((b, 0), jnp.int32), jnp.zeros((b,), jnp.float32))
    return _beam_jit(net, params, prompt, int(max_new_tokens),
                     int(num_beams), float(length_penalty), eos_id,
                     None if max_len is None else int(max_len))


def generate(net: NeuralNet, params, prompt,
             max_new_tokens: int, key: Optional[jax.Array] = None,
             temperature: float = 0.0, top_k: int = 0,
             eos_id: Optional[int] = None,
             max_len: Optional[int] = None,
             top_p: float = 0.0) -> jnp.ndarray:
    """Sample `max_new_tokens` continuations of `prompt` ((B, P) int32).
    Returns the (B, max_new_tokens) generated tokens.  One compiled
    program: prefill + a lax.scan decode loop with per-token sampling
    (greedy when temperature == 0; top-k truncation when top_k > 0;
    nucleus truncation when 0 < top_p < 1 — both filters compose,
    top-k first).  After `eos_id` is produced, a sequence keeps
    emitting `eos_id`.  `max_len` over-allocates the KV cache beyond
    prompt+new (the tail is mask-ignored) — lets callers fix the cache
    geometry across runs of different lengths (bench.py isolates
    prefill this way)."""
    if key is None:
        key = jax.random.PRNGKey(0)
    prompt = jnp.asarray(prompt, jnp.int32)
    if int(max_new_tokens) <= 0:
        return jnp.zeros((prompt.shape[0], 0), jnp.int32)
    return _generate_jit(net, params, prompt, int(max_new_tokens), key,
                         float(temperature), int(top_k), eos_id,
                         None if max_len is None else int(max_len),
                         float(top_p))
