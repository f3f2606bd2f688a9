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
from ..core.seq_layers import DECODE_CTX, AttentionLayer

CacheEntry = Dict[str, jnp.ndarray]   # one layer's decode state
Cache = Dict[str, CacheEntry]         # layer name -> its entry

# A layer that keeps state between decode steps declares it and steps it
# itself; the walkers below only hand it over.  The protocol (duck-typed
# methods of a core.layers.Layer):
#   init_cache(batch, max_len, dtype)            contiguous state
#   apply_cached(params, x, entry, pos, kmask=, plen=) -> (out, entry)
#   init_pool(num_slots, num_blocks, block_len, dtype)   serving state:
#       rows per token in paged blocks that grow under the table or
#       in a ring of blocks per slot (a windowed kAttention, which
#       takes no notice of `tables`), one fixed state per slot, or two
#       of these in one entry
#   apply_paged(params, x, entry, tables, ntoks)  -> (out, entry)
#   scatter_prefill(pool, cache, table_row, slot) -> pool
# `x` is the layer's source, or the list of them where the layer has
# several; `out` is an array, or a dict of named outputs that the
# layer's consumers read by name (`hybrid_layers.named_output`).
# kAttention (K/V per token, all of them or a window's), kMLA (a latent row per token), kKDA (a
# recurrent state and a conv tail per slot), kCCA (K/V per token AND
# conv tails and a shifted value per slot), kRoutedMoE (no state; its
# step's routing counts) and kZayaMoE (the same; its second source and
# second output are the router's state, an edge from expert layer to
# expert layer) implement it.


def keeps_state(layer) -> bool:
    return hasattr(layer, "apply_cached")


def init_cache(net: NeuralNet, batchsize: int, max_len: int,
               dtype=jnp.float32) -> Cache:
    """Zeroed contiguous decode state of every layer that keeps one."""
    return {name: net.layers[name].init_cache(batchsize, max_len, dtype)
            for name in net.topo if keeps_state(net.layers[name])}



def _walk(net: NeuralNet, params, tokens, state: Cache, step):
    """The LM over `tokens` with `step(layer, full, x, entry)` ->
    (out, entry) at every layer that keeps state; every other layer
    runs its normal `apply`.  Returns (logits float32, new state)."""
    full = net._resolve_params(params)
    outputs: Dict[str, Any] = {}
    new_state: Cache = dict(state)
    logits = None
    for idx, name in enumerate(net.topo):
        layer = net.layers[name]
        ltype = layer.cfg.type
        srcs = [net._src_out(outputs, s, name) for s in layer.cfg.srclayers]
        if ltype == "kSequenceData":
            outputs[name] = {"input": tokens, "target": tokens}
        elif ltype == "kSeqLabel":
            outputs[name] = tokens
        elif keeps_state(layer):
            outputs[name], new_state[name] = step(
                layer, full, srcs[0] if len(srcs) == 1 else srcs,
                state[name])
        elif ltype == "kLMHead":
            outputs[name] = layer.apply(full, srcs, DECODE_CTX)
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
    return logits.astype(jnp.float32), new_state


def forward_cached(net: NeuralNet, params, tokens: jnp.ndarray,
                   cache: Cache, pos,
                   kmask: Optional[jnp.ndarray] = None,
                   plen=None) -> Tuple[jnp.ndarray, Cache]:
    """Run the LM over a (B, T) token chunk at absolute offset `pos`.
    Returns (logits (B, T, V) float32, updated cache).  `kmask`
    (B, max_len) bool marks per-sequence attendable key positions
    (the serving tier's left-pad mask, `AttentionLayer.apply_cached`);
    None keeps the pure causal mask.  `plen` (traced scalar) says that
    only the chunk's first `plen` rows are real (the cb prefill's right
    padding): attention needs no telling, its causal mask hides what
    follows, but a recurrence must stop its state at the last real
    row."""
    return _walk(net, params, tokens, cache,
                 lambda layer, full, x, entry: layer.apply_cached(
                     full, x, entry, pos, kmask=kmask, plen=plen))


def forward_paged(net: NeuralNet, params, tokens: jnp.ndarray,
                  pools: Cache, tables, ntoks
                  ) -> Tuple[jnp.ndarray, Cache]:
    """One decode step for S slots against the serving state.
    `tokens` (1, S) int32 — slot s's last sampled token on the seq
    axis; `tables` (S, T) int32 block tables; `ntoks` (S,) int32
    tokens already written per slot (= the incoming token's absolute
    position; 0 for a slot that is not in use).  Returns (logits
    (1, S, V) float32, updated pools)."""
    return _walk(net, params, tokens, pools,
                 lambda layer, full, x, entry: layer.apply_paged(
                     full, x, entry, tables, ntoks))


def scatter_prefill(pools: Cache, cache: Cache, table_row,
                    slot=None, net: Optional[NeuralNet] = None) -> Cache:
    """Hand a batch-1 contiguous prefill state to the serving pools:
    rows per token go to the blocks `table_row` (P // block_len,) int32
    names (entries beyond the slot's real reservation are 0: garbage
    from pad positions lands in the null block, where no mask ever
    looks), a fixed state overwrites slot `slot`'s.  Each layer of
    `net` scatters its own entry; without `net` every entry is K/V of a
    kAttention layer.  Pool entries with no contiguous twin are kept."""
    out: Cache = dict(pools)
    for name, entry in cache.items():
        layer = AttentionLayer if net is None else net.layers[name]
        out[name] = layer.scatter_prefill(pools[name], entry, table_row,
                                          slot)
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
    geometry across runs of different lengths."""
    if key is None:
        key = jax.random.PRNGKey(0)
    prompt = jnp.asarray(prompt, jnp.int32)
    if int(max_new_tokens) <= 0:
        return jnp.zeros((prompt.shape[0], 0), jnp.int32)
    return _generate_jit(net, params, prompt, int(max_new_tokens), key,
                         float(temperature), int(top_k), eos_id,
                         None if max_len is None else int(max_len),
                         float(top_p))
