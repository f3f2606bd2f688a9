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
from typing import Any, Dict, NamedTuple, Optional, Tuple

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
#   apply_chunk(params, x, entry, row, slot, start, plen, piece)
#       -> (out, entry)   optional: a chunk of a prompt that is
#       prefilled in several, straight against the serving state (rows
#       [0, start) of the slot are in it already; `forward_chunk`).  A
#       layer without it keeps the model to prompts of one chunk
#       (`unchunked_layers`)
# `x` is the layer's source, or the list of them where the layer has
# several; `out` is an array, or a dict of named outputs that the
# layer's consumers read by name (`hybrid_layers.named_output`).
# kAttention (K/V per token, all of them or a window's), kMLA (a latent row per token), kKDA (a
# recurrent state and a conv tail per slot), kCCA (K/V per token AND
# conv tails and a shifted value per slot), kRoutedMoE (no state; its
# step's routing counts) and kZayaMoE (the same; its second source and
# second output are the router's state, an edge from expert layer to
# expert layer) implement it.
#
# A net with a kMTP layer (`hybrid_lm(mtp=...)`) holds a multi-token
# prediction MODULE behind it: a second, small stack with a latent
# cache of its own, fed by the main stack's output and the NEXT
# token's embedding, under the main model's embedding and head.  The
# walkers run one part a call: the main stack as ever (the module's
# layers are left out), or, handed the main stack's output `hidden`,
# the module (`draft_cached`, `draft_paged`).  The serving engine's
# verify-and-draft step is made of both (serve/engine.py).


def keeps_state(layer) -> bool:
    return hasattr(layer, "apply_cached")


def init_cache(net: NeuralNet, batchsize: int, max_len: int,
               dtype=jnp.float32) -> Cache:
    """Zeroed contiguous decode state of every layer that keeps one."""
    return {name: net.layers[name].init_cache(batchsize, max_len, dtype)
            for name in net.topo if keeps_state(net.layers[name])}



_HEADS = ("kLMHead", "kLMHeadLoss", "kSoftmaxLoss")


class MTPModule(NamedTuple):
    """A net's multi-token prediction module: its entry layer (kMTP),
    the names of its layers, the last of them (what the head projects
    for it), and the entry's two sources (the embedding, the main
    stack's output)."""
    entry: str
    layers: frozenset
    last: str
    embed: str
    hidden: str


def mtp_module(net: NeuralNet) -> Optional[MTPModule]:
    """The net's module, None where it has none."""
    entries = [n for n in net.topo if net.layers[n].cfg.type == "kMTP"]
    if not entries:
        return None
    if len(entries) > 1:
        raise ValueError(f"several kMTP layers {entries}: one module a net")
    entry = entries[0]
    inside, last = {entry}, entry
    for name in net.topo:
        cfg = net.layers[name].cfg
        if cfg.type not in _HEADS and inside & set(cfg.srclayers):
            inside.add(name)
            last = name
    embed, hidden = net.layers[entry].cfg.srclayers
    return MTPModule(entry, frozenset(inside), last, embed, hidden)


def _walk(net: NeuralNet, params, tokens, state: Cache, step, hidden=None,
          head: bool = True):
    """The LM over `tokens` with `step(layer, full, x, entry)` ->
    (out, entry) at every layer that keeps state; every other layer
    runs its normal `apply`.  Returns (logits float32, new state, the
    main stack's output where the net has a module); without `head`,
    what the head would project in the logits' place (`project_head`
    makes logits of any rows of it).

    With `hidden` the walk is the MODULE's: `tokens` are, for every
    position, the token AFTER it; their embedding and `hidden` go into
    the module's entry, its layers run, and the head projects the last
    of them."""
    full = net._resolve_params(params)
    outputs: Dict[str, Any] = {}
    new_state: Cache = dict(state)
    want_logits, head, head_src = head, None, None
    module = mtp_module(net)
    drafting = hidden is not None
    if drafting:
        outputs[module.hidden] = hidden
    for idx, name in enumerate(net.topo):
        layer = net.layers[name]
        ltype = layer.cfg.type
        if ltype == "kSequenceData":
            outputs[name] = {"input": tokens, "target": tokens}
            continue
        if ltype == "kSeqLabel":
            outputs[name] = tokens
            continue
        if module is not None and ltype not in _HEADS:
            mine = name in module.layers or (drafting
                                             and name == module.embed)
            if mine != drafting:
                continue       # the other part's layer
        srcs = [net._src_out(outputs, s, name) for s in layer.cfg.srclayers]
        if ltype == "kMTP":
            outputs[name] = layer.combine(full, *srcs)
        elif keeps_state(layer):
            outputs[name], new_state[name] = step(
                layer, full, srcs[0] if len(srcs) == 1 else srcs,
                state[name])
        elif ltype in ("kLMHead", "kLMHeadLoss"):
            head, head_src = layer, srcs[0]    # after the walk: the
            outputs[name] = None               # module's end may follow
        elif ltype == "kSoftmaxLoss":
            outputs[name] = None     # no loss at decode
        else:
            ctx = Context(batch={}, train=False, rng=None, layer_index=idx,
                          mesh=None, compute_dtype=None)
            outputs[name] = layer.apply(full, srcs, ctx)
    if head is None:
        raise ValueError("net has no kLMHead/kLMHeadLoss layer")
    if drafting:
        head_src = outputs[module.last]
    logits = (_head_logits(head, full, head_src) if want_logits
              else head_src)
    return (logits, new_state,
            None if module is None or drafting else outputs[module.hidden])


def _head_logits(head, full, head_src):
    # the fused loss layer's projection is reused to emit logits
    logits = (head.apply(full, [head_src], DECODE_CTX)
              if head.cfg.type == "kLMHead"
              else head.project_logits(full, head_src))
    return logits.astype(jnp.float32)


def project_head(net: NeuralNet, params, rows):
    """Logits (B, T, V) float32 of `rows` (B, T, E) of what a walk
    without `head` handed back."""
    head = next(net.layers[n] for n in net.topo
                if net.layers[n].cfg.type in ("kLMHead", "kLMHeadLoss"))
    return _head_logits(head, net._resolve_params(params), rows)


def forward_cached(net: NeuralNet, params, tokens: jnp.ndarray,
                   cache: Cache, pos,
                   kmask: Optional[jnp.ndarray] = None,
                   plen=None, with_hidden: bool = False):
    """Run the LM over a (B, T) token chunk at absolute offset `pos`.
    Returns (logits (B, T, V) float32, updated cache).  `kmask`
    (B, max_len) bool marks per-sequence attendable key positions
    (the serving tier's left-pad mask, `AttentionLayer.apply_cached`);
    None keeps the pure causal mask.  `plen` (traced scalar) says that
    only the chunk's first `plen` rows are real (the cb prefill's right
    padding): attention needs no telling, its causal mask hides what
    follows, but a recurrence must stop its state at the last real
    row.  `with_hidden` hands the main stack's output (B, T, E) back
    third: what a net's MTP module takes (`draft_cached`)."""
    out = _walk(net, params, tokens, cache,
                lambda layer, full, x, entry: layer.apply_cached(
                    full, x, entry, pos, kmask=kmask, plen=plen))
    return out if with_hidden else out[:2]


def draft_cached(net: NeuralNet, params, hidden, next_tokens, cache: Cache,
                 pos, kmask=None, plen=None) -> Tuple[jnp.ndarray, Cache]:
    """The net's MTP module over a chunk: `hidden` (B, T, E) the main
    stack's output at the chunk's positions, `next_tokens` (B, T) the
    token after each.  Returns (logits (B, T, V) float32: position i's
    are the module's for token i + 2; the cache with the module's
    entries updated)."""
    return _walk(net, params, next_tokens, cache,
                 lambda layer, full, x, entry: layer.apply_cached(
                     full, x, entry, pos, kmask=kmask, plen=plen),
                 hidden=hidden)[:2]


def forward_paged(net: NeuralNet, params, tokens: jnp.ndarray,
                  pools: Cache, tables, ntoks, with_hidden: bool = False):
    """One decode step for S slots against the serving state.
    `tokens` (1, S) int32 — slot s's last sampled token on the seq
    axis; `tables` (S, T) int32 block tables; `ntoks` (S,) int32
    tokens already written per slot (= the incoming token's absolute
    position; 0 for a slot that is not in use).  Returns (logits
    (1, S, V) float32, updated pools).

    `tokens` (1, S * R): R tokens a slot, one after another, at
    positions ntoks[s] .. ntoks[s] + R - 1 (a verify step: the last
    token and R - 1 drafted ones); every layer of the net that keeps
    rows per token has to take them (kMLA does).  `with_hidden` as
    `forward_cached`'s."""
    out = _walk(net, params, tokens, pools,
                lambda layer, full, x, entry: layer.apply_paged(
                    full, x, entry, tables, ntoks))
    return out if with_hidden else out[:2]


def unchunked_layers(net: NeuralNet) -> Tuple[str, ...]:
    """What keeps the net to prompts of one chunk: the kinds of its
    layers whose serving state a chunk at start > 0 cannot carry on
    from (no `apply_chunk`: a latent cache, a ring, CCA's tails), and
    the MTP module.  Empty where every layer can."""
    kinds = []
    for name in net.topo:
        layer = net.layers[name]
        if hasattr(layer, "init_pool") and \
                getattr(layer, "apply_chunk", None) is None:
            kind = layer.cfg.type
            if getattr(layer, "window", 0):
                kind += " with a window (a ring of blocks)"
            if kind not in kinds:
                kinds.append(kind)
    if mtp_module(net) is not None and "kMTP" not in kinds:
        kinds.append("kMTP")
    return tuple(kinds)


def forward_chunk(net: NeuralNet, params, tokens: jnp.ndarray, pools: Cache,
                  row, slot, start, plen, piece: int):
    """One chunk of a prompt that is prefilled in several, straight
    against the serving state of slot `slot`: `tokens` (1, T) the
    chunk's, right-padded, its first `plen` real (traced), the first at
    absolute position `start` (traced; a multiple of `piece`, the widest
    chunk).  `row` is the slot's WHOLE table row.  Rows [0, start) are
    in the pools already: a recurrent layer goes on from the slot's own
    state and tails (zeros at start 0), an attention layer writes the
    chunk's K/V rows into the slot's blocks and attends them causally
    and rows [0, start) in full, `piece` rows of the pool at a time.
    Returns (what the head projects, (1, T, E); updated pools): the
    head is the caller's, on the one row it needs (`project_head`)."""
    out = _walk(net, params, tokens, pools,
                lambda layer, full, x, entry: layer.apply_chunk(
                    full, x, entry, row, slot, start, plen, piece),
                head=False)
    return out[:2]


def draft_paged(net: NeuralNet, params, hidden, next_tokens, pools: Cache,
                tables, ntoks) -> Tuple[jnp.ndarray, Cache]:
    """`draft_cached` against the serving state: `hidden` (1, S * R, E)
    and `next_tokens` (1, S * R) at positions ntoks[s] .. ntoks[s] +
    R - 1 of every slot."""
    return _walk(net, params, next_tokens, pools,
                 lambda layer, full, x, entry: layer.apply_paged(
                     full, x, entry, tables, ntoks), hidden=hidden)[:2]


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


def _filtered(logits: jnp.ndarray, temperature: float, top_k: int,
              top_p: float) -> jnp.ndarray:
    """(..., V) logits over the temperature (> 0), those the top-k and
    nucleus filters drop at -1e30: what a token is drawn from."""
    logits = logits / temperature
    if top_k > 0 and top_k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
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
    return logits


def _sample(logits: jnp.ndarray, key, temperature: float,
            top_k: int, top_p: float) -> jnp.ndarray:
    """logits: (B, V) -> (B,) int32.  temperature 0 = greedy."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, _filtered(logits, temperature, top_k, top_p),
        axis=-1).astype(jnp.int32)


def _at(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """table (..., V) at ids (...)."""
    return jnp.take_along_axis(table, ids[..., None], axis=-1)[..., 0]


def sample_logprob(logits: jnp.ndarray, key, temperature: float, top_k: int,
                   top_p: float):
    """logits (B, V) -> (token (B,) int32, its log-probability (B,)
    float32, the distribution (B, V) float32 it was drawn from).  At
    temperature 0 the token is the largest logit's, the log-probability
    the plain softmax's and no distribution is handed back (None)."""
    if temperature == 0.0:
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return tok, _at(jax.nn.log_softmax(logits, axis=-1), tok), None
    z = _filtered(logits, temperature, top_k, top_p)
    tok = jax.random.categorical(key, z, axis=-1).astype(jnp.int32)
    logp = jax.nn.log_softmax(z, axis=-1)
    return tok, _at(logp, tok), jnp.exp(logp)


def verify_draft(logits: jnp.ndarray, draft: jnp.ndarray, q, key,
                 temperature: float, top_k: int, top_p: float):
    """The accept-or-resample rule of speculative sampling, one draft a
    sequence.  logits (S, 2, V): the main model's at the row of the
    last token (the distribution p of the token the draft stands for)
    and at the draft's row; draft (S,) int32, drawn from q (S, V).
    The draft is accepted with probability min(1, p(d) / q(d)); else
    the token is drawn from norm(max(0, p - q)); behind an accepted
    draft a bonus token is drawn from the second row.  Every emitted
    token is distributed as the main model alone would have drawn it.
    At temperature 0 (q is not read) a draft is accepted where it IS the
    largest logit's token, and the tokens are the largest logits'.

    Returns (first token, bonus token, accepted, and the log-probability
    of each of the two under the row it came from): (S,) each; the
    bonus counts only where `accepted`."""
    if temperature == 0.0:
        toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        lp = _at(jax.nn.log_softmax(logits, axis=-1), toks)
        return toks[:, 0], toks[:, 1], draft == toks[:, 0], lp[:, 0], lp[:, 1]
    z = _filtered(logits, temperature, top_k, top_p)
    logp = jax.nn.log_softmax(z, axis=-1)
    p = jnp.exp(logp[:, 0])
    k_accept, k_again, k_bonus = jax.random.split(key, 3)
    accepted = (jax.random.uniform(k_accept, draft.shape) * _at(q, draft)
                < _at(p, draft))
    left = jnp.maximum(p - q, 0.0)
    # p == q to the last bit leaves nothing; then every draft is accepted
    left = jnp.where(left.sum(-1, keepdims=True) > 0, left, p)
    again = jax.random.categorical(k_again, jnp.log(left), axis=-1)
    first = jnp.where(accepted, draft, again).astype(jnp.int32)
    bonus = jax.random.categorical(k_bonus, z[:, 1], axis=-1).astype(
        jnp.int32)
    return (first, bonus, accepted, _at(logp[:, 0], first),
            _at(logp[:, 1], bonus))


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
