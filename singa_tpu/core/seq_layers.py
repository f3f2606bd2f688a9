"""Sequence-model layer family: the transformer extension of the layer
zoo, declared in the same NetProto-style config IR as the conv layers.

New capability (SURVEY.md §5: the reference predates attention) exposed
"the same way the reference exposes partitioning, i.e. as declarative
config": attention_param.seq_parallel selects none/ring/ulysses; expert
parallelism comes from MoE expert-stacked params sharded over the
mesh's "expert" axis; tensor parallelism from partition_dim on the
projection weights.

Layer types: kSequenceData, kEmbed, kRMSNorm, kAttention, kFeedForward,
kMoE, kLMHead.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from ..config.schema import ParamConfig
from ..ops import moe as moe_ops
from ..ops.attention import (attention_reference, chunk_attention,
                             expand_kv_heads, flash_attention, flash_part,
                             flash_part_legal, flash_prefill,
                             merge_attention, rope)
from ..ops.paged_attention import paged_decode_attention, ring_blocks
from .layers import Context, Layer, LayerError, register_layer

# keys of the fallbacks already reported once: (layer name, seq_len,
# head_dim) for dense attention, (layer name, "head", shapes...) for
# the chunked LM head
_flash_fallback_warned: set = set()


def _gaussian(std: float) -> ParamConfig:
    return ParamConfig(init_method="kGaussain", mean=0.0, std=std)


def _declare_with_default(layer: Layer, i: int, name: str, shape,
                          init_std: float, partition_dim: int = -1,
                          mesh_axis: Optional[str] = None) -> str:
    """Declare a param with a Gaussian default when the config gives no
    explicit ParamProto (transformer configs usually don't)."""
    from .layers import ParamSpec
    if i < len(layer.cfg.param):
        key = layer._declare(i, name, shape, fan_in=shape[0],
                             partition_dim=partition_dim)
        layer.param_specs[-1].mesh_axis = mesh_axis
        return key
    pcfg = _gaussian(init_std)
    key = f"{layer.name}/{name}"
    layer.param_specs.append(
        ParamSpec(key, tuple(shape), shape[0], pcfg, partition_dim,
                  mesh_axis))
    return key


@register_layer("kSequenceData")
class SequenceDataLayer(Layer):
    """Token-sequence input: ctx.batch[name] = {"input": (B,S) int32,
    "target": (B,S) int32}."""

    is_data = True

    def setup(self, src_shapes, sample_shapes: Optional[Dict] = None):
        p = self.cfg.seqdata_param
        bs = p.batchsize if p else (self.cfg.data_param.batchsize
                                    if self.cfg.data_param else 0)
        seq = p.seq_len if p else 0
        self.batchsize, self.seq_len = bs, seq
        self.vocab_size = p.vocab_size if p else 0
        if sample_shapes:
            self.out_shape = {k: (bs,) + tuple(v)
                              for k, v in sample_shapes.items()}
        else:
            self.out_shape = {"input": (bs, seq), "target": (bs, seq)}

    def apply(self, params, srcs, ctx):
        return ctx.batch[self.name]


@register_layer("kEmbed")
class EmbedLayer(Layer):
    """Token embedding: (B, S) int32 → (B, S, E)."""

    def setup(self, src_shapes):
        p = self.cfg.embed_param
        if p is None or not p.vocab_size or not p.embed_dim:
            raise LayerError(f"{self.name}: embed_param vocab_size/embed_dim "
                             "required")
        src = src_shapes[0]
        shape = src["input"] if isinstance(src, dict) else tuple(src)
        self.out_shape = tuple(shape) + (p.embed_dim,)
        self.scale = p.scale
        self.w_key = _declare_with_default(
            self, 0, "embedding", (p.vocab_size, p.embed_dim),
            init_std=1.0 / math.sqrt(p.embed_dim), partition_dim=1)

    def apply(self, params, srcs, ctx):
        src = srcs[0]
        tokens = src["input"] if isinstance(src, dict) else src
        emb = params[self.w_key]
        if ctx.compute_dtype is not None:
            emb = emb.astype(ctx.compute_dtype)
        rows = jnp.take(emb, tokens.astype(jnp.int32), axis=0)
        if self.scale:
            rows = (rows.astype(jnp.float32) * self.scale).astype(rows.dtype)
        return rows


@register_layer("kSeqLabel")
class SeqLabelLayer(Layer):
    """Next-token targets from the sequence data dict."""

    def setup(self, src_shapes):
        self.out_shape = tuple(src_shapes[0]["target"])

    def apply(self, params, srcs, ctx):
        return srcs[0]["target"]


@register_layer("kRMSNorm")
class RMSNormLayer(Layer):
    def setup(self, src_shapes):
        p = self.cfg.rmsnorm_param
        self.eps = p.epsilon if p else 1e-6
        s = tuple(src_shapes[0])
        self.out_shape = s
        key = f"{self.name}/scale"
        from .layers import ParamSpec
        self.param_specs.append(ParamSpec(
            key, (s[-1],), 0, ParamConfig(init_method="kConstant", value=1.0)))
        self.w_key = key

    def apply(self, params, srcs, ctx):
        return rms_norm(srcs[0], params[self.w_key], self.eps)


def rms_norm(x, scale, eps: float):
    """x over the root of its last axis's mean square (float32), times
    a learned scale; in x's dtype."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    y = x * jax.lax.rsqrt(var + eps).astype(x.dtype)
    return y * scale.astype(x.dtype)


# what a layer's decode-time methods hand to shared helpers that take a
# Context: inference, no rng, no mesh, the params' own dtype
DECODE_CTX = Context(batch={}, train=False, rng=None, layer_index=0,
                     mesh=None, compute_dtype=None)


def write_token(pool, bidx, off, new):
    """Row `off[s]` of pool block `bidx[s]` becomes `new[s]` (heads, D),
    for every slot s; inactive slots all write the null block.  A K/V
    pool's `new` is the token's key heads and then its value heads
    (`paged_rows`): one patched block, one scatter, for both.

    Whole blocks are read, patched and scattered back, so the scatter's
    window is the pool's trailing (heads, block_len, D) dims.  The
    direct form, `pool.at[bidx, :, off].set(new)`, has the window
    (heads, D) around the scattered block_len axis; XLA:TPU gives that
    scatter's operand another layout than the pool arrives and leaves
    in, and copies the WHOLE pool there and back, for each layer of
    every decode step."""
    rows = jnp.arange(pool.shape[2])[None, None, :, None]
    blocks = jnp.where(rows == off[:, None, None, None],
                       new.astype(pool.dtype)[:, :, None, :], pool[bidx])
    return pool.at[bidx].set(blocks)


def paged_rows(k, v):
    """Keys and values (..., Hkv, n, D) as a K/V pool holds them: the
    key heads and then the value heads, (..., 2 * Hkv, n, D)."""
    return jnp.concatenate([k, v], axis=-3)


def attend_cache(q, k_cache, v_cache, pos, kmask=None, window=0):
    """A chunk's queries q (B, H, T, D), the first at absolute position
    `pos`, against contiguous caches (B, Hkv, max_len, D) that already
    hold the chunk's own rows: causal (with `window`, the last `window`
    positions only), `kmask` (B, max_len) ANDed in, GQA read at Hkv
    width, f32 scores and softmax.  Returns (B, T, H * D) in the
    values' dtype.

    A whole chunk at position 0 (the cb prefill: `pos` the integer 0,
    no `kmask`, the chunk the whole cache, whole lane tiles of rows and
    a head the kernel is legal at) is plain causal self-attention, and
    the flash forward kernel computes it with no score square in HBM;
    everything else (a decode token, a traced position, a masked batch,
    a chunk under a lane tile) is the dense scores below."""
    _, heads, t, head_dim = q.shape
    if (isinstance(pos, int) and pos == 0 and kmask is None
            and t == k_cache.shape[2] and t % 128 == 0
            and head_dim % 8 == 0 and heads % k_cache.shape[1] == 0):
        return _attend_chunk(q, k_cache.astype(q.dtype),
                             v_cache.astype(q.dtype), window)
    return _attend_dense(q, k_cache, v_cache, pos, kmask, window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attend_chunk(q, k, v, window):
    """`attend_cache`'s whole chunk at position 0 through
    `ops.attention.flash_prefill`: the kernel takes the projections'
    (B, T, H * D), so the heads go back behind the rows.  The kernel is
    forward only; a gradient is the dense scores'."""
    b, heads, t, _ = q.shape

    def packed(a):
        return a.transpose(0, 2, 1, 3).reshape(b, t, -1)
    return flash_prefill(packed(q), packed(k), packed(v), heads,
                         k.shape[1], window)


def _attend_chunk_fwd(q, k, v, window):
    return _attend_chunk(q, k, v, window), (q, k, v)


def _attend_chunk_bwd(window, res, g):
    return jax.vjp(lambda q, k, v: _attend_dense(q, k, v, 0, None, window),
                   *res)[1](g)


_attend_chunk.defvjp(_attend_chunk_fwd, _attend_chunk_bwd)


def _attend_dense(q, k_cache, v_cache, pos, kmask, window):
    """`attend_cache` as masked-dense f32 scores."""
    b, heads, t, head_dim = q.shape
    kv_heads = k_cache.shape[1]
    groups = heads // kv_heads
    kk = k_cache.astype(q.dtype)
    vv = v_cache.astype(q.dtype)
    qpos = pos + jnp.arange(t)[:, None]            # (T, 1) absolute
    kpos = jnp.arange(kk.shape[2])[None, :]        # (1, max_len)
    allowed = (kpos <= qpos)[None]                 # (1, T, max_len)
    if window:
        allowed = allowed & (kpos > qpos - window)[None]
    if kmask is not None:
        allowed = allowed & kmask[:, None, :]      # (B, T, max_len)
    if groups == 1:
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, kk,
                            preferred_element_type=jnp.float32)
        scores = scores / jnp.sqrt(jnp.float32(head_dim))
        scores = jnp.where(allowed[:, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(vv.dtype), vv)
    else:
        qg = q.reshape(b, kv_heads, groups, t, head_dim)
        scores = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kk,
                            preferred_element_type=jnp.float32)
        scores = scores / jnp.sqrt(jnp.float32(head_dim))
        scores = jnp.where(allowed[:, None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhgqk,bhkd->bhgqd", probs.astype(vv.dtype), vv)
        out = out.reshape(b, heads, t, head_dim)
    return out.transpose(0, 2, 1, 3).reshape(b, t, -1)


def attend_prefix(q, k, v, pool, row, start, piece: int):
    """A chunk's attention where the rows before it are in the paged
    pool: q (1, H, T, D), the first at absolute position `start`
    (traced, a multiple of `piece`), k / v (1, Hkv, T, D) the chunk's
    own; `pool` (num_blocks, 2 * Hkv, block_len, D) holds rows
    [0, start) of the sequence in the blocks `row` names (the slot's
    whole table row).  Causal within the chunk, in full over the rows
    before it, `piece` rows of the pool at a time: each part gives
    (out, log-sum-exp) and `merge_attention` joins them, so the scores
    that exist at once are one part's and the time follows `start`, not
    the table's width.  A part goes through the flash forward kernel
    (`ops.attention.flash_part`) where its shapes are legal there, and
    through `chunk_attention`'s dense scores where not (the tiny test
    sizes).  Returns (1, T, H * D) float32."""
    _, heads, t, d = q.shape
    hkv, bl = k.shape[1], pool.shape[2]
    nb = piece // bl
    flash = flash_part_legal(t, t, d, heads, hkv) and \
        flash_part_legal(t, piece, d, heads, hkv)

    def packed(a):                       # (1, n, rows, D) -> (1, rows, n D)
        return a.transpose(0, 2, 1, 3).reshape(1, a.shape[2], -1)

    def part(kk, vv, causal):
        """(out (1, T, H, D), lse (1, T, H, 1)), both float32."""
        if flash:
            out, lse = flash_part(packed(q), packed(kk.astype(q.dtype)),
                                  packed(vv.astype(q.dtype)), heads, hkv,
                                  causal)
            return (out.reshape(1, t, heads, d).astype(jnp.float32),
                    lse[..., None])
        out, lse = chunk_attention(q, expand_kv_heads(kk.astype(q.dtype), heads),
                                   expand_kv_heads(vv.astype(q.dtype), heads),
                                   causal, 0, 0)
        return out.transpose(0, 2, 1, 3), lse.transpose(0, 2, 1, 3)

    def before(i, carry):
        blocks = pool[jax.lax.dynamic_slice_in_dim(row, i * nb, nb)]
        rows = blocks.transpose(1, 0, 2, 3).reshape(1, 2 * hkv, piece, d)
        return merge_attention(*carry,
                               *part(rows[:, :hkv], rows[:, hkv:], False))

    out, _ = jax.lax.fori_loop(0, start // piece, before, part(k, v, True))
    return out.reshape(1, t, heads * d)


@register_layer("kAttention")
class AttentionLayer(Layer):
    """Multi-head (GQA) causal self-attention with RoPE.

    seq_parallel: "none" → Pallas flash attention on the local chunk;
    "ring" / "ulysses" → sequence-parallel attention over the mesh's
    "seq" axis (singa_tpu.parallel.sequence).

    Three options of attention_param, each off unless set, and a layer
    without them is the program it was before they existed: `window` W
    (query t sees keys t - W + 1 .. t; a mask over dense scores in
    `apply` and `apply_cached`, blocks skipped by the flash forward
    kernel where `attend_cache` takes it, and in the serving pools a
    RING of blocks per slot read by the windowed walk of the paged
    kernel),
    `qk_norm` (a learned RMSNorm over head_dim on every query and key
    head, before RoPE), `gate` (out = (sigmoid(x Wg) * attention) Wo).
    """

    def setup(self, src_shapes):
        p = self.cfg.attention_param
        if p is None:
            raise LayerError(f"{self.name}: attention_param required")
        b, s, e = tuple(src_shapes[0])
        self.heads = p.num_heads
        self.kv_heads = p.num_kv_heads or p.num_heads
        self.head_dim = p.head_dim
        self.causal = p.causal
        self.seq_parallel = p.seq_parallel
        self.use_rope = p.rope
        self.rope_theta = p.rope_theta
        self.window = int(p.window)
        self.qk_norm, self.norm_eps, self.gate = (p.qk_norm,
                                                  p.norm_epsilon, p.gate)
        if self.window < 0 or (self.window and (
                not self.causal or self.seq_parallel != "none")):
            raise LayerError(
                f"{self.name}: a window of {self.window} needs causal "
                f"attention on one chip's sequence")
        self.out_shape = (b, s, e)
        hd = self.heads * self.head_dim
        kvd = self.kv_heads * self.head_dim
        std = 1.0 / math.sqrt(e)
        self.wq = _declare_with_default(self, 0, "wq", (e, hd), std, 1)
        self.wk = _declare_with_default(self, 1, "wk", (e, kvd), std, 1)
        self.wv = _declare_with_default(self, 2, "wv", (e, kvd), std, 1)
        self.wo = _declare_with_default(self, 3, "wo", (hd, e), std, 0)
        if self.gate:
            self.wg = _declare_with_default(self, 4, "wg", (e, hd), std, 1)
        if self.qk_norm:
            from .layers import ParamSpec
            one = ParamConfig(init_method="kConstant", value=1.0)
            self.q_norm, self.k_norm = (f"{self.name}/q_norm",
                                        f"{self.name}/k_norm")
            self.param_specs += [
                ParamSpec(key, (self.head_dim,), 0, one)
                for key in (self.q_norm, self.k_norm)]
        if self.window:
            # the serving state is a ring per slot, not table blocks;
            # and a ring does not hold the rows before a chunk
            self.init_pool = self._init_ring
            self.scatter_prefill = self._scatter_ring
            self.apply_chunk = None

    def _proj(self, params, key, x, ctx):
        w = params[key]
        if ctx.compute_dtype is not None:
            w = w.astype(ctx.compute_dtype)
        return jnp.einsum("bse,ed->bsd", x, w,
                          preferred_element_type=jnp.float32).astype(x.dtype)

    def qkv(self, params, x, positions, ctx):
        """Projection + head-split + RoPE prologue, shared by training
        `apply` and the KV-cache decode path (models/generate.py).
        `positions`: (S,) absolute token positions for RoPE.  Returns
        q (B, H, S, D) and k, v (B, Hkv, S, D) — pre-GQA-expansion."""
        b, s, e = x.shape
        q = self._proj(params, self.wq, x, ctx).reshape(
            b, s, self.heads, self.head_dim).transpose(0, 2, 1, 3)
        k = self._proj(params, self.wk, x, ctx).reshape(
            b, s, self.kv_heads, self.head_dim).transpose(0, 2, 1, 3)
        v = self._proj(params, self.wv, x, ctx).reshape(
            b, s, self.kv_heads, self.head_dim).transpose(0, 2, 1, 3)
        if self.qk_norm:       # over a head's dims, one scale for all heads
            q = rms_norm(q, params[self.q_norm], self.norm_eps)
            k = rms_norm(k, params[self.k_norm], self.norm_eps)
        if self.use_rope:
            q = rope(q, positions, self.rope_theta)
            k = rope(k, positions, self.rope_theta)
        return q, k, v

    def _out(self, params, x, attended, ctx):
        """The heads' outputs (B, S, H * D), gated where the layer has a
        gate, through Wo."""
        attended = attended.astype(x.dtype)
        if self.gate:
            attended = attended * jax.nn.sigmoid(
                self._proj(params, self.wg, x, ctx))
        return self._proj(params, self.wo, attended, ctx)

    def _packed_eligible(self, b: int, s: int, ctx) -> bool:
        """The zero-transpose packed flash path: flash-legal shapes, GQA
        included (the kernels read each q head's group kv slice
        in-kernel — no expand_kv_heads copies).  Since round 5 mesh runs
        take it too, as a shard_map local step (batch on "data", heads
        on "model" — parallel.sequence.packed_attention_sharded), when
        the batch/head counts split evenly over those axes; "seq" must
        be unsharded (a sharded S would need offset-aware masks) and
        "pipe" never reaches here (stage bodies see ctx.mesh None)."""
        if not (self.seq_parallel == "none"
                and self.heads % self.kv_heads == 0
                and s % 128 == 0 and self.head_dim % 8 == 0
                and not (self.window or self.qk_norm or self.gate)):
            return False
        if ctx.mesh is None:
            return True
        shape = dict(ctx.mesh.shape)
        tp = shape.get("model", 1)
        return (shape.get("seq", 1) == 1 and shape.get("pipe", 1) == 1
                and b % max(shape.get("data", 1), 1) == 0
                and self.heads % max(tp, 1) == 0
                and self.kv_heads % max(tp, 1) == 0)

    def apply(self, params, srcs, ctx):
        x = srcs[0]
        b, s, e = x.shape
        if self._packed_eligible(b, s, ctx):
            # packed path: (B, S, H·D) end to end — the projection
            # output feeds the kernel directly and the kernel output
            # feeds wo directly.  The (B,S,H,D)→(B,H,S,D) transposes of
            # the strided path cost ~5ms/step on the 12-head S=1024
            # bench stack.
            from ..ops.attention import flash_attention_packed, rope_packed
            positions = jnp.arange(s)
            q = self._proj(params, self.wq, x, ctx)
            k = self._proj(params, self.wk, x, ctx)
            v = self._proj(params, self.wv, x, ctx)
            if self.use_rope:
                q = rope_packed(q, positions, self.heads, self.rope_theta)
                k = rope_packed(k, positions, self.kv_heads,
                                self.rope_theta)
            from ..ops.attention import flash_blocks
            bq, bk = flash_blocks(s)
            if ctx.mesh is not None:
                from ..parallel.sequence import packed_attention_sharded
                out = packed_attention_sharded(
                    q, k, v, ctx.mesh, self.heads, self.kv_heads,
                    self.causal, bq, bk)
            else:
                # custom_vjp + nondiff_argnums: positional args only
                out = flash_attention_packed(q, k, v, self.heads,
                                             self.causal, bq, bk, None,
                                             self.kv_heads)
            return self._proj(params, self.wo, out.astype(x.dtype), ctx)
        q, k, v = self.qkv(params, x, jnp.arange(s), ctx)

        if self.window:
            # the window as a mask over dense scores (the backward
            # flash kernels know no window)
            return self._out(params, x, _attend_dense(
                q, k, v, 0, None, self.window), ctx)
        if self.seq_parallel == "ring" and ctx.mesh is not None:
            # k/v stay at Hkv width: the ring rotates (and Ulysses
            # all-to-alls) unexpanded KV; group expansion happens on
            # the local chunk inside the SP step (round 5)
            from ..parallel.sequence import ring_attention
            out = ring_attention(q, k, v, ctx.mesh, "seq", self.causal)
        elif self.seq_parallel == "ulysses" and ctx.mesh is not None:
            from ..parallel.sequence import ulysses_attention
            out = ulysses_attention(q, k, v, ctx.mesh, "seq", self.causal)
        elif s % 128 == 0 and self.head_dim % 8 == 0:
            k = expand_kv_heads(k, self.heads)
            v = expand_kv_heads(v, self.heads)
            from ..ops.attention import flash_blocks
            out = flash_attention(q, k, v, self.causal, *flash_blocks(s))
        else:
            # once-keyed on (name, shape): a second model reusing a
            # layer name at a different geometry still warns
            if (self.cfg.name, s, self.head_dim) \
                    not in _flash_fallback_warned:
                _flash_fallback_warned.add(
                    (self.cfg.name, s, self.head_dim))
                import sys
                print(f"warning: attention layer {self.cfg.name!r} "
                      f"(seq_len={s}, head_dim={self.head_dim}) falls "
                      f"back to dense O(S^2)-memory attention — the "
                      f"flash kernel needs seq_len % 128 == 0 and "
                      f"head_dim % 8 == 0", file=sys.stderr)
            out = attention_reference(q, expand_kv_heads(k, self.heads),
                                      expand_kv_heads(v, self.heads),
                                      self.causal)
        out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
        return self._out(params, x, out, ctx)



    # -- decode state: the protocol models/generate.py and
    # serve/kvcache.py drive for every mixer layer -------------------------
    def init_cache(self, batch: int, max_len: int, dtype):
        """Contiguous K/V for `batch` sequences of up to `max_len`; a
        windowed layer's entry also says how many of its rows are real
        (what its ring keeps of a prefill, `_scatter_ring`)."""
        shape = (batch, self.kv_heads, max_len, self.head_dim)
        entry = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        if self.window:
            entry["rows"] = jnp.zeros((), jnp.int32)
        return entry

    def init_pool(self, num_slots: int, num_blocks: int, block_len: int,
                  dtype):
        """Paged K/V, one pool: (num_blocks, 2 * Hkv, block_len, D), a
        block's key heads and then its value heads, so that the paged
        kernel brings a block in one copy."""
        return {"kv": jnp.zeros((num_blocks, 2 * self.kv_heads, block_len,
                                 self.head_dim), dtype)}

    @staticmethod
    def scatter_prefill(pool, cache, table_row, slot=None):
        """A batch-1 contiguous prefill cache ((1, Hkv, P, D) a side, P
        a block_len multiple) into the pool blocks `table_row` names:
        one scatter of whole blocks."""
        kv = pool["kv"]
        bl = kv.shape[2]
        rows = paged_rows(cache["k"][0], cache["v"][0])     # (2 Hkv, P, D)
        heads, p, d = rows.shape
        blocks = rows.reshape(heads, p // bl, bl, d).transpose(1, 0, 2, 3)
        return {"kv": kv.at[table_row].set(blocks.astype(kv.dtype))}

    # a windowed layer's serving state: a ring of blocks per slot.  It
    # needs the last `window` positions of a slot and no more, so slot
    # s owns pool blocks 1 + s R .. (s + 1) R, R = `ring_blocks`, for
    # good, and position p lives in the slot's column (p // bl) % R.
    # Nothing is allocated at admission and nothing freed at
    # retirement, as with a recurrent layer's state per slot.
    def _init_ring(self, num_slots: int, num_blocks: int, block_len: int,
                   dtype):
        """Paged K/V of `num_slots` rings (and the null block):
        `num_blocks`, the table layers' pool size, plays no part."""
        blocks = num_slots * ring_blocks(self.window, block_len) + 1
        return AttentionLayer.init_pool(self, num_slots, blocks, block_len,
                                        dtype)

    @staticmethod
    def ring_tables(slots: int, ring: int):
        """(slots, ring) int32: the pool block behind every slot's
        every ring column."""
        return (1 + jnp.arange(slots, dtype=jnp.int32)[:, None] * ring
                + jnp.arange(ring, dtype=jnp.int32)[None, :])

    def _scatter_ring(self, pool, cache, table_row, slot=None):
        """A batch-1 contiguous prefill cache into slot `slot`'s ring:
        of the cache's blocks, the last `ring_blocks` that hold a real
        row (`cache["rows"]` of them are), each to its column; the
        others to the null block."""
        if slot is None:
            raise LayerError(f"{self.name}: a windowed layer's prefill "
                             f"has to be told its slot")
        bl = pool["kv"].shape[2]
        ring = ring_blocks(self.window, bl)
        nb = cache["k"].shape[2] // bl
        block = jnp.arange(nb, dtype=jnp.int32)
        last = (cache["rows"] - 1) // bl
        kept = (block <= last) & (block > last - ring)
        row = jnp.where(kept, 1 + slot * ring + block % ring, 0)
        return AttentionLayer.scatter_prefill(pool, cache, row)

    def apply_cached(self, params, x, entry, pos, kmask=None, plen=None):
        """`plen` (real rows of a right-padded chunk) is for recurrent
        mixers; the causal mask alone keeps pad keys out here.

        Attention for a (B, T, E) chunk whose first token sits at absolute
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
        assert self.causal, f"{self.name}: decode requires causal attention"
        b, t, e = x.shape
        q, k, v = self.qkv(params, x, pos + jnp.arange(t), DECODE_CTX)

        k_cache = jax.lax.dynamic_update_slice(
            entry["k"], k.astype(entry["k"].dtype), (0, 0, pos, 0))
        v_cache = jax.lax.dynamic_update_slice(
            entry["v"], v.astype(entry["v"].dtype), (0, 0, pos, 0))

        out = attend_cache(q, k_cache, v_cache, pos, kmask, self.window)
        out = self._out(params, x, out, DECODE_CTX)
        entry = {"k": k_cache, "v": v_cache}
        if self.window:
            entry["rows"] = jnp.asarray(pos + t if plen is None else plen,
                                        jnp.int32)
        return out, entry



    def apply_chunk(self, params, x, entry, row, slot, start, plen, piece):
        """A chunk of a prompt that is prefilled in several
        (`generate.forward_chunk`): x (1, T, E) at positions `start` ..
        start + T - 1, T whole blocks.  Its K/V rows go into the blocks
        of the slot's table row `row` that hold those positions (pads
        too: into reserved blocks a decode step writes before it reads,
        or the null block), then `attend_prefix`: causal within the
        chunk, in full over rows [0, start) of the pool.  `plen` is for
        recurrent mixers, as in `apply_cached`."""
        assert self.causal, f"{self.name}: decode requires causal attention"
        pool = entry["kv"]
        bl, t = pool.shape[2], x.shape[1]
        q, k, v = self.qkv(params, x, start + jnp.arange(t), DECODE_CTX)
        rows = paged_rows(k[0], v[0])                        # (2 Hkv, T, D)
        blocks = rows.reshape(rows.shape[0], t // bl, bl, -1).transpose(
            1, 0, 2, 3)
        mine = jax.lax.dynamic_slice_in_dim(row, start // bl, t // bl)
        out = attend_prefix(q, k, v, pool, row, start, piece)
        pool = pool.at[mine].set(blocks.astype(pool.dtype))
        return self._out(params, x, out, DECODE_CTX), {"kv": pool}

    def apply_paged(self, params, x, entry, tables, ntoks):
        """Single-token decode attention over a block/paged KV pool.

        `x` is (1, S, E): the serving tier's S decode slots ride the SEQ
        axis of a batch-1 chunk, so every position-wise layer (embed,
        rmsnorm, ffn, lmhead) and `self.qkv`'s per-position RoPE treat a
        slot exactly like a sequence position — `ntoks` (S,) int32 is both
        the per-slot absolute position vector RoPE rotates by and the
        per-slot key-visibility horizon.  The slots never attend each
        other: attention below is per-slot against that slot's own blocks.

        `entry` holds the layer's pool {"kv"}, (num_blocks, 2 * Hkv,
        block_len, D), a block's key heads and then its value heads;
        `tables` (S, T) int32 maps slot s's logical block t
        to a pool index (block 0 = null: inactive slots and table tails
        point there).  Token position p of slot s lives at
        pool[tables[s, p // bl], :, p % bl].

        Write-before-read: the new K/V is scattered at position ntoks[s]
        first (one patched block a slot for both), then
        `ops.paged_attention.paged_decode_attention` attends
        positions `<= ntoks[s]` — the same self-inclusive causal horizon as
        `_attn_cached` at T=1 — walking ntoks[s] // bl + 1 blocks of the
        slot's table row and no more (an inactive slot: the null block).
        It is the one formulation on every backend (interpreted off the
        TPU).  Same math as the contiguous read, f32 scores and softmax,
        but summed chunk by chunk: the tests pin greedy-token identity with
        `generate()` and a tolerance against the gather reference, not
        bit-equality.

        A windowed layer takes no notice of `tables`: its pool is a ring
        per slot (`_init_ring`), the new row goes to the slot's column
        (ntoks[s] // bl) % R and the kernel walks the window's blocks
        only."""
        assert self.causal, f"{self.name}: decode requires causal attention"
        _, s, _ = x.shape
        bl = entry["kv"].shape[2]
        q, k, v = self.qkv(params, x, ntoks, DECODE_CTX)    # (1,H,S,D)/(1,Hkv,S,D)

        if self.window:
            # the slot's ring in place of its table row: position p in
            # column (p // bl) % R, and the kernel's windowed walk
            ring = ring_blocks(self.window, bl)
            tables = self.ring_tables(s, ring)
            bidx = tables[jnp.arange(s), (ntoks // bl) % ring]
        else:
            bidx = tables[jnp.arange(s), ntoks // bl]  # (S,) pool block
        off = ntoks % bl                               # (S,) offset in block
        new = paged_rows(k[0], v[0]).transpose(1, 0, 2)    # (S, 2 Hkv, D)
        pool = write_token(entry["kv"], bidx, off, new)

        out = paged_decode_attention(q[0].transpose(1, 0, 2), pool, tables,
                                     ntoks, window=self.window)  # (S, H, D)
        out = self._out(params, x, out.reshape(1, s, -1), DECODE_CTX)
        return out, {"kv": pool}


@register_layer("kFeedForward")
class FeedForwardLayer(Layer):
    """Gated (SwiGLU) or plain MLP over (B, S, E)."""

    def setup(self, src_shapes):
        p = self.cfg.ffn_param
        if p is None or not p.hidden_dim:
            raise LayerError(f"{self.name}: ffn_param.hidden_dim required")
        b, s, e = tuple(src_shapes[0])
        f = p.hidden_dim
        if p.activation not in ("silu", "gelu", "relu"):
            raise LayerError(f"{self.name}: unknown ffn activation "
                             f"{p.activation!r} (silu|gelu|relu)")
        self.activation = p.activation
        self.gated = p.gated
        self.out_shape = (b, s, e)
        std = 1.0 / math.sqrt(e)
        self.w1 = _declare_with_default(self, 0, "w1", (e, f), std, 1)
        self.w2 = _declare_with_default(self, 1, "w2", (f, e),
                                        1.0 / math.sqrt(f), 0)
        if self.gated:
            self.w3 = _declare_with_default(self, 2, "w3", (e, f), std, 1)

    def apply(self, params, srcs, ctx):
        x = srcs[0]

        def cast(w):
            return (w.astype(ctx.compute_dtype)
                    if ctx.compute_dtype is not None else w)
        act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
               "relu": jax.nn.relu}[self.activation]
        h = jnp.einsum("bse,ef->bsf", x, cast(params[self.w1]),
                       preferred_element_type=jnp.float32).astype(x.dtype)
        h = act(h)
        if self.gated:
            g = jnp.einsum("bse,ef->bsf", x, cast(params[self.w3]),
                           preferred_element_type=jnp.float32).astype(x.dtype)
            h = h * g
        return jnp.einsum("bsf,fe->bse", h, cast(params[self.w2]),
                          preferred_element_type=jnp.float32).astype(x.dtype)


@register_layer("kMoE")
class MoELayer(Layer):
    """Mixture-of-experts FFN; expert-stacked weights shard over the
    "expert" mesh axis (partition_dim=0 on the stacked leading dim)."""

    is_loss = False

    def setup(self, src_shapes):
        p = self.cfg.moe_param
        if p is None:
            raise LayerError(f"{self.name}: moe_param required")
        b, s, e = tuple(src_shapes[0])
        self.n_exp = p.num_experts
        self.k = p.experts_per_token
        self.capacity_factor = p.capacity_factor
        self.aux_coef = p.router_aux_coef
        f = p.expert_hidden or 4 * e
        self.out_shape = (b, s, e)
        std = 1.0 / math.sqrt(e)
        self.router = _declare_with_default(self, 0, "router",
                                            (e, self.n_exp), std)
        self.w1 = _declare_with_default(self, 1, "w1",
                                        (self.n_exp, e, f), std, 0,
                                        mesh_axis="expert")
        self.b1 = _declare_with_default(self, 2, "b1", (self.n_exp, f),
                                        0.0, 0, mesh_axis="expert")
        self.w2 = _declare_with_default(self, 3, "w2",
                                        (self.n_exp, f, e),
                                        1.0 / math.sqrt(f), 0,
                                        mesh_axis="expert")
        self.b2 = _declare_with_default(self, 4, "b2", (self.n_exp, e),
                                        0.0, 0, mesh_axis="expert")
        self._aux = None

    def apply(self, params, srcs, ctx):
        x = srcs[0]
        p = {"router": params[self.router], "w1": params[self.w1],
             "b1": params[self.b1], "w2": params[self.w2],
             "b2": params[self.b2]}
        if ctx.compute_dtype is not None:
            p = {k: v.astype(ctx.compute_dtype) for k, v in p.items()}
        out, aux = moe_ops.moe_ffn(x, p, self.k, self.capacity_factor)
        # expose the router aux loss through a side metric dict entry
        self._aux = self.aux_coef * aux
        return out


@register_layer("kResidualAdd")
class ResidualAddLayer(Layer):
    """out = srcs[0] + srcs[1] — explicit residual edges in the DAG."""

    def setup(self, src_shapes):
        self.out_shape = tuple(src_shapes[0])

    def apply(self, params, srcs, ctx):
        return srcs[0] + srcs[1]


class _HeadProjection:
    """Shared (E, V) projection for the LM head layers — the single
    definition of the tied-transpose + compute-dtype semantics, used by
    training (`apply`) and the KV-cache decode path (models/generate.py)
    alike."""

    def head_weight(self, params, compute_dtype=None):
        """(weight, is_vE): the raw (V, E) embedding table when tied —
        consumers contract E on the last dim (dot_general) instead of
        transposing, so no transposed copy of the table materializes
        (measured ~1-2 ms/step on the 32k-vocab bench stack, worse
        with an f32 master table)."""
        w = params[self.w_key]
        if compute_dtype is not None:
            w = w.astype(compute_dtype)
        return w, self.tied

    def project_logits(self, params, hidden, compute_dtype=None):
        """(B, S, E) hidden → (B, S, V) float32 logits."""
        w, is_vE = self.head_weight(params, compute_dtype)
        spec = "bse,ve->bsv" if is_vE else "bse,ev->bsv"
        return jnp.einsum(spec, hidden, w,
                          preferred_element_type=jnp.float32)


@register_layer("kLMHead")
class LMHeadLayer(Layer, _HeadProjection):
    """(B, S, E) → (B, S, V) logits; optionally tied to the embedding via
    share_param."""

    def setup(self, src_shapes):
        p = self.cfg.embed_param
        if p is None or not p.vocab_size:
            raise LayerError(f"{self.name}: embed_param.vocab_size required")
        b, s, e = tuple(src_shapes[0])
        self.out_shape = (b, s, p.vocab_size)
        # tied head: share_param aliases the (vocab, E) embedding, which
        # must be transposed at use — decided here from the config, not
        # from a shape heuristic (vocab == E would be ambiguous)
        self.tied = bool(self.cfg.share_param)
        self.w_key = _declare_with_default(
            self, 0, "w", (e, p.vocab_size), 1.0 / math.sqrt(e), 1)

    def apply(self, params, srcs, ctx):
        return self.project_logits(params, srcs[0], ctx.compute_dtype)


@register_layer("kLMHeadLoss")
class LMHeadLossLayer(Layer, _HeadProjection):
    """Fused LM head + softmax-xent + top-k precision: (B, S, E) hidden
    + (B, S) labels → metrics, WITHOUT materializing (B, S, V) logits
    (ops.loss.chunked_lm_xent: chunked scan, checkpointed recompute in
    the backward).  Numerically identical to kLMHead → kSoftmaxLoss; use
    this form for large vocabularies where the logits tensor would
    dominate HBM traffic."""

    is_loss = True

    def setup(self, src_shapes):
        p = self.cfg.embed_param
        if p is None or not p.vocab_size:
            raise LayerError(f"{self.name}: embed_param.vocab_size required")
        b, s, e = tuple(src_shapes[0])
        lp = self.cfg.softmaxloss_param
        self.topk = lp.topk if lp else 1
        self.scale = lp.scale if lp else 1.0
        self.chunk = p.loss_chunk or 4096
        self.tied = bool(self.cfg.share_param)
        self.w_key = _declare_with_default(
            self, 0, "w", (e, p.vocab_size), 1.0 / math.sqrt(e), 1)
        self.flops_shape = (b, s, e, p.vocab_size)   # for utils.flops
        self.out_shape = (2,)

    def _use_fused(self, h2, w, is_vE, ctx) -> bool:
        """Whether the fused Pallas forward applies: tied (V, E)
        layout, top-1 metric, kernel-legal shapes, no mesh.  The
        platform does not enter the choice (off-TPU the same kernel
        runs interpreted, as the flash kernels do), so CPU tests walk
        the branch the chip takes.  Under a mesh the chunked XLA head
        stays: GSPMD cannot partition a Pallas custom call, so the
        fused head on mesh-sharded operands would gather them and run
        whole on every chip, while the chunked head's dots partition
        like any other matmul."""
        from ..ops.head_loss import eligible
        return (self.topk == 1 and is_vE and ctx.mesh is None
                and eligible(h2, w))

    @staticmethod
    def _shard_tokens(h2, l2, b, s, ctx):
        """Keep the flattened (B·S, ·) token dim sharded over
        ("data", "seq") jointly.  Without this constraint GSPMD resolves
        the (B, S, E)→(B·S, E) reshape under sequence parallelism by
        ALL-GATHERING the full sequence per data shard (observed in
        lowered HLO: an f32[B/dp, S, E] gather) — which defeats the
        O(S/n) activation memory SP exists for.  NOT free: the merged
        row order is b-major, so the ("data","seq") tiling differs from
        the source (b-block, s-block) tiles and GSPMD inserts an
        all-to-all reshard (visible in lowered HLO) costing O(local
        bytes) over ICI per step — the bounded price for never
        materializing full-S activations."""
        if ctx.mesh is None:
            return h2, l2
        from jax.sharding import NamedSharding, PartitionSpec as P
        shape = dict(ctx.mesh.shape)
        dp, sp = shape.get("data", 1), shape.get("seq", 1)
        if sp <= 1 or b % dp or s % sp:
            return h2, l2
        tok = P(("data", "seq"))
        h2 = jax.lax.with_sharding_constraint(
            h2, NamedSharding(ctx.mesh, P(("data", "seq"), None)))
        l2 = jax.lax.with_sharding_constraint(
            l2, NamedSharding(ctx.mesh, tok))
        return h2, l2

    def apply(self, params, srcs, ctx):
        from ..ops.head_loss import fused_lm_xent
        from ..ops.loss import chunked_lm_xent
        hidden, labels = srcs
        w, is_vE = self.head_weight(params, ctx.compute_dtype)
        b, s, e = hidden.shape
        h2, l2 = hidden.reshape(b * s, e), labels.reshape(-1)
        h2, l2 = self._shard_tokens(h2, l2, b, s, ctx)
        # fused Pallas forward (one pass over vocab blocks, logits
        # VMEM-only — ops/head_loss.py) for tied heads at kernel-legal
        # shapes; the chunked XLA path covers everything else
        if self._use_fused(h2, w, is_vE, ctx):
            loss, prec = fused_lm_xent(h2, w, l2, self.scale,
                                       self.chunk)
            return {"loss": loss, "precision": prec}
        key = (self.cfg.name, "head") + tuple(h2.shape) + tuple(w.shape)
        if key not in _flash_fallback_warned:
            _flash_fallback_warned.add(key)
            import sys
            print(f"note: LM head {self.cfg.name!r} (tokens={h2.shape[0]}, "
                  f"weight={tuple(w.shape)}) runs the chunked XLA head — "
                  f"the fused kernel needs a tied (V, E) weight, topk=1, "
                  f"no mesh, tokens % 512 == 0, V % 2048 == 0 and "
                  f"E % 128 == 0", file=sys.stderr)
        loss, prec = chunked_lm_xent(
            h2, w, l2, chunk_size=self.chunk, topk=self.topk,
            scale=self.scale, w_is_vE=is_vE)
        return {"loss": loss, "precision": prec}
