"""Mixer, expert and residual layers of hybrid recurrent / latent /
sparse LMs:

  kKDA        Kimi Delta Attention: a gated delta rule whose state is a
              fixed (H, Dk, Dv) float32 matrix per sequence plus the
              short convolution's tail (ops/kda.py)
  kMLA        multi-head latent attention, without positions or with
              its rope dims rotated, the query full-rank or through a
              bottleneck: what is cached per token is one latent row
              (kv_lora_rank + rope dims) shared by every head
  kMTP        the entry of a multi-token prediction module: the main
              stack's output beside the NEXT token's embedding, and per
              serving slot the draft the module last made
  kRoutedMoE  sigmoid router with a selection bias over ALL routed
              experts, the top k renormalised and scaled, a shared
              expert on every token, and only the experts this process
              holds computed (ops/moe.py); no token is dropped
  kCCA        compressed convolutional attention: GQA in the
              compressed widths behind two causal convolutions, a q-k
              mean and a value half shifted by one token (ops/cca.py).
              What is cached is of BOTH kinds at once: K and V rows per
              token in paged blocks, and per slot the convolutions'
              tails and the last token's shifted value half
  kZayaMoE    a softmax MLP router read from its own state, which each
              expert layer hands to the next (a second source and a
              second, named output: an edge of the layer DAG), top 1,
              a balancing bias on the choice only; the experts held
              here computed as kRoutedMoE's are
  kScaledResidual   (a x + b) + (c f(x) + d), learned per channel

The first five implement the decode-state protocol of
models/generate.py beside `apply`, as kAttention does, so the same
walkers and the same cache manager serve them.  Matrices are stored
(in, out).  `apply` is the forward pass over whole sequences; training
these layers (gradients through the chunked recurrence, an auxiliary
loss, expert parallelism over a mesh) is not done yet (ROADMAP
M1/M3/M4/M8).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..config.schema import ParamConfig
from ..ops import cca as cca_ops
from ..ops import kda as kda_ops
from ..ops import moe as moe_ops
from ..ops.attention import NEG_INF
from ..ops.paged_attention import extent_blocks, paged_decode_attention
from .layers import Layer, LayerError, ParamSpec, register_layer
from .seq_layers import (AttentionLayer, _declare_with_default, attend_cache,
                         paged_rows, write_token)


def _dot(x, w):
    """x (..., in) by w (in, out): operands as stored, float32 sums."""
    return jnp.einsum("...i,io->...o", x, w,
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _declare_const(layer: Layer, name: str, shape, value: float) -> str:
    key = f"{layer.name}/{name}"
    layer.param_specs.append(ParamSpec(
        key, tuple(shape), 0,
        ParamConfig(init_method="kConstant", value=value)))
    return key


def _valid_rows(t: int, pos, kmask, plen):
    """(B, T) bool or None: which rows of a chunk at offset `pos` are
    real, from the serving tier's left-pad key mask and/or the count of
    real rows of a right-padded chunk."""
    valid = None
    if kmask is not None:
        valid = jax.lax.dynamic_slice_in_dim(kmask, pos, t, axis=1)
    if plen is not None:
        real = (jnp.arange(t) < plen)[None, :]
        valid = real if valid is None else valid & real
    return valid


# ---------------------------------------------------------------------------

@register_layer("kKDA")
class KDALayer(Layer):
    """Kimi Delta Attention over (B, S, E).

    q~, k~, v~ = x Wq, x Wk, x Wv, each through a causal depthwise conv
    (kernel `conv_kernel`) and SiLU; per head q = l2norm(q~) / sqrt(D),
    k = l2norm(k~), v = v~; beta = sigmoid(x Wbeta), or twice that with
    `neg_eigval` (the transition I - beta k k^T then has eigenvalues in
    [-1, 1]: a state can flip a direction as well as forget it), in the
    one-token step, the scan and the chunked form alike; log-decay per head
    and key channel g = -exp(A_log) softplus(Wfb (Wfa x) + dt_bias);
    the delta rule of ops/kda.py; o = RMSNorm_D(o) * sigmoid(Wgb (Wga
    x)) per head, then Wo.  The recurrence, its decay and the norms are
    float32 whatever the params' dtype."""

    def setup(self, src_shapes):
        p = self.cfg.kda_param
        if p is None:
            raise LayerError(f"{self.name}: kda_param required")
        b, s, e = tuple(src_shapes[0])
        self.heads, self.head_dim = p.num_heads, p.head_dim
        self.conv_kernel, self.eps = p.conv_kernel, p.epsilon
        self.beta_scale = 2.0 if p.neg_eigval else 1.0
        self.out_shape = (b, s, e)
        h, d = self.heads, self.head_dim
        hd, se, sd = h * d, 1.0 / math.sqrt(e), 1.0 / math.sqrt(d)
        dec = _declare_with_default
        self.wq = dec(self, 0, "wq", (e, hd), se, 1)
        self.wk = dec(self, 1, "wk", (e, hd), se, 1)
        self.wv = dec(self, 2, "wv", (e, hd), se, 1)
        sk = 1.0 / math.sqrt(self.conv_kernel)
        self.conv = [dec(self, 3 + i, f"conv_{n}", (hd, self.conv_kernel), sk)
                     for i, n in enumerate("qkv")]
        self.w_beta = dec(self, 6, "w_beta", (e, h), se)
        self.w_fa = dec(self, 7, "w_fa", (e, d), se)
        self.w_fb = dec(self, 8, "w_fb", (d, hd), sd)
        self.w_ga = dec(self, 9, "w_ga", (e, d), se)
        self.w_gb = dec(self, 10, "w_gb", (d, hd), sd)
        self.wo = dec(self, 11, "wo", (hd, e), 1.0 / math.sqrt(hd), 0)
        # A = 4, dt = softplus(-3) ~ 0.05: a horizon of a few tokens;
        # real values come with the weights
        self.a_log = _declare_const(self, "a_log", (h,), math.log(4.0))
        self.dt_bias = _declare_const(self, "dt_bias", (hd,), -3.0)
        self.o_norm = _declare_const(self, "o_norm", (d,), 1.0)

    # -- the layer's mathematics, around the recurrence -------------------
    def _inputs(self, params, x, tails, valid):
        """x (B, T, E) -> q, k, v, g (B, T, H, D) float32, beta (B, T, H)
        and the new conv tails (B, K-1, 3 H D)."""
        b, t, _ = x.shape
        h, d = self.heads, self.head_dim
        pre = jnp.concatenate([_dot(x, params[w]).astype(x.dtype)
                               for w in (self.wq, self.wk, self.wv)], -1)
        w = jnp.concatenate([params[c] for c in self.conv], 0)
        mixed, tails = kda_ops.short_conv(pre, tails, w, valid)
        q, k, v = (a.reshape(b, t, h, d) for a in jnp.split(mixed, 3, -1))
        norm = lambda a: a * jax.lax.rsqrt(                  # noqa: E731
            jnp.sum(jnp.square(a), -1, keepdims=True) + 1e-6)
        q, k = norm(q) * d ** -0.5, norm(k)
        beta = jax.nn.sigmoid(_dot(x, params[self.w_beta]))
        if self.beta_scale != 1.0:
            beta = beta * self.beta_scale
        low = _dot(x, params[self.w_fa]).astype(x.dtype)
        f = _dot(low, params[self.w_fb]) + params[self.dt_bias].astype(
            jnp.float32)
        a = jnp.exp(params[self.a_log].astype(jnp.float32))
        g = -a[:, None] * jax.nn.softplus(f).reshape(b, t, h, d)
        return q, k, v, g, beta, tails

    def _output(self, params, x, o):
        """o (B, T, H, D) float32 -> (B, T, E): per-head norm, the
        sigmoid gate, Wo."""
        b, t, h, d = o.shape
        low = _dot(x, params[self.w_ga]).astype(x.dtype)
        gate = jax.nn.sigmoid(_dot(low, params[self.w_gb]))
        o = _rms(o, params[self.o_norm], self.eps) * gate.reshape(b, t, h, d)
        return _dot(o.reshape(b, t, h * d).astype(x.dtype),
                    params[self.wo]).astype(x.dtype)

    def apply(self, params, srcs, ctx):
        x = srcs[0]
        return self.apply_cached(
            params, x, self.init_cache(x.shape[0], 0, x.dtype), 0)[0]

    # -- decode state ------------------------------------------------------
    def _state(self, rows: int, dtype):
        h, d = self.heads, self.head_dim
        return {"S": jnp.zeros((rows, h, d, d), jnp.float32),
                "conv": jnp.zeros((rows, self.conv_kernel - 1, 3 * h * d),
                                  dtype)}

    def init_cache(self, batch: int, max_len: int, dtype):
        return self._state(batch, dtype)

    def init_pool(self, num_slots: int, num_blocks: int, block_len: int,
                  dtype):
        if num_slots < 1:
            raise ValueError(f"{self.name}: a state per slot needs "
                             f"num_slots >= 1")
        return self._state(num_slots, dtype)

    def apply_cached(self, params, x, entry, pos, kmask=None, plen=None):
        """A chunk of T tokens from the state `entry` holds; the state
        handed back is the one after the chunk's last REAL row."""
        valid = _valid_rows(x.shape[1], pos, kmask, plen)
        q, k, v, g, beta, tails = self._inputs(params, x, entry["conv"],
                                               valid)
        if x.shape[1] == 1:
            o, state = kda_ops.delta_rule_step(
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], entry["S"])
            if valid is not None:
                state = jnp.where(valid[:, 0, None, None, None], state,
                                  entry["S"])
            o = o[:, None]
        else:
            o, state = kda_ops.delta_rule_chunked(q, k, v, g, beta,
                                                  entry["S"], valid)
        return self._output(params, x, o), {"S": state, "conv": tails}

    def apply_chunk(self, params, x, entry, row, slot, start, plen, piece):
        """A chunk of a prompt that is prefilled in several
        (`generate.forward_chunk`): x (1, T, E) goes on from slot
        `slot`'s own state and tails as the chunk before left them
        (zeros at `start` 0, whatever the slot's last tenant left), and
        leaves them as they are after the chunk's last REAL row."""
        here = lambda a: jnp.where(start == 0, jnp.zeros_like(a[:1]),  # noqa: E731
                                   jax.lax.dynamic_index_in_dim(a, slot, 0))
        out, state = self.apply_cached(
            params, x, {"S": here(entry["S"]), "conv": here(entry["conv"])},
            start, plen=plen)
        return out, self.scatter_prefill(entry, state, row, slot)

    def apply_paged(self, params, x, entry, tables, ntoks):
        """x (1, S, E): slot s's token against slot s's state, stepped
        in place.  A slot that is not in use (ntoks 0) keeps its state:
        admission overwrites it whole."""
        xs = x[0][:, None, :]                                # (S, 1, E)
        busy = ntoks > 0
        q, k, v, g, beta, tails = self._inputs(params, xs, entry["conv"],
                                               None)
        o, state = kda_ops.delta_rule_step(
            q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], entry["S"])
        state = jnp.where(busy[:, None, None, None], state, entry["S"])
        tails = jnp.where(busy[:, None, None], tails, entry["conv"])
        out = self._output(params, xs, o[:, None])           # (S, 1, E)
        return out[:, 0][None], {"S": state, "conv": tails}

    @staticmethod
    def scatter_prefill(pool, cache, table_row, slot):
        return {"S": pool["S"].at[slot].set(cache["S"][0]),
                "conv": pool["conv"].at[slot].set(
                    cache["conv"][0].astype(pool["conv"].dtype))}


# ---------------------------------------------------------------------------

# Query heads `_attend_expanded` scores at a time: a chunk's f32 scores
# are heads x rows x rows (32 heads at a prefill of 1,024 rows 134 MB,
# the 128 of a wide model 537 MB at once).
_HEAD_CHUNK = 32


@register_layer("kMLA")
class MLALayer(Layer):
    """Multi-head latent attention over (B, S, E).

    q = x Wq -> H x (nope + rope dims), or with `q_lora_rank`
    q = RMSNorm(x Wqa) Wqb; x Wkva -> rank + rope dims: the first
    `rank` through RMSNorm are the latent c, the rest k_pe, one row
    shared by all heads; [k_nope | v] = c Wkvb per head; scores (q_nope
    . k_nope + q_pe . k_pe) / sqrt(nope + rope), causal softmax, Wo.
    With `rope_theta` k_pe and every head's q_pe are rotated at the
    token's position (half against half inside the rope dims), without
    it nothing is (NoPE).  What is cached per token is [c | k_pe], k_pe
    as it is scored (rotated).

    A chunk of tokens expands the cached rows to per-head keys and
    values; a decode step absorbs Wkvb into the query and the output
    instead and attends the latent rows themselves (one shared key of
    rank + rope dims, one shared value of rank dims): same sums, in
    another order."""

    def setup(self, src_shapes):
        p = self.cfg.mla_param
        if p is None:
            raise LayerError(f"{self.name}: mla_param required")
        b, s, e = tuple(src_shapes[0])
        self.heads = p.num_heads
        self.nope, self.rope = p.qk_nope_head_dim, p.qk_rope_head_dim
        self.vdim, self.rank, self.eps = (p.v_head_dim, p.kv_lora_rank,
                                          p.epsilon)
        self.q_rank, self.theta = p.q_lora_rank, p.rope_theta
        self.causal = True
        self.out_shape = (b, s, e)
        h, se = self.heads, 1.0 / math.sqrt(e)
        dec = _declare_with_default
        q_in = self.q_rank or e
        self.wq = dec(self, 0, "wq", (q_in, h * (self.nope + self.rope)),
                      1.0 / math.sqrt(q_in), 1)
        self.w_kva = dec(self, 1, "w_kva", (e, self.rank + self.rope), se)
        self.w_kvb = dec(self, 2, "w_kvb",
                         (self.rank, h * (self.nope + self.vdim)),
                         1.0 / math.sqrt(self.rank), 1)
        self.wo = dec(self, 3, "wo", (h * self.vdim, e),
                      1.0 / math.sqrt(h * self.vdim), 0)
        self.kv_norm = _declare_const(self, "kv_norm", (self.rank,), 1.0)
        if self.q_rank:
            self.wq_a = dec(self, 4, "wq_a", (e, self.q_rank), se)
            self.q_norm = _declare_const(self, "q_norm", (self.q_rank,), 1.0)

    @property
    def latent_dim(self) -> int:
        return self.rank + self.rope

    @property
    def pool_row(self) -> int:
        """Width of a token's row in the paged pool: the latent padded
        with zeros to whole 128-lane tiles.  The tiled layout stores
        that much a row anyway, and for a last dim that is not whole
        tiles (576) XLA:TPU gives the pool a transposed layout instead
        and copies the WHOLE pool there and back around every scatter
        and gather (0.75 ms each way a layer at 12 k blocks)."""
        return -(-self.latent_dim // 128) * 128

    def _project(self, params, x, positions=None):
        """x (B, T, E) at `positions` (T,) or (B, T), 0 .. T - 1 where
        not given -> q (B, T, H, nope + rope), latent rows (B, T, rank +
        rope), both in x's dtype."""
        b, t, _ = x.shape
        if positions is None:
            positions = jnp.arange(t)
        cq = x
        if self.q_rank:
            cq = _rms(_dot(x, params[self.wq_a]), params[self.q_norm],
                      self.eps).astype(x.dtype)
        q = _dot(cq, params[self.wq]).reshape(
            b, t, self.heads, self.nope + self.rope)
        kva = _dot(x, params[self.w_kva])
        c = _rms(kva[..., :self.rank], params[self.kv_norm], self.eps)
        k_pe = kva[..., self.rank:]
        if self.theta:
            turn = lambda a: cca_ops.partial_rope(           # noqa: E731
                a, positions, self.rope, self.theta)
            q = jnp.concatenate(
                [q[..., :self.nope], turn(q[..., self.nope:])], -1)
            k_pe = turn(k_pe[:, :, None])[:, :, 0]
        lat = jnp.concatenate([c, k_pe], -1)
        return q.astype(x.dtype), lat.astype(x.dtype)

    def _wkvb(self, params):
        return params[self.w_kvb].reshape(self.rank, self.heads,
                                          self.nope + self.vdim)

    def _attend_expanded(self, params, q, lat, allowed):
        """q (B, T, H, .) against latent rows lat (B, L, .) expanded to
        per-head keys and values; allowed (B or 1, T, L) bool.  More
        than `_HEAD_CHUNK` heads go a chunk of them at a time."""
        b, t, h = q.shape[:3]
        wkvb = self._wkvb(params)
        if h <= _HEAD_CHUNK or h % _HEAD_CHUNK or t == 1:
            return self._attend_heads(q, lat, wkvb, allowed)
        n = h // _HEAD_CHUNK
        o = jax.lax.map(
            lambda a: self._attend_heads(a[0], lat, a[1], allowed),
            (jnp.moveaxis(q.reshape(b, t, n, _HEAD_CHUNK, -1), 2, 0),
             jnp.moveaxis(wkvb.reshape(self.rank, n, _HEAD_CHUNK, -1), 1, 0)))
        return jnp.moveaxis(o, 0, 2).reshape(b, t, -1)

    def _attend_heads(self, q, lat, wkvb, allowed):
        """`_attend_expanded` for the heads q and wkvb (rank, heads, nope
        + vdim) hold."""
        b, t = q.shape[:2]
        kv = jnp.einsum("blr,rhd->blhd", lat[..., :self.rank], wkvb,
                        preferred_element_type=jnp.float32).astype(q.dtype)
        k_nope, v = kv[..., :self.nope], kv[..., self.nope:]
        sc = (jnp.einsum("bthd,blhd->bhtl", q[..., :self.nope], k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bthd,bld->bhtl", q[..., self.nope:],
                           lat[..., self.rank:],
                           preferred_element_type=jnp.float32))
        sc = sc / math.sqrt(self.nope + self.rope)
        sc = jnp.where(allowed[:, None], sc, NEG_INF)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bhtl,blhd->bthd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return o.reshape(b, t, -1).astype(q.dtype)

    def _absorb_query(self, params, q, width):
        """q (N, H, nope + rope) -> (N, H, width): Wkvb's key half
        folded into the query, so that it scores latent rows of `width`
        columns (rank + rope, zeros behind) directly."""
        q_lat = jnp.einsum("nhd,rhd->nhr", q[..., :self.nope],
                           self._wkvb(params)[..., :self.nope],
                           preferred_element_type=jnp.float32)
        q_lat = jnp.concatenate(
            [q_lat.astype(q.dtype), q[..., self.nope:]], -1)
        return jnp.pad(q_lat, ((0, 0), (0, 0),
                               (0, width - self.latent_dim)))

    def _expand_output(self, params, o_lat):
        """o_lat (N, H, rank), the attended latents -> (N, H * vdim):
        Wkvb's value half applied after the sum over positions."""
        o = jnp.einsum("nhr,rhd->nhd", o_lat,
                       self._wkvb(params)[..., self.nope:],
                       preferred_element_type=jnp.float32)
        return o.reshape(o.shape[0], -1).astype(o_lat.dtype)

    def apply(self, params, srcs, ctx):
        x = srcs[0]
        t = x.shape[1]
        q, lat = self._project(params, x)
        causal = jnp.tril(jnp.ones((t, t), bool))[None]
        o = self._attend_expanded(params, q, lat, causal)
        return _dot(o, params[self.wo]).astype(x.dtype)

    # -- decode state ------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype):
        return {"c": jnp.zeros((batch, max_len, self.latent_dim), dtype)}

    def init_pool(self, num_slots: int, num_blocks: int, block_len: int,
                  dtype):
        return {"c": jnp.zeros((num_blocks, block_len, self.pool_row), dtype)}

    def paged_extent(self, block_len: int, dtype, table_width: int) -> int:
        """Consecutive blocks the paged kernel copies at once of this
        layer's pool as `apply_paged` hands it over (one head, the
        value a row's leading columns): what the serving cache's free
        list has to deal in (serve/kvcache.py)."""
        return extent_blocks((0, 1, block_len, self.pool_row), dtype,
                             self.rank, table_width)

    def apply_cached(self, params, x, entry, pos, kmask=None, plen=None):
        t = x.shape[1]
        q, lat = self._project(params, x, pos + jnp.arange(t))
        cache = jax.lax.dynamic_update_slice(
            entry["c"], lat.astype(entry["c"].dtype), (0, pos, 0))
        qpos = pos + jnp.arange(t)[:, None]
        allowed = (jnp.arange(cache.shape[1])[None, :] <= qpos)[None]
        if kmask is not None:
            allowed = allowed & kmask[:, None, :]
        o = self._attend_expanded(params, q, cache.astype(x.dtype), allowed)
        return _dot(o, params[self.wo]).astype(x.dtype), {"c": cache}

    def apply_paged(self, params, x, entry, tables, ntoks):
        """x (1, S, E): slot s's token against the latent rows of slot
        s's blocks, its own written first (position ntoks[s], a whole
        block rewritten: a row-wise scatter makes XLA:TPU copy the
        pool).  The absorbed step is paged multi-query attention over
        the pool itself, one key row a token shared by all heads whose
        first `rank` columns are the value, so the paged kernel attends
        it and reads blocks 0 .. ntoks[s] // bl of slot s's table row
        only; Wkvb goes into the query and the output here, in XLA.

        x (1, S * R, E), R > 1 (a verify step, models/generate.py): slot
        s's R tokens one after another, at positions ntoks[s] ..
        ntoks[s] + R - 1.  Their rows are written one after another too
        (they may lie in two blocks), then all R attend in one call,
        row j up to its own position."""
        pool = entry["c"]
        s, bl = ntoks.shape[0], pool.shape[1]
        r = x.shape[1] // s
        q, lat = self._project(params, x[0].reshape(s, r, -1),
                               ntoks[:, None] + jnp.arange(r))
        lat = jnp.pad(lat, ((0, 0), (0, 0),
                            (0, self.pool_row - self.latent_dim)))
        rows = jnp.arange(bl)[None, :, None]
        for j in range(r):
            at = ntoks + j
            bidx = tables[jnp.arange(s), at // bl]
            blocks = jnp.where(rows == (at % bl)[:, None, None],
                               lat[:, j:j + 1].astype(pool.dtype), pool[bidx])
            pool = pool.at[bidx].set(blocks)
        q = self._absorb_query(params, q.reshape(s * r, self.heads, -1),
                               self.pool_row)
        o_lat = paged_decode_attention(
            q.reshape(s, r * self.heads, -1), pool[:, None], tables, ntoks,
            value_dim=self.rank, rows=r,
            scale=1.0 / math.sqrt(self.nope + self.rope))
        o = self._expand_output(
            params, o_lat.reshape(s * r, self.heads, self.rank))
        return _dot(o, params[self.wo]).astype(x.dtype)[None], {"c": pool}

    @staticmethod
    def scatter_prefill(pool, cache, table_row, slot=None):
        _, bl, row = pool["c"].shape
        rows = cache["c"][0]                                 # (P, .)
        rows = jnp.pad(rows, ((0, 0), (0, row - rows.shape[1])))
        return {"c": pool["c"].at[table_row].set(
            rows.reshape(rows.shape[0] // bl, bl, row).astype(
                pool["c"].dtype))}


# ---------------------------------------------------------------------------

@register_layer("kRoutedMoE")
class RoutedMoELayer(Layer):
    """Sparse experts over (B, S, E): out = shared(x) + sum over the
    token's chosen experts THAT ARE HELD HERE of weight_i expert_i(x).

    The router scores all `num_routed` experts and chooses among all of
    them; `num_held` of them, from `first_held`, have their weights in
    this process, and what the others would add is left out (their
    chips add it).  Experts and the shared expert are SwiGLU."""

    def setup(self, src_shapes):
        p = self.cfg.routed_moe_param
        if p is None:
            raise LayerError(f"{self.name}: routed_moe_param required")
        b, s, e = tuple(src_shapes[0])
        self.n_routed, self.k = p.num_routed, p.experts_per_token
        self.n_held = p.num_held or p.num_routed
        self.first = p.first_held
        if self.first < 0 or self.first + self.n_held > self.n_routed:
            raise LayerError(
                f"{self.name}: held experts {self.first}.."
                f"{self.first + self.n_held - 1} of {self.n_routed}")
        self.renormalize, self.scale = p.renormalize, p.routed_scale
        f, fs = p.expert_hidden or 4 * e, p.shared_hidden
        self.out_shape = (b, s, e)
        se, sf = 1.0 / math.sqrt(e), 1.0 / math.sqrt(f)
        dec = _declare_with_default
        self.router = dec(self, 0, "router", (e, self.n_routed), se)
        x = self.n_held
        self.w_gate = dec(self, 1, "w_gate", (x, e, f), se, 0,
                          mesh_axis="expert")
        self.w_up = dec(self, 2, "w_up", (x, e, f), se, 0,
                        mesh_axis="expert")
        self.w_down = dec(self, 3, "w_down", (x, f, e), sf, 0,
                          mesh_axis="expert")
        self.shared = None
        if fs:
            self.shared = (dec(self, 4, "shared_gate", (e, fs), se, 1),
                           dec(self, 5, "shared_up", (e, fs), se, 1),
                           dec(self, 6, "shared_down", (fs, e),
                               1.0 / math.sqrt(fs), 0))
        self.router_bias = _declare_const(self, "router_bias",
                                          (self.n_routed,), 0.0)

    def _ffn(self, params, x, valid, tile_rows=False):
        """x (T, E) -> (out (T, E), counts int32 (3,); with `tile_rows`
        a run that goes grouped adds a fourth, the rows of the tiles its
        products visited)."""
        idx, weights = moe_ops.route_sigmoid(
            x, params[self.router], params[self.router_bias], self.k,
            self.renormalize, self.scale)
        y, counts = moe_ops.held_experts_ffn(
            x, idx, weights, params[self.w_gate], params[self.w_up],
            params[self.w_down], self.first, valid, max_load=True,
            tile_rows=tile_rows)
        if self.shared is not None:
            gate, up, down = (params[w] for w in self.shared)
            hid = (jax.nn.silu(_dot(x, gate)) * _dot(x, up)).astype(x.dtype)
            y = y + _dot(hid, down)
        return y.astype(x.dtype), counts

    def apply(self, params, srcs, ctx):
        x = srcs[0]
        b, s, e = x.shape
        return self._ffn(params, x.reshape(b * s, e), None)[0].reshape(
            b, s, e)

    # -- decode state: none but the last decode step's routing counts -----
    def init_cache(self, batch: int, max_len: int, dtype):
        return {}

    def init_pool(self, num_slots: int, num_blocks: int, block_len: int,
                  dtype):
        """[assignments on held experts, held experts touched, the
        busiest held expert's assignments] of the last decode step,
        busy slots only: the engine hands them to the host with the
        step's tokens."""
        return {"routed": jnp.zeros((3,), jnp.int32)}

    def apply_cached(self, params, x, entry, pos, kmask=None, plen=None):
        b, t, e = x.shape
        valid = _valid_rows(t, pos, kmask, plen)
        if valid is not None:
            valid = jnp.broadcast_to(valid, (b, t)).reshape(b * t)
        out, _ = self._ffn(params, x.reshape(b * t, e), valid)
        return out.reshape(b, t, e), entry

    def apply_chunk(self, params, x, entry, row, slot, start, plen, piece):
        """A chunk of a prompt prefilled in several: no state to carry;
        the chunk's routing counts over its real rows are left where a
        decode step leaves its own, and behind them, where the chunk
        went grouped, the rows of the tiles its products visited (the
        chunk program takes that one off again: `_chunk_counts`)."""
        _, t, e = x.shape
        out, counts = self._ffn(params, x.reshape(t, e),
                                jnp.arange(t) < plen, tile_rows=True)
        return out.reshape(1, t, e), {"routed": counts}

    def apply_paged(self, params, x, entry, tables, ntoks):
        busy = ntoks > 0
        rows = x.shape[1] // busy.shape[0]     # of a slot (a verify step)
        out, counts = self._ffn(params, x[0],
                                jnp.repeat(busy, rows) if rows > 1 else busy)
        return out[None], {"routed": counts}

    @staticmethod
    def scatter_prefill(pool, cache, table_row, slot=None):
        return pool


# ---------------------------------------------------------------------------

@register_layer("kCCA")
class CCALayer(Layer):
    """Compressed convolutional attention over (B, S, E): the equations
    of ops/cca.py, causal GQA over q^, k^, v in the compressed widths
    (scores / sqrt(D), RoPE on the first `rotary_factor` of a head),
    then Wo.  Norms, softmax and the convolutions' sums are float32
    whatever the params' dtype; c and c' are rounded to the params'
    dtype where they are kept (a tail holds what a longer run saw).

    The key/value heads split into this token's and the token before's:
    the first ceil(Hkv / 2) value heads are x_t Wv1, the rest
    x_{t-1} Wv2."""

    def setup(self, src_shapes):
        p = self.cfg.cca_param
        if p is None:
            raise LayerError(f"{self.name}: cca_param required")
        b, s, e = tuple(src_shapes[0])
        self.heads, self.kv_heads = p.num_heads, p.num_kv_heads
        self.head_dim = d = p.head_dim
        self.k0, self.k1 = p.conv_kernel0, p.conv_kernel1
        self.rot = int(d * p.rotary_factor) // 2 * 2
        self.theta = p.rope_theta
        if (self.heads % self.kv_heads or self.kv_heads < 2
                or min(self.k0, self.k1) < 2):
            raise LayerError(
                f"{self.name}: {self.heads} query heads over "
                f"{self.kv_heads} key/value heads (at least 2: one half is "
                f"the token before's), convolutions of {self.k0} and "
                f"{self.k1} taps (at least 2)")
        self.causal = True
        self.out_shape = (b, s, e)
        n = self.heads + self.kv_heads
        self.v_now = (self.kv_heads + 1) // 2 * d
        self.v_prev = self.kv_heads * d - self.v_now
        se = 1.0 / math.sqrt(e)
        dec = _declare_with_default
        self.wq = dec(self, 0, "wq", (e, self.heads * d), se, 1)
        self.wk = dec(self, 1, "wk", (e, self.kv_heads * d), se, 1)
        self.wv1 = dec(self, 2, "wv1", (e, self.v_now), se, 1)
        self.wv2 = dec(self, 3, "wv2", (e, self.v_prev), se, 1)
        self.conv0 = dec(self, 4, "conv0", (n * d, self.k0),
                         1.0 / math.sqrt(self.k0))
        self.conv1 = dec(self, 5, "conv1", (n, self.k1, d, d),
                         1.0 / math.sqrt(self.k1 * d))
        self.wo = dec(self, 6, "wo", (self.heads * d, e),
                      1.0 / math.sqrt(self.heads * d), 0)
        self.bias0 = _declare_const(self, "bias0", (n * d,), 0.0)
        self.bias1 = _declare_const(self, "bias1", (n, d), 0.0)
        self.tau = _declare_const(self, "tau", (self.kv_heads,), 1.0)

    def _qkv(self, params, x, tails, valid, positions):
        """x (B, T, E) behind `tails` -> q^ (B, H, T, D), k^, v
        (B, Hkv, T, D) in x's dtype, and the tails after the last real
        row."""
        b, t, _ = x.shape
        h, hk, d = self.heads, self.kv_heads, self.head_dim
        proj = lambda w: _dot(x, params[w]).astype(x.dtype)  # noqa: E731
        pre = jnp.concatenate([proj(self.wq), proj(self.wk)], -1)
        full, conv = cca_ops.window(pre, tails["conv"], valid)
        c1 = cca_ops.depthwise(full, params[self.conv0],
                               params[self.bias0], t).astype(x.dtype)
        full, mix = cca_ops.window(c1, tails["mix"], valid)
        c2 = cca_ops.head_mix(full.reshape(b, -1, h + hk, d),
                              params[self.conv1], params[self.bias1], t)
        m_q, m_k = cca_ops.qk_mean(pre.reshape(b, t, h + hk, d), h, hk)
        q = cca_ops.unit_heads(c2[:, :, :h] + m_q)
        k = cca_ops.unit_heads(c2[:, :, h:] + m_k, params[self.tau])
        q = cca_ops.partial_rope(q, positions, self.rot, self.theta)
        k = cca_ops.partial_rope(k, positions, self.rot, self.theta)
        full, vprev = cca_ops.window(proj(self.wv2), tails["vprev"], valid)
        v = jnp.concatenate([proj(self.wv1), full[:, :t]], -1)
        heads_first = lambda a, n: a.astype(x.dtype).reshape(  # noqa: E731
            b, t, n, d).transpose(0, 2, 1, 3)
        return (heads_first(q, h), heads_first(k, hk), heads_first(v, hk),
                {"conv": conv, "mix": mix, "vprev": vprev})

    def apply(self, params, srcs, ctx):
        x = srcs[0]
        return self.apply_cached(
            params, x, self.init_cache(x.shape[0], x.shape[1], x.dtype),
            0)[0]

    # -- decode state: rows per token AND a tail per slot -------------------
    def _tails(self, rows: int, dtype):
        c = (self.heads + self.kv_heads) * self.head_dim
        return {"conv": jnp.zeros((rows, self.k0 - 1, c), dtype),
                "mix": jnp.zeros((rows, self.k1 - 1, c), dtype),
                "vprev": jnp.zeros((rows, 1, self.v_prev), dtype)}

    def init_cache(self, batch: int, max_len: int, dtype):
        shape = (batch, self.kv_heads, max_len, self.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
                **self._tails(batch, dtype)}

    def init_pool(self, num_slots: int, num_blocks: int, block_len: int,
                  dtype):
        """K and V in paged blocks of one pool, (num_blocks, 2 * Hkv,
        block_len, D) as kAttention's, and the tails of each of
        `num_slots` slots."""
        if num_slots < 1:
            raise ValueError(f"{self.name}: a tail per slot needs "
                             f"num_slots >= 1")
        return {"kv": jnp.zeros((num_blocks, 2 * self.kv_heads, block_len,
                                 self.head_dim), dtype),
                **self._tails(num_slots, dtype)}

    def apply_cached(self, params, x, entry, pos, kmask=None, plen=None):
        """A chunk of T tokens at offset `pos` against the cached rows,
        from the tails `entry` holds; the tails handed back are those
        after the chunk's last REAL row."""
        t = x.shape[1]
        valid = _valid_rows(t, pos, kmask, plen)
        q, k, v, tails = self._qkv(params, x, entry, valid,
                                   pos + jnp.arange(t))
        k_cache = jax.lax.dynamic_update_slice(
            entry["k"], k.astype(entry["k"].dtype), (0, 0, pos, 0))
        v_cache = jax.lax.dynamic_update_slice(
            entry["v"], v.astype(entry["v"].dtype), (0, 0, pos, 0))
        o = attend_cache(q, k_cache, v_cache, pos, kmask)
        return (_dot(o.astype(x.dtype), params[self.wo]).astype(x.dtype),
                {"k": k_cache, "v": v_cache, **tails})

    def apply_paged(self, params, x, entry, tables, ntoks):
        """x (1, S, E): slot s's token from slot s's tails, its K and V
        rows written at position ntoks[s] (one whole block a slot, as
        kAttention writes them), then the paged kernel over the slot's
        live blocks.  A slot that is not in use keeps its tails."""
        s, bl = x.shape[1], entry["kv"].shape[2]
        q, k, v, tails = self._qkv(params, x[0][:, None, :], entry, None,
                                   ntoks[:, None])
        busy = (ntoks > 0)[:, None, None]
        tails = {n: jnp.where(busy, a, entry[n]) for n, a in tails.items()}
        bidx = tables[jnp.arange(s), ntoks // bl]
        pool = write_token(entry["kv"], bidx, ntoks % bl,
                           paged_rows(k, v)[:, :, 0])
        o = paged_decode_attention(q[:, :, 0], pool, tables, ntoks)
        out = _dot(o.reshape(s, -1).astype(x.dtype), params[self.wo])
        return out.astype(x.dtype)[None], {"kv": pool, **tails}

    @staticmethod
    def scatter_prefill(pool, cache, table_row, slot):
        out = AttentionLayer.scatter_prefill(pool, cache, table_row)
        for n in ("conv", "mix", "vprev"):
            out[n] = pool[n].at[slot].set(cache[n][0].astype(pool[n].dtype))
        return out


# ---------------------------------------------------------------------------

def named_output(src, name: str = "out"):
    """The output `name` of a source that has several (a dict of named
    outputs, as kZayaMoE's), the source itself where it has one."""
    return src[name] if isinstance(src, dict) else src


def _sources(srcs):
    """A layer's sources as the decode walkers hand them (the one array,
    or the list of several), as the list `apply` takes."""
    return list(srcs) if isinstance(srcs, (list, tuple)) else [srcs]


@register_layer("kZayaMoE")
class ZayaMoELayer(Layer):
    """Top-1 experts under an MLP router with a state of its own, over
    (B, S, E).  Sources: the normed input and, in every expert layer
    but the first, the expert layer before (its output "router").
    Outputs, by name: "out" (B, S, E) and "router" (B, S, R) float32:

        r = x Wd + bd (+ gamma * the state handed in)
        p = softmax(W3 gelu(W2 gelu(W1 RMSNorm(r) + b1) + b2))
        e* = argmax(p + bal);  out = p[e*] expert_{e*}(x)   where held

    The router is float32 at full precision; `num_held` experts from
    `first_held` are computed here, as kRoutedMoE's are."""

    def setup(self, src_shapes):
        p = self.cfg.zaya_moe_param
        if p is None:
            raise LayerError(f"{self.name}: zaya_moe_param required")
        b, s, e = tuple(src_shapes[0])
        self.n_routed, self.k = p.num_routed, p.experts_per_token
        self.n_held = p.num_held or p.num_routed
        self.first = p.first_held
        if self.first < 0 or self.first + self.n_held > self.n_routed:
            raise LayerError(
                f"{self.name}: held experts {self.first}.."
                f"{self.first + self.n_held - 1} of {self.n_routed}")
        r, self.eps = p.router_hidden, p.epsilon
        self.carried = len(src_shapes) > 1
        if self.carried and tuple(src_shapes[1]["router"]) != (b, s, r):
            raise LayerError(
                f"{self.name}: a router state of {src_shapes[1]['router']} "
                f"handed to a router {r} wide")
        f = p.expert_hidden or 4 * e
        self.out_shape = {"out": (b, s, e), "router": (b, s, r)}
        se, sr, sf = (1.0 / math.sqrt(n) for n in (e, r, f))
        dec = _declare_with_default
        self.w_down_r = dec(self, 0, "router_down", (e, r), se)
        self.mlp = [dec(self, 1, "router_w1", (r, r), sr),
                    dec(self, 2, "router_w2", (r, r), sr),
                    dec(self, 3, "router_w3", (r, self.n_routed), sr)]
        x = self.n_held
        self.w_gate = dec(self, 4, "w_gate", (x, e, f), se, 0,
                          mesh_axis="expert")
        self.w_up = dec(self, 5, "w_up", (x, e, f), se, 0,
                        mesh_axis="expert")
        self.w_down = dec(self, 6, "w_down", (x, f, e), sf, 0,
                          mesh_axis="expert")
        self.b_down_r = _declare_const(self, "router_down_bias", (r,), 0.0)
        self.mlp_bias = [_declare_const(self, "router_b1", (r,), 0.0),
                         _declare_const(self, "router_b2", (r,), 0.0), None]
        self.router_norm = _declare_const(self, "router_norm", (r,), 1.0)
        self.router_bias = _declare_const(self, "router_bias",
                                          (self.n_routed,), 0.0)
        self.gamma = (_declare_const(self, "gamma", (r,), 0.5)
                      if self.carried else None)

    def _ffn(self, params, x, state, valid):
        """x (T, E), state (T, R) or None -> (out (T, E), the router's
        state (T, R) float32, counts int32 (3,))."""
        f32 = jnp.float32
        r = _dot(x, params[self.w_down_r]) + params[self.b_down_r].astype(f32)
        if self.carried:
            r = r + params[self.gamma].astype(f32) * state.astype(f32)
        idx, weights = moe_ops.route_mlp_softmax(
            r, params[self.router_norm], self.eps,
            [(params[w], None if b is None else params[b])
             for w, b in zip(self.mlp, self.mlp_bias)],
            params[self.router_bias], self.k)
        y, counts = moe_ops.held_experts_ffn(
            x, idx, weights, params[self.w_gate], params[self.w_up],
            params[self.w_down], self.first, valid, max_load=True)
        return y.astype(x.dtype), r, counts

    def _run(self, params, srcs, valid):
        """The sources (the normed input, then the layer before where
        there is one) -> ({"out", "router"}, counts)."""
        x = srcs[0]
        b, t, e = x.shape
        state = (named_output(srcs[1], "router").reshape(b * t, -1)
                 if self.carried else None)
        if valid is not None:
            valid = jnp.broadcast_to(valid, (b, t)).reshape(b * t)
        y, r, counts = self._ffn(params, x.reshape(b * t, e), state, valid)
        return {"out": y.reshape(b, t, e),
                "router": r.reshape(b, t, -1)}, counts

    def apply(self, params, srcs, ctx):
        return self._run(params, srcs, None)[0]

    # -- decode state: none but the last decode step's routing counts -----
    def init_cache(self, batch: int, max_len: int, dtype):
        return {}

    def init_pool(self, num_slots: int, num_blocks: int, block_len: int,
                  dtype):
        """[assignments on held experts, held experts touched, the
        busiest held expert's assignments] of the last decode step,
        busy slots only."""
        return {"routed": jnp.zeros((3,), jnp.int32)}

    def apply_cached(self, params, srcs, entry, pos, kmask=None, plen=None):
        srcs = _sources(srcs)
        valid = _valid_rows(srcs[0].shape[1], pos, kmask, plen)
        return self._run(params, srcs, valid)[0], entry

    def apply_paged(self, params, srcs, entry, tables, ntoks):
        out, counts = self._run(params, _sources(srcs),
                                (ntoks > 0)[None, :])
        return out, {"routed": counts}

    @staticmethod
    def scatter_prefill(pool, cache, table_row, slot=None):
        return pool


@register_layer("kMTP")
class MTPLayer(Layer):
    """The entry of a multi-token prediction module over (B, S, E).
    Sources: the embedding and the main stack's output h (after its
    final norm).  For position i, with the token AFTER it,

        z_i = [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)] W_eh

    (2 E -> E); the module's block, its final norm and the main
    model's head follow and give the distribution of t_{i+2}.  `apply`
    sees one sequence, so it shifts the embedding by a token itself
    (the last row gets zeros); the decode walkers hand `combine` the
    next tokens' embedding as they have it (models/generate.py).

    Serving state, per slot: the draft the module last made for the
    slot's next step and the distribution it was drawn from (what the
    verify step's accept-or-resample rule needs of it)."""

    def setup(self, src_shapes):
        p = self.cfg.mtp_param
        if p is None:
            raise LayerError(f"{self.name}: mtp_param required")
        b, s, e = tuple(src_shapes[0])
        self.vocab, self.eps = p.vocab_size, p.epsilon
        self.out_shape = (b, s, e)
        self.w_eh = _declare_with_default(self, 0, "w_eh", (2 * e, e),
                                          1.0 / math.sqrt(2 * e))
        self.e_norm = _declare_const(self, "e_norm", (e,), 1.0)
        self.h_norm = _declare_const(self, "h_norm", (e,), 1.0)

    def combine(self, params, e_next, h):
        z = jnp.concatenate(
            [_rms(e_next, params[self.e_norm], self.eps),
             _rms(h, params[self.h_norm], self.eps)], -1).astype(h.dtype)
        return _dot(z, params[self.w_eh]).astype(h.dtype)

    def apply(self, params, srcs, ctx):
        e, h = srcs
        e_next = jnp.concatenate([e[:, 1:], jnp.zeros_like(e[:, :1])], 1)
        return self.combine(params, e_next, h)

    def init_pool(self, num_slots: int, num_blocks: int, block_len: int,
                  dtype):
        if num_slots < 1:
            raise ValueError(f"{self.name}: a draft per slot needs "
                             f"num_slots >= 1")
        return {"draft": jnp.zeros((num_slots,), jnp.int32),
                "q": jnp.zeros((num_slots, self.vocab), jnp.float32)}


@register_layer("kScaledResidual")
class ScaledResidualLayer(Layer):
    """out = (a * srcs[0] + b) + (c * srcs[1] + d): a residual whose two
    terms each carry a learned scale and bias per channel (a, c start
    at 1 and b, d at 0: kResidualAdd).  Summed in float32."""

    def setup(self, src_shapes):
        self.out_shape = shape = tuple(src_shapes[0])
        self.keys = [_declare_const(self, n, shape[-1:], v)
                     for n, v in (("a", 1.0), ("b", 0.0), ("c", 1.0),
                                  ("d", 0.0))]

    def apply(self, params, srcs, ctx):
        x, y = srcs[0], named_output(srcs[1])
        a, b, c, d = (params[k].astype(jnp.float32) for k in self.keys)
        return ((a * x + b) + (c * y + d)).astype(x.dtype)
