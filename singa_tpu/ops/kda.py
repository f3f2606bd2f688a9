"""Kimi Delta Attention (KDA): a gated delta rule with one decay per
head AND per key channel, and the short causal convolution in front of
it.  Plain XLA: a chunked form for a run of tokens, a one-token step
for decode.

Per head, with a state S (d_k x d_v, float32):

    S' = diag(alpha_t) S_{t-1}            alpha_t = exp(g_t), g_t <= 0
    u  = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u^T                    o_t = S_t^T q_t

`delta_rule_step` is those four lines for every slot at once.
`delta_rule_chunked` walks a sequence a chunk of C tokens at a time:
inside a chunk, with G_t the running sum of g from the chunk's start,

    A[t, i] = sum_c k_t[c] k_i[c] exp(G_t[c] - G_i[c])        (i < t)
    (I + diag(beta) A) U = diag(beta) (V - (exp(G) * K) S_0)
    O = (exp(G) * Q) S_0 + B U,   B[t, i] = sum_c q_t[c] k_i[c]
                                            exp(G_t[c] - G_i[c])  (i <= t)
    S_C = diag(exp(G_C)) S_0 + (exp(G_C - G) * K)^T U

Every exponent is a difference G_t - G_i with i <= t, so it is never
positive: nothing overflows however fast a channel forgets (the
textbook factoring exp(G_t) * exp(-G_i) does, at exp(-G_i), once a
channel's decay over a chunk passes e^88).

A and B are built from sub-chunks of SUB = 16 rows (a chunk that is no
multiple of 16, or no longer, is one sub-chunk).  With t in sub-chunk a
and g0_a the value of G at a's first row:

    i in a too (the diagonal blocks): the sum over channels as written,
        on (16, 16, d_k), with ONE e[t, i, c] = exp(G_t[c] - G_i[c])
        (i <= t) for both matrices: A_d = sum_c k_t (k_i e),
        B_d = sum_c q_t (k_i e), A's diagonal masked afterwards
    i before a (the off-diagonal blocks):
        exp(G_t - G_i) = exp(G_t - g0_a) * exp(g0_a - G_i), so
        A[t, i] = (k_t * exp(G_t - g0_a)) . (k_i * exp(g0_a - G_i))
        and B the same with q_t: matmuls over d_k, on the MXU

Both new exponents are still <= 0, because G only falls and i lies
before a's first row, which lies at or before t: G_t <= g0_a <= G_i.
No exp(-G_i) is formed.  A factor that underflows to 0 stands for a
true product exp(G_t - G_i) that is smaller still (it is that factor
times another <= 1), so 0 is its float32 value too.  The products
are m - 1 (sub-chunk 0 has no earlier column) over the C - 16 columns
that have a later sub-chunk; a column at or after a's first row gets
the exponent -inf, a factor of exactly 0, and the diagonal blocks are
laid over those by a select on the block mask.  A
feeds the triangular solve, so the off-diagonal products run at
`Precision.HIGHEST` (float32 in, float32 out: 4 GFLOP a layer of a
2,048-row program at 64 heads); the diagonal blocks are float32 on the
VPU as the whole chunk was.  What is left of the chunk in plain XLA
after this is the solve itself (a block forward substitution on the
same 16 x 16 blocks would be the next step).

A position with `valid` false (a pad) leaves the state as it was:
beta = 0 and g = 0 there, whatever the pad's k and v hold.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

CHUNK = 64
SUB = 16     # rows of a sub-chunk of the chunked form's intra-chunk matrices


def short_conv(x, tail, w, valid=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Causal depthwise convolution over time, then SiLU.

    x (B, T, C) the new pre-convolution rows; tail (B, K-1, C) the K-1
    rows before them (zeros at a sequence's start); w (C, K), w[:, K-1]
    on the current row.  `valid` (B, T) bool marks real rows: pads are
    zeroed (left pads then read as the zeros before a sequence's start)
    and the tail handed back is the K-1 rows up to the LAST real one,
    so a right-padded prompt leaves the tail a decode step needs.
    Returns (y (B, T, C) float32, new tail in tail's dtype)."""
    b, t, c = x.shape
    k = w.shape[1]
    if valid is not None:
        x = jnp.where(valid[:, :, None], x, jnp.zeros_like(x))
    full = jnp.concatenate([tail.astype(x.dtype), x], axis=1)  # (B,T+K-1,C)
    wf = w.astype(jnp.float32)
    y = sum(full[:, j:j + t].astype(jnp.float32) * wf[:, j]
            for j in range(k))
    if valid is None:
        new_tail = full[:, t:]
    else:
        # rows [last+1, last+K) of `full` are x rows last-K+2 .. last
        last = jnp.max(jnp.where(valid, jnp.arange(t)[None, :], -1), axis=1)
        new_tail = jax.vmap(lambda f, s: jax.lax.dynamic_slice_in_dim(
            f, s, k - 1, axis=0))(full, last + 1)
    return jax.nn.silu(y), new_tail.astype(tail.dtype)


def delta_rule_step(q, k, v, g, beta, state):
    """One token for every row.  q, k, g (N, H, Dk); v (N, H, Dv); beta
    (N, H); state (N, H, Dk, Dv) float32.  Returns (o (N, H, Dv), new
    state).  o = S'^T q + u (k . q) is S_t^T q with the state read
    once."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    decayed = state * jnp.exp(g)[..., None]
    reads = jnp.einsum("nhkv,nhjk->nhjv", decayed, jnp.stack([k, q], 2))
    u = beta[..., None] * (v - reads[:, :, 0])
    o = reads[:, :, 1] + u * jnp.sum(k * q, -1, keepdims=True)
    return o, decayed + k[..., None] * u[..., None, :]


def delta_rule_scan(q, k, v, g, beta, state):
    """The recurrence token by token (B, T, H, .): the plain form the
    chunked one is tested against."""
    def one(s, xs):
        o, s = delta_rule_step(*xs, s)
        return s, o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    state, o = jax.lax.scan(one, state.astype(jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1), state


def delta_rule_chunked(q, k, v, g, beta, state,
                       valid: Optional[jnp.ndarray] = None,
                       chunk: Optional[int] = None):
    """q, k, g (B, T, H, Dk); v (B, T, H, Dv); beta (B, T, H); state
    (B, H, Dk, Dv) float32; valid (B, T) bool or None.  Returns
    (o (B, T, H, Dv) float32, state after the last valid token)."""
    f32 = jnp.float32
    b, t, h, dk = q.shape
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    if valid is not None:
        g = jnp.where(valid[:, :, None, None], g, 0.0)
        beta = jnp.where(valid[:, :, None], beta, 0.0)
    c = min(chunk or CHUNK, t)
    pad = -t % c
    if pad:              # trailing pads: beta 0, g 0 leave the state alone
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    n = (t + pad) // c

    def chunks(a):       # (B, n*C, H, ...) -> (n, B, H, C, ...)
        a = a.reshape((b, n, c) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    # the intra-chunk matrices come from sub-chunks of SUB rows (one, the
    # whole chunk, where the chunk does not divide: the diagonal form alone)
    sub = SUB if c > SUB and c % SUB == 0 else c
    m = c // sub
    strict = jnp.tril(jnp.ones((sub, sub), bool), -1)
    incl = jnp.tril(jnp.ones((sub, sub), bool))
    eye = jnp.eye(c, dtype=f32)

    def intra(qc, kc, gc):
        """A (strictly lower) and B (lower) of one chunk, (B, H, C, C)
        each, from the running sum gc."""
        blocks = lambda a: a.reshape(a.shape[:2] + (m, sub, dk))  # noqa: E731
        qs, ks, gs = blocks(qc), blocks(kc), blocks(gc)
        # diagonal blocks: one exponential a (t, i, channel) for both
        diff = gs[..., :, None, :] - gs[..., None, :, :]
        ke = ks[..., None, :, :] * jnp.exp(
            jnp.where(incl[..., None], diff, -jnp.inf))   # (B,H,m,t,i,Dk)
        a_d = jnp.where(strict, jnp.sum(ks[..., :, None, :] * ke, -1), 0.0)
        b_d = jnp.sum(qs[..., :, None, :] * ke, -1)
        if m == 1:
            return a_d[:, :, 0], b_d[:, :, 0]
        # off-diagonal blocks: rows of sub-chunk a >= 1 against the columns
        # before it through g0, the running sum at a's first row.  m - 1
        # products of (2 sub, Dk) x (Dk, C - sub): of the five layouts
        # timed on the chip the quickest (PERF.md 6, PR 44)
        same = jnp.eye(m, dtype=bool)[:, None, :, None]   # (a, 1, a', 1)
        cols = c - sub          # the last sub-chunk's columns: diagonal only
        before = (jnp.arange(cols)[None, :]
                  < sub * jnp.arange(1, m)[:, None])      # (a - 1, i)
        g0 = gs[:, :, 1:, :1]                             # (B,H,m-1,1,Dk)
        row = jnp.exp(gs[:, :, 1:] - g0)                  # <= 1
        col = kc[:, :, None, :cols] * jnp.exp(jnp.where(
            before[..., None], g0 - gc[:, :, None, :cols], -jnp.inf))
        off = jnp.einsum("bhatk,bhaik->bhati",       # k's rows, then q's
                         jnp.concatenate([ks[:, :, 1:] * row,
                                          qs[:, :, 1:] * row], axis=3), col,
                         precision=jax.lax.Precision.HIGHEST)
        # zeros for sub-chunk 0's rows and for the last sub-chunk's columns
        off = jnp.pad(off, ((0, 0), (0, 0), (1, 0), (0, 0), (0, sub)))

        def place(diag, off):   # (B,H,m,sub,sub), (B,H,m,sub,C) -> (B,H,C,C)
            off = off.reshape(off.shape[:4] + (m, sub))
            full = jnp.where(same, diag[:, :, :, :, None, :], off)
            return full.reshape(full.shape[:2] + (c, c))

        return place(a_d, off[..., :sub, :]), place(b_d, off[..., sub:, :])

    def one(s0, xs):
        qc, kc, vc, gc, bc = xs              # (B, H, C, .), bc (B, H, C)
        gc = jnp.cumsum(gc, axis=2)
        grow = jnp.exp(gc)                   # <= 1
        a, bm = intra(qc, kc, gc)
        lhs = eye + bc[..., None] * a
        rhs = bc[..., None] * (vc - jnp.einsum("bhck,bhkv->bhcv",
                                               kc * grow, s0))
        u = jax.scipy.linalg.solve_triangular(lhs, rhs, lower=True,
                                              unit_diagonal=True)
        o = (jnp.einsum("bhck,bhkv->bhcv", qc * grow, s0)
             + jnp.einsum("bhci,bhiv->bhcv", bm, u))
        end = gc[:, :, -1:, :]
        s1 = (s0 * jnp.exp(end[:, :, 0, :, None])
              + jnp.einsum("bhck,bhcv->bhkv", kc * jnp.exp(end - gc), u))
        return s1, o

    xs = tuple(chunks(a) for a in (q, k, v, g, beta))
    if valid is None:
        state, o = jax.lax.scan(one, state.astype(f32), xs)
    else:
        # no chunk after the last one that holds a real row is walked: a
        # right-padded prompt costs its own length, not the compiled one
        rows = jnp.pad(valid, ((0, 0), (0, pad))).reshape(b, n, c)
        live = jnp.max(jnp.where(jnp.any(rows, axis=(0, 2)),
                                 jnp.arange(n) + 1, 0))

        def walk(i, carry):
            s0, o = carry
            s1, oc = one(s0, tuple(a[i] for a in xs))
            return s1, jax.lax.dynamic_update_index_in_dim(o, oc, i, 0)

        state, o = jax.lax.fori_loop(
            0, live, walk,
            (state.astype(f32), jnp.zeros((n, b, h, c, v.shape[-1]), f32)))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2)    # (B, n, C, H, Dv)
    return o.reshape(b, n * c, h, -1)[:, :t], state
