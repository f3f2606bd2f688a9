"""Compressed convolutional attention (CCA): what lies between the
projections into the compressed widths and the attention itself.  Plain
XLA; the attention is `seq_layers.attend_cache` for a run of tokens and
the paged kernel for a decode step.

Token t, C = (H + Hkv) D channels in H + Hkv heads of D:

    c_t   = [x_t Wq ; x_t Wk]
    c'_t  = sum_j w0[:, j] * c_{t-K0+1+j} + b0           depthwise, causal
    c''_t = sum_j c'^{(h)}_{t-K1+1+j} W1[h, j] + b1      a full D x D mix
                                                         per head and tap
    q = c''[:H] + m_q,  m_q^{(h)} = (q~^{(h)} + k~^{(g(h))}) / 2
    k = c''[H:] + m_k,  m_k^{(g)} = mean of m_q over group g's heads
    q^ = sqrt(D) q / |q|,  k^ = tau_g sqrt(D) k / |k|    per head
    v_t = [x_t Wv1 ; x_{t-1} Wv2]                        the value shift

so a sequence cut at any row goes on from three tails: the K0 - 1 rows
of c and the K1 - 1 rows of c' before the cut, and the cut row's
x Wv2.  `window` prepends a tail and hands back the next one; a row
with `valid` false (a pad) is zeroed where it enters and the tail
handed back ends at the LAST real row, as `ops.kda.short_conv` does it.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .attention import _rotate_halves


def window(x, tail, valid=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B, T, C) behind the rows before it, tail (B, K-1, C): returns
    (full (B, T+K-1, C) in x's dtype, the K-1 rows up to the last real
    row in tail's dtype).  Rows with `valid` (B, T) false enter as
    zeros: a left pad then reads as the zeros before a sequence's start,
    and a right-padded prompt leaves the tail a decode step needs."""
    t, k1 = x.shape[1], tail.shape[1]
    if valid is not None:
        x = jnp.where(valid[:, :, None], x, jnp.zeros_like(x))
    full = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    if valid is None:
        new_tail = full[:, t:]
    else:
        # rows [last+1, last+K) of `full` are x rows last-K+2 .. last
        last = jnp.max(jnp.where(valid, jnp.arange(t)[None, :], -1), axis=1)
        new_tail = jax.vmap(lambda f, s: jax.lax.dynamic_slice_in_dim(
            f, s, k1, axis=0))(full, last + 1)
    return full, new_tail.astype(tail.dtype)


def depthwise(full, w, bias, t: int):
    """c' of the T rows behind the tail: full (B, T+K-1, C), w (C, K)
    with w[:, K-1] on the current row, bias (C,).  Float32."""
    wf = w.astype(jnp.float32)
    y = sum(full[:, j:j + t].astype(jnp.float32) * wf[:, j]
            for j in range(w.shape[1]))
    return y + bias.astype(jnp.float32)


def head_mix(full, w, bias, t: int):
    """c'' of the T rows behind the tail: full (B, T+K-1, heads, D),
    w (heads, K, D, D) stored (in, out) with w[:, K-1] on the current
    row, bias (heads, D).  Operands as stored, float32 sums."""
    y = sum(jnp.einsum("bthd,hde->bthe", full[:, j:j + t], w[:, j],
                       preferred_element_type=jnp.float32)
            for j in range(w.shape[1]))
    return y + bias.astype(jnp.float32)


def qk_mean(pre, heads: int, kv_heads: int):
    """The skip around the convolutions.  pre (B, T, heads + kv_heads,
    D), the projections themselves.  Returns (m_q (B, T, heads, D),
    m_k (B, T, kv_heads, D)) float32."""
    b, t, _, d = pre.shape
    pre = pre.astype(jnp.float32)
    q = pre[:, :, :heads].reshape(b, t, kv_heads, heads // kv_heads, d)
    m_q = (q + pre[:, :, heads:, None]) / 2
    return m_q.reshape(b, t, heads, d), jnp.mean(m_q, axis=3)


def unit_heads(x, scale=None):
    """sqrt(D) x / |x| per head, the 1e-6 inside the root; times
    `scale` (heads,) where given.  x (B, T, heads, D) float32."""
    d = x.shape[-1]
    y = x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                          + 1e-6) * d ** 0.5
    return y if scale is None else y * scale.astype(jnp.float32)[:, None]


def partial_rope(x, positions, rot: int, theta: float):
    """RoPE on the first `rot` dims of every head, paired half against
    half inside them; the rest stay.  x (B, T, heads, D) float32;
    positions (T,) or (B, T)."""
    half = rot // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs   # (.., T, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    return jnp.concatenate(
        [_rotate_halves(x[..., :rot], cos, sin), x[..., rot:]], axis=-1)
