"""Pallas fused LM-head forward: projection + online softmax stats.

The chunked XLA head (ops/loss.py chunked_lm_xent) is bwd-near-optimal
but its FORWARD materializes the f32 logits chunk in HBM (512MB at
N=8k, V=32k) and re-reads it for logsumexp, the label gather, and the
top-1 argmax — ~2.7ms of pure logits traffic per step on the bench
stack.  This kernel computes the three per-token statistics the loss
needs — lse, label logit, argmax hit — in ONE pass over vocab blocks
with the logits block living only in VMEM, flash-attention style
(online max/sum-exp rescaling; argmax with top_k's lowest-index-wins
tie break).

Backward stays the XLA chunked path via custom_vjp, with the saved lse
as a residual (so the backward skips the lse recompute the checkpoint
form needed): p = exp(logits - lse); dh = (p - onehot) @ w;
dw = (p - onehot)^T @ h — dots XLA already runs at ~80-87% of peak.

Weight layout is (V, E) — the embedding-table layout tied heads share —
and the projection contracts E on the last dim of both operands, so no
transposed copy of the table ever materializes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: Stable kernel name, as ops/attention.py KERNEL_NAMES.
KERNEL_NAME = "singa_lm_head_stats"


def _fwd_kernel(h_ref, w_ref, lbl_ref, lse_ref, ll_ref, hit_ref,
                m_ref, d_ref, amax_ref, ll_acc_ref, *, bv, nv):
    vb = pl.program_id(1)

    @pl.when(vb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        d_ref[...] = jnp.zeros_like(d_ref)
        amax_ref[...] = jnp.zeros_like(amax_ref)
        ll_acc_ref[...] = jnp.zeros_like(ll_acc_ref)

    h = h_ref[...]                       # (bn, E) compute dtype
    w = w_ref[...]                       # (bv, E)
    logits = lax.dot_general(h, w, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    bn = logits.shape[0]
    col = vb * bv + lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    lbl = lbl_ref[...]                   # (bn, 1) int32

    # online logsumexp
    bmax = jnp.max(logits, axis=1, keepdims=True)          # (bn, 1)
    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, bmax)
    bsum = jnp.sum(jnp.exp(logits - m_new), axis=1, keepdims=True)
    d_ref[...] = d_ref[...] * jnp.exp(m_old - m_new) + bsum
    # argmax with lowest-index-wins ties: strictly-greater update, and
    # within the block the first max column wins via iota tie-break
    in_block_max = logits == bmax
    bidx = jnp.min(jnp.where(in_block_max, col, jnp.int32(2 ** 30)),
                   axis=1, keepdims=True)
    take = bmax > m_old
    amax_ref[...] = jnp.where(take, bidx, amax_ref[...])
    m_ref[...] = m_new
    # label logit (exact f32 value from this block when the label
    # falls in it; zero contribution otherwise)
    ll_acc_ref[...] = ll_acc_ref[...] + jnp.sum(
        jnp.where(col == lbl, logits, 0.0), axis=1, keepdims=True)

    @pl.when(vb == nv - 1)
    def _done():
        lse_ref[...] = m_ref[...] + jnp.log(d_ref[...])
        ll_ref[...] = ll_acc_ref[...]
        hit_ref[...] = (amax_ref[...] == lbl).astype(jnp.float32)


def _head_stats_pallas(h, w_vE, labels, bn: int, bv: int,
                       interpret: bool):
    """(lse, ll, hit) per token: one fused pass, logits VMEM-only."""
    n, e = h.shape
    v = w_vE.shape[0]
    grid = (n // bn, v // bv)
    lbl2 = labels.astype(jnp.int32).reshape(n, 1)
    out_spec = pl.BlockSpec((bn, 1), lambda i, j: (i, 0))
    params = (None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary")))
    lse, ll, hit = pl.pallas_call(
        functools.partial(_fwd_kernel, bv=bv, nv=v // bv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, e), lambda i, j: (i, 0)),
            pl.BlockSpec((bv, e), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[out_spec, out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((n, 1), jnp.float32)] * 3,
        scratch_shapes=[pltpu.VMEM((bn, 1), jnp.float32)] * 2
        + [pltpu.VMEM((bn, 1), jnp.int32),
           pltpu.VMEM((bn, 1), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name=KERNEL_NAME,
    )(h, w_vE, lbl2)
    return lse[:, 0], ll[:, 0], hit[:, 0]


def eligible(h, w_vE, bn: int = 512, bv: int = 2048) -> bool:
    n, e = h.shape
    v = w_vE.shape[0]
    return (n % bn == 0 and v % bv == 0 and e % 128 == 0
            and h.dtype == w_vE.dtype
            and h.dtype in (jnp.bfloat16, jnp.float32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def fused_lm_xent(h, w_vE, labels, scale: float = 1.0,
                  chunk_size: int = 4096, bn: int = 512, bv: int = 2048,
                  interpret: Optional[bool] = None):
    """(loss, precision) for an LM head with (V, E) weight — fused
    Pallas forward, chunked XLA backward.  Top-1 precision only (the
    kernel tracks argmax; topk>1 callers use chunked_lm_xent).
    `interpret` None = interpreted off-TPU, compiled on it (the flash
    kernels' convention)."""
    return _fused_fwd(h, w_vE, labels, scale, chunk_size, bn, bv,
                      interpret)[0]


def _fused_fwd(h, w_vE, labels, scale, chunk_size, bn, bv, interpret):
    if interpret is None:
        from .attention import _on_tpu
        interpret = not _on_tpu()
    n = h.shape[0]
    lse, ll, hit = _head_stats_pallas(h, w_vE, labels, bn, bv, interpret)
    loss = scale * jnp.sum(lse - ll) / n
    prec = scale * jnp.sum(hit) / n
    return (loss, prec), (h, w_vE, labels, lse)


def _fused_bwd(scale, chunk_size, bn, bv, interpret, res, g):
    from .loss import _largest_divisor_leq

    h, w_vE, labels, lse = res
    dloss, _ = g                       # precision is metric-only
    n, e = h.shape
    c = _largest_divisor_leq(n, chunk_size)
    nchunk = n // c
    hb = h.reshape(nchunk, c, e)
    lb = labels.astype(jnp.int32).reshape(nchunk, c)
    sb = lse.reshape(nchunk, c)
    coef = (dloss * scale / n).astype(jnp.float32)

    def step(dw, xs):
        hc, lc, lsec = xs
        logits = lax.dot_general(hc, w_vE, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        p = jnp.exp(logits - lsec[:, None])
        onehot = (lax.broadcasted_iota(jnp.int32, p.shape, 1)
                  == lc[:, None])
        dl = ((p - onehot.astype(jnp.float32)) * coef).astype(h.dtype)
        dh_c = lax.dot_general(dl, w_vE, (((1,), (0,)), ((), ())))
        dw = dw + lax.dot_general(dl, hc, (((0,), (0,)), ((), ())))
        return dw, dh_c

    dw0 = jnp.zeros(w_vE.shape, jnp.float32)
    dw, dh = lax.scan(step, dw0, (hb, lb, sb))
    return (dh.reshape(n, e).astype(h.dtype), dw.astype(w_vE.dtype),
            None)


fused_lm_xent.defvjp(_fused_fwd, _fused_bwd)
