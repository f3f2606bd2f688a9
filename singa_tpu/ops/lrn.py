"""Local response normalization (cross-channel), reference numerics.

Reference: layer.cc:331-378 —
    norm = chpool_sum(x^2, lsize) * (alpha/lsize) + knorm
    y    = x * norm^(-beta)
where chpool sums x^2 over a channel window of lsize centered at each
channel (zero-padded).  The reference's hand-written gradient
(layer.cc:366-377) is the exact derivative of this forward, so the
numerics match.

On TPU (NHWC path): the channel-window sum is a banded-matrix matmul on
the MXU — a lane-axis reduce_window costs activation-sized relayout
passes, and a lane-shift add chain measured ~12% slower end-to-end on
the AlexNet stack.  The whole chain runs in the compute dtype.  Under
bf16 that rounds the window sum, norm, and n^-β to ~0.4% relative —
the same order as the unavoidable final bf16 rounding of y = x·n^-β
itself, so the achievable accuracy is output-resolution-bound either
way (in the caffe-alpha regime n = 1 + O(1e-4), bf16 rounds n^-β to
exactly 1 — but so does the bf16 cast of y = x·(1 - O(1e-4))).  An
f32 norm/pow chain measured 1.7-3ms/step slower at batch 2048 (f32
intermediates/residuals cost real HBM) for accuracy the output dtype
then discards.  The f32 NCHW oracle below is exact, and the golden
tests compare the two paths in f32, where they agree to 1e-6.

The backward is a hand-written custom_vjp (the same closed form the
reference derives): letting XLA autodiff through the band matmul under
jax.checkpoint generated bitpacked-relu-mask + f32-recompute fusion
soup that cost ~10% of the whole AlexNet train step.  The residual is
x alone; the backward recomputes the window sum with a second band
matmul — MXU time is cheaper here than writing and re-reading an
activation-sized s tensor through HBM.

`relu=True` fuses the reference's conv→relu→lrn chain: ReLU is applied
in-register before the window sum and its mask folds into the
backward, so the relu activation and its separate backward pass never
touch HBM (the net marks these chains — see NeuralNet._fuse_relu_lrn).
A hand-written Pallas kernel for this chain was tried and measured
*slower* (43.7 vs 36.3 ms/step): XLA lays conv activations out
batch-in-lanes here, and the (N·H·W, C) view a row-blocked kernel
needs forces full relayout copies at the kernel boundary.  A
batch-in-lanes kernel lost as well (13 ms forward on norm1 against
6.4 ms for XLA's fused band-dot: the window sum costs ~12 VPU passes
when done with sublane shifts; PERF.md 7).  The jnp form lets XLA
keep its layouts and fuse around the custom_vjp.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _band(c: int, local_size: int, dtype) -> jnp.ndarray:
    """(C, C) 0/1 banded matrix: band[i, j] = |i - j| <= local_size//2."""
    idx = jnp.arange(c)
    return (jnp.abs(idx[:, None] - idx[None, :])
            <= local_size // 2).astype(dtype)


def _pow_neg_beta(n: jnp.ndarray, beta: float) -> jnp.ndarray:
    if beta == 0.75:
        # norm^-3/4 == rsqrt(norm)*sqrt(rsqrt(norm)): sqrt/rsqrt are
        # single VPU ops, vs pow = exp∘log transcendentals which
        # measured as expensive as the windowed sum itself.
        r = lax.rsqrt(n)
        return r * jnp.sqrt(r)
    return n ** -beta


def _window_sum(a: jnp.ndarray, local_size: int) -> jnp.ndarray:
    """Channel-window sum of a² in a's dtype.  No preferred_element_type:
    the TPU MXU accumulates bf16 products in f32 internally anyway, and
    requesting an f32 dot *output* forces a separate f32 tile write +
    convert pass (measured +2ms/step on the AlexNet stack).  On backends
    that accumulate bf16 partials in bf16 the extra rounding stays within
    the ~0.4% relative tolerance documented in the module docstring."""
    sq = jnp.square(a)
    return jnp.dot(sq, _band(a.shape[-1], local_size, a.dtype))


def _p_of_s(s: jnp.ndarray, local_size: int, alpha: float, beta: float,
            knorm: float):
    """(n, n^-β) in the compute dtype from the window sum."""
    n = s * jnp.asarray(alpha / local_size, s.dtype) + jnp.asarray(
        knorm, s.dtype)
    return n, _pow_neg_beta(n, beta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _lrn_nhwc(x, local_size, alpha, beta, knorm, relu):
    return _lrn_nhwc_fwd(x, local_size, alpha, beta, knorm, relu)[0]


def _lrn_nhwc_fwd(x, local_size, alpha, beta, knorm, relu):
    a = jnp.maximum(x, jnp.zeros((), x.dtype)) if relu else x
    s = _window_sum(a, local_size)
    _, p = _p_of_s(s, local_size, alpha, beta, knorm)
    # Residual is x alone: spilling n for the backward was measured
    # time-neutral on chip (the recompute dot fuses into the backward's
    # band-dot emitter nearly free), so the lean-memory form wins.
    return a * p, x


def _lrn_nhwc_bwd(local_size, alpha, beta, knorm, relu, res, g):
    # d/da of y_i = a_i·n_i^-β with n = k + (α/L)·B(a²):
    #   da = g·n^-β − 2β(α/L)·a·Bᵀ(g·a·n^{-β-1})
    # (B symmetric, so Bᵀ = B); matches the reference's closed form
    # (layer.cc:366-377).  With relu fused, a = max(x, 0) is recomputed
    # from the residual x (register op) and da is masked by x > 0.
    x = res
    a = jnp.maximum(x, jnp.zeros((), x.dtype)) if relu else x
    s = _window_sum(a, local_size)
    n, p = _p_of_s(s, local_size, alpha, beta, knorm)
    t = g * a * (p / n)                     # g·a·n^{-β-1}
    u = jnp.dot(t, _band(x.shape[-1], local_size, x.dtype))
    da = g * p - jnp.asarray(
        2 * beta * alpha / local_size, x.dtype) * a * u
    if relu:
        # NOTE: XLA hoists this predicate into the forward as a
        # bitpacked mask tensor; an arithmetic `da * sign(a)` form that
        # avoids the hoist was A/B-measured on chip and is ~1%
        # SLOWER — the packed-mask read beats the extra VPU pass.
        da = jnp.where(x > 0, da, jnp.zeros((), da.dtype))
    return (da,)


_lrn_nhwc.defvjp(_lrn_nhwc_fwd, _lrn_nhwc_bwd)


def lrn(x: jnp.ndarray, local_size: int = 5, alpha: float = 1.0,
        beta: float = 0.75, knorm: float = 1.0,
        layout: str = "NCHW") -> jnp.ndarray:
    """Cross-channel LRN; x (N, C, H, W) or (N, H, W, C) per layout."""
    if layout == "NHWC":
        return _lrn_nhwc(x, local_size, alpha, beta, knorm, False)
    half = local_size // 2
    sq = jnp.square(x.astype(jnp.float32))
    dims = (1, local_size, 1, 1)
    pad = ((0, 0), (half, half), (0, 0), (0, 0))
    norm = lax.reduce_window(sq, 0.0, lax.add, dims, (1, 1, 1, 1), pad)
    norm = norm * (alpha / local_size) + knorm
    return (x.astype(jnp.float32) * _pow_neg_beta(norm, beta)).astype(x.dtype)


def relu_lrn(x: jnp.ndarray, local_size: int = 5, alpha: float = 1.0,
             beta: float = 0.75, knorm: float = 1.0, relu: bool = False,
             layout: str = "NHWC") -> jnp.ndarray:
    """(optionally ReLU, then) cross-channel LRN — the fused form the
    net builder selects for conv→relu→lrn chains (NHWC only)."""
    if layout == "NHWC":
        return _lrn_nhwc(x, local_size, alpha, beta, knorm, relu)
    a = jnp.maximum(x, jnp.zeros((), x.dtype)) if relu else x
    return lrn(a, local_size, alpha, beta, knorm, layout)
