"""Attention ops: Pallas flash attention + pure-jnp reference.

New capability (the reference predates attention; SURVEY.md §5
"long-context"): blockwise attention with online softmax so the S×S
score matrix never materializes in HBM — the TPU memory-hierarchy-aware
formulation (HBM→VMEM streaming, MXU matmuls per tile).

`flash_attention` / `flash_attention_packed` run Pallas kernels on TPU
(interpreter mode on the CPU backend, i.e. in tests).  The backward pass is the
hand-written dq/dkv kernel pair: tilewise recompute of the probabilities
from (q, k, lse), every matmul on the MXU, no S×S materialization.
`blockwise_attention` is kept as the autodiff-able memory-profile
oracle of the same math (lax.scan + checkpoint over KV blocks).

Also here: rotary position embeddings (RoPE) and GQA head expansion
used by the transformer model family.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Tuned flash block geometry.  (512, 512) won the S=1024 sweep
# (BASELINE.md "Explored and rejected": strided 1024 ties but pays
# transposes); the long-S rows come from BASELINE.md "Long context
# (round-4 kernel work)".  `set_flash_blocks` pins an override (the
# training cell pins (256, 512) through it).
_FLASH_BLOCK_OVERRIDE: Optional[tuple] = None

# Causal kernels compile ONE compute body, masked on every visited
# block.  A second, unmasked body for fully-visible blocks measured a
# wash at 512x512 and -55% at 512x1024 on v5e at S=4096 (the duplicated
# body defeats Mosaic's pipelining): BASELINE.md, same section,
# "Measured negatives".


def set_flash_blocks(override: Optional[tuple]) -> None:
    """Override (block_q, block_k) globally (None = tuned table).
    Takes effect on the next trace — re-jit after changing."""
    global _FLASH_BLOCK_OVERRIDE
    _FLASH_BLOCK_OVERRIDE = override


def flash_blocks(seq_len: int) -> tuple:
    """Tuned (block_q, block_k) for a sequence length.

    v5e, in-process in-net A/B (BASELINE.md, round 4):
    bk=1024 wins at every S >= 1024 — the fatter KV block halves the
    per-block VPU overhead passes (rescale/max bookkeeping) per score —
    by +1.1% (S=1024), +10% (S=4096), +12% (S=8192) over 512x512;
    bq=256 loses 3-13% everywhere (all from an earlier installation,
    not re-measured).  On libtpu 0.0.34, bq=1024 and bk=2048 at 12
    heads x 64 overflow the trainer's 32MB scoped-VMEM budget
    (RESOURCE_EXHAUSTED at compile) and compile at 64MB; not timed."""
    if _FLASH_BLOCK_OVERRIDE is not None:
        return _FLASH_BLOCK_OVERRIDE
    if seq_len >= 1024:
        return (512, 1024)
    return (512, 512)


# ---------------------------------------------------------------------------
# reference attention (oracle + backward path)


def attention_reference(q, k, v, causal: bool = True,
                        q_offset: int = 0, kv_offset: int = 0):
    """q: (B, H, Sq, D), k/v: (B, H, Sk, D). Offsets give the absolute
    positions of the local q/kv chunks (used by ring attention)."""
    d = q.shape[-1]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(d)
    if causal:
        qpos = jnp.arange(q.shape[2]) + q_offset
        kpos = jnp.arange(k.shape[2]) + kv_offset
        mask = qpos[:, None] >= kpos[None, :]
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# Pallas flash kernel


def _on_tpu() -> bool:
    """True when JAX's default backend is the TPU: the one switch
    between compiled (Mosaic) and interpreted kernels, and for the
    trainer's TPU compiler options.  A backend that fails to
    initialise raises here — an unreachable chip must never read as
    "not a TPU" and quietly select the interpreter."""
    return jax.default_backend() == "tpu"


def _fit_block(s: int, want: int) -> int:
    """Largest block <= `want` dividing s.  The kernels need blocks of
    at least a (8, 128) TPU tile row count; a seq len that only admits
    smaller blocks (odd / non-multiple-of-128 S) would otherwise
    surface as an obscure Mosaic tiling error, so fail loudly here and
    point callers at the dense fallback."""
    c = min(want, s)
    while s % c:
        c //= 2
    if c % 8:
        raise ValueError(
            f"flash attention needs a block size that is a multiple of "
            f"8 dividing seq_len={s} (got best fit {c}); pad the "
            f"sequence to a multiple of 128 or use "
            f"attention_reference (the dense fallback)")
    return c


def _causal_mask_block(iq, ik, block_q, block_k, window=0):
    """(block_q, block_k) bool: key at or before the query and, with a
    `window`, among the query's last `window` positions."""
    qpos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    if window:
        return (qpos >= kpos) & (kpos > qpos - window)
    return qpos >= kpos


def _flash_forward(q, k, v, causal: bool, block_q: int, block_k: int,
                   interpret: bool):
    """Strided (B, H, S, D) flash forward.  (B·H, S, D) IS the packed
    layout with one head per row, so this is the packed kernel with
    num_heads=1 — one online-softmax implementation serves both entry
    points.  Returns (out, lse (B, H, S, 1))."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    out, lse = _packed_forward(
        q.reshape(b * h, sq, d), k.reshape(b * h, sk, d),
        v.reshape(b * h, sk, d), 1, causal, block_q, block_k, interpret)
    return out.reshape(b, h, sq, d), lse.reshape(b, h, sq, 1)


def _flash_backward(q, k, v, out, lse, do, causal, block_q, block_k,
                    interpret):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    dq, dk, dv = _packed_backward(
        q.reshape(b * h, sq, d), k.reshape(b * h, sk, d),
        v.reshape(b * h, sk, d), out.reshape(b * h, sq, d),
        lse.reshape(b * h, sq, 1), do.reshape(b * h, sq, d),
        1, causal, block_q, block_k, interpret)
    return (dq.reshape(q.shape), dk.reshape(k.shape),
            dv.reshape(v.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 512,
                    block_k: int = 512, interpret: Optional[bool] = None):
    """FlashAttention. q/k/v: (B, H, S, D).  On non-TPU backends (or with
    interpret=True) the Pallas kernels run interpreted.  Backward is the
    hand-written dq/dkv Pallas kernel pair (_flash_backward) — tilewise
    recompute from (q, k, lse), every matmul on the MXU."""
    if interpret is None:
        interpret = not _on_tpu()
    return _flash_forward(q, k, v, causal, block_q, block_k, interpret)[0]


def chunk_attention(q, k, v, causal: bool, q_off, kv_off):
    """Partial attention of a Q chunk vs a KV chunk with absolute-position
    causal masking.  Returns (normalized out, lse) — the mergeable form
    shared by the blockwise backward here and ring attention
    (singa_tpu.parallel.sequence)."""
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    if causal:
        qpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2) + q_off
        kpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 3) + kv_off
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    m_safe = jnp.maximum(m, NEG_INF / 2)   # guard fully-masked rows
    p = jnp.exp(s - m_safe)
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    out = out / jnp.maximum(l, 1e-30)
    lse = jnp.where(l > 0, m_safe + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)
    return out, lse


def merge_attention(out1, lse1, out2, lse2):
    """Merge two partial (normalized, lse) attention results."""
    lse = jnp.logaddexp(jnp.maximum(lse1, NEG_INF),
                        jnp.maximum(lse2, NEG_INF))
    return out1 * jnp.exp(lse1 - lse) + out2 * jnp.exp(lse2 - lse), lse


def chunk_attention_blockwise(q, k, v, causal: bool, q_off, kv_off,
                              block_k: int = 512):
    """chunk_attention with flash-style memory: the KV chunk is scanned
    in `block_k` sub-blocks with online log-sum-exp merging and
    jax.checkpoint per sub-block, so peak memory is O(Sq·block_k)
    instead of O(Sq·Sk).  Same (normalized out, lse) contract and same
    autodiff path as chunk_attention — ring attention
    (singa_tpu.parallel.sequence) calls this for its local step so the
    per-rotation score matrix never materializes at full chunk size."""
    b, h, sk, d = k.shape
    if sk <= block_k or sk % block_k:
        return chunk_attention(q, k, v, causal, q_off, kv_off)
    nb = sk // block_k
    kb = k.reshape(b, h, nb, block_k, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, h, nb, block_k, d).transpose(2, 0, 1, 3, 4)

    @jax.checkpoint
    def sub(q, kc, vc, off):
        return chunk_attention(q, kc, vc, causal, q_off, off)

    def step(carry, blk):
        out, lse = carry
        kc, vc, i = blk
        o_new, l_new = sub(q, kc, vc, kv_off + i * block_k)
        return merge_attention(out, lse, o_new, l_new), None

    out0 = jnp.zeros(q.shape, jnp.float32)
    lse0 = jnp.full(q.shape[:3] + (1,), NEG_INF, jnp.float32)
    (out, lse), _ = jax.lax.scan(step, (out0, lse0),
                                 (kb, vb, jnp.arange(nb)))
    return out, lse


def blockwise_attention(q, k, v, causal: bool = True, block_k: int = 512):
    """O(S·block_k)-memory attention: lax.scan over KV chunks with
    jax.checkpoint per chunk, merging partials in log-sum-exp space.
    Kept as the autodiff-able oracle of the flash memory profile (the
    production backward is the hand-written dq/dkv kernel pair)."""
    b, h, sk, d = k.shape
    bk = min(block_k, sk)
    if sk % bk:
        return attention_reference(q, k, v, causal)
    nkv = sk // bk

    kb = k.reshape(b, h, nkv, bk, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, h, nkv, bk, d).transpose(2, 0, 1, 3, 4)

    @jax.checkpoint
    def chunk(q, kc, vc, kv_off):
        return chunk_attention(q, kc, vc, causal, 0, kv_off)

    def step(carry, blk):
        out, lse = carry
        kc, vc, i = blk
        o_new, lse_new = chunk(q, kc, vc, i * bk)
        return merge_attention(out, lse, o_new, lse_new), None

    out0 = jnp.zeros(q.shape, jnp.float32)
    lse0 = jnp.full(q.shape[:3] + (1,), NEG_INF, jnp.float32)
    (out, _), _ = jax.lax.scan(step, (out0, lse0),
                               (kb, vb, jnp.arange(nkv)))
    return out.astype(q.dtype)


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    if interpret is None:
        interpret = not _on_tpu()
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    if interpret is None:
        interpret = not _on_tpu()
    return _flash_backward(q, k, v, out, lse, g, causal, block_q,
                           block_k, interpret)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# RoPE + GQA helpers


def _rope_angles(positions: jnp.ndarray, d: int, theta: float):
    """(cos, sin) each (S, D/2) — shared by both rope layouts."""
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def _rotate_halves(x, cos, sin):
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin],
        axis=-1).astype(x.dtype)


def rope(x: jnp.ndarray, positions: jnp.ndarray,
         theta: float = 10000.0) -> jnp.ndarray:
    """Rotary embeddings. x: (B, H, S, D) with even D; positions: (S,)."""
    cos, sin = _rope_angles(positions, x.shape[-1], theta)
    return _rotate_halves(x, cos, sin)


def expand_kv_heads(kv: jnp.ndarray, num_heads: int) -> jnp.ndarray:
    """GQA: repeat kv heads to match q heads. kv: (B, Hkv, S, D)."""
    hkv = kv.shape[1]
    if hkv == num_heads:
        return kv
    assert num_heads % hkv == 0
    return jnp.repeat(kv, num_heads // hkv, axis=1)


# ---------------------------------------------------------------------------
# packed-layout flash attention: (B, S, H·D) in, (B, S, H·D) out


def _packed_params(interpret, vmem_limit_bytes=None):
    return (None if interpret
            else pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=vmem_limit_bytes))


LOG2E = 1.4426950408889634

#: Stable kernel names (forward, dq, dkv): they appear in the compiled
#: module's Mosaic custom calls and in profiler traces, so a check that
#: the kernels were compiled (chip_smoke.py) and a trace reduction both
#: find them after a refactor.
KERNEL_NAMES = ("singa_flash_fwd", "singa_flash_dq", "singa_flash_dkv")


def _packed_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                       acc_ref, *, heads, kv_heads, causal, scale, bq,
                       bk, window=0, fold_scale=True):
    """All-heads blocks: refs are (1, bq|bk, H·D); the head loop runs
    in-kernel over D-column slices (Mosaic rejects last-dim blocks
    narrower than a lane tile, so per-head blocks of D=64 are not an
    option — the full H·D width equals the array dim, which is).

    VPU economy (the co-bottleneck at D=64, where exp work per score is
    within ~2x of MXU work): scores live in the base-2 domain — the
    softmax scale and log2(e) fold into the q load (one mult per q
    element instead of per score, exp → native exp2) — and causal
    blocks split into fully-visible (no mask select at all; the vast
    majority at long S) vs diagonal-partial (masked).  m/l trackers are
    base-2; the stored lse converts back to natural once at finalize.

    `window` (static, causal only; 0 = none): a query sees its last
    `window` keys.  A K block wholly before the q block's first window
    is skipped as one wholly after the diagonal is; a partial one is
    masked.  A row whose keys of a visited block are all masked adds
    exp2(0) a key there, and the first block that holds a visible key
    (its own diagonal at the latest) wipes that with alpha = 0."""
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    d = q_ref.shape[-1] // heads
    grp = heads // kv_heads   # GQA: q heads per kv head

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def compute(masked):
        mask = (_causal_mask_block(iq, ik, bq, bk, window) if masked
                else None)
        for h in range(heads):
            sl = slice(h * d, (h + 1) * d)
            slk = slice((h // grp) * d, (h // grp + 1) * d)
            # operands stay in their input dtype: bf16 x bf16 -> f32
            # runs the MXU at full rate (an f32 upcast halves it); the
            # base-2 scale folds into q in that dtype, flash-standard
            # (one more rounding of q), or multiplies the f32 scores
            q = q_ref[0, :, sl]
            if fold_scale:
                q = q * jnp.asarray(scale * LOG2E, q_ref.dtype)
            k = k_ref[0, :, slk]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if not fold_scale:
                s = s * (scale * LOG2E)
            if mask is not None:
                s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[:, h:h + 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp2(s - m_new)
            alpha = jnp.exp2(m_prev - m_new)
            l_ref[:, h:h + 1] = (l_ref[:, h:h + 1] * alpha
                                 + jnp.sum(p, axis=1, keepdims=True))
            acc_ref[:, sl] = acc_ref[:, sl] * alpha + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, :, slk],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[:, h:h + 1] = m_new

    if causal:
        visited = ik * bk <= (iq + 1) * bq - 1
        if window:
            visited &= (ik + 1) * bk - 1 >= iq * bq - window + 1

        @pl.when(visited)
        def _():
            compute(True)
    else:
        compute(False)

    @pl.when(ik == nk - 1)
    def _finalize():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        # natural-log lse: m is base-2, l is linear
        lse_ref[0] = m_ref[...] * (1.0 / LOG2E) + jnp.log(l_safe)
        for h in range(heads):
            sl = slice(h * d, (h + 1) * d)
            o_ref[0, :, sl] = (acc_ref[:, sl]
                               / l_safe[:, h:h + 1]).astype(o_ref.dtype)


def _packed_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                      dq_ref, acc_ref, *, heads, kv_heads, causal,
                      scale, bq, bk):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    d = q_ref.shape[-1] // heads
    grp = heads // kv_heads

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def compute(masked):
        mask = (_causal_mask_block(iq, ik, bq, bk) if masked else None)
        for h in range(heads):
            sl = slice(h * d, (h + 1) * d)
            slk = slice((h // grp) * d, (h // grp + 1) * d)
            # operands stay in their input dtype: bf16 x bf16 -> f32
            # runs the MXU at full rate (an f32 upcast halves it); the
            # base-2 scale folds into q in that dtype, flash-standard
            q = q_ref[0, :, sl] * jnp.asarray(scale * LOG2E,
                                              q_ref.dtype)
            k = k_ref[0, :, slk]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if mask is not None:
                s = jnp.where(mask, s, NEG_INF)
            p = jnp.exp2(s - lse_ref[0, :, h:h + 1] * LOG2E)
            dp = jax.lax.dot_general(
                do_ref[0, :, sl], v_ref[0, :, slk],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - dl_ref[0, :, h:h + 1])
            acc_ref[:, sl] = acc_ref[:, sl] + jax.lax.dot_general(
                ds.astype(k_ref.dtype), k_ref[0, :, slk],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    if causal:
        @pl.when(ik * bk <= (iq + 1) * bq - 1)
        def _():
            compute(True)
    else:
        compute(False)

    @pl.when(ik == nk - 1)
    def _done():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _packed_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                       dk_ref, dv_ref, dk_acc, dv_acc, *, heads,
                       kv_heads, causal, scale, bq, bk):
    ik = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)
    d = q_ref.shape[-1] // heads
    grp = heads // kv_heads

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def compute(masked):
        mask = (_causal_mask_block(iq, ik, bq, bk) if masked else None)
        for h in range(heads):
            sl = slice(h * d, (h + 1) * d)
            # GQA: every q head in a group accumulates into its shared
            # kv slice (the sequential in-kernel adds ARE the head-sum)
            slk = slice((h // grp) * d, (h // grp + 1) * d)
            # operands stay in their input dtype: bf16 x bf16 -> f32
            # runs the MXU at full rate (an f32 upcast halves it); the
            # base-2 scale folds into q in that dtype, flash-standard
            q = q_ref[0, :, sl] * jnp.asarray(scale * LOG2E,
                                              q_ref.dtype)
            k = k_ref[0, :, slk]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if mask is not None:
                s = jnp.where(mask, s, NEG_INF)
            p = jnp.exp2(s - lse_ref[0, :, h:h + 1] * LOG2E)
            dv_acc[:, slk] = dv_acc[:, slk] + jax.lax.dot_general(
                p.astype(do_ref.dtype), do_ref[0, :, sl],
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                do_ref[0, :, sl], v_ref[0, :, slk],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - dl_ref[0, :, h:h + 1])
            dk_acc[:, slk] = dk_acc[:, slk] + jax.lax.dot_general(
                ds.astype(q_ref.dtype), q_ref[0, :, sl],
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    if causal:
        @pl.when(ik * bk <= (iq + 1) * bq - 1)
        def _():
            compute(True)
    else:
        compute(False)

    @pl.when(iq == nq - 1)
    def _done():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _packed_forward(q, k, v, num_heads, causal, block_q, block_k,
                    interpret, num_kv_heads=None, window=0,
                    fold_scale=True, heads_per_step=0,
                    vmem_limit_bytes=None):
    """`window` (static): see `_packed_fwd_kernel`; one that holds every
    key is none, and the call is the unwindowed one.  Forward only: the
    backward kernels know no window.

    `heads_per_step` (static; 0 = all of them, the trainer's): a grid
    step holds that many q heads of one kv head's group (blocks of
    n·D query and D key columns, so head_dim a multiple of 128 and n a
    divisor of the group): the same body unrolled over n heads in place
    of H.  The unrolled heads are what a serving process pays for in
    Python, each time a program is traced and lowered, compile cache or
    none: 0.24 s a head on the v5e's host, 7.7 s a rung at 32 heads
    (PERF.md 6, PR 36)."""
    b, sq, hd = q.shape
    sk = k.shape[1]
    if window >= sk:
        window = 0
    assert causal or not window, "a window needs causal attention"
    d = hd // num_heads
    kv_heads = num_kv_heads or num_heads
    hd_kv = kv_heads * d
    assert k.shape[-1] == hd_kv, (k.shape, kv_heads, d)
    bq, bk = _fit_block(sq, block_q), _fit_block(sk, block_k)
    assert sq % bq == 0 and sk % bk == 0
    scale = 1.0 / math.sqrt(d)
    if heads_per_step:
        # grid axis 0 walks (batch, run of heads); the run's kv head
        # is the key block's column; lse comes out (B·H/n, S, n)
        heads_in, kv_in = heads_per_step, 1
        runs, grp = num_heads // heads_in, num_heads // kv_heads
        assert grp % heads_in == 0 and d % 128 == 0, (grp, heads_in, d)
        steps = b * runs

        def q_at(g, i):
            return (g // runs, i, g % runs)

        def k_at(g, i):
            return (g // runs, i, g % runs * heads_in // grp)
    else:
        steps, heads_in, kv_in = b, num_heads, kv_heads

        def q_at(b_, i):
            return (b_, i, 0)
        k_at = q_at
    q_spec = pl.BlockSpec((1, bq, heads_in * d),
                          lambda b_, iq, ik: q_at(b_, iq))
    k_spec = pl.BlockSpec((1, bk, kv_in * d),
                          lambda b_, iq, ik: k_at(b_, ik))
    out, lse = pl.pallas_call(
        functools.partial(_packed_fwd_kernel, heads=heads_in,
                          kv_heads=kv_in, causal=causal, scale=scale,
                          bq=bq, bk=bk, window=window,
                          fold_scale=fold_scale),
        grid=(steps, sq // bq, sk // bk),
        in_specs=[q_spec, k_spec, k_spec],
        out_specs=[
            q_spec,
            pl.BlockSpec((1, bq, heads_in),
                         lambda b_, iq, ik: (b_, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sq, hd), q.dtype),
            jax.ShapeDtypeStruct((steps, sq, heads_in), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, heads_in), jnp.float32),
            pltpu.VMEM((bq, heads_in), jnp.float32),
            pltpu.VMEM((bq, heads_in * d), jnp.float32),
        ],
        compiler_params=_packed_params(interpret, vmem_limit_bytes),
        interpret=interpret,
        name=KERNEL_NAMES[0],
    )(q, k, v)
    if heads_per_step:
        lse = lse.reshape(b, runs, sq, heads_in).transpose(
            0, 2, 1, 3).reshape(b, sq, num_heads)
    return out, lse


def _packed_backward(q, k, v, out, lse, do, num_heads, causal, block_q,
                     block_k, interpret, num_kv_heads=None, dlse=None):
    b, sq, hd = q.shape
    sk = k.shape[1]
    d = hd // num_heads
    kv_heads = num_kv_heads or num_heads
    hd_kv = kv_heads * d
    bq, bk = _fit_block(sq, block_q), _fit_block(sk, block_k)
    scale = 1.0 / math.sqrt(d)
    # delta[b, s, h] = rowsum(do·out) within head h; when the lse
    # output is live (ring merging differentiates through it), its
    # cotangent joins here: ds = p·(dp − (rowsum(do·out) − dlse))
    delta = jnp.sum(
        (do.astype(jnp.float32) * out.astype(jnp.float32))
        .reshape(b, sq, num_heads, d), axis=-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    dor = do.astype(q.dtype)

    q_spec = pl.BlockSpec((1, bq, hd), lambda b_, iq, ik: (b_, iq, 0))
    k_spec = pl.BlockSpec((1, bk, hd_kv), lambda b_, iq, ik: (b_, ik, 0))
    r_spec = pl.BlockSpec((1, bq, num_heads),
                          lambda b_, iq, ik: (b_, iq, 0))
    dq = pl.pallas_call(
        functools.partial(_packed_dq_kernel, heads=num_heads,
                          kv_heads=kv_heads, causal=causal, scale=scale,
                          bq=bq, bk=bk),
        grid=(b, sq // bq, sk // bk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, r_spec, r_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, sq, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        compiler_params=_packed_params(interpret),
        interpret=interpret,
        name=KERNEL_NAMES[1],
    )(q, k, v, dor, lse, delta)

    q_spec2 = pl.BlockSpec((1, bq, hd), lambda b_, ik, iq: (b_, iq, 0))
    k_spec2 = pl.BlockSpec((1, bk, hd_kv), lambda b_, ik, iq: (b_, ik, 0))
    r_spec2 = pl.BlockSpec((1, bq, num_heads),
                           lambda b_, ik, iq: (b_, iq, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_packed_dkv_kernel, heads=num_heads,
                          kv_heads=kv_heads, causal=causal, scale=scale,
                          bq=bq, bk=bk),
        grid=(b, sk // bk, sq // bq),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, r_spec2, r_spec2],
        out_specs=[k_spec2, k_spec2],
        out_shape=[jax.ShapeDtypeStruct((b, sk, hd_kv), k.dtype),
                   jax.ShapeDtypeStruct((b, sk, hd_kv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, hd_kv), jnp.float32),
                        pltpu.VMEM((bk, hd_kv), jnp.float32)],
        compiler_params=_packed_params(interpret),
        interpret=interpret,
        name=KERNEL_NAMES[2],
    )(q, k, v, dor, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention_packed(q, k, v, num_heads: int, causal: bool = True,
                           block_q: int = 512, block_k: int = 512,
                           interpret: Optional[bool] = None,
                           num_kv_heads: Optional[int] = None):
    """FlashAttention on the packed projection layout: q (B, S, H·D),
    k/v (B, S, Hkv·D) — exactly what the qkv projections emit — with an
    in-kernel head loop over D-column slices.  No (B,S,H,D)→(B,H,S,D)
    transposes anywhere: on the 12-head S=1024 bench stack those
    relayout copies cost ~5ms/step.  GQA runs natively (round 4): each
    q head reads its group's kv slice in-kernel and the dkv kernel's
    sequential per-head adds ARE the group sum — no expand_kv_heads
    materialization, no strided fallback."""
    if interpret is None:
        interpret = not _on_tpu()
    return _packed_forward(q, k, v, num_heads, causal, block_q, block_k,
                           interpret, num_kv_heads)[0]


def _packed_vjp_fwd(q, k, v, num_heads, causal, block_q, block_k,
                    interpret, num_kv_heads=None):
    if interpret is None:
        interpret = not _on_tpu()
    out, lse = _packed_forward(q, k, v, num_heads, causal, block_q,
                               block_k, interpret, num_kv_heads)
    return out, (q, k, v, out, lse)


def _packed_vjp_bwd(num_heads, causal, block_q, block_k, interpret,
                    num_kv_heads, res, g):
    q, k, v, out, lse = res
    if interpret is None:
        interpret = not _on_tpu()
    return _packed_backward(q, k, v, out, lse, g, num_heads, causal,
                            block_q, block_k, interpret, num_kv_heads)


flash_attention_packed.defvjp(_packed_vjp_fwd, _packed_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention_packed_lse(q, k, v, num_heads: int,
                               causal: bool = True, block_q: int = 512,
                               block_k: int = 512,
                               interpret: Optional[bool] = None,
                               num_kv_heads: Optional[int] = None):
    """flash_attention_packed that ALSO returns the natural log-sum-exp
    (B, S, H) — the mergeable (normalized out, lse) pair ring attention
    needs for its per-rotation partials.  Differentiable in both
    outputs: the backward folds the lse cotangent into the delta term
    (ds = p·(dp − (rowsum(do·out) − dlse)))."""
    if interpret is None:
        interpret = not _on_tpu()
    return _packed_forward(q, k, v, num_heads, causal, block_q, block_k,
                           interpret, num_kv_heads)


def _packed_lse_vjp_fwd(q, k, v, num_heads, causal, block_q, block_k,
                        interpret, num_kv_heads=None):
    if interpret is None:
        interpret = not _on_tpu()
    out, lse = _packed_forward(q, k, v, num_heads, causal, block_q,
                               block_k, interpret, num_kv_heads)
    return (out, lse), (q, k, v, out, lse)


def _packed_lse_vjp_bwd(num_heads, causal, block_q, block_k, interpret,
                        num_kv_heads, res, g):
    q, k, v, out, lse = res
    do, dlse = g
    if interpret is None:
        interpret = not _on_tpu()
    return _packed_backward(q, k, v, out, lse, do, num_heads, causal,
                            block_q, block_k, interpret, num_kv_heads,
                            dlse=dlse)


flash_attention_packed_lse.defvjp(_packed_lse_vjp_fwd,
                                  _packed_lse_vjp_bwd)


# ---------------------------------------------------------------------------
# the serving prefill's call of the forward kernel

# Scoped VMEM the prefill's call states for itself, the trainer's 32 MB
# (core/trainer.py): the serving programs are compiled without that
# option.  One head a step needs no more than Mosaic's default of 16 MB;
# but a stated limit is also what XLA then plans the REST of the program
# with: it keeps more of its own arrays in VMEM (the held experts' block
# intermediates among them), and Trinity's 2,048-row rung takes 52.0 ms
# where it takes 59.4 with nothing stated (v5e; PERF.md 6, PR 36).
_PREFILL_VMEM_BYTES = 32 * 1024 * 1024


def prefill_blocks(s: int, cols: int, cols_kv: int, itemsize: int) -> tuple:
    """(block_q, block_k) of `flash_prefill` at S rows, where a grid
    step holds `cols` query and `cols_kv` key columns: (512, 1024),
    each halved until the q and out blocks (double-buffered) with the
    f32 accumulator fit an eighth of `_PREFILL_VMEM_BYTES` and the k
    and v blocks a sixteenth (Mosaic's own temporaries take most of
    the rest).  A K block of 1,024 rows because the accumulator's
    rescale and the running max and sum are paid a K block: at 32 / 4
    heads x 128 and 2,048 rows, 2 heads a step, (512, 1024) takes
    0.496 ms and (256, 1024) 0.609; 8 heads a step (256, 1024) 0.480,
    (256, 512) 0.630, (512, 512) 0.557, (128, 512) 0.78 (v5e; PERF.md
    6, PR 36).  `flash_blocks()` keys on S alone and stays the
    trainer's."""
    bq, bk = min(s, 512), min(s, 1024)
    while bq > 128 and bq * cols * (4 * itemsize + 4) > _PREFILL_VMEM_BYTES // 8:
        bq //= 2
    while bk > 128 and 4 * bk * cols_kv * itemsize > _PREFILL_VMEM_BYTES // 16:
        bk //= 2
    return bq, bk


def flash_prefill(q, k, v, num_heads: int, num_kv_heads: int,
                  window: int = 0):
    """Causal self-attention of a whole chunk, forward only: q
    (B, S, H·D), k / v (B, S, Hkv·D), S a multiple of 128; `window` as
    `_packed_fwd_kernel` has it (one that holds the chunk is dropped
    here, so that layers with and without it share a lowering).  The
    serving prefill's attention (`core.seq_layers.attend_cache`).

    Where a head is whole lane tiles (D a multiple of 128, as every
    serving configuration's) a grid step holds ONE head
    (`_packed_forward`'s `heads_per_step`): more heads a step are a
    faster kernel (K and V are read once a step; at 32 / 4 heads x 128,
    2,048 rows, (512, 1024): 1 head 0.578 ms, 2 heads 0.496, 4 heads
    0.450, 8 heads 0.433) and a slower start, 0.24 s a head and rung of
    every process; one head keeps a Trinity start within 2.4 s of the
    dense scores' (PERF.md 6, PR 36)."""
    s, d = q.shape[1], q.shape[-1] // num_heads
    per_step = 1 if d % 128 == 0 else 0
    held, held_kv = (1, 1) if per_step else (num_heads, num_kv_heads)
    bq, bk = prefill_blocks(s, held * d, held_kv * d, q.dtype.itemsize)
    return singa_flash_prefill(
        q, k, v, num_heads=num_heads, num_kv_heads=num_kv_heads,
        window=0 if window >= s else int(window), block_q=bq, block_k=bk,
        heads_per_step=per_step, interpret=not _on_tpu())


# Jitted and named as `singa_paged_decode` is: the layers of one rung
# share one trace and one Mosaic lowering, and the function's name is
# the row that holds the kernel's time in a device trace.
@functools.partial(jax.jit, static_argnames=(
    "num_heads", "num_kv_heads", "window", "block_q", "block_k",
    "heads_per_step", "interpret"))
def singa_flash_prefill(q, k, v, *, num_heads, num_kv_heads, window,
                        block_q, block_k, heads_per_step, interpret):
    # the f32 scores are scaled, as the dense scores and the paged
    # kernel's are: q is not rounded a second time (free on the v5e:
    # 0.612 ms a layer either way at 32 / 4 heads x 128, 2,048 rows)
    return _packed_forward(q, k, v, num_heads, True, block_q, block_k,
                           interpret, num_kv_heads, window,
                           fold_scale=False,
                           heads_per_step=heads_per_step,
                           vmem_limit_bytes=_PREFILL_VMEM_BYTES)[0]


def flash_part_legal(sq: int, sk: int, head_dim: int, num_heads: int,
                     num_kv_heads: int) -> bool:
    """Whether `flash_part` takes queries of `sq` rows against keys of
    `sk`: whole lane tiles of rows on both sides and a head the kernel
    is legal at (`core.seq_layers.attend_cache`'s own conditions)."""
    return (sq % 128 == 0 and sk % 128 == 0 and head_dim % 8 == 0
            and num_heads % num_kv_heads == 0)


def flash_part(q, k, v, num_heads: int, num_kv_heads: int, causal: bool):
    """A chunk's queries q (B, Sq, H·D) against ONE PART of their keys,
    k / v (B, Sk, Hkv·D), forward only: the chunk's own rows (`causal`,
    Sq = Sk, the first query at the first key) or rows that all lie
    before it (not causal).  Returns (out (B, Sq, H·D) normalised over
    the part, natural log-sum-exp (B, Sq, H) float32): the pair
    `merge_attention` joins, so that a chunk of a long prompt attends
    its prefix a piece of the paged pool at a time and no array of
    chunk x context scores exists (`core.seq_layers.attend_prefix`).
    Blocks and heads a step as `flash_prefill` chooses them."""
    sq, sk, d = q.shape[1], k.shape[1], q.shape[-1] // num_heads
    per_step = 1 if d % 128 == 0 else 0
    held, held_kv = (1, 1) if per_step else (num_heads, num_kv_heads)
    bq, _ = prefill_blocks(sq, held * d, held_kv * d, q.dtype.itemsize)
    _, bk = prefill_blocks(sk, held * d, held_kv * d, q.dtype.itemsize)
    return singa_flash_part(
        q, k, v, num_heads=num_heads, num_kv_heads=num_kv_heads,
        causal=bool(causal), block_q=bq, block_k=bk,
        heads_per_step=per_step, interpret=not _on_tpu())


# named as `singa_flash_prefill` is: the row of a device trace that
# holds the chunk programs' attention
@functools.partial(jax.jit, static_argnames=(
    "num_heads", "num_kv_heads", "causal", "block_q", "block_k",
    "heads_per_step", "interpret"))
def singa_flash_part(q, k, v, *, num_heads, num_kv_heads, causal, block_q,
                     block_k, heads_per_step, interpret):
    return _packed_forward(q, k, v, num_heads, causal, block_q, block_k,
                           interpret, num_kv_heads, 0, fold_scale=False,
                           heads_per_step=heads_per_step,
                           vmem_limit_bytes=_PREFILL_VMEM_BYTES)


def flash_chunk(q, k, v, causal: bool,
                interpret: Optional[bool] = None,
                block_q: int = 512, block_k: int = 512):
    """Ring-attention local step on the Pallas kernels: strided
    (B, H, Sq, D) × (B, H, Sk, D) → (normalized out f32, natural lse
    (B, H, Sq, 1)) — the same mergeable contract as chunk_attention.
    Only legal for equal q/kv offsets (the diagonal rotation) or
    causal=False (fully-visible rotations); the ring driver picks the
    case per rotation."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    out, lse = flash_attention_packed_lse(
        q.reshape(b * h, sq, d), k.reshape(b * h, sk, d),
        v.reshape(b * h, sk, d), 1, causal, block_q, block_k, interpret)
    return (out.reshape(b, h, sq, d).astype(jnp.float32),
            lse.reshape(b, h, sq, 1))


def flash_chunk_legal(sq: int, sk: int, d: int) -> bool:
    """Whether flash_chunk's kernels can tile these local chunk shapes
    ((8, 128)-tile-able blocks; see _fit_block)."""
    def ok(n):
        c = min(512, n)
        while n % c:
            c //= 2
        return c % 8 == 0
    return sq >= 8 and sk >= 8 and d % 8 == 0 and ok(sq) and ok(sk)


def rope_packed(x: jnp.ndarray, positions: jnp.ndarray, num_heads: int,
                theta: float = 10000.0) -> jnp.ndarray:
    """RoPE on the packed (B, S, H·D) layout: per-head rotation applied
    through a free trailing-dim split/merge (no transposes)."""
    b, s, hd = x.shape
    d = hd // num_heads
    cos, sin = _rope_angles(positions, d, theta)
    out = _rotate_halves(x.reshape(b, s, num_heads, d),
                         cos[None, :, None, :], sin[None, :, None, :])
    return out.reshape(b, s, hd)
