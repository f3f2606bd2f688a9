"""Sequence/context parallelism: ring attention + Ulysses.

New first-class capability (SURVEY.md §5: the reference has no sequence
axis; its closest mechanism is generic layer partitioning).  Both
strategies shard the sequence axis of (B, H, S, D) attention inputs over
the mesh's "seq" axis — and keep the batch dim on "data" and the head
dim on "model", so they compose with data/tensor parallelism on the same
mesh instead of gathering the global batch onto every device:

- **Ring attention** (blockwise attention + KV rotation): each device
  keeps its Q chunk and rotates KV chunks around the ring with
  `jax.lax.ppermute` (XLA collective-permute over ICI), merging partial
  attention results in log-sum-exp space.  Memory per device is O(S/n).

- **Ulysses**: two `all_to_all`s re-shard seq→heads, run dense local
  attention on H/(sp·tp) heads at full sequence length, then shard back.
  Cheaper comm volume for moderate S; needs H/tp divisible by sp.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import (NEG_INF, attention_reference,
                             chunk_attention_blockwise, flash_chunk,
                             flash_chunk_legal, merge_attention)


def _spec(mesh: Mesh, seq_axis: str, heads: int):
    """(B, H, S, D): batch on data, heads on model (when divisible), seq
    on the sequence axis."""
    head_axis = "model" if heads % mesh.shape["model"] == 0 else None
    return P("data", head_axis, seq_axis, None)


def packed_attention_sharded(q, k, v, mesh: Mesh, num_heads: int,
                             num_kv_heads: int, causal: bool,
                             block_q: int, block_k: int) -> jnp.ndarray:
    """The packed flash kernels (in-kernel GQA, zero transposes) as a
    shard_map local step over the mesh: batch on "data", heads on
    "model".  q: (B, S, H·D), k/v: (B, S, Hkv·D) — the projections'
    native layout, globally sharded exactly as TP partition_dim=1
    leaves them, so no resharding happens at the shard_map boundary.

    Each device runs the same kernel the single-chip path runs, on its
    (B/dp, S, (H/tp)·D) slice.  GQA group slices stay aligned because
    the caller guarantees heads % tp == 0 AND kv_heads % tp == 0:
    shard i holds q heads [i·H/tp, (i+1)·H/tp) and exactly their kv
    group heads [i·Hkv/tp, (i+1)·Hkv/tp).  This closes the round-4 gap
    where `ctx.mesh is None` fenced the packed layout (and its +28% GQA
    win at S=4096) out of every multi-device run."""
    from ..ops.attention import flash_attention_packed
    tp = mesh.shape.get("model", 1)
    dp = mesh.shape.get("data", 1)
    assert num_heads % max(tp, 1) == 0 and num_kv_heads % max(tp, 1) == 0
    h_local = num_heads // max(tp, 1)
    hkv_local = num_kv_heads // max(tp, 1)
    spec = P("data" if dp > 1 else None, None, "model" if tp > 1 else None)

    def local(q, k, v):
        return flash_attention_packed(q, k, v, h_local, causal, block_q,
                                      block_k, None, hkv_local)

    return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


def ring_attention(q, k, v, mesh: Mesh, axis: str = "seq",
                   causal: bool = True,
                   use_flash: bool | None = None) -> jnp.ndarray:
    """q: (B, H, S, D); k/v: (B, Hkv, S, D) with Hkv <= H (GQA) and S
    sharded over `axis`.  Returns attention output with q's sharding.

    KV rotates UNEXPANDED (round 5): every ppermute moves Hkv-head
    chunks — for the 8-head/2-kv dryrun case that is 4x less ICI
    traffic and 4x less rotating KV memory than expanding first; the
    group expansion happens inside the local step, on the local chunk
    only.  (Bandwidth frugality is the reference's core comm design,
    param_manager.cc:85-93.)

    Local step: the Pallas flash kernels when the chunk shapes tile
    (`use_flash` None = auto).  Under a causal mask every ring rotation
    is one of exactly three cases — diagonal (kv_off == q_off: the
    standard causal kernel), fully visible (kv strictly earlier:
    non-causal kernel), fully masked (kv strictly later: contributes
    nothing) — so the offset-aware mask the XLA fallback needs never
    enters the kernel; a lax.cond picks visible-vs-masked per device.
    The rotation loop is Python-unrolled (nseq is static), making the
    per-rotation case static too."""
    from ..ops.attention import expand_kv_heads
    nseq = mesh.shape[axis]
    if nseq == 1:
        return attention_reference(q, expand_kv_heads(k, q.shape[1]),
                                   expand_kv_heads(v, q.shape[1]), causal)
    b, h, s_global, d = q.shape
    hkv = k.shape[1]
    # heads ride "model" only when BOTH q and kv head counts divide it —
    # a mismatched split would misalign the local GQA groups
    tp = mesh.shape["model"]
    head_axis = "model" if h % tp == 0 and hkv % tp == 0 else None
    spec = P("data", head_axis, axis, None)
    chunk = s_global // nseq
    if use_flash is None:
        use_flash = flash_chunk_legal(chunk, chunk, d)

    # per-chunk tuned block geometry (bk=1024 wins for chunks >= 1024,
    # same table as the single-device and Ulysses paths)
    from ..ops.attention import flash_blocks
    fbq, fbk = flash_blocks(chunk)

    def local_flash(q, k, v):
        idx = jax.lax.axis_index(axis)
        perm = [(i, (i + 1) % nseq) for i in range(nseq)]
        h_local = q.shape[1]
        out = jnp.zeros(q.shape, jnp.float32)
        lse = jnp.full(q.shape[:3] + (1,), NEG_INF, jnp.float32)
        k_cur, v_cur = k, v
        for s in range(nseq):
            # group expansion happens on the LOCAL chunk only (the
            # rotating carry stays at Hkv width), and for the
            # conditional rotations INSIDE the visible branch so
            # fully-masked hops do no attention-side work at all
            def vis(args, causal_=False):
                qq, kk, vv = args
                return flash_chunk(qq, expand_kv_heads(kk, h_local),
                                   expand_kv_heads(vv, h_local),
                                   causal_, block_q=fbq, block_k=fbk)

            if not causal:
                o_new, l_new = vis((q, k_cur, v_cur))
            elif s == 0:
                # diagonal: kv_off == q_off on every device
                o_new, l_new = vis((q, k_cur, v_cur), True)
            else:
                # kv chunk s hops back: visible iff it wrapped no ring
                # boundary (idx >= s); otherwise it is entirely in the
                # future and contributes nothing
                o_new, l_new = jax.lax.cond(
                    idx >= s,
                    vis,
                    lambda args: (
                        jnp.zeros(args[0].shape, jnp.float32),
                        jnp.full(args[0].shape[:3] + (1,), NEG_INF,
                                 jnp.float32)),
                    (q, k_cur, v_cur))
            out, lse = merge_attention(out, lse, o_new, l_new)
            if s < nseq - 1:
                k_cur = jax.lax.ppermute(k_cur, axis, perm)
                v_cur = jax.lax.ppermute(v_cur, axis, perm)
        return out.astype(q.dtype)

    def local(q, k, v):
        idx = jax.lax.axis_index(axis)
        chunk = q.shape[2]
        q_off = idx * chunk
        h_local = q.shape[1]

        def step(carry, s):
            k_cur, v_cur, out, lse = carry
            src = jax.lax.rem(idx - s + nseq, nseq)  # owner of current kv
            # chunked-flash local step: the per-rotation score matrix
            # stays O(chunk·block) even for long local KV chunks
            o_new, lse_new = chunk_attention_blockwise(
                q, expand_kv_heads(k_cur, h_local),
                expand_kv_heads(v_cur, h_local), causal, q_off,
                src * chunk)
            out, lse = merge_attention(out, lse, o_new, lse_new)
            # rotate kv to the next device (ring over ICI), Hkv-wide
            perm = [(i, (i + 1) % nseq) for i in range(nseq)]
            k_nxt = jax.lax.ppermute(k_cur, axis, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis, perm)
            return (k_nxt, v_nxt, out, lse), None

        out0 = jnp.zeros(q.shape, jnp.float32)
        lse0 = jnp.full(q.shape[:3] + (1,), NEG_INF, jnp.float32)
        (k, v, out, lse), _ = jax.lax.scan(
            step, (k, v, out0, lse0), jnp.arange(nseq))
        return out.astype(q.dtype)

    return shard_map(local_flash if use_flash else local, mesh=mesh,
                     in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


def ulysses_attention(q, k, v, mesh: Mesh, axis: str = "seq",
                      causal: bool = True,
                      attn_fn=None) -> jnp.ndarray:
    """Ulysses SP: all-to-all seq→heads, local full-sequence attention,
    all-to-all back.  q: (B, H, S, D); k/v: (B, Hkv, S, D), Hkv <= H
    (GQA), S sharded over `axis`.

    When Hkv splits the same way H does (over "model" and the seq
    axis), k/v travel the all-to-alls at Hkv width — group expansion
    happens on the post-a2a local chunk, so comm volume scales with
    Hkv, not H (round 5, same frugality as the ring path).  Otherwise
    k/v are pre-expanded (the pre-round-5 layout).

    The local step defaults to the Pallas flash kernel (the post-a2a
    chunk is FULL sequence length with no position offsets — plain
    causal attention, exactly the kernel's contract) whenever the
    global S and D tile; dense reference otherwise or when attn_fn is
    given."""
    from ..ops.attention import expand_kv_heads
    nseq = mesh.shape[axis]
    s_global, d = q.shape[2], q.shape[3]
    h = q.shape[1]
    hkv = k.shape[1]
    if attn_fn is None:
        if flash_chunk_legal(s_global, s_global, d):
            from ..ops.attention import flash_attention, flash_blocks
            bq, bk = flash_blocks(s_global)
            attn_fn = lambda q, k, v, c: flash_attention(  # noqa: E731
                q, k, v, c, bq, bk)
        else:
            attn_fn = attention_reference
    if nseq == 1:
        return attn_fn(q, expand_kv_heads(k, h), expand_kv_heads(v, h),
                       causal)
    tp = mesh.shape["model"]
    h_local = h // tp if h % tp == 0 and tp > 1 else h
    if h_local % nseq:
        raise ValueError(
            f"Ulysses needs heads ({h}"
            f"{f'/tp={tp}' if tp > 1 and h % tp == 0 else ''}) "
            f"% seq axis ({nseq}) == 0")
    # kv rides at Hkv width iff it splits exactly like q's heads do:
    # same model-axis divisibility (so both shard or neither does) and
    # the local kv head count splits over the seq axis — then the
    # contiguous a2a blocks keep q-head groups aligned with their kv
    # slice and the local expansion is exact
    head_on_model = h % tp == 0
    hkv_local = hkv // tp if head_on_model and hkv % tp == 0 else hkv
    kv_native = (hkv != h
                 and (hkv % tp == 0) == head_on_model
                 and hkv_local % nseq == 0)
    if hkv != h and not kv_native:
        k = expand_kv_heads(k, h)
        v = expand_kv_heads(v, h)

    spec = _spec(mesh, axis, h)
    kv_spec = (P("data", "model" if head_on_model else None, axis, None)
               if kv_native else spec)

    def local(q, k, v):
        def to_heads(x):   # (B, H, S/n, D) -> (B, H/n, S, D)
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                      tiled=True)

        def to_seq(x):     # (B, H/n, S, D) -> (B, H, S/n, D)
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)

        qh = to_heads(q)
        kh, vh = to_heads(k), to_heads(v)
        if kh.shape[1] != qh.shape[1]:
            kh = expand_kv_heads(kh, qh.shape[1])
            vh = expand_kv_heads(vh, qh.shape[1])
        out = attn_fn(qh, kh, vh, causal)
        return to_seq(out)

    return shard_map(local, mesh=mesh, in_specs=(spec, kv_spec, kv_spec),
                     out_specs=spec, check_vma=False)(q, k, v)
