"""Paged decode attention: one query token per serving slot against that
slot's blocks of a paged KV pool, as a Pallas kernel that reads the
live blocks only.

The pool of one attention layer is (num_blocks, 2 * Hkv, block_len, D)
(serve/kvcache.py): a block holds its Hkv key heads and then its Hkv
value heads, contiguous in HBM.  Slot s's logical block t is pool block
`tables[s, t]`, and token position p lives at
pool[tables[s, p // block_len], :, p % block_len].  A slot that has
written `ntoks[s]` tokens attends positions 0..ntoks[s] (its newest
token was written just before the call), which is
ntoks[s] // block_len + 1 blocks of its table row; the rest of the row
(null-block tail, reserved-but-unwritten blocks) is never read.

`paged_decode_attention` is the kernel: the pool stays in HBM, the block
table and the lengths are scalar-prefetched, and each slot's live blocks
are copied HBM -> VMEM a chunk at a time, double buffered, the next
slot's first chunk in flight while this slot's last one is computed.
Scores and the online softmax are f32; GQA is done in the kernel (q
grouped (Hkv, G, D), no expanded K/V).  Every caller hands ONE pool, so
a block is one copy: `kAttention` and `kCCA` (few, narrow heads) a pool
whose blocks hold keys and then values, 1 / sqrt(D); `kMLA`'s absorbed
decode step a pool of latent rows shared by all heads (Hkv 1) whose
leading `value_dim` columns are the value, its own scale.  One body,
the two told apart by the pool's heads beside the output's.

The copy schedule follows the pool's shape.  Beside its transfer a live
byte costs scalar work a block (a table read, a descriptor and its
issue) and fixed work a chunk, and neither shrinks with the block: a
block of (2 x 8, 16, 128) bf16 is a 64 KB copy that hides them, one of
(2 x 2, 16, 128) a 16 KB copy that does not (and was two copies of 8
KB while keys and values lay in two pools: PERF.md 6, PR 38).  So a
chunk is a number of bytes (`chunk_positions`); a chunk's copies signal
one DMA semaphore, which counts bytes, and are waited for together, by
size, with no second walk of the table; and `start` issues a group of
blocks in straight-line code before it loops.  A latent pool's block
is 20 KB and cannot hide its descriptor either, but it has ONE head:
consecutive blocks are consecutive rows of the pool and of the buffer
alike, so where the table promises runs of `extent_blocks` consecutive
blocks (serve/kvcache.py hands a latent net's blocks out so) `start`
reads one table entry a run and brings it in one copy.

A WINDOWED call (`window` W > 0, a sliding-window `kAttention` layer)
attends positions max(0, ntoks[s] - W + 1)..ntoks[s] and its table row
is a RING: logical block b lives in column b % T, T =
`ring_blocks(W, block_len)` the most blocks a window can touch.  The
walk starts at the window's first block, reads its column modulo the
ring and masks the positions of that block that lie before the window.
The window is a static argument: a call without one lowers to the
kernel it lowered to before there was one.

`paged_attention_reference` is the plain-jnp gather of every slot's
whole table, the formulation the serving engine ran before the kernel:
it materialises (S, T, 2 * Hkv, block_len, D) and is kept only as
the oracle the tests compare the kernel against (kMLA's is
`tests/oracles.py:attend_absorbed` over the gathered rows) and as the row
`tools/paged_kernel_bench.py` times the kernel beside.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import attention as _attention

#: Stable name of the Mosaic custom call in compiled modules.
KERNEL_NAME = "singa_paged_decode_kernel"

# Bytes of KEY rows that a chunk (one trip of a slot's loop) brings in;
# where a block holds its values behind its keys the chunk brings as
# many again.  A trip's fixed work (the loop's carry, the softmax's
# rescale, a wait) is paid a chunk and a copy's scalar work a block,
# whatever the bytes they move, so the chunk is a number of bytes and
# its positions follow the keys' row: 256 at the dense cells' 8 heads x
# 128 bf16, 512 at Trinity's 4 x 128 and at a latent pool's 1 x 640,
# 1,024 at kCCA's 2 x 128.  On the v5e (tools/paged_kernel_bench.py;
# PERF.md 6, PR 34: keys and values in two pools then, a copy each), at
# 128 / 256 / 512 / 1,024 positions a chunk, us a layer: 32 slots x 80
# blocks of (8, 16, 128) 161 / 141 / 139 / 140 with 57 % of the table
# live and 37 / 34 / 42 / 63 with 8 % live (the masked tail of a slot's
# last chunk is computed whole); 96 x 128 blocks of (1, 16, 640), 23 %
# live, 242 / 182 / 157 / 166; 64 x 256 blocks of (2, 16, 128), 26 %
# live, 296 / 219 / 191 / 181 (2,048: slower again).
_CHUNK_BYTES = 512 * 1024

# Blocks whose copies `start` issues in straight-line code (the table
# reads and descriptors of one overlap the next's) before it loops: 1 /
# 4 / 8 / 16 read 211 / 194 / 181 / 180 us at the last geometry above,
# and all the same at the first, where the transfer hides the issue.
# Mosaic's only: the interpreter gains nothing from it and pays for
# every copy it traces (a decode program's lowering and compile on the
# CPU 1.4 -> 4.1 s), so `paged_decode_attention` gives it 1.
_ISSUE_GROUP = 8

# Bytes ONE copy brings of a one-head pool of latent rows, whose
# consecutive blocks are consecutive rows of HBM and of the buffer
# alike: `extent_blocks` of them, 8 at (1, 16, 640) bf16.  On the v5e
# (tools/paged_kernel_bench.py; PERF.md 6, PR 41), at 1 / 2 / 4 / 8 /
# 16 / 32 blocks a copy, us a call: 64 slots x 448 blocks under two
# query rows of 128 heads (held by its operations), 9,702 live blocks,
# 822 / 756 / 717 / 697 / 686 / 680, and with the table full 2,188 /
# 1,990 / 1,872 / 1,817 / 1,787 / 1,770: 14.8 ns a descriptor removed,
# seven eighths of them gone at 8; 96 slots x 128 blocks under one row
# of 32 heads (held by its bytes), 2,835 live blocks, 156 / 145 / 144 /
# 144 / 146 / 153: past 8 the extent that holds a short slot's horizon
# reads more than its descriptors cost.  A shuffled table read a block
# a copy takes what a run of blocks read a block a copy takes (822).
_EXTENT_BYTES = 160 * 1024


def key_heads(pool_shape, value_dim=None) -> int:
    """Hkv of a (num_blocks, heads, bl, D) pool: half its heads where a
    block holds values behind keys (`value_dim` None), all of them
    where a row's leading `value_dim` columns are its value."""
    heads = pool_shape[1]
    if value_dim is not None:
        return heads
    if heads % 2:
        raise ValueError(f"a pool of shape {tuple(pool_shape)} holds no "
                         f"value heads behind its key heads")
    return heads // 2


def chunk_positions(pool_shape, dtype, value_dim=None):
    """Key positions a chunk holds of a (num_blocks, heads, bl, D) pool:
    the power of two whose KEY rows come nearest `_CHUNK_BYTES`."""
    row = (key_heads(pool_shape, value_dim) * pool_shape[3]
           * jnp.dtype(dtype).itemsize)
    return 2 ** round(math.log2(_CHUNK_BYTES / row))


def extent_blocks(pool_shape, dtype, value_dim, table_width) -> int:
    """Consecutive pool blocks ONE copy brings of a (num_blocks, heads,
    bl, D) pool read through a table `table_width` columns wide: the
    extent the paged cache's free list deals in (serve/kvcache.py) and
    the kernel copies, from the pool's shape alone so that the two
    cannot disagree.  1 wherever a block has more than one head (the
    buffer is head-major: a run of blocks is no one slab of it); for a
    one-head pool whose rows hold keys and values (`value_dim` given)
    the power of two whose bytes come nearest `_EXTENT_BYTES`, halved
    until it divides a chunk's blocks and the table row."""
    _, heads, bl, d = pool_shape
    if heads != 1 or value_dim is None:
        return 1
    block = bl * d * jnp.dtype(dtype).itemsize
    extent = 2 ** max(0, round(math.log2(_EXTENT_BYTES / block)))
    cb = max(1, min(chunk_positions(pool_shape, dtype, value_dim) // bl,
                    table_width))
    while cb % extent or table_width % extent:
        extent //= 2
    return extent


def ring_blocks(window: int, block_len: int) -> int:
    """Blocks a window of `window` positions can touch, wherever it
    lies: the width of a windowed layer's ring (W / block_len + 1 where
    the block divides the window)."""
    return -(-(int(window) - 1) // int(block_len)) + 1


def paged_attention_reference(q, pool, tables, ntoks, *, scale=None,
                              value_dim=None, window=0, rows=1):
    """Gather formulation, `paged_decode_attention`'s arguments.  q
    (S, H, D); pool (num_blocks, 2 * Hkv, bl, D), or (num_blocks, Hkv,
    bl, D) with `value_dim`; tables (S, T) int32; ntoks (S,) int32.
    Returns (S, H, D) in q's dtype: softmax(q k^T / sqrt(D)) v over
    positions <= ntoks[s], and with `window` over the last `window` of
    them, the table row read as a ring.  With `rows` R > 1 q holds R
    query rows a slot, row j's heads attending positions <= ntoks[s] +
    j."""
    s, h, d = q.shape
    bl = pool.shape[2]
    hkv = key_heads(pool.shape, value_dim)
    t = tables.shape[1]
    groups = h // hkv
    # (S, heads, T*bl, D)
    lines = pool[tables].transpose(0, 2, 1, 3, 4).reshape(
        s, pool.shape[1], t * bl, d).astype(q.dtype)
    kk = lines[:, :hkv]
    vv = lines[:, hkv:] if value_dim is None else kk[..., :value_dim]
    if window:
        # column j holds the newest logical block b <= ntoks // bl with
        # b % T == j (an older one it held has been overwritten)
        newest = (ntoks // bl)[:, None]
        blk = newest - (newest - jnp.arange(t)[None, :]) % t   # (S, T)
        pos = (blk[:, :, None] * bl + jnp.arange(bl)).reshape(s, t * bl)
        allowed = ((pos <= ntoks[:, None]) & (pos >= 0)
                   & (pos > ntoks[:, None] - window))
    else:
        allowed = jnp.arange(t * bl)[None, :] <= ntoks[:, None]  # (S, T*bl)
    if rows > 1:
        # (S, 1, G, T*bl): the heads of query row j see j positions more
        ahead = jnp.repeat(jnp.arange(rows), groups // rows)
        seen = (jnp.arange(t * bl)[None, None, :]
                <= ntoks[:, None, None] + ahead[None, :, None])[:, None]
        allowed = allowed | seen[:, 0].any(1)         # values: the last row's
    qg = q.reshape(s, hkv, groups, d)
    scores = jnp.einsum("shgd,shkd->shgk", qg, kk,
                        preferred_element_type=jnp.float32)
    scores = (scores / jnp.sqrt(jnp.float32(d)) if scale is None
              else scores * scale)
    scores = jnp.where(seen if rows > 1 else allowed[:, None, None], scores,
                       _attention.NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    # a masked lane's probability is an exact zero, but 0 * (inf | nan)
    # is nan: values the mask hides must not reach the product
    vv = jnp.where(allowed[:, None, :, None], vv, 0)
    out = jnp.einsum("shgk,shkd->shgd", probs.astype(vv.dtype), vv)
    return out.reshape(s, h, vv.shape[-1])


def _kernel(ntoks_ref, tables_ref, q_ref, pool, o_ref, into, sems, base_ref,
            *, bl, cb, tw, scale, group, window, qrows=1, extent=1):
    """Grid step s attends slot s.  `pool` lies in HBM; `into` is the
    buffer (2, heads, cb*bl, D): two chunks of cb blocks each, a
    block's (heads, bl, D) slab, its key heads and behind them its
    value heads, copied to rows [c*bl, (c+1)*bl) of every head.  The
    buffer that holds a slot's first chunk alternates with the number
    of chunks walked so far (`base_ref`), because the copy of slot
    s+1's first chunk is started under slot s's last.  With a `window`
    the walk starts at the window's first block and the table row is a
    ring of `tw` columns.  With `qrows` R > 1 the slot's query heads are
    R rows of heads, row j at position ntoks + j: the walk goes to the
    last row's horizon and row j's scores stop at its own.  With an
    `extent` E > 1 the pool is one head's rows, (1, num_blocks * bl,
    D), and a copy brings the E consecutive blocks that begin at a
    table entry: E * bl rows of the pool to E * bl rows of the buffer."""
    s = pl.program_id(0)
    slots = pl.num_programs(0)
    span = cb * bl
    hkv, g, vd = o_ref.shape[1:]

    def horizon(slot):
        """Last position the slot attends; without a ring held inside
        its table row, so that no length can send a copy after a block
        index read from beyond the table."""
        if window:
            return ntoks_ref[slot]
        last = ntoks_ref[slot]
        if qrows > 1:
            last = last + (qrows - 1)
        return jnp.minimum(last, tw * bl - 1)

    def walk(upto):
        """(the first block a walk up to position `upto` reads, how
        many it reads)."""
        last = upto // bl
        if not window:
            return 0, last + 1
        first = jnp.maximum(upto - (window - 1), 0) // bl
        return first, last - first + 1

    n = horizon(s)
    head, blocks = walk(n)                    # the slot's live blocks
    chunks = (blocks + cb - 1) // cb
    base = jnp.where(s == 0, 0, base_ref[0])

    def start(slot, chunk, buf, walked):
        """Start the copies of the chunk's live blocks, the slot's walk
        being `walked` (its first block, its count), one a block (an
        extent: the one that holds the horizon whole); all signal the
        semaphore of `buf`."""
        first, of = walked
        live = jnp.minimum(of - chunk * cb, cb)
        if extent > 1:
            live = (live + (extent - 1)) // extent
        if window:
            # the chunk's first column of the ring; a block's own wraps
            # by a compare, not by a division of its own
            entry = slot * tw
            column = (first + chunk * cb) % tw
        else:
            entry = slot * tw + chunk * cb

        run = extent * bl                   # rows a copy brings

        def block(c):
            if extent > 1:
                # one table entry an extent: the blocks behind it are
                # the next of the pool (serve/kvcache.py)
                blk = tables_ref[entry + c * extent]
                src = pool.at[:, pl.ds(pl.multiple_of(blk * bl, bl), run), :]
            else:
                if window:
                    at = column + c
                    blk = tables_ref[entry
                                     + jnp.where(at >= tw, at - tw, at)]
                else:
                    blk = tables_ref[entry + c]
                src = pool.at[blk]
            rows = pl.ds(pl.multiple_of(c * run, run), run)
            pltpu.make_async_copy(src, into.at[buf, :, rows, :],
                                  sems.at[buf]).start()

        def issue(width):
            # `width` blocks a trip in straight-line code: the table
            # reads and the descriptors of one overlap the next's
            def trip(i, carry):
                for u in range(width):
                    block(i * width + u)
                return carry
            return trip

        whole = live // group
        jax.lax.fori_loop(0, whole, issue(group), None)
        if group > 1:
            jax.lax.fori_loop(whole * group, live, issue(1), None)

    def wait(buf, count):
        """Wait for `count` (static) blocks.  A DMA semaphore counts
        bytes, so one descriptor of the copies' size together stands
        for them all, whatever blocks they brought: no wait reads the
        table."""
        landed = into.at[buf, :, pl.ds(0, count * bl), :]
        pltpu.make_async_copy(landed, landed, sems.at[buf]).wait()

    def attend(chunk, buf, carry, last):
        m, l, acc = carry
        q = q_ref[0]                                    # (Hkv, G, D)
        k = into[buf, :hkv].astype(q.dtype)             # (Hkv, span, D)
        # whole heads behind the keys, or a row's leading columns
        v = (into[buf, hkv:].astype(q.dtype) if into.shape[1] > hkv
             else k[..., :vd])                          # (Hkv, span, Dv)
        sc = jnp.einsum("hgd,htd->hgt", q, k,
                        preferred_element_type=jnp.float32) * scale
        if window or last:
            first = chunk * span
            if window:
                first = first + head * bl
            lane = first + jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, span), 2)
            # made where it is used: an unwindowed call traces the ops
            # it traced before there was a window, in their order
            rows = lambda: first + jax.lax.broadcasted_iota(  # noqa: E731
                jnp.int32, (1, span, 1), 1)
        if window:
            # the walk's first block begins before the window does
            sc = jnp.where(lane > n - window, sc, _attention.NEG_INF)
            v = jnp.where(rows() > n - window, v, jnp.zeros_like(v))
        if last:
            # the only chunk with positions past the slot's horizon:
            # rows no copy wrote hold whatever the buffer held before
            upto = n
            if qrows > 1:
                # query row j ends qrows - 1 - j before the last; the
                # heads of one row lie together
                of_head = jax.lax.broadcasted_iota(jnp.int32, (1, g, 1), 1)
                upto = n - sum((of_head < j * (g // qrows)).astype(jnp.int32)
                               for j in range(1, qrows))
            sc = jnp.where(lane <= upto, sc, _attention.NEG_INF)
            v = jnp.where(rows() <= n, v, jnp.zeros_like(v))
        m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new)
        l = alpha * l + p.sum(axis=-1, keepdims=True)
        acc = alpha * acc + jnp.einsum(
            "hgt,htd->hgd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    @pl.when(s == 0)
    def _():
        start(0, 0, 0, (head, blocks))

    def full_chunk(i, carry):
        buf = (base + i) % 2
        start(s, i + 1, 1 - buf, (head, blocks))
        wait(buf, cb)
        return attend(i, buf, carry, last=False)

    init = (jnp.full((hkv, g, 1), _attention.NEG_INF, jnp.float32),
            jnp.zeros((hkv, g, 1), jnp.float32),
            jnp.zeros((hkv, g, vd), jnp.float32))
    carry = jax.lax.fori_loop(0, chunks - 1, full_chunk, init)
    buf = (base + chunks - 1) % 2

    @pl.when(s + 1 < slots)
    def _():
        start(s + 1, 0, 1 - buf, walk(horizon(s + 1)))

    # the slot's last chunk, 1..cb live blocks: a wait a binary digit
    # of their count (of their extents', each of `extent` blocks)
    live = blocks - (chunks - 1) * cb
    if extent > 1:
        live = (live + (extent - 1)) // extent
    for bit in range((cb // extent).bit_length()):
        @pl.when((live >> bit) & 1 == 1)
        def _():
            wait(buf, extent << bit)

    _, l, acc = attend(chunks - 1, buf, carry, last=True)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    base_ref[0] = 1 - buf


def _check_tiling(q, pool, value_dim):
    """Mosaic copies whole (sublane, lane) tiles: a block's (bl, D) face
    has to be made of them, and so has the part of a row that is its
    value.  Interpreted kernels take any shape."""
    _, _, bl, d = pool.shape
    sublanes = 32 // jnp.dtype(pool.dtype).itemsize
    if bl % sublanes or d % 128 or value_dim % 128:
        raise ValueError(
            f"paged_decode_attention cannot tile a {pool.dtype} pool of "
            f"shape {pool.shape} (q {q.shape}, values of {value_dim}) on "
            f"the TPU: block_len must be a multiple of {sublanes}, "
            f"head_dim and the value's width of 128")


def paged_decode_attention(q, pool, tables, ntoks, *, scale=None,
                           value_dim=None, window=0, rows=1):
    """q (S, H, D) against a pool (num_blocks, 2 * Hkv, bl, D), a
    block's key heads and then its value heads, through `tables` (S, T)
    int32 and `ntoks` (S,) int32.  Returns (S, H, D) in q's dtype,
    equal to `paged_attention_reference` up to the order of the f32
    sums.  Reads ntoks[s] // bl + 1 blocks of slot s's row, each in one
    copy, and nothing else of the pool (a latent pool: whole extents,
    below).  `scale` multiplies the f32
    scores (default 1 / sqrt(D)).

    `value_dim`: the pool is (num_blocks, Hkv, bl, D) and holds both
    sides in the same rows, a row's first `value_dim` columns being its
    value; the result is (S, H, value_dim).  That is a latent (MLA)
    cache under an absorbed query: Hkv 1, one row a token shared by all
    heads, scale 1 / sqrt(nope + rope).

    `window` W > 0: slot s attends its last W positions only,
    max(0, ntoks[s] - W + 1)..ntoks[s], and its table row is a ring
    (position p in column (p // bl) % T, T >= `ring_blocks(W, bl)`);
    the walk starts at the window's first block.

    `rows` R > 1 (a verify step: a slot's last token and its drafts):
    q is (S, R * H, D), a slot's R query rows one after another, each
    H heads; row j stands at position ntoks[s] + j and attends
    positions 0..ntoks[s] + j, all R rows written before the call.  A
    block is still read once.  Not with a window.

    A one-head pool of latent rows (`value_dim`, Hkv 1) is read an
    EXTENT a copy, E = `extent_blocks` of the pool's shape and the
    table's width: columns k E .. k E + E - 1 of a row's real part
    have to be consecutive pool blocks (as `PagedKVCache` hands them
    out; the kernel reads column k E alone), and the pool holds more
    than E blocks: an extent behind the null block, and a null entry
    reads blocks 0 .. E - 1.  The
    extent that holds a slot's horizon is copied whole: its blocks are
    the slot's own, and their rows past the horizon are masked as the
    unwritten rows of a last block are (scores to NEG_INF, values to
    0).  That reads half an extent a slot a call beyond the live
    blocks, ~3 % of the bytes at the Pangu cell's mean of 2,000 rows a
    slot and ~14 % at the Kimi cell's 450, and is the cheaper side:
    the first call is held by its operations, not its bytes, the
    second is 1.5 % of its step, and a call of the first kind issues
    an eighth of the descriptors (PERF.md 6, PR 41).  The same
    positions meet in the same chunks, so the f32 sums keep their
    order.

    Compiled by Mosaic on the TPU, interpreted elsewhere
    (`ops.attention._on_tpu`)."""
    if window and tables.shape[1] < ring_blocks(window, pool.shape[2]):
        raise ValueError(
            f"a window of {window} positions touches up to "
            f"{ring_blocks(window, pool.shape[2])} blocks of "
            f"{pool.shape[2]}; the ring has {tables.shape[1]}")
    hkv = key_heads(pool.shape, value_dim)
    if q.shape[1] % (hkv * rows):
        raise ValueError(f"{q.shape[1]} query heads in {rows} rows over "
                         f"{hkv} key/value heads")
    if rows > 1 and (window or hkv > 1):
        raise ValueError("several query rows a slot are attended over one "
                         "shared key head and no window")
    d = q.shape[-1]
    if value_dim is not None and not 0 < value_dim <= d:
        raise ValueError(f"values of {value_dim} columns from the key "
                         f"rows of {d}")
    interpret = not _attention._on_tpu()
    if not interpret:
        _check_tiling(q, pool, d if value_dim is None else value_dim)
    extent = 1 if window else extent_blocks(pool.shape, pool.dtype,
                                            value_dim, tables.shape[1])
    return singa_paged_decode(
        q, pool, tables, ntoks, interpret=interpret,
        chunk=chunk_positions(pool.shape, pool.dtype, value_dim),
        group=1 if interpret else _ISSUE_GROUP, value_dim=value_dim,
        scale=1.0 / math.sqrt(d) if scale is None else float(scale),
        window=int(window), rows=int(rows), extent=extent)


# Jitted, so that the layers of one program share one trace and one
# Mosaic lowering (16 of them cost a process 1.3 s of every start), and
# named as the kernel: the function's name is the name of the op, and so
# of the row, that holds the kernel's time in a device trace.
@functools.partial(jax.jit, static_argnames=("interpret", "chunk", "group",
                                             "scale", "value_dim", "window",
                                             "rows", "extent"))
def singa_paged_decode(q, pool, tables, ntoks, *, interpret, chunk, scale,
                       value_dim=None, group=_ISSUE_GROUP, window=0, rows=1,
                       extent=1):
    s, h, d = q.shape
    nb, heads, bl, _ = pool.shape
    hkv = key_heads(pool.shape, value_dim)
    tw = tables.shape[1]
    cb = max(1, min(chunk // bl, tw))
    groups = h // hkv
    vd = d if value_dim is None else value_dim
    if extent > 1:
        if heads != 1 or value_dim is None or window:
            raise ValueError(
                f"extents of {extent} blocks are copied of a one-head "
                f"pool of latent rows under a growing table; the pool "
                f"is {pool.shape}, window {window}")
        if cb % extent or tw % extent:
            raise ValueError(
                f"an extent of {extent} blocks divides neither a chunk "
                f"of {cb} nor a table row of {tw}")
        if nb <= extent:
            raise ValueError(
                f"a pool of {nb} blocks holds no extent of {extent} "
                f"behind its null block (and a null table entry reads "
                f"blocks 0..{extent - 1})")
        # one head: a run of blocks is a run of rows, of the pool as
        # of the buffer
        pool = pool.reshape(1, nb * bl, d)
        group = min(group, cb // extent)

    def of_slot(width):
        return pl.BlockSpec((1, hkv, groups, width),
                            lambda i, *_: (i, 0, 0, 0))

    out = pl.pallas_call(
        functools.partial(_kernel, bl=bl, cb=cb, tw=tw, scale=scale,
                          group=group, window=window, extent=extent,
                          **({"qrows": rows} if rows > 1 else {})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s,),
            in_specs=[of_slot(d), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=of_slot(vd),
            scratch_shapes=[pltpu.VMEM((2, heads, cb * bl, d), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((s, hkv, groups, vd), q.dtype),
        compiler_params=(None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))),
        interpret=interpret,
        name=KERNEL_NAME,
    )(ntoks.astype(jnp.int32), tables.astype(jnp.int32).reshape(-1),
      q.reshape(s, hkv, groups, d), pool)
    return out.reshape(s, h, vd)
