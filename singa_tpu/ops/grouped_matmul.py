"""Grouped matmul: the rows of `lhs`, sorted by group, each run of rows
through its own group's matrix, as a Pallas kernel whose row tile is
small and may hold rows of several groups.

`grouped_matmul(lhs (m, K), rhs (X, K, N), group_sizes (X,))` is
`jax.lax.ragged_dot`'s contract: rows [sum(sizes[:g]), sum(sizes[:g +
1])) of `lhs` times `rhs[g]`, float32 sums, a float32 result, the
operands in the dtype they come in.  It is the product of
`ops/moe.py:_grouped`, the grouped form of the experts held here.

The schedule is a list of VISITS, one for every (row tile, group) pair
that shares a row, tile by tile and within a tile group by group
(`schedule`: a few scalars a group, computed by XLA before the call and
scalar-prefetched).  A visit multiplies the tile's `tm` rows by the
group's matrix and keeps the rows that are the group's; the tile's
other rows are another visit's.  So m rows in X groups cost at most
ceil(m / tm) + X - 1 visits of `tm` rows, where a tile a group (XLA's
own lowering of `ragged_dot` on the TPU: 512 rows) costs X tiles
however few rows a group has: 2,048 rows on 40 experts are 55 visits of
128 rows here and 48 of 512 there (PERF.md 6, PR 45).  The grid's
visits are a traced number: tiles behind the last group's last row are
not visited whatever `m` is handed, their rows of the result are NOT
WRITTEN (they hold what the buffer held), and a tile's rows behind the
last group are whatever the last visit's buffer held.  No caller may
read them; `_grouped` does not.

The grid is (N tiles, visits, K tiles), K innermost.  A group's (tk,
tn) block of `rhs` is fetched when its index changes, so with tk = K
(the rule wherever a (K, tn) block fits) consecutive visits of one
group share one fetch and every matrix crosses HBM once a call; the
`lhs` is read once an N tile, so tn is as wide as the budget allows.
The output tile stays in VMEM over the consecutive visits of its row
tile and is written back once.

`tiles` is the rule for (tm, tk, tn): a function of m, K, N, the
operands' width and `_VMEM_BYTES`, no setting.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import attention as _attention

#: Stable name of the Mosaic custom call in compiled modules.
KERNEL_NAME = "singa_grouped_matmul_kernel"

# Rows of a tile.  The MXU's own height: a visit cannot cost less, and
# with 51 rows a group a wider tile multiplies more rows that are
# another group's.
ROW_TILE = 128

# What the call may hold in VMEM, stated to Mosaic (whose default is 16
# MB): the `rhs` block twice (the next group's in flight while this
# one's is multiplied), the `lhs` and output blocks twice, the
# accumulator and the product's own temporaries.
_VMEM_BYTES = 32 * 1024 * 1024


def tiles(m: int, k: int, n: int, itemsize: int) -> tuple:
    """(tm, tk, tn) for (m, K) by (X, K, N) operands `itemsize` bytes
    wide: `ROW_TILE` rows (all of them where there are fewer); all of K
    and the widest tn, N over a whole number and whole 128-lane tiles,
    whose (K, tn) block takes a sixth of `_VMEM_BYTES`; where not even
    128 columns of all of K fit, K over a whole number too."""
    tm = ROW_TILE if m >= ROW_TILE else m
    block = _VMEM_BYTES // 6

    def widest(size, other):
        """`size` over the least whole number that leaves whole tiles
        and a block of `other` rows inside the budget; else None."""
        for d in range(1, size // 128 + 1):
            if size % d == 0 and (size // d) % 128 == 0 \
                    and (size // d) * other * itemsize <= block:
                return size // d
        return None

    tn = widest(n, k)
    if tn is not None:
        return tm, k, tn
    tn = 128 if n % 128 == 0 else n
    return tm, widest(k, tn) or k, tn


def schedule(group_sizes, m: int, tm: int):
    """The visits of `m` rows in tiles of `tm` under `group_sizes` (X,)
    int32: (offsets (X + 1,): group g's rows are [offsets[g], offsets[g
    + 1]); group (V,) and tile (V,) of every visit, V = ceil(m / tm) +
    X - 1 the most there can be; visits: how many there are).  A group
    is visited once a tile it has a row in; an empty group never."""
    x = group_sizes.shape[0]
    tiles_m = -(-m // tm)
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    first_tile = (ends - group_sizes) // tm
    spans = jnp.where(group_sizes > 0, -(-ends // tm) - first_tile, 0)
    visit_ends = jnp.cumsum(spans)
    visit_starts = visit_ends - spans
    # a visit's group by comparison, (V, X): a search and two gathers
    # would be a handful of small ops a product where this is one fusion
    v = jnp.arange(tiles_m + x - 1, dtype=jnp.int32)[:, None]
    mine = (v >= visit_starts) & (v < visit_ends)
    group = jnp.sum(jnp.where(mine, jnp.arange(x, dtype=jnp.int32), 0), 1)
    tile = v[:, 0] + jnp.sum(jnp.where(mine, first_tile - visit_starts, 0), 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    # behind the last visit nothing is computed; an index is still one
    return (offsets, group, jnp.clip(tile, 0, tiles_m - 1),
            visit_ends[-1])


def _kernel(offsets_ref, group_ref, tile_ref, lhs_ref, rhs_ref, out_ref,
            *acc, tm, tiles_k):
    v, kk = pl.program_id(1), pl.program_id(2)
    part = jnp.dot(lhs_ref[...], rhs_ref[...],
                   preferred_element_type=jnp.float32)

    def keep(product):
        g = group_ref[v]
        row = tile_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, product.shape, 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        out_ref[...] = jnp.where(mine, product, out_ref[...])

    if tiles_k == 1:
        keep(part)
        return
    acc_ref, = acc

    @pl.when(kk == 0)
    def _():
        acc_ref[...] = part

    @pl.when(kk > 0)
    def _():
        acc_ref[...] += part

    @pl.when(kk == tiles_k - 1)
    def _():
        keep(acc_ref[...])


def grouped_matmul(lhs, rhs, group_sizes):
    """lhs (m, K) sorted by group, rhs (X, K, N), group_sizes (X,)
    int32 summing to at most m.  Returns (out (m, N) float32, the rows
    of the tiles the schedule visits, int32: visits x tm).  Rows of
    `out` behind the last group are not written and must not be read.
    Compiled by Mosaic on the TPU, interpreted elsewhere
    (`ops.attention._on_tpu`)."""
    m, k = lhs.shape
    x, k2, n = rhs.shape
    if k != k2 or group_sizes.shape != (x,) or lhs.dtype != rhs.dtype:
        raise ValueError(f"lhs {lhs.shape} {lhs.dtype} by rhs {rhs.shape} "
                         f"{rhs.dtype} under sizes {group_sizes.shape}")
    tm, tk, tn = tiles(m, k, n, lhs.dtype.itemsize)
    offsets, group, tile, visits = schedule(group_sizes, m, tm)
    out = singa_grouped_matmul(lhs, rhs, offsets, group, tile, visits,
                               tiling=(tm, tk, tn),
                               interpret=not _attention._on_tpu())
    return out, visits * tm


# Jitted, so that the layers and products of one program share one trace
# and one Mosaic lowering a shape, and named as the kernel: the
# function's name is the name of the op, and so of the row, that holds
# the kernel's time in a device trace.
@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def singa_grouped_matmul(lhs, rhs, offsets, group, tile, visits, *, tiling,
                         interpret):
    m, k = lhs.shape
    n = rhs.shape[2]
    tm, tk, tn = tiling
    if k % tk or n % tn:
        raise ValueError(f"tiles of ({tk}, {tn}) do not divide ({k}, {n})")
    tiles_k = k // tk
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, tiles_k=tiles_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, visits, tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda j, v, kk, o, g, t: (t[v], kk)),
                pl.BlockSpec((None, tk, tn),
                             lambda j, v, kk, o, g, t: (g[v], kk, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, kk, o, g, t: (t[v], j)),
            scratch_shapes=([] if tiles_k == 1
                            else [pltpu.VMEM((tm, tn), jnp.float32)])),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=(None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(offsets, group, tile, lhs, rhs)
