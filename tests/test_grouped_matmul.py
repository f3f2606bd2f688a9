"""The grouped matmul of the experts' grouped form (ops/grouped_matmul.py;
ISSUE 45), interpreted on the CPU: against a loop over the groups and
against `jax.lax.ragged_dot` on the rows that are in groups, the
schedule's arithmetic against a brute count of (tile, group) pairs, and
the rule for the tiles."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from singa_tpu.ops import grouped_matmul as gm  # noqa: E402
from singa_tpu.ops import moe as moe_ops  # noqa: E402

# name: (rows handed, K, N, group sizes, (tm, tk, tn))
CASES = {
    "empty_groups_first": (64, 128, 128, [0, 0, 20, 30, 14], (16, 128, 128)),
    "empty_groups_middle": (64, 128, 128, [20, 0, 0, 30, 14],
                            (16, 128, 128)),
    "empty_groups_last": (64, 128, 128, [20, 30, 14, 0, 0], (16, 128, 128)),
    "a_group_spans_three_tiles": (64, 128, 128, [5, 40, 10], (16, 128, 128)),
    "one_favourite_holds_half": (96, 128, 128, [3, 4, 48, 5, 6, 7, 8, 15],
                                 (16, 128, 128)),
    "groups_of_one_row": (32, 128, 128, [1] * 20, (8, 128, 128)),
    "a_tail_not_in_groups": (128, 128, 128, [9, 0, 17, 11], (16, 128, 128)),
    "exactly_the_rows_handed": (64, 128, 128, [16, 7, 25, 16],
                                (16, 128, 128)),
    "nothing_in_groups": (32, 128, 128, [0, 0, 0], (16, 128, 128)),
    "the_way_up": (48, 512, 128, [10, 20, 18], (16, 512, 128)),
    "the_way_down": (48, 128, 512, [10, 20, 18], (16, 128, 256)),
    "rows_not_a_multiple_of_the_tile": (50, 128, 128, [10, 21, 19],
                                        (16, 128, 128)),
    "k_in_tiles": (48, 512, 256, [10, 20, 18], (16, 128, 128)),
    "one_tile_of_all_rows": (40, 128, 128, [3, 0, 7, 22], (40, 128, 128)),
}


def _operands(m, k, n, sizes, dtype, seed=0):
    rng = np.random.default_rng(seed)
    lhs = jnp.asarray(rng.standard_normal((m, k)), dtype)
    rhs = jnp.asarray(rng.standard_normal((len(sizes), k, n)) / 8, dtype)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32)


def _by_group(lhs, rhs, sizes):
    """Each group's rows times its matrix, float32 at full precision."""
    lhs, rhs = np.asarray(lhs, np.float32), np.asarray(rhs, np.float32)
    out, at = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32), 0
    for g, size in enumerate(np.asarray(sizes)):
        out[at:at + size] = lhs[at:at + size] @ rhs[g]
        at += size
    return out, at


def _pairs(sizes, m, tm):
    """(tile, group) pairs that share a row, counted the slow way."""
    ends = np.cumsum(sizes)
    return [(tile, g) for tile in range(-(-m // tm))
            for g, (size, end) in enumerate(zip(sizes, ends))
            if size and max(end - size, tile * tm) < min(end, (tile + 1) * tm)]


def _call(lhs, rhs, sizes, tiling):
    plan = gm.schedule(sizes, lhs.shape[0], tiling[0])
    return gm.singa_grouped_matmul(lhs, rhs, *plan, tiling=tiling,
                                   interpret=True), plan


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_loop_over_groups(case):
    """float32 operands: the rows in groups against a product a group
    and against `ragged_dot`; rows behind the last group are not held
    to anything."""
    m, k, n, sizes, tiling = CASES[case]
    lhs, rhs, gs = _operands(m, k, n, sizes, jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, _ = _call(lhs, rhs, gs, tiling)
        ragged = jax.lax.ragged_dot(lhs, rhs, gs,
                                    preferred_element_type=jnp.float32)
    want, rows = _by_group(lhs, rhs, sizes)
    assert got.shape == (m, n) and got.dtype == jnp.float32
    np.testing.assert_allclose(got[:rows], want[:rows], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[:rows], ragged[:rows], rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_schedule_is_the_pairs_that_share_a_row(case):
    """Every (tile, group) pair with a row in common once, tile by tile
    and group by group; the rows of the visited tiles are what
    `grouped_matmul` returns beside its product."""
    m, _, _, sizes, (tm, _, _) = CASES[case]
    offsets, group, tile, visits = gm.schedule(
        jnp.asarray(sizes, jnp.int32), m, tm)
    pairs = _pairs(sizes, m, tm)
    assert int(visits) == len(pairs) <= -(-m // tm) + len(sizes) - 1
    assert group.shape == tile.shape == (-(-m // tm) + len(sizes) - 1,)
    assert list(zip(np.asarray(tile)[:len(pairs)],
                    np.asarray(group)[:len(pairs)])) == pairs
    assert list(np.asarray(offsets)) == [0] + list(np.cumsum(sizes))
    # what lies behind the last visit is still an index
    assert np.all((np.asarray(tile) >= 0) & (np.asarray(tile) < -(-m // tm)))
    assert np.all((np.asarray(group) >= 0) & (np.asarray(group) < len(sizes)))


@pytest.mark.parametrize("case", ["one_favourite_holds_half", "the_way_up",
                                  "the_way_down", "a_tail_not_in_groups"])
def test_bf16_operands_give_a_float32_result(case):
    m, k, n, sizes, tiling = CASES[case]
    lhs, rhs, gs = _operands(m, k, n, sizes, jnp.bfloat16, seed=1)
    got, _ = _call(lhs, rhs, gs, tiling)
    want, rows = _by_group(lhs, rhs, sizes)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got[:rows], want[:rows], rtol=1e-5, atol=1e-4)


def test_the_entry_takes_its_tiles_from_the_rule_and_counts_their_rows():
    """`grouped_matmul` is the call `_grouped` makes: tiles by `tiles`,
    the product and the rows of the tiles its schedule visits."""
    m, k, n, sizes = 300, 256, 384, [40, 0, 100, 1, 60, 29]
    lhs, rhs, gs = _operands(m, k, n, sizes, jnp.float32, seed=2)
    tm, tk, tn = gm.tiles(m, k, n, 4)
    assert (tm, tk, tn) == (128, 256, 384)
    with jax.default_matmul_precision("highest"):
        got, tile_rows = jax.jit(gm.grouped_matmul)(lhs, rhs, gs)
    want, rows = _by_group(lhs, rhs, sizes)
    np.testing.assert_allclose(got[:rows], want[:rows], rtol=1e-5, atol=1e-5)
    # 230 rows in two tiles of 128: six groups, five with a row, one of
    # them over the tiles' boundary
    assert int(tile_rows) == 128 * len(_pairs(sizes, m, tm)) == 128 * 6
    with pytest.raises(ValueError, match="under sizes"):
        gm.grouped_matmul(lhs, rhs, gs[:-1])
    with pytest.raises(ValueError, match="do not divide"):
        _call(lhs, rhs, gs, (128, 256, 256))


@pytest.mark.parametrize("m,k,n,itemsize,want", [
    # the four sparse configurations' products, up and down (bf16)
    (8192, 4096, 1280, 2, (128, 4096, 640)),
    (8192, 1280, 4096, 2, (128, 1280, 2048)),
    (4096, 2304, 1024, 2, (128, 2304, 1024)),
    (4096, 1024, 2304, 2, (128, 1024, 2304)),
    (1024, 2048, 2048, 2, (128, 2048, 1024)),
    (8192, 2048, 1024, 2, (128, 2048, 1024)),
    # fewer rows than a tile: all of them; columns that are no whole
    # lanes: all of them
    (34, 16, 12, 4, (34, 16, 12)),
    # a K of which not even 128 columns fit: K in tiles too
    (256, 65536, 256, 2, (128, 16384, 128)),
])
def test_the_tiles_follow_the_shapes(m, k, n, itemsize, want):
    assert gm.tiles(m, k, n, itemsize) == want
    tm, tk, tn = want
    assert k % tk == 0 and n % tn == 0
    assert tk * tn * itemsize <= gm._VMEM_BYTES // 6 or (tk, tn) == (k, n)


def test_the_grouped_form_hands_back_its_tiles_rows_when_asked(monkeypatch):
    """`held_experts_ffn(tile_rows=True)`: the grouped form adds the
    rows of the tiles its three products visited behind its counts; the
    dense walk, and a caller that did not ask, get the counts alone."""
    rng = np.random.default_rng(3)
    t, e, f, held, first = 40, 16, 12, 6, 5
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    x, wg, wu, wd = f32(t, e), f32(held, e, f), f32(held, e, f), f32(held, f, e)
    idx = jnp.asarray(np.stack([rng.permutation(20)[:3] for _ in range(t)]),
                      jnp.int32)
    weights = jnp.ones((t, 3), jnp.float32)
    args = (x, idx, weights, wg, wu, wd, first)
    _, dense = moe_ops.held_experts_ffn(*args, max_load=True, tile_rows=True)
    monkeypatch.setattr(moe_ops, "ROW_BLOCK", 8)
    _, plain = moe_ops.held_experts_ffn(*args, max_load=True)
    _, counts = moe_ops.held_experts_ffn(*args, max_load=True, tile_rows=True)
    assert dense.shape == plain.shape == (3,) and counts.shape == (4,)
    np.testing.assert_array_equal(counts[:3], dense)
    # 120 assignments, 60 handed at a time: one tile of the rows handed,
    # a visit a held expert with a row in that half, three products
    local = np.asarray(idx) - first
    ends = np.cumsum(np.bincount(local[(local >= 0) & (local < held)],
                                 minlength=held))
    visits = sum(len(_pairs(np.diff(np.clip(ends, lo, lo + 60), prepend=lo),
                            60, 60)) for lo in (0, 60))
    assert int(counts[3]) == 3 * 60 * visits
    assert int(counts[1]) <= visits <= int(counts[1]) + 1
    assert 3 * int(counts[0]) <= int(counts[3])
