"""Paged decode attention (ops/paged_attention.py): the kernel against
the plain gather reference on poisoned pools, in kAttention's geometry
(one pool whose blocks hold their key heads and then their value heads)
and in kMLA's (one pool of latent rows shared by all heads, its own
scale), the one copy a block, the compiled cb decode program's freedom
from the materialised gather, and the scheduler's `cb_live_block_share`
counter.

The kernel runs interpreted here (CPU), at geometries kept tiny: the
Mosaic compile at the serving cell's real geometry is
tests/benchmark/test_bench_preflight.py's."""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oracles import attend_absorbed
from singa_tpu.config.schema import LayerConfig, MLAConfig
from singa_tpu.core.hybrid_layers import MLALayer
from singa_tpu.core.net import build_net
from singa_tpu.models.transformer import transformer_lm
from singa_tpu.ops.paged_attention import (chunk_positions, extent_blocks,
                                           paged_attention_reference,
                                           paged_decode_attention,
                                           ring_blocks, singa_paged_decode)
from singa_tpu.serve import InferenceEngine, InferenceServer, ServeSpec
from singa_tpu.serve.kvcache import NULL_BLOCK

pytestmark = pytest.mark.serve

S, HKV, D, BL, T = 4, 2, 8, 4, 6
FULL = T * BL - 1
# per-slot ntoks; None marks an inactive slot (ntoks 0, null table row)
LENGTHS = {
    "all_inactive": [None, None, None, None],
    "ntoks_0": [0, 0, 0, 0],
    "ntoks_bl-1": [BL - 1] * S,
    "ntoks_bl": [BL] * S,
    "ntoks_bl+1": [BL + 1] * S,
    "ntoks_T*bl-1": [FULL] * S,
    "mixed": [1, BL, 2 * BL + 1, FULL],
    "inactive_among_active": [None, 5, None, 17],
}
# The ZAYA cell's geometry (kCCA: 8 query over 2 key/value heads of
# 128, blocks of 16, a table of 256 blocks), where a chunk is wider
# than at the dense cells' 8 heads: lengths around the chunk's edges
NARROW = dict(hkv=2, d=128, bl=16, t=256)


def _narrow_lengths(dtype):
    """ntoks a slot: the newest token one below, on and one past the
    last position of a chunk, the same one chunk on, the table's full
    256 blocks, no token, one whole block, an inactive slot."""
    w = chunk_positions((1, 2 * NARROW["hkv"], NARROW["bl"], NARROW["d"]),
                        dtype)
    full = NARROW["t"] * NARROW["bl"] - 1
    return {"one_chunk": [w - 2, w - 1, w, None],
            "two_chunks": [2 * w - 2, 2 * w - 1, 2 * w, 0],
            "full_table": [full, 0, NARROW["bl"] - 1, w - 1]}


_dense_kernel = paged_decode_attention        # jitted inside
_dense_reference = jax.jit(paged_attention_reference)


def _narrow_kernel(q, pool, tables, ntoks):
    """The schedule the chip runs: the interpreter is given groups of
    one block (`paged_decode_attention`), Mosaic the default."""
    return singa_paged_decode(
        q, pool, tables, ntoks, interpret=True,
        chunk=chunk_positions(pool.shape, pool.dtype),
        scale=1.0 / np.sqrt(NARROW["d"]))


def _one_pool(sides):
    """Keys and values (nb, Hkv, bl, D) as the pool holds them: a
    block's key heads and then its value heads.  A latent pool has one
    side and is itself."""
    return np.concatenate(sides, axis=1)


def _case(lengths, groups, dtype, seed, hkv=HKV, d=D, sides=2, bl=BL,
          t=T, extent=1, rows=1):
    """q, a clean and a poisoned copy of the pool (`sides` 2: a block's
    key heads and then its value heads; 1: latent rows), tables, ntoks,
    a slot a length.  The table is a shuffled (non-monotone) draw of
    the pool's blocks, with an `extent` E > 1 of its aligned extents
    (E consecutive blocks from 1 + k E, as `PagedKVCache` hands them
    out); a slot's reservation ends somewhere at or after its last
    live block (whole extents) and the row's tail is the null block.
    With `rows` R > 1 q holds R rows of heads a slot and the slot's
    last R - 1 positions beyond ntoks are written too.  In the
    poisoned copy every position no slot may see is nan (the key half,
    or the latent rows of both sides) or inf (the value half): blocks
    no live table entry names, the tail of each last live block, and
    the null block past its position 0 (which an inactive slot
    attends)."""
    rng = np.random.default_rng(seed)
    slots = len(lengths)
    nb = slots * t + 1
    q = rng.standard_normal((slots, hkv * groups * rows, d)).astype(
        np.float32)
    pools = [rng.standard_normal((nb, hkv, bl, d)).astype(np.float32)
             for _ in range(sides)]
    tables = (1 + rng.permutation((nb - 1) // extent)[:, None] * extent
              + np.arange(extent)).reshape(slots, t).astype(np.int32)
    ntoks = np.zeros((slots,), np.int32)
    seen = np.zeros((nb, bl), bool)
    seen[NULL_BLOCK, :rows] = True
    for s, n in enumerate(lengths):
        if n is None:
            tables[s] = NULL_BLOCK
            continue
        ntoks[s] = n
        at = np.arange(n + rows)
        live = -(-(at[-1] // bl + 1) // extent)
        tables[s, rng.integers(live, t // extent + 1) * extent:] = NULL_BLOCK
        seen[tables[s, at // bl], at % bl] = True
    hide = ~seen[:, None, :, None]
    clean = _one_pool([np.where(hide, 0.0, a) for a in pools])
    poisoned = _one_pool([np.where(hide, bad, a)
                          for a, bad in zip(pools, (np.nan, np.inf))])
    to = lambda a: jnp.asarray(a, dtype)                      # noqa: E731
    return (to(q), to(clean), to(poisoned), jnp.asarray(tables),
            jnp.asarray(ntoks))


# kMLA's decode step at a tiny size: 3 heads over one latent row a
# token, rank 8 + rope 4 = 12 columns stored as 16 (kMLA pads its rows
# to whole lane tiles with zeros; here the pad holds noise, which a
# query padded with zeros must not see), value = the first 8 columns,
# scores over sqrt(nope + rope) = sqrt(9), not sqrt(16)
MLA = MLAConfig(num_heads=3, kv_lora_rank=8, qk_nope_head_dim=5,
                qk_rope_head_dim=4, v_head_dim=6)
MLA_ROW = 16


@pytest.fixture(scope="module")
def mla():
    layer = MLALayer(LayerConfig(name="mla", type="kMLA", mla_param=MLA))
    layer.setup([(1, 1, 24)])
    rng = np.random.default_rng(7)
    params = {spec.name: jnp.asarray(rng.standard_normal(spec.shape),
                                     jnp.float32)
              for spec in layer.param_specs}
    return layer, params


def _latent_reference(layer, params, q, pool, tables, ntoks):
    """`oracles.attend_absorbed` over every slot's gathered table:
    what `apply_paged` ran before the kernel.  Returns the attended
    latents' expansion, (S, H * vdim)."""
    s, t = tables.shape
    rows = pool[tables][:, :, 0].reshape(s, t * BL, pool.shape[-1])
    allowed = jnp.arange(t * BL)[None, :] <= ntoks[:, None]
    return attend_absorbed(layer, params, q, rows.astype(q.dtype), allowed)


def _latent_kernel(layer, params, q, pool, tables, ntoks):
    """`MLALayer.apply_paged`'s middle: the kernel between the two
    halves of Wkvb."""
    o_lat = paged_decode_attention(
        layer._absorb_query(params, q, pool.shape[-1]), pool, tables,
        ntoks, value_dim=layer.rank,
        scale=1.0 / np.sqrt(layer.nope + layer.rope))
    assert o_lat.shape == (len(ntoks), layer.heads, layer.rank)
    return layer._expand_output(params, o_lat)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize(
    "name,groups",
    [(name, groups) for groups in (1, 4, "latent") for name in LENGTHS]
    + [(name, "narrow") for name in _narrow_lengths(jnp.bfloat16)],
    ids=lambda v: {1: "mha", 4: "gqa4"}.get(v, v))
def test_kernel_matches_gather_reference_on_poisoned_pools(name, groups,
                                                           dtype, tol, mla):
    if groups == "narrow":
        q, clean, poisoned, tables, ntoks = _case(
            _narrow_lengths(dtype)[name], 4, dtype, seed=len(name), **NARROW)
        _reference, _kernel = _dense_reference, _narrow_kernel
    elif groups == "latent":
        layer, params = mla
        params = {k: v.astype(dtype) for k, v in params.items()}
        # a latent pool's table comes in the extents its shape gives
        # (2 blocks of a table of 6 here)
        extent = extent_blocks((1, 1, BL, MLA_ROW), dtype, layer.rank, T)
        assert extent == 2
        q, clean, poisoned, tables, ntoks = _case(
            LENGTHS[name], layer.heads, dtype, seed=len(name), hkv=1,
            d=MLA_ROW, sides=1, extent=extent)
        q = q[..., :layer.nope + layer.rope]
        _reference = functools.partial(_latent_reference, layer, params)
        _kernel = functools.partial(_latent_kernel, layer, params)
    else:
        q, clean, poisoned, tables, ntoks = _case(
            LENGTHS[name], groups, dtype, seed=len(name) + groups)
        _reference, _kernel = _dense_reference, _dense_kernel
    want = np.asarray(_reference(q, clean, tables, ntoks), np.float32)
    got = np.asarray(_kernel(q, poisoned, tables, ntoks), np.float32)
    assert np.isfinite(got).all(), "the kernel read past a slot's horizon"
    # Wkvb's value half (unit normal here) scales the latent case's output
    size = np.max(np.abs(want)) if groups == "latent" else 1.0
    assert np.max(np.abs(got - want)) <= tol * size
    # the table's tail and the unseen blocks do not reach the result
    # of the reference either: it is a fair oracle on the same pools
    same = np.asarray(_reference(q, poisoned, tables, ntoks), np.float32)
    assert np.array_equal(same, want)


# -- the windowed walk over a ring of blocks per slot -------------------------

# (Hkv, G, D, block_len, window, sides, value columns): the Trinity
# cell's geometry (a pool of (2 x 4, 16, 128) under 32 query heads, a
# window of 2,048: a ring of 129 blocks, four chunks of 512 positions
# and a fifth of one block), and a window over each of the three
# geometries the kernel already served.  `tiny` walks several chunks of
# two blocks with copies issued two at a time.
RINGS = {
    "trinity": (4, 8, 128, 16, 2048, 2, 128),
    "dense": (8, 4, 128, 16, 512, 2, 128),
    "latent": (1, 8, 640, 16, 1024, 1, 512),
    "narrow": (2, 4, 128, 16, 2048, 2, 128),
    "tiny": (2, 2, 8, 4, 10, 2, 8),
}


def _ring_lengths(window, bl):
    """ntoks a slot: no token, one short of the window, the newest
    token the window's last, one past it (the first block's head is
    masked), a block's last row and the next block's first well past
    the window, and a ring that has wrapped several times."""
    ring = ring_blocks(window, bl)
    return [0, window - 2, window - 1, window, window + bl - 1,
            3 * window + bl, 5 * ring * bl + 3]


def _ring_case(lengths, hkv, groups, d, bl, window, sides, dtype, seed):
    """As `_case`, the table row a ring: position p of slot s lives in
    pool block 1 + s R + (p // bl) % R, and every row that lies outside
    the slot's window is poisoned."""
    rng = np.random.default_rng(seed)
    slots, ring = len(lengths), ring_blocks(window, bl)
    nb = slots * ring + 1
    q = rng.standard_normal((slots, hkv * groups, d)).astype(np.float32)
    pools = [rng.standard_normal((nb, hkv, bl, d)).astype(np.float32)
             for _ in range(sides)]
    tables = (1 + np.arange(slots)[:, None] * ring
              + np.arange(ring)[None, :]).astype(np.int32)
    seen = np.zeros((nb, bl), bool)
    for s, n in enumerate(lengths):
        at = np.arange(max(0, n - window + 1), n + 1)
        seen[tables[s, (at // bl) % ring], at % bl] = True
    hide = ~seen[:, None, :, None]
    clean = _one_pool([np.where(hide, 0.0, a) for a in pools])
    poisoned = _one_pool([np.where(hide, bad, a)
                          for a, bad in zip(pools, (np.nan, np.inf))])
    to = lambda a: jnp.asarray(a, dtype)                      # noqa: E731
    return (to(q), to(clean), to(poisoned), jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32))


def _window_oracle(q, pool, sides, tables, ntoks, window, bl, vd):
    """The window's rows gathered position by position, in numpy."""
    q = np.asarray(q, np.float32)
    pool = np.asarray(pool, np.float32)
    hkv = pool.shape[1] // sides
    k, v = pool[:, :hkv], pool[:, -hkv:, :, :vd]
    slots, h, d = q.shape
    groups, ring = h // k.shape[1], tables.shape[1]
    out = np.zeros((slots, h, vd), np.float32)
    for s in range(slots):
        n = int(ntoks[s])
        at = np.arange(max(0, n - window + 1), n + 1)
        blk = np.asarray(tables)[s, (at // bl) % ring]
        for head in range(h):
            sc = k[blk, head // groups, at % bl] @ q[s, head] / np.sqrt(d)
            w = np.exp(sc - sc.max())
            out[s, head] = (w / w.sum()) @ v[blk, head // groups, at % bl]
    return out


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 5e-6),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("cell", list(RINGS))
def test_windowed_walk_reads_the_ring_and_nothing_outside_the_window(
        cell, dtype, tol):
    hkv, groups, d, bl, window, sides, vd = RINGS[cell]
    lengths = _ring_lengths(window, bl)
    q, clean, poisoned, tables, ntoks = _ring_case(
        lengths, hkv, groups, d, bl, window, sides, dtype, seed=len(cell))
    kw = dict(window=window, value_dim=vd if sides == 1 else None)
    if cell == "tiny":
        def kernel(q, pool, tables, ntoks):
            return singa_paged_decode(
                q, pool, tables, ntoks, interpret=True, chunk=2 * bl,
                group=2, scale=1.0 / np.sqrt(d), **kw)
    else:
        kernel = functools.partial(paged_decode_attention, **kw)
    want = np.asarray(paged_attention_reference(
        q, clean, tables, ntoks, **kw), np.float32)
    if dtype == jnp.float32:
        # the gather itself against a position-by-position reading
        oracle = _window_oracle(q, clean, sides, tables, ntoks, window, bl,
                                vd)
        assert np.max(np.abs(want - oracle)) <= tol
    got = np.asarray(kernel(q, poisoned, tables, ntoks), np.float32)
    assert np.isfinite(got).all(), "the kernel read outside a window"
    assert np.max(np.abs(got - want)) <= tol
    same = np.asarray(paged_attention_reference(
        q, poisoned, tables, ntoks, **kw), np.float32)
    assert np.array_equal(same, want)


# -- a block's two halves -----------------------------------------------------

# chunks of 2 blocks of 4 positions: a slot's newest token around the
# edges of the first and second chunk, a table's (or a window's) whole
# width, no token
EDGES = [0, 2 * BL - 2, 2 * BL - 1, 2 * BL, 4 * BL - 1, 4 * BL, FULL]
HALVES_WINDOW = 3 * BL + 2


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("window", [0, HALVES_WINDOW], ids=["table", "ring"])
def test_each_half_of_a_block_is_read_as_what_it_is(window, dtype, tol):
    """The halves of the one pool poisoned DIFFERENTLY: the value half
    inf at every position a slot does not attend (beyond its horizon,
    before its window), the key half nan in every block the walk must
    not read and LARGE but finite in the rows of a walked block that
    the mask hides.  A kernel that took a value for a key, a key for a
    value, or waited for one half's bytes only, reads one of them."""
    rng = np.random.default_rng(11)
    slots = len(EDGES)
    width = ring_blocks(window, BL) if window else T
    nb = slots * width + 1
    q = rng.standard_normal((slots, HKV * 2, D)).astype(np.float32)
    k, v = (rng.standard_normal((nb, HKV, BL, D)).astype(np.float32)
            for _ in range(2))
    tables = (1 + rng.permutation(nb - 1)).reshape(slots, width).astype(
        np.int32)
    seen = np.zeros((nb, BL), bool)
    for s, n in enumerate(EDGES):
        at = np.arange(max(0, n - window + 1) if window else 0, n + 1)
        seen[tables[s, (at // BL) % width], at % BL] = True
    walked = seen.any(axis=1)[:, None, None, None]
    hide = ~seen[:, None, :, None]
    clean = np.concatenate([np.where(hide, 0.0, k), np.where(hide, 0.0, v)], 1)
    poisoned = np.concatenate(
        [np.where(walked, np.where(hide, 1e4, k), np.nan),
         np.where(hide, np.inf, v)], 1)
    to = lambda a: jnp.asarray(a, dtype)                      # noqa: E731
    args = jnp.asarray(tables), jnp.asarray(EDGES, jnp.int32)
    got = np.asarray(singa_paged_decode(
        to(q), to(poisoned), *args, interpret=True, chunk=2 * BL, group=2,
        scale=1.0 / np.sqrt(D), window=window), np.float32)
    want = np.asarray(paged_attention_reference(
        to(q), to(clean), *args, window=window), np.float32)
    assert np.isfinite(got).all()
    assert np.max(np.abs(got - want)) <= tol


# -- the writers of the one pool against K and V written apart ----------------

def _attention_layer(window=0):
    from singa_tpu.config.schema import AttentionConfig
    from singa_tpu.core.seq_layers import AttentionLayer
    layer = AttentionLayer(LayerConfig(
        name="attn", type="kAttention", attention_param=AttentionConfig(
            num_heads=2 * HKV, num_kv_heads=HKV, head_dim=D, window=window)))
    layer.setup([(1, 1, 2 * HKV * D)])
    return layer


def _cca_layer():
    from singa_tpu.config.schema import CCAConfig
    from singa_tpu.core.hybrid_layers import CCALayer
    layer = CCALayer(LayerConfig(name="cca", type="kCCA", cca_param=CCAConfig(
        num_heads=2 * HKV, num_kv_heads=HKV, head_dim=D)))
    layer.setup([(1, 1, 2 * HKV * D)])
    return layer


def _apart(pool, hkv=HKV):
    """The K and the V pool a block's two halves would be."""
    pool = np.asarray(pool)
    assert pool.shape[1] == 2 * hkv
    return pool[:, :hkv], pool[:, hkv:]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_write_token_patches_one_block_with_both_rows(dtype):
    """`write_token` over the one pool with a token's key heads and
    then its value heads is the K pool and the V pool each written
    with its own rows; slots that share the null block aside, no other
    row of the pool changes."""
    from singa_tpu.core.seq_layers import paged_rows, write_token
    rng = np.random.default_rng(3)
    nb, slots = 9, 4
    pool = jnp.asarray(rng.standard_normal((nb, 2 * HKV, BL, D)), dtype)
    k_new, v_new = (jnp.asarray(rng.standard_normal((slots, HKV, D)), dtype)
                    for _ in range(2))
    bidx = jnp.asarray([3, 7, 1, 5], jnp.int32)
    off = jnp.asarray([0, BL - 1, 2, 1], jnp.int32)
    new = paged_rows(k_new[:, :, None], v_new[:, :, None])[:, :, 0]
    got_k, got_v = _apart(write_token(pool, bidx, off, new))
    want_k, want_v = _apart(pool)
    want_k, want_v = want_k.copy(), want_v.copy()
    want_k[np.asarray(bidx), :, np.asarray(off)] = np.asarray(k_new)
    want_v[np.asarray(bidx), :, np.asarray(off)] = np.asarray(v_new)
    assert np.array_equal(got_k, want_k) and np.array_equal(got_v, want_v)


def _prefill_apart(cache, nb, table_row):
    """The parent's two scatters: each side's rows cut into blocks and
    set at `table_row`, in a pool of its own."""
    out = []
    for side in ("k", "v"):
        rows = np.asarray(cache[side])[0]               # (Hkv, P, D)
        hkv, p, d = rows.shape
        blocks = rows.reshape(hkv, p // BL, BL, d).transpose(1, 0, 2, 3)
        pool = np.zeros((nb, hkv, BL, d), rows.dtype)
        for block, at in zip(blocks, np.asarray(table_row)):
            pool[at] = block              # in order: the last write wins
        out.append(pool)
    return out


@pytest.mark.parametrize("kind", ["table", "ring", "cca"])
def test_scatter_prefill_writes_a_prompts_blocks_with_both_halves(kind):
    """The three `scatter_prefill`s (kAttention under a table, under a
    ring, kCCA) against K and V scattered apart: the pool's key half is
    the K pool, its value half the V pool, block for block."""
    rng = np.random.default_rng(5)
    layer = {"table": _attention_layer, "cca": _cca_layer,
             "ring": lambda: _attention_layer(window=2 * BL + 1)}[kind]()
    slots, nb, plen = 3, 12, 6 * BL
    pool = layer.init_pool(slots, nb, BL, jnp.float32)
    cache = layer.init_cache(1, plen, jnp.float32)
    for side in ("k", "v"):
        cache[side] = jnp.asarray(
            rng.standard_normal(cache[side].shape), jnp.float32)
    row = jnp.asarray([4, 9, 2, 11, 0, 0], jnp.int32)    # 4 real blocks
    slot = 1
    if kind == "ring":
        # 14 real rows: blocks 0..3, of which the ring keeps the last
        # `ring_blocks` = 3, each in its column; the rest go to null
        cache["rows"] = jnp.asarray(3 * BL + 2, jnp.int32)
        ring = ring_blocks(layer.window, BL)
        assert ring == 3
        want_row = [0, 1 + slot * ring + 1, 1 + slot * ring + 2,
                    1 + slot * ring + 0, 0, 0]
    else:
        want_row = row
    out = layer.scatter_prefill(pool, cache, row, slot)
    assert set(out) == set(pool)
    got_k, got_v = _apart(out["kv"])
    want_k, want_v = _prefill_apart(cache, out["kv"].shape[0], want_row)
    # the null block takes whatever pad blocks were sent there
    assert np.array_equal(got_k[1:], want_k[1:])
    assert np.array_equal(got_v[1:], want_v[1:])
    assert not np.array_equal(got_k[1:], got_v[1:])


def test_a_ring_narrower_than_its_window_is_refused():
    q, clean, _, tables, ntoks = _ring_case(
        [5, 40], 2, 2, 8, 4, 10, 2, jnp.float32, seed=1)
    with pytest.raises(ValueError, match="the ring has 3"):
        paged_decode_attention(q, clean, tables[:, :3], ntoks, window=10)


def test_an_unwindowed_call_lowers_without_a_trace_of_the_window():
    """The window is a static argument: a call without one traces to
    the kernel it traced to before there was one (no modulo, no lower
    mask), so the cells that have no window do not pay for it."""
    q, clean, _, tables, ntoks = _case(LENGTHS["mixed"], 4, jnp.float32, 3)
    plain = str(jax.make_jaxpr(paged_decode_attention)(
        q, clean, tables, ntoks))
    ring = _ring_case([5, 40], HKV, 4, D, BL, 10, 2, jnp.float32, seed=1)
    windowed = str(jax.make_jaxpr(functools.partial(
        paged_decode_attention, window=10))(
            ring[0], ring[1], ring[3], ring[4]))
    # the lower mask's compares and the ring's column are the windowed
    # call's alone
    assert plain.count(" gt ") < windowed.count(" gt ")
    assert plain.count("remainder") < windowed.count("remainder")
    again = str(jax.make_jaxpr(functools.partial(
        paged_decode_attention, window=0))(q, clean, tables, ntoks))
    assert again == plain


# (heads, block_len, D) of a pool, the value's columns where its rows
# hold both sides -> positions a chunk: the four serving cells' pools
# (Mistral's, Trinity's and ZAYA's keys and values, 2 x Hkv heads a
# block; Kimi's latent rows), and the float32 pools `chip_smoke.py`'s
# serve leg and the f32 tests run.  A chunk is a number of bytes of KEY
# rows, so it follows one side's row and is what it was while the
# values lay in a pool of their own; at the dense cells' shape it has
# to stay 256 (512 there read 25 % slower at chat's short rows:
# PERF.md 6, PR 30).
CHUNKS = {
    "dense": ((16, 16, 128), None, {"bfloat16": 256, "float32": 128}),
    "trinity": ((8, 16, 128), None, {"bfloat16": 512, "float32": 256}),
    "narrow": ((4, 16, 128), None, {"bfloat16": 1024, "float32": 512}),
    "latent": ((1, 16, 640), 512, {"bfloat16": 512, "float32": 256}),
}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("cell", list(CHUNKS))
def test_chunk_follows_the_pools_row_bytes(cell, dtype):
    shape, value_dim, want = CHUNKS[cell]
    assert chunk_positions((4097,) + shape, jnp.dtype(dtype),
                           value_dim) == want[dtype]


def _copies(jaxpr, found=None):
    """Every `dma_start` of a jaxpr and of the jaxprs inside it."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dma_start":
            found.append(eqn)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _copies(sub, found)
    return found


@pytest.mark.parametrize("window", [0, 10], ids=["table", "ring"])
@pytest.mark.parametrize("sides", [2, 1], ids=["keys_and_values", "latent"])
@pytest.mark.parametrize("group", [1, 4])
def test_the_kernel_starts_one_copy_a_block(group, sides, window):
    """`start` issues `group` blocks a trip in straight-line code and
    the rest one a trip, and is traced at three places (slot 0's first
    chunk, the next chunk under this one, the next slot's first): with
    one copy a block that is 3 x (group + 1) `dma_start`s in the
    kernel's jaxpr (group 1: 3), each of a whole block of the pool,
    key heads and value heads together.  Two pools traced twice as
    many, each of half a block."""
    case = (_ring_case([5, 40], HKV, 2, D, BL, window, sides, jnp.float32, 1)
            if window else _case(LENGTHS["mixed"], 2, jnp.float32, 1,
                                 sides=sides))
    q, pool, _, tables, ntoks = case
    jaxpr = jax.make_jaxpr(functools.partial(
        singa_paged_decode, interpret=True, chunk=2 * BL, group=group,
        scale=0.3, value_dim=D if sides == 1 else None, window=window))(
            q, pool, tables, ntoks)
    starts = _copies(jaxpr.jaxpr)
    assert len(starts) == 3 * (group + (group > 1))
    for eqn in starts:               # each from the one pool in HBM
        src = eqn.invars[0].aval
        assert src.shape == pool.shape and src.dtype == pool.dtype


@pytest.mark.parametrize("sides", [2, 1], ids=["keys_and_values", "latent"])
def test_waits_cover_the_copies_under_the_dma_model(sides):
    """One wait stands for a whole chunk's copies, and a few for the
    live blocks of a slot's last chunk.  Under JAX's model of the TPU's
    DMA engine (a semaphore counts bytes; a copy is carried out no
    sooner than a wait asks for its bytes) a wait that covered fewer
    bytes than were copied would leave rows of the buffer unwritten
    (nan), and one that covered more would never return."""
    from jax.experimental.pallas import tpu as pltpu

    # chunks of 3 blocks, copies issued two blocks at a time: slots of
    # one and of two chunks, a last chunk of one, two and three live
    # blocks, an inactive slot
    lengths = [1, 2 * BL - 1, 3 * BL - 1, 3 * BL, 5 * BL + 1, FULL, None]
    q, clean, poisoned, tables, ntoks = _case(lengths, 4, jnp.float32, 5,
                                              sides=sides)
    value_dim = D if sides == 1 else None
    got = singa_paged_decode(
        q, poisoned, tables, ntoks, chunk=3 * BL, group=2, scale=0.3,
        value_dim=value_dim, interpret=pltpu.InterpretParams(
            dma_execution_mode="on_wait", detect_races=True))
    want = paged_attention_reference(q, clean, tables, ntoks, scale=0.3,
                                     value_dim=value_dim)
    assert np.max(np.abs(np.asarray(got) - np.asarray(want))) <= 2e-6


# -- extents: several consecutive blocks a copy --------------------------------

# The four multi-head geometries of tools/paged_kernel_bench.py, as
# (heads a block, block_len, D) and the table's width: an extent is one
# block wherever a block holds several heads
MANY_HEADS = {"dense": ((16, 16, 128), 80), "cca": ((4, 16, 128), 256),
              "table": ((8, 16, 128), 512), "ring": ((8, 16, 128), 129)}


@pytest.mark.parametrize("cell", list(MANY_HEADS))
def test_an_extent_is_one_block_wherever_a_block_holds_several_heads(cell):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "paged_kernel_bench", "tools/paged_kernel_bench.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    g = bench.GEOMETRIES[cell]
    shape, width = MANY_HEADS[cell]
    assert (g.sides * g.hkv, g.bl, g.d) == shape and g.table == width
    for dtype in (jnp.bfloat16, jnp.float32):
        assert extent_blocks((4097,) + shape, dtype, None, width) == 1
    # and whatever is said of its values: several heads are no one slab
    assert extent_blocks((4097,) + shape, jnp.bfloat16, 128, width) == 1


@pytest.mark.parametrize("width,dtype,want", [
    (448, "bfloat16", 8), (128, "bfloat16", 8), (448, "float32", 4),
    (4, "bfloat16", 4), (12, "bfloat16", 4), (7, "bfloat16", 1)],
    ids=["pangu", "kimi", "f32", "narrow_table", "table_of_12", "odd_table"])
def test_a_latent_pools_extent_follows_its_bytes_and_divides_what_it_must(
        width, dtype, want):
    """8 blocks of (1, 16, 640) bf16 are the 160 KB a copy is aimed at;
    a float32 block is twice the bytes; an extent divides the table row
    and a chunk's blocks, or the walk would copy past either."""
    assert extent_blocks((4097, 1, 16, 640), jnp.dtype(dtype), 512,
                         width) == want


def _extent_kernel(extent, chunk, rows=1, group=1, interpret=True):
    def kernel(q, pool, tables, ntoks):
        return singa_paged_decode(
            q, pool, tables, ntoks, interpret=interpret, chunk=chunk,
            group=group, scale=0.3, value_dim=pool.shape[-1] // 2,
            rows=rows, extent=extent)
    return kernel


@pytest.mark.parametrize("rows", [1, 2], ids=["one_row", "two_rows"])
@pytest.mark.parametrize("shape", ["tiny", "one_extent_wide", "lane_true"])
def test_extents_are_copied_whole_and_masked_past_the_horizon(shape, rows):
    """A latent pool whose table comes in aligned extents, against the
    gather: a horizon at EVERY position of the table (so at every
    position of an extent, on and around the edges of blocks, extents
    and chunks; with two rows the pair straddles each of them), a slot
    whose row is all null blocks, a table one extent wide.  The extent
    that holds a horizon is copied whole: what lies past the horizon in
    it is poisoned here as everything else unseen."""
    bl, d, t, extent, chunk, heads, dtype, tol = {
        # chunks of two extents of two blocks, three chunks a row
        "tiny": (4, 16, 12, 2, 4 * 4, 3, jnp.float32, 2e-6),
        # the whole row one copy, and one chunk
        "one_extent_wide": (4, 16, 4, 4, 4 * 4, 3, jnp.float32, 2e-6),
        # the serving cells' latent block, (1, 16, 640) bf16: 8 a copy,
        # 32 a chunk, a row of two chunks
        "lane_true": (16, 640, 64, 8, 512, 2, jnp.bfloat16, 2e-2),
    }[shape]
    last = t * bl - rows
    lengths = (list(range(last + 1)) if shape != "lane_true" else
               [0, 15, 16, 127, 128, 129, 511, 512, 513, 640, last])
    lengths.append(None)
    q, clean, poisoned, tables, ntoks = _case(
        lengths, heads, dtype, seed=rows, hkv=1, d=d, sides=1, bl=bl, t=t,
        extent=extent, rows=rows)
    kernel = _extent_kernel(extent, chunk, rows, group=2)
    kw = dict(scale=0.3, value_dim=d // 2, rows=rows)
    want = np.asarray(paged_attention_reference(
        q, clean, tables, ntoks, **kw), np.float32)
    got = np.asarray(kernel(q, poisoned, tables, ntoks), np.float32)
    assert np.isfinite(got).all(), "the kernel attended past a horizon"
    assert np.max(np.abs(got - want)) <= tol
    # a block a copy reads the same table to the same sums: the same
    # positions meet in the same chunks
    blockwise = np.asarray(_extent_kernel(1, chunk, rows, group=2)(
        q, poisoned, tables, ntoks), np.float32)
    assert np.array_equal(got, blockwise)


def test_an_extents_waits_cover_its_copies_under_the_dma_model():
    """As `test_waits_cover_the_copies_under_the_dma_model`, a copy an
    extent of two blocks: chunks of two extents, a last chunk of one
    and of two live extents, the second one's horizon in its first and
    in its second block, an inactive slot."""
    from jax.experimental.pallas import tpu as pltpu

    t, extent = 8, 2
    lengths = [1, BL, 2 * BL - 1, 2 * BL, 3 * BL, 4 * BL - 1, 4 * BL,
               6 * BL + 1, t * BL - 1, None]
    q, clean, poisoned, tables, ntoks = _case(
        lengths, 3, jnp.float32, 5, hkv=1, sides=1, t=t, extent=extent)
    got = _extent_kernel(extent, 4 * BL, interpret=pltpu.InterpretParams(
        dma_execution_mode="on_wait", detect_races=True))(
            q, poisoned, tables, ntoks)
    want = paged_attention_reference(q, clean, tables, ntoks, scale=0.3,
                                     value_dim=D // 2)
    assert np.max(np.abs(np.asarray(got) - np.asarray(want))) <= 2e-6


def test_one_copy_an_extent_and_a_multi_head_call_as_it_was():
    """The kernel's jaxpr at an extent of E holds the `dma_start`s it
    held (three places that start a chunk's copies), each of E blocks'
    rows of the one-head pool; a multi-head call through
    `paged_decode_attention` traces what an explicit extent of 1
    traces, copy for copy and equation for equation."""
    t, extent = 8, 4
    q, pool, _, tables, ntoks = _case(
        [3, 2 * BL, t * BL - 1], 3, jnp.float32, 2, hkv=1, sides=1, t=t,
        extent=extent)
    for e, rows_a_copy in ((extent, extent * BL), (1, None)):
        starts = _copies(jax.make_jaxpr(_extent_kernel(e, 4 * BL))(
            q, pool, tables, ntoks).jaxpr)
        assert len(starts) == 3
        for eqn in starts:
            src, dst = eqn.invars[0].aval, eqn.invars[2].aval
            if rows_a_copy is None:      # a block of the pool as it is
                assert src.shape == pool.shape
            else:                        # a run of rows of its one head
                assert src.shape == (1, pool.shape[0] * BL, D)
            assert dst.shape == (2, 1, 4 * BL, D)
    # through the rule: a latent call copies extents, a table of 8
    # blocks of 4 x 8 float32 whole
    ruled = _copies(jax.make_jaxpr(functools.partial(
        paged_decode_attention, scale=0.3, value_dim=D // 2))(
            q, pool, tables, ntoks).jaxpr)
    assert len(ruled) == 3
    assert ruled[0].invars[0].aval.shape == (1, pool.shape[0] * BL, D)
    many = _case(LENGTHS["mixed"], 2, jnp.float32, 1)
    q, pool, _, tables, ntoks = many
    through = jax.make_jaxpr(paged_decode_attention)(q, pool, tables, ntoks)
    explicit = jax.make_jaxpr(functools.partial(
        singa_paged_decode, interpret=True, group=1, scale=1 / np.sqrt(D),
        chunk=chunk_positions(pool.shape, pool.dtype)))(
            q, pool, tables, ntoks)
    assert len(_copies(through.jaxpr)) == len(_copies(explicit.jaxpr)) == 3
    assert str(through) == str(explicit)


@pytest.mark.parametrize("bad,match", [
    ({"extent": 4}, "one-head pool"),             # keys and values apart
    ({"extent": 4, "sides": 1, "t": 6}, "divides neither"),
    ({"extent": 8, "sides": 1, "t": 8, "slots": 0}, "holds no extent")],
    ids=["many_heads", "a_table_it_does_not_divide", "a_pool_too_small"])
def test_an_extent_the_pool_or_the_table_cannot_hold_is_refused(bad, match):
    sides, t = bad.get("sides", 2), bad.get("t", 8)
    q, pool, _, tables, ntoks = _case(
        [3, 9], 2, jnp.float32, 0, sides=sides, t=t,
        **({"hkv": 1} if sides == 1 else {}))
    if "slots" in bad:
        pool = pool[:4]
    with pytest.raises(ValueError, match=match):
        singa_paged_decode(
            q, pool, tables, ntoks, interpret=True, chunk=8 * BL, scale=0.3,
            value_dim=D if sides == 1 else None, extent=bad["extent"])


@pytest.mark.parametrize("bad", [{"value_dim": 0}, {"value_dim": D + 1},
                                 {"heads": 3}],
                         ids=["none", "wider_than_a_row",
                              "of_no_heads_behind_the_keys"])
def test_kernel_refuses_values_that_are_no_part_of_a_row(bad):
    """A latent pool's value is some leading columns of its rows; a
    pool without `value_dim` holds as many value heads as key heads."""
    q, clean, _, tables, ntoks = _case(LENGTHS["mixed"], 1, jnp.float32, 0)
    pool = clean[:, :bad.pop("heads", HKV)]
    with pytest.raises(ValueError, match="values of|no value heads"):
        paged_decode_attention(q, pool, tables, ntoks, **bad)


def test_kernel_refuses_a_shape_it_cannot_tile_on_the_chip(monkeypatch):
    from singa_tpu.ops import attention
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    q, clean, _, tables, ntoks = _case(LENGTHS["mixed"], 1, jnp.float32, 0)
    with pytest.raises(ValueError, match=r"\(25, 4, 4, 8\)"):
        paged_decode_attention(q, clean, tables, ntoks)


# -- the compiled decode program ---------------------------------------------

VOCAB, SEQ = 64, 16
LM_HEADS, LM_KV_HEADS, LM_HEAD_DIM = 4, 2, 8


def _engine(**spec):
    cfg = transformer_lm(vocab_size=VOCAB, num_layers=2, embed_dim=32,
                         num_heads=LM_HEADS, num_kv_heads=LM_KV_HEADS,
                         head_dim=LM_HEAD_DIM,
                         seq_len=SEQ, batchsize=2)
    net = build_net(cfg, "kTest",
                    {"data": {"input": (SEQ,), "target": (SEQ,)}})
    params = net.init_params(jax.random.PRNGKey(0))
    spec = ServeSpec(buckets=((2, SEQ),), temperature=0.0,
                     request_timeout_s=30.0, cb="on", cb_block_len=4,
                     **spec)
    return InferenceEngine(net, spec, params=params, log_fn=lambda s: None)


def test_decode_program_never_materialises_the_gathered_tables():
    """What `_attn_paged` used to build per layer, (S, T, 2 Hkv, bl, D)
    and its (S, 2 Hkv, T*bl, D) transpose, is in no value of the
    lowered cb decode program; the reference's lowering, the same
    search's control, has both."""
    engine = _engine(max_new_tokens=8, cb_slots=3)
    spec = engine.spec
    s, t, bl = spec.cb_slots, spec.cb_blocks_per_slot, spec.cb_block_len
    heads, d = 2 * LM_KV_HEADS, LM_HEAD_DIM
    gathered = (f"tensor<{s}x{t}x{heads}x{bl}x{d}x",
                f"tensor<{s}x{heads}x{t * bl}x{d}x")
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.int32)  # noqa: E731
    # one pool a layer goes in (and, donated, comes out): its keys and
    # values together
    for entry in engine._pools_spec().values():
        assert set(entry) == {"kv"}
        assert entry["kv"].shape == (spec.cb_pool_blocks, heads, bl, d)
    text = jax.jit(engine._build_cb_decode(), donate_argnums=(1,)).lower(
        engine.params, engine._pools_spec(), shape(s), shape(s),
        shape(s, t), jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text()
    assert "while" in text                     # the interpreted kernel
    for needle in gathered:
        assert needle not in text, needle
    pool = jax.ShapeDtypeStruct((spec.cb_pool_blocks, heads, bl, d),
                                jnp.float32)
    control = jax.jit(paged_attention_reference).lower(
        jax.ShapeDtypeStruct((s, LM_HEADS, d), jnp.float32), pool,
        shape(s, t), shape(s)).as_text()
    for needle in gathered:
        assert needle in control, needle


# -- the counter --------------------------------------------------------------

def test_cb_live_block_share_counts_what_the_kernel_walks():
    from singa_tpu.obs.metrics import MetricsRegistry

    engine = _engine(max_new_tokens=8, cb_slots=2)
    spec = engine.spec
    bl, slots, width = spec.cb_block_len, spec.cb_slots, \
        spec.cb_blocks_per_slot
    walked = steps = 0
    with InferenceServer(engine, http=False,
                         log_fn=lambda s: None) as server:
        assert server.snapshot()["cb_live_block_share"] is None
        # one request in flight at a time: prefill emits the first
        # token, then max_new - 1 decode steps see ntoks = plen,
        # plen + 1, ...; the other slot idles on the null block
        for plen, new in ((5, 6), (11, 8), (4, 2)):
            out = server.generate(np.arange(1, plen + 1, dtype=np.int32),
                                  max_new=new)
            assert len(out["tokens"]) == new
            for n in range(plen, plen + new - 1):
                walked += n // bl + 1 + (slots - 1)
                steps += 1
        # a request's last token is out before its step is counted
        deadline = time.monotonic() + 10
        while (engine.stats.cb_decode_steps < steps
               and time.monotonic() < deadline):
            time.sleep(0.01)
        snap = server.snapshot()
        reg = MetricsRegistry()
        engine.stats.register_into(reg)
        text = reg.render_prometheus()
    assert engine.stats.cb_decode_steps == steps
    assert engine.stats.cb_live_block_steps == walked
    # a block a copy: keys and values hold several heads a block
    assert engine.stats.cb_block_copies == walked
    assert snap["cb_extent_blocks"] == 1
    assert "singa_serve_cb_extent_blocks 1" in text
    assert f"singa_serve_cb_block_copies_total {walked}" in text
    want = walked / (steps * slots * width)
    assert snap["cb_live_block_share"] == round(want, 4)
    assert f"singa_serve_cb_live_block_share {round(want, 4)}" in text
