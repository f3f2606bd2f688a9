"""The paged cache's free list in extents (serve/kvcache.py): a net
whose pools hold several heads a block keeps the allocator it had,
block for block (E = 1); a net of latent pools (kMLA) is handed aligned
runs of E consecutive blocks, so that the paged kernel copies a run in
one descriptor (ops/paged_attention.py `extent_blocks`, the one rule
both sides read)."""

import hashlib
import types

import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu.config.schema import (AttentionConfig, LayerConfig,
                                     MLAConfig)
from singa_tpu.core.hybrid_layers import MLALayer
from singa_tpu.core.seq_layers import AttentionLayer
from singa_tpu.ops.paged_attention import extent_blocks
from singa_tpu.serve.kvcache import NULL_BLOCK, PagedKVCache, extent_of

pytestmark = pytest.mark.serve

BL, SLOTS, WIDTH = 4, 6, 24


def _net(kind):
    """The least a cache asks of a net: its stateful layers by name."""
    if kind == "latent":
        layer = MLALayer(LayerConfig(name="mix", type="kMLA",
                                     mla_param=MLAConfig(
            num_heads=3, kv_lora_rank=8, qk_nope_head_dim=5,
            qk_rope_head_dim=4, v_head_dim=6)))
        layer.setup([(1, 1, 24)])
    else:
        layer = AttentionLayer(LayerConfig(
            name="mix", type="kAttention", attention_param=AttentionConfig(
                num_heads=4, num_kv_heads=2, head_dim=8)))
        layer.setup([(1, 1, 32)])
    return types.SimpleNamespace(topo=["mix"], layers={"mix": layer})


# a net, the extent its pools give under a table of 24 blocks
NETS = {"heads": 1, "latent": 8}


def _cache(kind, num_blocks=SLOTS * WIDTH + 1):
    return PagedKVCache(_net(kind), SLOTS, WIDTH, num_blocks, BL)


@pytest.mark.parametrize("kind", list(NETS))
def test_the_extent_is_the_kernels_rule_over_the_nets_pools(kind):
    kv = _cache(kind)
    assert kv.extent_blocks == NETS[kind] == extent_of(
        _net(kind), BL, jnp.float32, WIDTH)
    assert kv.snapshot()["extent_blocks"] == NETS[kind]
    if kind == "latent":
        row = kv.pools["mix"]["c"].shape[-1]
        assert kv.extent_blocks == extent_blocks(
            (1, 1, BL, row), jnp.float32, 8, WIDTH)


def _churn(kv, seed, steps=2000):
    """Random admissions and retirements; yields the cache after each
    with what was asked: (slot or None, blocks asked)."""
    rng = np.random.default_rng(seed)
    for _ in range(steps):
        held = sorted(kv._slot_blocks)
        idle = sorted(set(range(kv.num_slots)) - set(held))
        if idle and (not held or rng.random() < 0.55):
            slot = int(rng.choice(idle))
            asked = kv.blocks_for(int(rng.integers(1, WIDTH * BL + 1)))
            fits = kv.can_admit(asked)
            if fits:
                kv.alloc(slot, asked)
            else:
                with pytest.raises(RuntimeError, match="exhausted"):
                    kv.alloc(slot, asked)
            yield slot if fits else None, asked
        else:
            kv.free(int(rng.choice(held)))
            yield None, 0


@pytest.mark.parametrize("num_blocks", [SLOTS * WIDTH + 1, 2 * WIDTH + 6],
                         ids=["auto", "small"])
@pytest.mark.parametrize("kind", list(NETS))
def test_random_admissions_and_retirements_keep_the_books(kind, num_blocks):
    """2,000 of them: (a) every real extent of every row consecutive
    and aligned, (b) no block in two rows, (c) free + held = usable,
    (d) `can_admit` true exactly when `alloc` succeeds (`_churn` holds
    an `alloc` that `can_admit` refused to its raise), the rounding
    included."""
    kv = _cache(kind, num_blocks)
    e = kv.extent_blocks
    assert kv.usable_blocks == (num_blocks - 1) // e * e
    admitted = refused = 0
    for slot, asked in _churn(kv, seed=e):
        admitted += slot is not None
        refused += slot is None and asked > 0
        if slot is not None:
            assert asked % e == 0
            assert np.count_nonzero(kv.tables[slot]) == asked
        real = kv.tables[kv.tables != NULL_BLOCK]
        assert len(set(real.tolist())) == real.size                 # (b)
        assert real.size == kv.blocks_in_use                        # (c)
        assert kv.free_blocks + kv.blocks_in_use == kv.usable_blocks
        assert kv.free_blocks == len(kv._free) * e
        for row in kv.tables:
            n = np.count_nonzero(row)
            assert n % e == 0 and not row[n:].any()
            runs = row[:n].reshape(-1, e)
            assert np.all((runs[:, 0] - 1) % e == 0)                # (a)
            assert np.all(runs == runs[:, :1] + np.arange(e))
            assert n == 0 or runs.max() <= kv.usable_blocks
    assert admitted > 500
    # only the small pool ever refuses: the auto pool holds every
    # slot's worst case, whole extents as it is
    assert (refused > 0) == (num_blocks < SLOTS * WIDTH + 1)


def test_a_small_pool_sheds_by_the_rounded_count():
    """30 blocks behind the null block are three extents of 8; a
    request of 9 blocks holds two of them, so a second one of 9 waits
    where 21 loose blocks would have taken it."""
    kv = _cache("latent", num_blocks=31)
    assert kv.usable_blocks == kv.free_blocks == 24
    asked = kv.blocks_for(9 * BL)
    assert asked == 16 and kv.blocks_for(8 * BL) == 8
    kv.alloc(0, asked)
    assert kv.free_blocks == 8 and kv.blocks_in_use == 16
    assert not kv.can_admit(kv.blocks_for(9 * BL))
    assert kv.can_admit(kv.blocks_for(8 * BL))
    # an unrounded count is rounded where it is held
    assert not kv.can_admit(9) and kv.can_admit(8)
    loose = _cache("heads", num_blocks=31)
    loose.alloc(0, loose.blocks_for(9 * BL))
    assert loose.blocks_in_use == 9
    assert loose.can_admit(loose.blocks_for(9 * BL))


def test_a_pool_with_no_whole_extent_is_refused():
    with pytest.raises(ValueError, match="holds no extent of 8"):
        _cache("latent", num_blocks=8)


def test_walked_blocks_counts_a_copy_an_extent():
    ntoks = np.array([0, BL - 1, BL, 8 * BL - 1, 8 * BL, 23 * BL + 1])
    for kind, e in NETS.items():
        walked = _cache(kind).walked_blocks(ntoks)
        assert walked["table"] == 1 + 1 + 2 + 8 + 9 + 24
        assert walked["copies"] == (walked["table"] if e == 1
                                    else 1 + 1 + 1 + 1 + 2 + 3)


class _ParentAllocator:
    """The free list as it was before there were extents (PR 39's
    `PagedKVCache`): single blocks popped off a stack that a retired
    slot's blocks are pushed onto in order."""

    def __init__(self, num_slots, width, num_blocks):
        self._free = list(range(num_blocks - 1, 0, -1))
        self.tables = np.zeros((num_slots, width), np.int32)
        self._held = {}

    def alloc(self, slot, n):
        blocks = [self._free.pop() for _ in range(n)]
        self.tables[slot] = NULL_BLOCK
        self.tables[slot, :n] = blocks
        self._held[slot] = blocks

    def free(self, slot):
        self._free.extend(self._held.pop(slot))
        self.tables[slot] = NULL_BLOCK


# sha256 over the int32 tables after each of `_churn(seed=1)`'s 2,000
# steps, recorded from the parent commit's `PagedKVCache` (9e3ab01)
PARENT_TABLES = (
    "0728a05854a41edce532213f5b36a704322dd4baf6a4b5491b91d91d2a4c26a6")


def test_a_net_of_many_heads_is_allocated_block_for_block_as_before():
    kv = _cache("heads")
    old = _ParentAllocator(SLOTS, WIDTH, SLOTS * WIDTH + 1)
    digest = hashlib.sha256()
    before = set()
    for slot, asked in _churn(kv, seed=1):
        if slot is not None:
            old.alloc(slot, asked)
        for gone in before - set(kv._slot_blocks):
            old.free(gone)
        before = set(kv._slot_blocks)
        assert np.array_equal(kv.tables, old.tables)
        digest.update(kv.tables.astype(np.int32).tobytes())
    assert digest.hexdigest() == PARENT_TABLES
