"""The serving cache: every layer's decode state behind one allocator,
with host-side block tables and refcounts.

A layer that keeps state between decode steps declares it
(`layer.init_pool`, the decode-state protocol of `models/generate.py`)
in one of three kinds, or in two at once (a `kCCA` layer: K and V rows
per token AND its convolutions' tails and shifted value half per slot,
in ONE entry), and this manager holds them all, allocates them
together, counts each part where it belongs (`state_bytes`) and hands
the prefill program the table row and the slot behind it:

  * rows per token in PAGED BLOCKS THAT GROW — K and V of a `kAttention`
    layer without a window in ONE pool, (num_blocks, 2 * Hkv, block_len,
    D), a block's key heads and then its value heads, so that the paged
    kernel brings a block in one copy (`block_copy` of `state_bytes`);
    the latent row of a `kMLA` layer, (num_blocks, block_len, rank +
    rope).  A slot holds an ordered list of block indices (its *block
    table* row), one table for all layers of this kind, and retiring a
    slot returns its blocks to the free list immediately.  Slot memory
    is O(active tokens), where the static bucket path allocates each
    batch a contiguous cache at max_len (reads stay at Hkv width
    exactly like `AttentionLayer.apply_cached`).  The free list deals
    in EXTENTS of `extent_blocks` consecutive blocks, the first at 1 +
    k E: columns k E .. k E + E - 1 of a row's real part are
    consecutive pool blocks, so the paged kernel brings an extent of a
    one-head latent pool in ONE copy (`ops.paged_attention.
    extent_blocks`, the one rule both sides read: 8 at a latent row of
    640 bf16, 1 wherever a block holds several heads, and then this
    class does block for block what it did before there were extents).
    A row still lists EVERY block: the writes, the prefill's scatter
    and the reference address blocks as they did.
  * rows per token in a RING OF BLOCKS PER SLOT — K and V of a
    `kAttention` layer with a window W: it needs a slot's last W
    positions and no more, so slot s owns `ring_blocks` = W / block_len
    + 1 blocks of that layer's pool for good (blocks 1 + s R ..
    (s + 1) R of (num_slots R + 1, 2 * Hkv, block_len, D)) and position p
    lives in ring column (p // block_len) % R.  A column is used again
    every R blocks; nothing is allocated at admission, nothing freed
    at retirement, and the table of growing blocks plays no part.  A
    context of any length costs such a layer W + block_len positions.
  * one FIXED STATE PER SLOT — the (H, Dk, Dv) float32 state and the
    conv tail of a `kKDA` layer, (num_slots, ...).  It does not grow:
    admission overwrites the slot's state whole (the prefill program is
    told the slot behind the table row, `prefill_target`), a decode step steps it in place,
    retiring needs nothing.

Split of responsibilities:

  * device side (jnp arrays in `pools`) — written/read only by the
    engine's two compiled cb programs (`models.generate.forward_paged`
    / `scatter_prefill`).  Block 0 is a reserved NULL block: inactive
    slots and table-tail entries point at it, so masked writes/reads
    land somewhere harmless and the compiled geometry never needs a
    "no block" special case.
  * host side (this class) — free list, per-block refcounts and the
    (num_slots, max_blocks_per_slot) int32 block table.  All
    bookkeeping is plain numpy under the scheduler's single thread; no
    jax dispatch happens here.

Growing blocks are reserved *conservatively at admission*: the
scheduler asks for ceil((plen + max_new) / block_len) blocks up front,
so pool exhaustion can only ever surface as an admission decision
(queue, then shed) — never as a mid-decode OOM or a deadlock between
half-admitted requests.  The reservation is of the growing kind alone:
a ring is the slot's already, whatever the request's length.  That a
request's whole life is reserved at once is what makes extents free:
`blocks_for` rounds the reservation up to whole extents (at most E - 1
blocks more), the auto pool holds every slot's worst-case row, itself
whole extents, and nothing ever grows a row block by block, so the
free list never has to find a run among scraps.

Where the model drafts (a verify-and-draft step writes TWO rows a slot,
positions n and n + 1, and the slot then advances by one or two:
serve/engine.py) the same reservation holds: a live request has produced
at most max_new - 1 tokens before a step, so its rows end at plen +
max_new - 1, inside its blocks, and they are there before every step in
flight because they were there at admission.  A rejected draft's row
(n + 1) stays where it was written: the next step starts at n + 1 and
writes over it before anything attends it.  The one step too many of a
slot that has just retired may write past its rows: into the null block
behind its table's real entries or, where the table row is full, into
its own last block, which only an admission's prefill or decode steps
will write again before they read it.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax.numpy as jnp
import numpy as np

Pools = Dict[str, Dict[str, jnp.ndarray]]   # layer -> its serving state

NULL_BLOCK = 0


def _stateful(net):
    """(name, layer) of every layer that keeps serving state
    (`models/generate.py`, the decode-state protocol)."""
    return [(n, net.layers[n]) for n in net.topo
            if hasattr(net.layers[n], "init_pool")]


def init_pools(net, num_blocks: int, block_len: int,
               dtype=jnp.float32, num_slots: int = 0) -> Pools:
    """Zeroed serving state of every layer that keeps one, each in the
    shape its kind declares (`layer.init_pool`): rows per token in
    `num_blocks` paged blocks of `block_len` (K/V of kAttention and
    kCCA, the latent of kMLA), one fixed state for each of `num_slots`
    slots (kKDA; kCCA's tails), or both in one entry.  The paged
    sibling of `generate.init_cache`."""
    return {name: layer.init_pool(num_slots, num_blocks, block_len, dtype)
            for name, layer in _stateful(net)}


def pool_bytes(net, num_blocks: int, block_len: int,
               dtype=jnp.float32, num_slots: int = 0) -> int:
    """Byte count of the pools `init_pools` would allocate, from their
    shapes alone (nothing is allocated).  MemoryWatch's HBM fallback on
    backends that expose no `memory_stats()` (the CPU test platform)
    uses this."""
    import jax
    shapes = jax.eval_shape(
        lambda: init_pools(net, num_blocks, block_len, dtype, num_slots))
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(shapes))


def window_of(net) -> int:
    """The window of the net's windowed layers (0: none has one).  One
    ring geometry a model: two windows would be two kinds more."""
    windows = {layer.window for _, layer in _stateful(net)
               if getattr(layer, "window", 0)}
    if len(windows) > 1:
        raise ValueError(f"layers with different windows "
                         f"{sorted(windows)}: one ring geometry a model")
    return windows.pop() if windows else 0


def state_bytes(net, block_len: int, dtype=jnp.float32) -> Dict[str, int]:
    """What one slot and one block cost, by kind: `slot` the bytes of
    the fixed per-slot states of all layers, `block` the bytes one more
    growing block adds over all layers that keep a table, `window_block`
    the bytes of one ring block over all windowed layers (a slot holds
    `ring_blocks` of them whatever its length).  And what ONE COPY of
    the paged kernel moves, a block of one layer's pool: `block_copy`
    under a table, `window_block_copy` in a ring (the widest layer's,
    should they differ).  Read off each layer's pool shapes at two
    sizes, so a layer whose entry holds two kinds adds to both.

    A slot IN PREFILL (`ContinuousScheduler._prefill_chunk`) holds the
    same and no more: its `slot` bytes and the blocks reserved for its
    whole prompt and answer, all through its chunks, while it takes part
    in no decode step; a chunk works straight on the pools.  (A
    whole-prompt prefill builds a contiguous cache beside them and
    scatters it: a transient of its program, counted with the program's
    temporaries and not here.)"""
    import jax
    from ..ops.paged_attention import ring_blocks

    def nbytes(a):
        return int(np.prod(a.shape)) * a.dtype.itemsize

    def arrays(layer, slots, blocks):
        return jax.tree_util.tree_leaves(jax.eval_shape(
            lambda: layer.init_pool(slots, blocks, block_len, dtype)))

    def a_block_of(small, grown):
        """Bytes of one block of the widest array that grew."""
        return max((nbytes(a) // a.shape[0] for a, b in zip(small, grown)
                    if b.shape[0] > a.shape[0]), default=0)

    out = {"slot": 0, "block": 0, "window_block": 0, "block_copy": 0,
           "window_block_copy": 0}
    for _, layer in _stateful(net):
        base, slots, blocks = (arrays(layer, 1, 1), arrays(layer, 2, 1),
                               arrays(layer, 1, 2))
        size = sum(map(nbytes, base))
        a_slot = sum(map(nbytes, slots)) - size
        window = getattr(layer, "window", 0)
        if window:
            out["window_block"] += a_slot // ring_blocks(window, block_len)
            out["window_block_copy"] = max(out["window_block_copy"],
                                           a_block_of(base, slots))
        else:
            out["slot"] += a_slot
        out["block"] += sum(map(nbytes, blocks)) - size
        out["block_copy"] = max(out["block_copy"], a_block_of(base, blocks))
    return out


def extent_of(net, block_len: int, dtype, table_width: int) -> int:
    """Consecutive blocks the free list hands out together: the largest
    extent the paged kernel copies of any of the net's pools under the
    growing table (`layer.paged_extent`, which a layer has where its
    pool's runs of blocks are runs of rows: kMLA), 1 where none does."""
    return max([layer.paged_extent(block_len, dtype, table_width)
                for _, layer in _stateful(net)
                if hasattr(layer, "paged_extent")], default=1)


def slot_behind_row(net) -> bool:
    """Whether a prefill has to be told its slot: some layer keeps a
    state or a ring per slot (its pools grow with the slots)."""
    return pool_bytes(net, 1, 1, num_slots=2) > pool_bytes(net, 1, 1,
                                                           num_slots=1)


class PagedKVCache:
    """Every layer's serving state + slot tables for one engine.  Single-owner:
    the `ContinuousScheduler` thread is the only mutator, so the
    bookkeeping needs no lock; `snapshot()` reads are approximate from
    other threads (ints are swapped atomically in CPython)."""

    def __init__(self, net, num_slots: int, max_blocks_per_slot: int,
                 num_blocks: int, block_len: int, dtype=jnp.float32):
        if num_blocks < 2:
            raise ValueError(f"num_blocks must be >= 2 (block 0 is the "
                             f"reserved null block), got {num_blocks}")
        if block_len < 1 or num_slots < 1 or max_blocks_per_slot < 1:
            raise ValueError("num_slots, max_blocks_per_slot and "
                             "block_len must all be >= 1")
        self.num_slots = int(num_slots)
        self.max_blocks_per_slot = int(max_blocks_per_slot)
        self.num_blocks = int(num_blocks)
        self.block_len = int(block_len)
        self.pools: Pools = init_pools(net, self.num_blocks,
                                       self.block_len, dtype,
                                       self.num_slots)
        # the windowed layers' ring: columns a slot, 0 where no layer
        # has a window
        from ..ops.paged_attention import ring_blocks
        self.window = window_of(net)
        self.ring_blocks = (ring_blocks(self.window, self.block_len)
                            if self.window else 0)
        # a layer with a state or a ring per slot: the prefill program
        # is told the slot behind the table row (`prefill_target`)
        self.per_slot_state = slot_behind_row(net)
        # blocks come and go in aligned runs of `extent_blocks`
        self.extent_blocks = extent_of(net, self.block_len, dtype,
                                       self.max_blocks_per_slot)
        if self.num_blocks <= self.extent_blocks:
            raise ValueError(
                f"num_blocks {self.num_blocks} holds no extent of "
                f"{self.extent_blocks} blocks behind the null block")
        # host bookkeeping: the first block of every free extent; block
        # 0 never enters the list, nor the blocks behind the last whole
        # extent
        self._free: List[int] = list(range(
            self.usable_blocks - self.extent_blocks + 1, 0,
            -self.extent_blocks))
        self._refcounts = np.zeros((self.num_blocks,), np.int32)
        self.tables = np.full((self.num_slots, self.max_blocks_per_slot),
                              NULL_BLOCK, np.int32)
        self._slot_blocks: Dict[int, List[int]] = {}

    # -- capacity -----------------------------------------------------------
    @property
    def usable_blocks(self) -> int:
        """Pool capacity in whole extents, excluding the null block."""
        e = self.extent_blocks
        return (self.num_blocks - 1) // e * e

    @property
    def free_blocks(self) -> int:
        return len(self._free) * self.extent_blocks

    @property
    def blocks_in_use(self) -> int:
        return self.usable_blocks - self.free_blocks

    def blocks_for(self, total_tokens: int) -> int:
        """Blocks a sequence of `total_tokens` (prompt + generated)
        holds — the conservative admission reservation, in whole
        extents."""
        blocks = -(-max(int(total_tokens), 1) // self.block_len)
        return self._extents(blocks) * self.extent_blocks

    def _extents(self, nblocks: int) -> int:
        """Extents that hold `nblocks` blocks."""
        return -(-int(nblocks) // self.extent_blocks)

    def can_admit(self, nblocks: int) -> bool:
        return self._extents(nblocks) <= len(self._free)

    def ring_block(self, slot: int, position: int) -> int:
        """The pool block of a windowed layer that holds `position` of
        slot `slot`: ring column (position // block_len) % ring_blocks."""
        column = (int(position) // self.block_len) % self.ring_blocks
        return 1 + int(slot) * self.ring_blocks + column

    def walked_blocks(self, ntoks: np.ndarray) -> Dict[str, int]:
        """Blocks one decode step's paged kernel walks over all slots,
        once a kind: `table` every slot's row up to its write position
        (an idle slot one block), `window` the blocks of its ring that
        the window touches (0 where no layer has a window); `copies`
        the extents of `table`, a descriptor each where the kernel
        copies an extent."""
        last = np.asarray(ntoks) // self.block_len
        table = int((last + 1).sum())
        out = {"table": table, "window": 0,
               "copies": table if self.extent_blocks == 1 else
               int((last // self.extent_blocks + 1).sum())}
        if self.window:
            first = np.maximum(np.asarray(ntoks) - (self.window - 1),
                               0) // self.block_len
            out["window"] = int((last - first + 1).sum())
        return out

    # -- slot lifecycle -----------------------------------------------------
    def alloc(self, slot: int, nblocks: int) -> np.ndarray:
        """Reserve `nblocks` blocks for `slot`, in whole extents
        (refcount 1 each), and return the slot's full table row (real
        blocks first, an extent's one after another, null padding
        after).  Raises RuntimeError when the pool cannot cover the
        reservation — the scheduler checks `can_admit` first, so
        reaching the raise is a bug, not backpressure."""
        if slot in self._slot_blocks:
            raise RuntimeError(f"slot {slot} already holds blocks")
        if nblocks > self.max_blocks_per_slot:
            raise ValueError(
                f"request needs {nblocks} blocks but a slot holds at "
                f"most {self.max_blocks_per_slot}")
        if not self.can_admit(nblocks):
            raise RuntimeError(
                f"block pool exhausted: need {nblocks}, "
                f"{self.free_blocks} free")
        firsts = [self._free.pop() for _ in range(self._extents(nblocks))]
        blocks = [first + i for first in firsts
                  for i in range(self.extent_blocks)]
        self._refcounts[blocks] += 1
        self.tables[slot] = NULL_BLOCK
        self.tables[slot, :len(blocks)] = blocks
        self._slot_blocks[slot] = blocks
        return self.tables[slot].copy()

    def prefill_target(self, slot: int, nblocks: int) -> np.ndarray:
        """Where a prefill of `nblocks` blocks writes slot `slot`'s
        state, as the prefill program takes it: the head of the slot's
        table row and, where some layer keeps a state per slot, the
        slot's index behind it."""
        row = self.tables[slot, :nblocks]
        return np.append(row, slot).astype(np.int32) \
            if self.per_slot_state else row.copy()

    def chunk_target(self, slot: int) -> np.ndarray:
        """Where a chunk of a prompt that is prefilled in several reads
        and writes slot `slot`'s state, as the chunk program takes it:
        the slot's WHOLE table row (the chunk's own blocks and those of
        the rows before it) and the slot's index behind it."""
        return np.append(self.tables[slot], slot).astype(np.int32)

    def free(self, slot: int) -> None:
        """Retire `slot`: drop each block's refcount and return
        zero-refcount extents to the free list immediately."""
        blocks = self._slot_blocks.pop(slot, None)
        if blocks is None:
            return
        self._refcounts[blocks] -= 1
        for b in blocks[::self.extent_blocks]:
            if self._refcounts[b] == 0:
                self._free.append(b)
        self.tables[slot] = NULL_BLOCK

    def free_all(self) -> None:
        for slot in list(self._slot_blocks):
            self.free(slot)

    # -- reads --------------------------------------------------------------
    def table_array(self, hide=None) -> np.ndarray:
        """Copy of the (num_slots, max_blocks_per_slot) int32 block
        table for upload to the compiled decode program.  Slot `hide`
        (one in prefill, which takes no part in the step) shows the null
        block throughout: the step's write for an idle slot lands at
        position 0 of its row, and that slot's row holds its prompt."""
        tables = self.tables.copy()
        if hide is not None:
            tables[hide] = NULL_BLOCK
        return tables

    def utilization(self) -> float:
        return (self.blocks_in_use / self.usable_blocks
                if self.usable_blocks else 0.0)

    def snapshot(self) -> Dict[str, Any]:
        return {"num_blocks": self.num_blocks,
                "usable_blocks": self.usable_blocks,
                "free_blocks": self.free_blocks,
                "blocks_in_use": self.blocks_in_use,
                "block_len": self.block_len,
                "num_slots": self.num_slots,
                "max_blocks_per_slot": self.max_blocks_per_slot,
                "window": self.window, "ring_blocks": self.ring_blocks,
                "extent_blocks": self.extent_blocks,
                "utilization": round(self.utilization(), 4)}
