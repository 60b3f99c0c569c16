"""Block-allocated paged KV-cache accounting with copy-on-write sharing.

The manager half of the paged cache (the physical pool lives in
``models/llama.py`` ``init_kv_pages``): a fixed population of
``block_size``-token blocks handed out on demand, one logical page table
per live sequence. Capacity is the admission signal — a full pool QUEUES
new work (the engine keeps it waiting) instead of OOMing a growing dense
cache, and freeing on completion/cancellation returns blocks for the next
admission. Physical block 0 is reserved as the trash block padding lanes
write into, so it is never allocated.

Prefix sharing (ROADMAP item 2, PR-14): every physical block carries a
REFCOUNT, and full prompt blocks are content-hashed into a shared index.
The hash of block ``i`` chains over everything before it
(``hash(prev_hash, block_tokens)``), because a block's K/V values depend
on its entire causal prefix, not just its own tokens — two blocks are
interchangeable iff their chains match. A new sequence whose prompt
chain-matches the index *references* the existing blocks instead of
allocating and recomputing them (the engine then prefills only the
unshared suffix). Copy-on-write discipline: a shared block is never
written in place and never reclaimed while ``refcount > 1`` — writers
always target fresh blocks (:meth:`extend` never returns a shared
block), and :meth:`free` only returns a block to the pool when its LAST
reference drops, unpublishing it from the index in the same breath
(refcount==0 means reclaimed, nothing lingers).

Cache groups (``models/engine_model.py``): a model whose layers keep
their K/V in different ways has one pool, one allocator and one
page-table row a sequence PER GROUP. The full group is everything above.
A window group holds a fixed RING of blocks a sequence
(:func:`window_ring_blocks`), claimed whole at admission and returned
whole on free; logical block ``j`` lives in ring entry ``j % len(ring)``,
and :func:`window_tables` writes the row a window layer is handed: the
ring's blocks at the last ``len(ring)`` logical columns, the trash block
at every column wholly behind the window, which is therefore neither
held nor read. A state group (a linear-attention layer's recurrent
state) holds no blocks at all but ONE SLOT a sequence, whatever its
length: its allocator is a ``BlockAllocator(1 + max_active, 1)`` whose
"blocks" are slots of one, claimed at admission (``allocate(seq, 1)``),
never extended, returned on free; slot 0 is the trash slot, as block 0
is the trash block, and the group's table row holds the slot in column
0. Nothing of a slot is hashed, shared or truncated.

Runs (``BlockAllocator(run=P)``): the paged kernel fetches a tile of
``P`` table columns with one copy a pool when the tile's live columns
hold ``P`` pool pages side by side (``paged_attention.whole_tiles``), so
the allocator hands out the kernel's tile and not a block. The pool
after the trash block is cut into aligned runs of ``P`` blocks (run
``k`` is blocks ``1 + k * P .. k * P + P``; what does not fill a last
run is never handed out and :attr:`capacity` leaves it out), the free
pool is a LIFO stack of RUNS, and a sequence's own column ``j`` sits at
``run_start + j % P`` of the run it holds for the tile ``j // P``: a
prompt takes a run for each tile its fresh columns reach, growth takes
the next block of the sequence's open run and a new run only where a
column opens a tile, a rollback puts trailing blocks back into the open
run, and a run returns to the pool when the last block of it that holds
a reference drops, whoever held it. Columns matched from the index are
referenced where they lie (in their publisher's runs); where the match
ends inside a tile the fresh columns of that tile share it with them
(they sit at ``j % P`` of a run of their own, whose leading ``matched %
P`` blocks stay unused): one tile stop a sequence that is not whole.
What admission may count on is whole runs (:attr:`free_blocks`,
:meth:`demand`); :attr:`blocks_in_use` stays the blocks that hold a
reference, and what lies in held runs without one (a sequence's open
run beyond its newest block, at most ``P - 1``; a mixed tile's leading
blocks; a run whose owner ended while the index still names a block of
it) is :attr:`blocks_reserved`. ``run=1`` is the allocator block for
block as it was before runs existed, the LIFO order included. A window
group's ring is a whole number of runs (:func:`window_ring_blocks`
rounds it up), so a ring tile never wraps. Which ``P`` a group gets is
the engine's to read off its shapes (``EngineConfig.group_runs``).

Pure bookkeeping: no clocks, no jax, single-owner (the engine's step
loop) — no locks.
"""

import hashlib
from typing import Dict, Iterable, List, Sequence, Tuple

import numpy as np

from client_tpu.utils import InferenceServerException

# Reserved physical block: bucketed-batch padding lanes and padded
# prompt tails scatter their K/V here; page-table entries of 0 mean
# "unallocated" and are masked out of attention.
TRASH_BLOCK = 0

# chain seed: makes the empty-prefix digest explicit
_CHAIN_SEED = b"kv-block-chain"


def window_ring_blocks(window: int, block_size: int, run: int = 1) -> int:
    """Blocks of a window group's ring. A window of ``window`` tokens
    can touch ``ceil(W / bs) + 1`` blocks at once (a span of W slots
    starts anywhere in a block): 9 blocks of 16 for ``mimo_v2``'s window
    of 128, 129 for ``afmoe``'s of 2,048. The ring is that, rounded up
    to a whole number of the allocator's runs of ``run`` blocks (12 at
    runs of 4, 144 at runs of 16), so that column ``j`` -> ring entry
    ``j % R`` never wraps inside a tile of ``run`` columns."""
    ring = -(-int(window) // int(block_size)) + 1
    return -(-ring // int(run)) * int(run)


def window_tables(rings, last_blocks, width: int) -> np.ndarray:
    """Page-table rows ``[B, width]`` of a window group: row ``b`` holds
    ``rings[b][j % R]`` at logical column ``j`` for the ``R`` columns up
    to ``last_blocks[b]`` (the block of the newest position, which the
    step writes), and the trash block everywhere else."""
    rings = np.asarray(rings, dtype=np.int32).reshape(len(last_blocks), -1)
    held = rings.shape[1]
    columns = (np.asarray(last_blocks, dtype=np.int64)[:, None]
               - np.arange(held - 1, -1, -1)[None, :])
    lanes = np.broadcast_to(np.arange(len(rings))[:, None], columns.shape)
    live = (columns >= 0) & (columns < width)
    tables = np.zeros([len(rings), width], dtype=np.int32)
    tables[lanes[live], columns[live]] = rings[lanes[live],
                                               columns[live] % held]
    return tables


class CacheCapacityError(InferenceServerException):
    """A block demand exceeded the pool's free (or total) capacity."""

    def __init__(self, msg: str):
        super().__init__(msg, status="RESOURCE_EXHAUSTED")


class BlockAllocator:
    """Fixed-size-block pool accounting for the paged KV cache.

    ``num_blocks`` counts PHYSICAL blocks including the reserved trash
    block; :attr:`capacity` is what sequences can actually hold
    (``num_blocks - 1`` at runs of 1). Blocks are identified by pool
    index; a block may be referenced by several sequences at once
    (shared prefix), and returns to the pool only when the last
    reference is freed. ``run`` is the blocks the pool hands out at a
    time (the module docstring's runs).
    """

    def __init__(self, num_blocks: int, block_size: int, run: int = 1):
        if num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is reserved)")
        if block_size < 1:
            raise ValueError("block_size must be >= 1")
        if run < 1:
            raise ValueError("run must be >= 1")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.run = int(run)
        # LIFO free stack of runs, each by its first block: recently
        # freed ones are re-issued first (their pages are hot in cache)
        self._free: List[int] = list(
            range(1 + (self.capacity // self.run - 1) * self.run, 0, -self.run)
        )
        # first block of a held run -> blocks of it that hold a reference
        self._live: Dict[int, int] = {}
        self._owned: Dict[object, List[int]] = {}
        # the first column of a sequence that is its own (not matched)
        self._fresh_from: Dict[object, int] = {}
        self._ref: Dict[int, int] = {}  # phys -> live reference count
        self._index: Dict[bytes, int] = {}  # chain digest -> phys
        self._hash_of: Dict[int, bytes] = {}  # phys -> its published digest
        # cumulative sharing counters (the engine mirrors them to metrics)
        self.prefix_hits = 0  # blocks whose prefill was skipped
        self.prefix_queries = 0  # allocations that consulted the index

    @property
    def capacity(self) -> int:
        """Allocatable blocks: the whole runs after the trash block."""
        return (self.num_blocks - 1) // self.run * self.run

    @property
    def free_blocks(self) -> int:
        """Blocks of the runs no sequence holds: what an allocation can
        be given (:meth:`demand` is what it would take of them)."""
        return len(self._free) * self.run

    @property
    def blocks_in_use(self) -> int:
        """Distinct PHYSICAL blocks that hold a reference — sharing
        keeps this low."""
        return len(self._ref)

    @property
    def blocks_reserved(self) -> int:
        """Blocks of held runs that hold no reference: no admission can
        have them until their run's last referenced block drops."""
        return self.capacity - self.free_blocks - len(self._ref)

    @property
    def blocks_shared(self) -> int:
        """Physical blocks currently referenced by more than one
        sequence (each is at least one whole prefill-block of compute
        and memory saved)."""
        return sum(1 for count in self._ref.values() if count >= 2)

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks needed to hold ``n_tokens`` of context."""
        return (max(0, n_tokens) + self.block_size - 1) // self.block_size

    def demand(self, n_blocks: int, n_matched: int = 0) -> int:
        """Blocks of the free pool that a sequence of ``n_blocks``
        columns takes when its first ``n_matched`` are referenced from
        the index: a run for every tile its own columns reach
        (``n_blocks - n_matched`` at runs of 1)."""
        if n_blocks <= n_matched:
            return 0
        tiles = (n_blocks - 1) // self.run - n_matched // self.run + 1
        return tiles * self.run

    def refcount(self, phys: int) -> int:
        """Live references to a physical block (0 = free/unallocated)."""
        return self._ref.get(phys, 0)

    def owned(self, seq_id) -> List[int]:
        """The sequence's block list (allocation order = logical order)."""
        return self._owned.get(seq_id, [])

    def _claim(self, blocks: List[int], fresh_from: int) -> int:
        """Append a fresh block for the next column of a sequence whose
        own columns start at ``fresh_from``: inside a tile the block
        after its last, where the column opens a tile (or is its first
        own one) the block at ``column % run`` of a new run."""
        column = len(blocks)
        if column % self.run and column > fresh_from:
            block = blocks[-1] + 1
        elif self._free:
            block = self._free.pop() + column % self.run
        else:
            raise CacheCapacityError(
                f"KV cache exhausted: 0 of {self.capacity} blocks free"
            )
        self._ref[block] = 1
        start = block - (block - 1) % self.run
        self._live[start] = self._live.get(start, 0) + 1
        blocks.append(block)
        return block

    def _reclaim(self, block: int) -> None:
        """Drop the last reference to ``block``; its run goes back to
        the pool with the last block of it that held one."""
        del self._ref[block]
        start = block - (block - 1) % self.run
        self._live[start] -= 1
        if not self._live[start]:
            del self._live[start]
            self._free.append(start)

    # -- prefix hashing / matching ------------------------------------------

    def chain_hashes(self, tokens: Sequence[int]) -> List[bytes]:
        """Chained sha256 digests of every FULL block of ``tokens``
        (block ``i``'s digest covers tokens ``0 .. (i+1)*block_size``).

        Cryptographic on purpose: a collision here would silently serve
        one prompt's K/V to a DIFFERENT prompt (wrong completions +
        cross-request prompt influence), so a 64-bit ``hash()`` chain is
        not acceptable identity for content-addressed cache blocks."""
        digest = hashlib.sha256(
            _CHAIN_SEED + self.block_size.to_bytes(4, "little")
        ).digest()
        out: List[bytes] = []
        for i in range(len(tokens) // self.block_size):
            block = tokens[i * self.block_size:(i + 1) * self.block_size]
            h = hashlib.sha256(digest)
            h.update(
                b"".join(
                    int(t).to_bytes(8, "little", signed=True) for t in block
                )
            )
            digest = h.digest()
            out.append(digest)
        return out

    def match_count(self, hashes: Iterable[bytes]) -> int:
        """Longest indexed prefix (in blocks) — a side-effect-free probe
        for admission math; no references are taken."""
        n = 0
        for h in hashes:
            if h not in self._index:
                break
            n += 1
        return n

    # -- allocation ----------------------------------------------------------

    def allocate(self, seq_id, n_blocks: int) -> List[int]:
        """Claim ``n_blocks`` for a new sequence; all-or-nothing."""
        blocks, _ = self.allocate_shared(seq_id, n_blocks, ())
        return blocks

    def allocate_shared(
        self, seq_id, n_blocks: int, prefix_hashes: Sequence[bytes]
    ) -> Tuple[List[int], int]:
        """Claim ``n_blocks``, referencing indexed blocks for the longest
        matching prefix of ``prefix_hashes`` and allocating the rest
        fresh. All-or-nothing: on :class:`CacheCapacityError` no
        reference has been taken. Returns ``(blocks, n_matched)`` —
        ``blocks[:n_matched]`` are shared (read-only for this sequence),
        the rest are exclusively owned. The returned list never aliases
        the ownership record."""
        if seq_id in self._owned:
            raise CacheCapacityError(
                f"sequence {seq_id!r} already owns blocks"
            )
        matched: List[int] = []
        for h in prefix_hashes:
            if len(matched) >= n_blocks:
                break
            phys = self._index.get(h)
            if phys is None:
                break
            matched.append(phys)
        need_new = self.demand(n_blocks, len(matched))
        if need_new > self.free_blocks:
            raise CacheCapacityError(
                f"KV cache exhausted: need {need_new} blocks "
                f"({n_blocks} minus {len(matched)} shared, in runs of "
                f"{self.run}), {self.free_blocks} of {self.capacity} free"
            )
        if prefix_hashes:
            self.prefix_queries += 1
            self.prefix_hits += len(matched)
        for phys in matched:
            self._ref[phys] += 1
        blocks = list(matched)
        while len(blocks) < n_blocks:
            self._claim(blocks, len(matched))
        self._owned[seq_id] = blocks
        self._fresh_from[seq_id] = len(matched)
        # a copy: callers keep their own page-table mirror, and a caller
        # appending to the returned list must not alias the ownership
        # record (a block listed twice would be freed twice)
        return list(blocks), len(matched)

    def extend(self, seq_id) -> int:
        """Claim ONE more block for a growing sequence (decode entering a
        new block): the next of its open run, or the first of a new run
        where the column opens a tile; raises
        :class:`CacheCapacityError` when that needs a run and the pool
        is dry — the engine's preemption signal. Always a FRESH block
        with refcount 1: growth never writes into shared storage."""
        blocks = self._owned.get(seq_id)
        if blocks is None:
            raise CacheCapacityError(f"sequence {seq_id!r} owns no blocks")
        return self._claim(blocks, self._fresh_from[seq_id])

    def truncate(self, seq_id, keep: int) -> int:
        """Give back a sequence's TRAILING blocks beyond its first
        ``keep`` (speculative-decode rollback: lookahead blocks claimed
        for draft-token writes that verification then rejected) into its
        open run; a run left with no block goes back to the pool.

        Only ever legal on exclusively-owned tail blocks — growth never
        lands in shared storage, so a truncated block with ``refcount !=
        1`` (or a published hash) means the allocator's COW discipline
        was violated upstream: that raises instead of freeing, the same
        engine-fatal posture as the step loop's write assertion.
        Returns the number of blocks reclaimed."""
        blocks = self._owned.get(seq_id)
        if blocks is None:
            raise CacheCapacityError(f"sequence {seq_id!r} owns no blocks")
        keep = max(0, int(keep))
        if keep >= len(blocks):
            return 0
        tail = blocks[keep:]
        for phys in tail:
            if self._ref.get(phys, 0) != 1 or phys in self._hash_of:
                raise InferenceServerException(
                    f"COW violation: speculative rollback of block "
                    f"{phys} (refcount {self._ref.get(phys, 0)}, "
                    f"published={phys in self._hash_of})"
                )
        for phys in reversed(tail):
            self._reclaim(phys)
        del blocks[keep:]
        return len(tail)

    def free(self, seq_id) -> int:
        """Drop a sequence's references (idempotent); returns the number
        of blocks whose last reference dropped. A block another
        sequence still references survives with its index entry; the
        last reference unpublishes and reclaims it, and its run is free
        again once no block of it holds a reference."""
        blocks = self._owned.pop(seq_id, None)
        self._fresh_from.pop(seq_id, None)
        if not blocks:
            return 0
        reclaimed = 0
        for phys in reversed(blocks):
            if self._ref[phys] > 1:
                self._ref[phys] -= 1
                continue
            published = self._hash_of.pop(phys, None)
            if published is not None and self._index.get(published) == phys:
                del self._index[published]
            self._reclaim(phys)
            reclaimed += 1
        return reclaimed

    # -- publication ---------------------------------------------------------

    def publish(self, seq_id, hashes: Sequence[bytes]) -> int:
        """Register a sequence's first ``len(hashes)`` blocks (its full,
        prefilled prompt blocks) in the shared index so later sequences
        can reference them. Blocks whose hash is already indexed (or that
        were themselves matched from the index) are skipped — first
        publisher wins, duplicates keep serving their own copy until
        freed. Returns the number of newly indexed blocks."""
        owned = self._owned.get(seq_id)
        if owned is None:
            return 0
        published = 0
        for phys, h in zip(owned, hashes):
            if phys in self._hash_of or h in self._index:
                continue
            self._index[h] = phys
            self._hash_of[phys] = h
            published += 1
        return published
