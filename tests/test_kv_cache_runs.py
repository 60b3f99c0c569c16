"""The allocator hands out the paged kernel's tile: runs of ``P`` blocks.

CPU, no jax: ``BlockAllocator(run=P)`` against its contract (the module
docstring of ``llm/kv_cache.py``) at ``P`` in 1, 4, 8, 16, a plain model
of ownership under a seeded random walk, and ``run=1`` against the
allocator as it was before runs existed (a list used as a stack).
"""

import numpy as np
import pytest

from client_tpu.llm.engine import EngineConfig
from client_tpu.llm.kv_cache import (
    BlockAllocator,
    CacheCapacityError,
    window_ring_blocks,
    window_tables,
)
from client_tpu.models.engine_model import FULL, STATE, WINDOW, CacheGroup
from client_tpu.models.paged_attention import count_tiles, visible_slots
from client_tpu.utils import InferenceServerException

RUNS = [1, 4, 8, 16]
BLOCK = 4


def _pool(run, runs=12):
    """An allocator of ``runs`` whole runs and the trash block."""
    return BlockAllocator(1 + runs * run, BLOCK, run)


def _assert_in_runs(blocks, run, num_blocks, fresh_from=0):
    """Own column ``j`` sits at ``run_start + j % run`` of one aligned
    run a tile, and every run lies inside the pool."""
    starts = {}
    for column in range(fresh_from, len(blocks)):
        start = blocks[column] - column % run
        assert (start - 1) % run == 0 and start >= 1, (column, blocks)
        assert start + run <= num_blocks
        assert starts.setdefault(column // run, start) == start, blocks
    assert len(set(starts.values())) == len(starts)  # a run a tile


class _Stack:
    """The allocator before runs: one LIFO stack of blocks."""

    def __init__(self, num_blocks):
        self.free = list(range(num_blocks - 1, 0, -1))
        self.owned = {}

    def allocate(self, seq, n):
        self.owned[seq] = [self.free.pop() for _ in range(n)]
        return list(self.owned[seq])

    def extend(self, seq):
        self.owned[seq].append(self.free.pop())
        return self.owned[seq][-1]

    def truncate(self, seq, keep):
        tail = self.owned[seq][keep:]
        self.free.extend(reversed(tail))
        del self.owned[seq][keep:]

    def release(self, seq):
        self.free.extend(reversed(self.owned.pop(seq)))


@pytest.mark.parametrize("run", RUNS)
def test_runs_are_aligned_and_consecutive(run):
    alloc = _pool(run)
    assert alloc.capacity == alloc.free_blocks == 12 * run
    held = {}
    for seq, n in enumerate([1, run, run + 1, 2 * run + 3, 3]):
        held[seq] = alloc.allocate(seq, n)
        assert len(held[seq]) == n
        _assert_in_runs(held[seq], run, alloc.num_blocks)
    everything = [b for blocks in held.values() for b in blocks]
    assert len(set(everything)) == len(everything) and 0 not in everything
    assert alloc.blocks_in_use == len(everything)
    assert alloc.free_blocks + alloc.blocks_in_use + alloc.blocks_reserved \
        == alloc.capacity
    # a pool that is no whole number of runs leaves the rest out
    odd = BlockAllocator(1 + 3 * run + (run - 1), BLOCK, run)
    assert odd.capacity == 3 * run
    _assert_in_runs(odd.allocate("a", 3 * run), run, odd.num_blocks)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_a_run_of_one_is_the_allocator_block_for_block(seed):
    """``run=1`` (every engine built without ``attn_tile_pages``) gives
    the blocks the stack gave, the LIFO order of free -> allocate
    included, through a seeded walk of admit, grow, roll back, end."""
    rng = np.random.default_rng(seed)
    alloc, stack = BlockAllocator(41, BLOCK), _Stack(41)
    assert alloc.run == 1 and alloc.capacity == 40
    live = []
    for step in range(400):
        roll = rng.random()
        if roll < 0.2 and alloc.free_blocks >= 6:
            n = int(rng.integers(1, 7))
            assert alloc.allocate(step, n) == stack.allocate(step, n)
            live.append(step)
        elif roll < 0.7 and live and alloc.free_blocks:
            seq = live[int(rng.integers(len(live)))]
            assert alloc.extend(seq) == stack.extend(seq)
        elif roll < 0.8 and live:
            seq = live[int(rng.integers(len(live)))]
            keep = int(rng.integers(0, len(alloc.owned(seq)) + 1))
            alloc.truncate(seq, keep)
            stack.truncate(seq, keep)
        elif live:
            seq = live.pop(int(rng.integers(len(live))))
            alloc.free(seq)
            stack.release(seq)
        assert alloc._free == stack.free
        assert alloc.free_blocks == len(stack.free)
        assert alloc.blocks_reserved == 0
        assert alloc.demand(7, 2) == 5
    # the order the docstring names: freed blocks come back first block
    # first
    fresh = BlockAllocator(9, BLOCK)
    first = fresh.allocate("a", 3)
    fresh.free("a")
    assert fresh.allocate("b", 3) == first == [1, 2, 3]


@pytest.mark.parametrize("run", RUNS)
def test_extend_crosses_a_tile_boundary_into_a_new_run(run):
    alloc = _pool(run)
    a, b = alloc.allocate("a", 1), alloc.allocate("b", 1)
    for _ in range(3 * run):  # two lanes growing in turn
        a.append(alloc.extend("a"))
        b.append(alloc.extend("b"))
    for blocks in (a, b):
        _assert_in_runs(blocks, run, alloc.num_blocks)
        assert blocks == alloc.owned("a" if blocks is a else "b")
    # inside a tile the next block is the next pool page, and free_blocks
    # moves only where a column opens a tile
    c = alloc.allocate("c", 1)
    for column in range(1, 2 * run + 1):
        before = alloc.free_blocks
        block = alloc.extend("c")
        if column % run:
            assert block == c[-1] + 1 and alloc.free_blocks == before
        else:
            assert alloc.free_blocks == before - run
        c.append(block)


@pytest.mark.parametrize("run", RUNS)
def test_free_reassembles_runs_whatever_order_sequences_end_in(run):
    rng = np.random.default_rng(run)
    alloc = _pool(run, runs=24)
    lanes = list(range(6))
    for lane in lanes:
        alloc.allocate(lane, 1 + lane % 3)
    for _ in range(run):
        for lane in lanes:
            alloc.extend(lane)
    for lane in rng.permutation(lanes):
        alloc.free(int(lane))
        assert alloc.free_blocks + alloc.blocks_in_use \
            + alloc.blocks_reserved == alloc.capacity
    assert alloc.free_blocks == alloc.capacity
    assert alloc.blocks_in_use == alloc.blocks_reserved == 0
    assert sorted(alloc._free) == list(range(1, alloc.capacity + 1, run))
    whole = alloc.allocate("all", alloc.capacity)
    _assert_in_runs(whole, run, alloc.num_blocks)
    assert sorted(whole) == list(range(1, alloc.num_blocks))
    assert alloc.free("all") == alloc.capacity


@pytest.mark.parametrize("run", RUNS)
def test_truncate_returns_into_the_open_run(run):
    alloc = _pool(run)
    blocks = alloc.allocate("a", 2 * run + 2)
    free = alloc.free_blocks
    # a rollback inside the open run keeps the run: the same blocks
    # come back, in order
    assert alloc.truncate("a", 2 * run + 1) == 1
    assert alloc.extend("a") == blocks[-1]
    if run > 1:
        assert alloc.free_blocks == free
    # one that empties the open run gives the run back (two runs of 1)
    assert alloc.truncate("a", 2 * run) == 2
    assert alloc.free_blocks == free + max(run, 2)
    assert alloc.owned("a") == blocks[: 2 * run]
    assert alloc.blocks_reserved == 0
    regrown = [alloc.extend("a") for _ in range(2)]
    assert regrown == blocks[2 * run:]  # LIFO: the run it just gave back
    _assert_in_runs(alloc.owned("a"), run, alloc.num_blocks)
    assert alloc.truncate("a", 99) == 0


@pytest.mark.parametrize("matched", ["a_multiple_of_run", "inside_a_tile"])
@pytest.mark.parametrize("run", RUNS)
def test_shared_prefix_admission(run, matched):
    alloc = _pool(run)
    n_shared = 2 * run if matched == "a_multiple_of_run" else 2 * run + 1
    tokens = list(range((n_shared + 1) * BLOCK))
    hashes = alloc.chain_hashes(tokens)[:n_shared]
    first, none = alloc.allocate_shared("a", n_shared + 2, hashes)
    assert none == 0
    assert alloc.publish("a", hashes) == n_shared
    assert alloc.demand(n_shared + 2 * run, n_shared) == 2 * run + (
        run if n_shared % run else 0)
    free = alloc.free_blocks
    blocks, n = alloc.allocate_shared("b", n_shared + run + 1, hashes)
    assert n == n_shared and blocks[:n] == first[:n]
    assert all(alloc.refcount(phys) == 2 for phys in blocks[:n])
    assert all(alloc.refcount(phys) == 1 for phys in blocks[n:])
    assert free - alloc.free_blocks == alloc.demand(len(blocks), n)
    # the fresh columns lie in runs of their own at j % run, the matched
    # ones where their publisher put them
    _assert_in_runs(blocks, run, alloc.num_blocks, fresh_from=n)
    assert not set(blocks[n:]) & set(first)
    # growth goes on in the open run and never into shared storage
    for _ in range(run + 1):
        blocks.append(alloc.extend("b"))
        assert alloc.refcount(blocks[-1]) == 1
    _assert_in_runs(blocks, run, alloc.num_blocks, fresh_from=n)
    # the tiles past the match are whole for the kernel; the tile the
    # match ends in (if it ends inside one) is the one stop that is not
    table = np.array([blocks + [0] * (4 * run + 8 - len(blocks))])
    positions = np.array([[len(blocks) * BLOCK - 1]])
    walked, whole = count_tiles(
        table, *visible_slots(positions, None), run, BLOCK, alloc.num_blocks)
    assert walked - whole == (1 if n_shared % run else 0)
    # the publisher ends: what b references stays, the rest of those
    # runs is stranded (reserved), and comes back with b
    alloc.free("a")
    assert all(alloc.refcount(phys) == 1 for phys in blocks[:n])
    assert alloc.blocks_in_use == len(blocks)
    alloc.free("b")
    assert alloc.free_blocks == alloc.capacity
    assert alloc.match_count(hashes) == 0


@pytest.mark.parametrize("run", RUNS)
def test_refcount_publish_and_cow_violations_still_raise(run):
    alloc = _pool(run)
    tokens = list(range(3 * BLOCK))
    hashes = alloc.chain_hashes(tokens)
    alloc.allocate_shared("a", 4, hashes)
    with pytest.raises(CacheCapacityError, match="already owns"):
        alloc.allocate("a", 1)
    with pytest.raises(CacheCapacityError, match="owns no blocks"):
        alloc.extend("nobody")
    with pytest.raises(CacheCapacityError, match="owns no blocks"):
        alloc.truncate("nobody", 0)
    assert alloc.publish("a", hashes) == 3
    assert alloc.publish("a", hashes) == 0  # first publisher wins
    assert alloc.publish("nobody", hashes) == 0
    # a published block is no rollback's to take, shared or not
    with pytest.raises(InferenceServerException, match="COW violation"):
        alloc.truncate("a", 2)
    blocks, n = alloc.allocate_shared("b", 4, hashes)
    assert n == 3
    with pytest.raises(InferenceServerException, match="COW violation"):
        alloc.truncate("b", 1)
    assert alloc.owned("b") == blocks  # nothing was taken
    assert alloc.truncate("b", 3) == 1  # its own tail block may go
    assert alloc.free("b") == 0 and alloc.free("b") == 0  # idempotent
    assert alloc.free("a") == 4
    assert alloc.free_blocks == alloc.capacity and not alloc._ref


@pytest.mark.parametrize("run", RUNS)
def test_capacity_error_when_no_run_is_left(run):
    alloc = _pool(run, runs=3)
    alloc.allocate("a", run + 1)  # two runs
    with pytest.raises(CacheCapacityError, match="KV cache exhausted"):
        alloc.allocate("b", run + 1)  # needs two, one is left
    assert alloc.owned("b") == [] and alloc.free_blocks == run
    alloc.allocate("b", 1)
    assert alloc.free_blocks == 0
    if run > 1:
        # the open runs still give: a dry pool refuses only a NEW run
        for _ in range(run - 1):
            alloc.extend("b")
    with pytest.raises(CacheCapacityError, match="0 of"):
        alloc.extend("b")
    for _ in range(run - 1):
        alloc.extend("a")
    with pytest.raises(CacheCapacityError, match="0 of"):
        alloc.extend("a")
    assert alloc.blocks_in_use == alloc.capacity  # every block in use
    alloc.free("a")
    assert alloc.extend("b") and alloc.free_blocks == run


@pytest.mark.parametrize("run", RUNS)
def test_a_sequence_reserves_at_most_a_run_less_one(run):
    alloc = _pool(run, runs=40)
    lanes = list(range(5))
    for lane in lanes:
        alloc.allocate(lane, 1 + lane)
    worst = 0
    for _ in range(3 * run):
        for lane in lanes:
            alloc.extend(lane)
            reserved = alloc.blocks_reserved
            assert reserved == sum(
                -len(alloc.owned(each)) % run for each in lanes)
            assert reserved <= (run - 1) * len(lanes)
            worst = max(worst, reserved)
    assert (worst > 0) == (run > 1)


@pytest.mark.parametrize("run", RUNS)
def test_random_walk_against_a_plain_model_of_ownership(run):
    """Admit (with and without a shared prefix), grow, roll back, end,
    in a seeded order: no block owned twice but by reference, none
    lost, every own column in its run, the counts adding up."""
    rng = np.random.default_rng(100 + run)
    alloc = _pool(run, runs=10)
    prompts = [list(rng.integers(0, 50, size=6 * run * BLOCK))
               for _ in range(3)]
    owner = {}  # seq -> (blocks, n_matched)
    refs = {}   # phys -> references, the model's
    for step in range(600):
        roll = rng.random()
        if roll < 0.25:
            prompt = prompts[int(rng.integers(len(prompts)))]
            n_full = int(rng.integers(0, 3 * run))
            hashes = alloc.chain_hashes(prompt[: n_full * BLOCK])
            n = n_full + int(rng.integers(1, run + 2))
            usable = alloc.match_count(hashes)
            if alloc.demand(n, usable) > alloc.free_blocks:
                with pytest.raises(CacheCapacityError):
                    alloc.allocate_shared(step, n, hashes)
                continue
            blocks, matched = alloc.allocate_shared(step, n, hashes)
            assert matched == usable and len(blocks) == n
            alloc.publish(step, hashes)
            owner[step] = (blocks, matched)
            for phys in blocks:
                refs[phys] = refs.get(phys, 0) + 1
        elif roll < 0.7 and owner:
            seq = list(owner)[int(rng.integers(len(owner)))]
            blocks, matched = owner[seq]
            try:
                block = alloc.extend(seq)
            except CacheCapacityError:
                assert not alloc.free_blocks
                assert len(blocks) % run == 0 or len(blocks) == matched
                continue
            assert block not in refs
            refs[block] = 1
            blocks.append(block)
        elif roll < 0.8 and owner:
            seq = list(owner)[int(rng.integers(len(owner)))]
            blocks, matched = owner[seq]
            keep = int(rng.integers(0, len(blocks) + 1))
            tail = blocks[keep:]
            if any(refs[phys] != 1 or phys in alloc._hash_of
                   for phys in tail):
                with pytest.raises(InferenceServerException):
                    alloc.truncate(seq, keep)
                continue
            assert alloc.truncate(seq, keep) == len(tail)
            for phys in tail:
                del refs[phys]
            del blocks[keep:]
        elif owner:
            seq = list(owner)[int(rng.integers(len(owner)))]
            blocks, _ = owner.pop(seq)
            gone = 0
            for phys in blocks:
                refs[phys] -= 1
                if not refs[phys]:
                    del refs[phys]
                    gone += 1
            assert alloc.free(seq) == gone
        # the model against the allocator
        assert {p: alloc.refcount(p) for p in refs} == refs
        assert alloc.blocks_in_use == len(refs)
        held_runs = {phys - (phys - 1) % run for phys in refs}
        assert alloc.free_blocks == alloc.capacity - len(held_runs) * run
        assert not held_runs & set(alloc._free)
        assert len(set(alloc._free)) == len(alloc._free)
        assert alloc.blocks_reserved == len(held_runs) * run - len(refs)
        for seq, (blocks, matched) in owner.items():
            assert alloc.owned(seq) == blocks
            _assert_in_runs(blocks, run, alloc.num_blocks, matched)
        own = [phys for blocks, matched in owner.values()
               for phys in blocks[matched:]]
        assert len(set(own)) == len(own)  # no block owned twice
    assert alloc.prefix_hits > 0  # the walk did share
    for seq in list(owner):
        alloc.free(seq)
    assert alloc.free_blocks == alloc.capacity  # none lost
    assert not alloc._ref and not alloc._live and not alloc._index


@pytest.mark.parametrize("window,block,run,ring", [
    (128, 16, 1, 9), (128, 16, 4, 12), (2048, 16, 16, 144),
    (2048, 16, 1, 129), (24, 8, 2, 4), (10, 4, 4, 4), (10, 4, 3, 6),
])
def test_a_ring_is_a_whole_number_of_runs(window, block, run, ring):
    assert window_ring_blocks(window, block, run) == ring
    assert ring % run == 0 and ring >= window_ring_blocks(window, block)


@pytest.mark.parametrize("run,window", [(4, 128), (16, 2048), (2, 24)])
def test_a_ring_of_whole_tiles_never_wraps_inside_a_tile(run, window):
    """``window_tables`` over a ring claimed in runs: whatever block the
    newest position is in, every tile the kernel walks is whole. The
    same ring unrounded wraps inside one tile a row."""
    block = 16
    ring = window_ring_blocks(window, block, run)
    alloc = BlockAllocator(1 + 3 * ring, block, run)
    alloc.allocate("other", ring)
    rings = [alloc.allocate("a", ring), alloc.allocate("b", ring)]
    width = 4 * ring
    bare = window_ring_blocks(window, block)
    loose = BlockAllocator(1 + 3 * bare, block)
    loose_rings = [loose.allocate("a", bare), loose.allocate("b", bare)]
    wrapped = 0
    for last in range(0, width * block, 7):
        positions = np.array([[last], [max(0, last - 5)]])
        last_blocks = (positions[:, 0] // block).tolist()
        slots = visible_slots(positions, window)
        tables = window_tables(rings, last_blocks, width)
        walked, whole = count_tiles(
            tables, *slots, run, block, alloc.num_blocks)
        assert walked == whole > 0, last
        tables = window_tables(loose_rings, last_blocks, width)
        walked, whole = count_tiles(
            tables, *slots, run, block, loose.num_blocks)
        wrapped += walked - whole
    assert wrapped > 0


def test_the_run_is_read_off_the_shapes():
    """``EngineConfig.group_runs``: a group's tile where its row holds
    several, else 1; no tile sizes, runs of 1; and the pools follow."""
    groups = (CacheGroup(FULL, (0,)), CacheGroup(WINDOW, (1,), window=2048))
    cell = EngineConfig(block_size=16, num_blocks=20481, max_active=64,
                        max_seq_len=8192, cache_groups=groups)
    assert cell.group_runs() == [1, 1]
    assert cell.group_runs((16, 16)) == [16, 16]
    assert cell.group_num_blocks() == [20481, 1 + 64 * 129]
    assert cell.group_num_blocks((16, 16)) == [20481, 1 + 64 * 144]
    # a tile as wide as the table, or as the window's blocks: runs of 1
    assert cell.group_runs((512, 256)) == [1, 1]
    assert cell.group_runs((256, 128)) == [256, 128]
    mimo = EngineConfig(
        block_size=16, num_blocks=8193, max_active=64, max_seq_len=2048,
        cache_groups=(CacheGroup(FULL, (0,)),
                      CacheGroup(WINDOW, (1,), window=128)))
    assert mimo.group_runs((8, 4)) == [8, 4]
    assert mimo.group_num_blocks((8, 4)) == [8193, 1 + 64 * 12]
    assert mimo.group_runs((8, 16)) == [8, 1]
    assert mimo.group_num_blocks((8, 16)) == [8193, 1 + 64 * 9]
    plain = EngineConfig(block_size=16, num_blocks=2049, max_seq_len=2048)
    assert plain.group_runs() == [1] and plain.group_runs((8,)) == [8]
    assert plain.group_num_blocks((8,)) == [2049]
    # GigaChat's one full group at the one-pool call's tile of 64 pages:
    # the table row holds eight of them, the pool 640 runs and a block
    latent = EngineConfig(block_size=16, num_blocks=40961, max_active=128,
                          max_seq_len=8192)
    assert latent.group_runs((64,)) == [64]
    assert latent.group_num_blocks((64,)) == [40961]
    assert BlockAllocator(40961, 16, 64).capacity == 640 * 64
    assert latent.group_runs((512,)) == [1]  # a tile as wide as the row
    with pytest.raises(ValueError, match="run must be"):
        BlockAllocator(9, 4, 0)


@pytest.mark.parametrize("run,reserved_most", [(64, 4000), (32, 1984)])
def test_gigachats_block_traffic_fits_the_pool_in_long_runs(
        run, reserved_most):
    """``gigachat3_702b.reason8k_128``'s block traffic through the
    allocator alone at the one-pool call's tile (64 pages; 32, the next
    shorter, beside it): 128 sequences over a pool of 40,961 blocks of
    16, prompts of 512 + 60 i tokens, a token a step each to 8,192, then
    a fresh prompt of 512 in the lane, so a completion every 60 steps.
    Nothing runs dry (no ``CacheCapacityError``: the engine would
    preempt), an admission always finds its runs, a sequence keeps at
    most a run less one in reserve, and every tile stop of the tables
    as the engine would write them is whole. Counters only."""
    lanes, pool, block, columns, last = 128, 40961, 16, 512, 8192
    alloc = BlockAllocator(pool, block, run)
    assert alloc.capacity == (pool - 1) // run * run
    held, at = {}, {}
    fresh = iter(range(1 << 30))

    def admit(lane, prompt):
        need = alloc.blocks_for(prompt + 1)
        assert alloc.demand(need) <= alloc.free_blocks, (lane, prompt)
        at[lane] = [next(fresh), prompt]  # id, the next position written
        held[lane] = alloc.allocate(at[lane][0], need)

    for lane in range(lanes):
        admit(lane, 512 + 60 * lane)
    worst = walked = whole = completions = 0
    for step in range(3000):
        for lane in range(lanes):
            if at[lane][1] == last:
                alloc.free(at[lane][0])
                completions += 1
                admit(lane, 512)
            seq, position = at[lane]
            while position // block >= len(held[lane]):
                held[lane].append(alloc.extend(seq))
            at[lane][1] = position + 1
        worst = max(worst, alloc.blocks_reserved)
        assert alloc.blocks_reserved == sum(
            -len(blocks) % run for blocks in held.values())
        if step % 50 == 0:
            tables = np.zeros((lanes, columns), np.int32)
            for lane, blocks in held.items():
                tables[lane, :len(blocks)] = blocks
            positions = np.array([[at[lane][1] - 1] for lane in range(lanes)])
            stops = count_tiles(
                tables, *visible_slots(positions, None), run, block, pool)
            walked += stops[0]
            whole += stops[1]
    assert completions == 2999 // 60  # one every 60 steps from step 60
    assert walked == whole > 0
    assert worst == reserved_most <= lanes * (run - 1)
    assert alloc.free_blocks + alloc.blocks_in_use + alloc.blocks_reserved \
        == alloc.capacity



@pytest.mark.parametrize("max_active,tile_pages", [
    (128, (16, 1)), (128, ()), (8, (4, 1)), (1, (16, 16))])
def test_a_state_group_is_counted_in_slots_not_blocks(max_active, tile_pages):
    """``EngineConfig`` beside a state group: its pool is ``1 +
    max_active`` slots whatever the block size, the table's width or the
    tile the full group runs at, its slots go one at a time, and the
    full group's pool and runs are what they are without it."""
    groups = (CacheGroup(FULL, (3, 7)), CacheGroup(STATE, (0, 1, 2, 4, 5, 6)))
    cell = EngineConfig(block_size=16, num_blocks=16385,
                        max_active=max_active, max_seq_len=2048,
                        cache_groups=groups)
    alone = EngineConfig(block_size=16, num_blocks=16385,
                         max_active=max_active, max_seq_len=2048)
    assert cell.group_runs(tile_pages)[1] == 1
    assert cell.group_runs(tile_pages)[0] == alone.group_runs(tile_pages)[0]
    assert cell.group_num_blocks(tile_pages) == [16385, 1 + max_active]
    # a slot allocator: one block a sequence, the trash slot never
    slots = BlockAllocator(1 + max_active, 1)
    held = [slots.allocate(seq, 1)[0] for seq in range(max_active)]
    assert sorted(held) == list(range(1, 1 + max_active))
    with pytest.raises(CacheCapacityError):
        slots.allocate("one too many", 1)
    slots.free(0)
    assert slots.allocate("next", 1) == [held[0]]


@pytest.mark.parametrize("pool", [65537, 40961])
def test_jambas_cell_holds_a_slot_a_lane_and_its_blocks_in_runs_of_64(pool):
    """``jamba2_3b.reason8k_128`` through the allocators alone: a full
    group of two attention layers whose tile at KV 1 / D 128 is 64 pages
    (a table row of 512 columns holds eight) beside a state group of 26
    layers. The configuration's pool of 65,537 blocks is 128 lanes of
    8,192 tokens and the trash block, so nothing can run dry whatever the
    traffic; GigaChat's 40,961 under the same mix would do too (the
    cell's steady state holds 128 x 4,352 tokens in whole runs). 128
    sequences of 512 + 60 i tokens grow a token a step to 8,192 and are
    followed by a fresh prompt of 512 in the lane: an admission always
    finds its runs AND its slot, the slots held are the lanes running,
    and a slot freed is the next one claimed."""
    groups = (CacheGroup(FULL, (7, 21)),
              CacheGroup(STATE, tuple(i for i in range(28) if i % 14 != 7)))
    cell = EngineConfig(block_size=16, num_blocks=pool, max_active=128,
                        max_seq_len=8192, cache_groups=groups)
    assert cell.group_runs((64, 1)) == [64, 1]
    assert cell.group_num_blocks((64, 1)) == [pool, 129]
    blocks, slots = BlockAllocator(pool, 16, 64), BlockAllocator(129, 1)
    at, fresh = {}, iter(range(1 << 30))

    def admit(lane, prompt):
        need = blocks.blocks_for(prompt + 1)
        assert blocks.demand(need) <= blocks.free_blocks, (lane, prompt)
        at[lane] = [next(fresh), prompt]
        blocks.allocate(at[lane][0], need)
        (slot,) = slots.allocate(at[lane][0], 1)
        assert 1 <= slot <= 128
        return slot

    held = {lane: admit(lane, 512 + 60 * lane) for lane in range(128)}
    assert sorted(held.values()) == list(range(1, 129))
    completions = 0
    for _ in range(1200):
        for lane in range(128):
            if at[lane][1] == 8192:
                blocks.free(at[lane][0])
                slots.free(at[lane][0])
                completions += 1
                assert admit(lane, 512) == held[lane]
            seq, position = at[lane]
            if position % 16 == 0:
                blocks.extend(seq)
            at[lane][1] = position + 1
        assert slots.blocks_in_use == 128 and slots.free_blocks == 0
    assert completions >= 19
    assert blocks.blocks_in_use + blocks.blocks_reserved <= blocks.capacity


@pytest.mark.parametrize("pool", [32769, 20481])
def test_phi4s_cell_holds_runs_of_8_a_ring_of_5_tiles_and_a_slot_a_lane(pool):
    """``phi4_mini_flash.reason8k`` through the allocators alone: THREE
    groups, a full group of one layer and a window group of eight whose
    tile at 10 rows of 128 a token is 8 pages (the power of two nearest
    the 6.4 that the kernel's budget holds), beside a state group of
    nine. A window of 512 touches 33 blocks; its ring is 40, five whole
    tiles (36 at the runs of 4 the cell had before PR 45, 48 at 16: the
    ring is rounded to the run). The configuration's full pool of 32,769
    blocks is 64 lanes of 8,192 tokens and the trash block, 64 runs of 8
    a lane at the longest; Trinity's 20,481 under the same mix would do
    too. 64 sequences of 512 + 120 i tokens grow a token a step to 8,192
    and are followed by a fresh prompt of 512 in the lane: an admission
    always finds its runs, its ring AND its slot."""
    groups = (CacheGroup(FULL, (17,)),
              CacheGroup(WINDOW, tuple(range(1, 16, 2)), window=512),
              CacheGroup(STATE, tuple(range(0, 17, 2))))
    cell = EngineConfig(block_size=16, num_blocks=pool, max_active=64,
                        max_seq_len=8192, cache_groups=groups)
    assert cell.group_runs((8, 8, 1)) == [8, 8, 1]
    assert window_ring_blocks(512, 16) == 33
    assert [window_ring_blocks(512, 16, run) for run in (4, 8, 16)] == [
        36, 40, 48]
    assert cell.group_num_blocks((8, 8, 1)) == [pool, 1 + 64 * 40, 65]
    blocks = BlockAllocator(pool, 16, 8)
    rings, slots = BlockAllocator(1 + 64 * 40, 16, 8), BlockAllocator(65, 1)
    at, fresh = {}, iter(range(1 << 30))

    def admit(lane, prompt):
        need = blocks.blocks_for(prompt + 1)
        assert blocks.demand(need) <= blocks.free_blocks, (lane, prompt)
        at[lane] = [next(fresh), prompt]
        blocks.allocate(at[lane][0], need)
        ring = rings.allocate(at[lane][0], 40)
        # five whole tiles: every run of 8 starts on a tile of the pool
        assert all(ring[j] % 8 == 1 and ring[j:j + 8] == list(
            range(ring[j], ring[j] + 8)) for j in range(0, 40, 8))
        (slot,) = slots.allocate(at[lane][0], 1)
        return slot

    held = {lane: admit(lane, 512 + 120 * lane) for lane in range(64)}
    assert sorted(held.values()) == list(range(1, 65))
    assert rings.free_blocks == 0
    completions = 0
    for _ in range(1200):
        for lane in range(64):
            if at[lane][1] == 8192:
                for allocator in (blocks, rings, slots):
                    allocator.free(at[lane][0])
                completions += 1
                assert admit(lane, 512) == held[lane]
            seq, position = at[lane]
            if position % 16 == 0:
                blocks.extend(seq)
            at[lane][1] = position + 1
        assert slots.blocks_in_use == 64 and rings.free_blocks == 0
    assert completions >= 9
    assert blocks.blocks_in_use + blocks.blocks_reserved <= blocks.capacity
