"""Lap spans in the LLM engine's step loop (``LapSpans`` + ``PHASES``).

- the helper alone, on a fake clock: phases tile the un-parked wall
  time, parked time is in no phase, no profiler is no failure;
- stub engines on a clock that ticks at every read: the sum of
  ``phase_ns`` IS the clock's time from the loop's first boundary to its
  last, over plain decode, prefills with preemption, and speculation;
  the counters beside them count what was submitted and never go back;
  the record of the turns adds up to the phases, and a second the
  device double holds in one ``wait`` is a stall in the counters, in
  ``/v2/debug/state`` and in the log;
- the float32 tiny llama: ``/v2/debug/state`` serves the counters, a
  ``jax.profiler.trace`` holds ``engine.*`` events on one host line that
  agree with the counters, streams are bit-identical with and without a
  profiler session, and the device programs carry their names.
"""

import asyncio
import collections
import glob
import json
import os
import threading
import urllib.request

import numpy as np
import pytest

from client_tpu.llm import EngineConfig, LlmEngine, NgramProposer
from client_tpu.llm.engine import PHASES, decode_fn_from_logits
from client_tpu.observability import LapSpans
from client_tpu.observability import profiling

pytestmark = pytest.mark.llm

VOCAB = 32
COUNTERS = ("prefills", "admitted", "queue_wait_ns", "steps", "lane_steps",
            "tokens_generated", "attn_blocks_live", "attn_blocks_bucket",
            "attn_tiles_walked", "attn_tiles_whole", "attn_slots_fetched",
            "attn_slots_live")


class _TickingClock:
    """Every read is later than the last by a different amount, so a lap
    that was dropped or booked twice cannot cancel out."""

    def __init__(self):
        self.now = 0
        self.reads = 0

    def __call__(self):
        self.reads += 1
        self.now += 1_000 + 37 * (self.reads % 11)
        return self.now


class _Boundaries:
    """The laps' own view of a clock: what it read at the first boundary
    and at the last."""

    def __init__(self, clock):
        self.clock = clock
        self.first = self.last = None

    def __call__(self):
        self.last = self.clock()
        if self.first is None:
            self.first = self.last
        return self.last


# -- the helper ---------------------------------------------------------------


def test_lap_spans_tile_the_unparked_time_on_a_fake_clock():
    times = iter([100, 130, 190, 200, 1_000, 1_040, 1_100])
    laps = LapSpans({"a": "loop.a", "b": "loop.b"},
                    clock_ns=lambda: next(times))
    assert laps.ns == {"a": 0, "b": 0}
    assert laps.enter("a") == 100
    assert laps.enter("b") == 130
    # entering the open phase again is no boundary and reads no clock
    assert laps.enter("b") == 130
    assert laps.enter("a") == 190
    laps.park()  # at 200
    laps.park()  # idempotent: no clock read
    assert laps.ns == {"a": 40, "b": 60}  # 100 -> 200
    # 200 -> 1000 was parked: in no phase
    laps.enter("b")
    assert laps.ns == {"a": 40, "b": 60}  # up to the last boundary
    laps.enter("a")
    assert laps.ns == {"a": 40, "b": 100}
    laps.park()
    assert laps.ns == {"a": 100, "b": 100}  # and 1000 -> 1100


def test_lap_spans_count_without_a_profiler(monkeypatch):
    monkeypatch.setattr(profiling, "_trace_annotation", lambda: None)
    clock = _Boundaries(_TickingClock())
    laps = LapSpans({"a": "loop.a", "b": "loop.b"}, clock_ns=clock)
    for _ in range(5):
        laps.enter("a")
        laps.enter("b")
    laps.park()
    assert sum(laps.ns.values()) == clock.last - clock.first > 0


# -- stub engines on the ticking clock ---------------------------------------


def _logits_row(token, position):
    row = np.linspace(0.0, 1.0, VOCAB, dtype=np.float32)
    row[(int(token) + int(position)) % VOCAB] = 3.0
    return row


def _stub_engine(clock, speculative=False, tile_pages=(), **overrides):
    def prefill(tokens, page_table, pages, last_index, start):
        return _logits_row(tokens[0, last_index], start + last_index)[None], pages

    def decode(tokens, positions, page_tables, pages):
        return np.stack([_logits_row(t, p)
                         for t, p in zip(tokens, positions)]), pages

    def decode_multi(tokens, positions, lengths, page_tables, pages):
        b, t = tokens.shape
        out = np.zeros([b, t, VOCAB], dtype=np.float32)
        for i in range(b):
            for j in range(t):
                out[i, j] = _logits_row(tokens[i, j], positions[i, j])
        return out, pages

    defaults = dict(block_size=4, num_blocks=33, max_active=4, max_queue=8,
                    max_seq_len=64, spec_k=3 if speculative else 0)
    defaults.update(overrides)
    engine = LlmEngine(
        prefill, decode_fn_from_logits(decode), pages=object(),
        engine_config=EngineConfig(**defaults), model_name="stub",
        clock_ns=clock,
        decode_multi_fn=decode_multi if speculative else None,
        proposer=NgramProposer(k=3, ngram=2) if speculative else None,
        attn_tile_pages=tile_pages,
    )
    # the laps read the engine's clock through a witness of their own
    engine._laps = LapSpans(engine._laps._names, clock_ns=_Boundaries(clock),
                            on_stall=engine._log_stall)
    return engine


async def _collect(seq):
    out = []
    async for token, final in seq:
        out.append(token)
        if final:
            break
    return out


def _run_stub(engine, prompts, max_tokens, watch=None):
    async def run():
        seqs = [engine.submit(p, max_tokens=max_tokens) for p in prompts]
        if watch is not None:
            watcher = asyncio.ensure_future(watch())
        out = await asyncio.gather(*[_collect(s) for s in seqs])
        if watch is not None:
            watcher.cancel()
        # one more turn of the loop: the step loop parks itself
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        return out

    return asyncio.run(run())


def _tiles(engine, stats=None):
    """The phases add up to the clock's time between the loop's first
    boundary and its last (these runs park once, at their end)."""
    stats = stats or engine.stats()
    witness = engine._laps._clock_ns
    assert set(stats["phase_ns"]) == set(PHASES)
    assert sum(stats["phase_ns"].values()) == witness.last - witness.first > 0
    # the record of the turns: closed turns only, so it trails the
    # phases by the open turn and meets them where the loop is parked
    closed = {p: stats["steady_phase_ns"][p] + stats["stall_phase_ns"][p]
              for p in PHASES}
    assert all(closed[p] <= stats["phase_ns"][p] for p in PHASES)
    assert sum(closed.values()) == stats["loop_ns"]
    if engine._laps._phase is None:
        assert closed == stats["phase_ns"]
    return stats["phase_ns"]


def test_phases_tile_the_loop_over_plain_decode():
    engine = _stub_engine(_TickingClock())
    out = _run_stub(engine, [[1, 2, 3], [4, 5], [6]], 9)
    assert all(len(tokens) == 9 for tokens in out)
    phases = _tiles(engine)
    assert phases["propose"] == 0
    assert all(phases[p] > 0 for p in PHASES if p != "propose")
    # parked: a later look finds nothing moved
    assert engine.stats()["phase_ns"] == phases
    engine.close()


def test_phases_tile_the_loop_with_prefills_and_preemption():
    # two allocatable blocks of 4 tokens; both sequences outgrow one
    engine = _stub_engine(_TickingClock(), num_blocks=3, max_seq_len=8)
    out = _run_stub(engine, [[1, 2, 3], [4, 5, 6]], 5)
    assert [len(tokens) for tokens in out] == [5, 5]
    stats = engine.stats()
    _tiles(engine, stats)
    assert stats["preemptions"] > 0
    # a resume is a prefill of the whole context, and no second admission
    assert stats["admitted"] == 2
    assert stats["prefills"] == 2 + stats["preemptions"]
    engine.close()


def test_phases_tile_the_loop_of_an_ngram_speculative_toy():
    engine = _stub_engine(_TickingClock(), speculative=True)
    # the stub's greedy chain from a repeating prompt soon repeats
    # itself, so the n-gram proposer has drafts to verify
    out = _run_stub(engine, [[1, 2, 1, 2, 1, 2], [3, 3, 3, 3]], 24)
    assert [len(tokens) for tokens in out] == [24, 24]
    stats = engine.stats()
    phases = _tiles(engine, stats)
    assert stats["speculative"] and stats["spec_steps"] > 0
    assert phases["propose"] > 0
    engine.close()


def test_counters_count_what_was_submitted():
    clock = _TickingClock()
    engine = _stub_engine(clock, max_active=2)

    async def run():
        submitted_at = clock.now
        seqs = [engine.submit([1 + i] * (3 + i), max_tokens=4)
                for i in range(5)]
        out = await asyncio.gather(*[_collect(s) for s in seqs])
        return submitted_at, out

    submitted_at, out = asyncio.run(run())
    stats = engine.stats()
    assert stats["admitted"] == stats["prefills"] == stats["completed"] == 5
    # two lanes: the last three waited for a lane, at least a whole
    # generation of the first two; nobody waited longer than the run
    waited = stats["queue_wait_ns"]
    assert waited > 3 * 3 * 1_000
    assert waited < 5 * (clock.now - submitted_at)
    engine.close()


@pytest.mark.parametrize("block_size,max_seq_len,blocks,columns", [
    # trinity_mini.reason8k: the widest lane a few tokens under 8,065
    (16, 8192, 505, 512), (16, 8192, 504, 512), (16, 8192, 497, 512),
    (16, 8192, 496, 496), (16, 8192, 33, 40),
    # the cells that end at 2,048
    (16, 2048, 128, 128), (16, 2048, 113, 128), (16, 2048, 112, 112),
    # small tables keep their powers of two, and no table passes its end
    (4, 64, 9, 16), (4, 64, 8, 8), (4, 64, 3, 4), (4, 16, 3, 4), (4, 8, 2, 2),
])
def test_the_last_bucket_under_the_whole_table_takes_the_whole_table(
        block_size, max_seq_len, blocks, columns):
    """Streams that end at ``max_seq_len`` one after another keep the
    widest lane within a bucket of it: a dip of a few tokens under the
    edge must not ask for a second decode program."""
    import types

    engine = _stub_engine(_TickingClock(), block_size=block_size,
                          max_seq_len=max_seq_len)
    batch = [types.SimpleNamespace(blocks=[1] * n) for n in (1, blocks)]
    assert engine._table_columns(batch) == columns
    engine.close()


@pytest.mark.parametrize("speculative", [False, True],
                         ids=["decode", "verify"])
def test_attn_block_counters_follow_the_tables_the_device_saw(speculative):
    """``attn_blocks_bucket`` is every column of every page table a
    decode or verify step handed the device, ``attn_blocks_live`` those
    that hold a sequence's block (block 0 is the trash block: padding
    lanes and the columns past a lane's last block)."""
    engine = _stub_engine(_TickingClock(), speculative=speculative)
    tables = {"decode": [], "verify": []}

    def watched(name, call, table_at):
        def step(*args):
            tables[name].append(np.array(args[table_at]))
            seen = engine.stats()
            # booked where the table is built: this step is in already
            assert seen["attn_blocks_bucket"] == sum(
                t.size for ts in tables.values() for t in ts)
            assert seen["attn_blocks_live"] == sum(
                np.count_nonzero(t) for ts in tables.values() for t in ts)
            return call(*args)
        return step

    engine._decode = watched("decode", engine._decode, 4)
    if speculative:
        engine._decode_multi = watched("verify", engine._decode_multi, 3)
    # three lanes pad to a batch bucket of 4; contexts pass 2 blocks of 4
    out = _run_stub(engine, [[1, 2, 1, 2, 1, 2], [3, 3, 3, 3], [5]], 12)
    assert [len(tokens) for tokens in out] == [12, 12, 12]
    stats = engine.stats()
    assert len(tables["verify"]) == stats["spec_steps"]
    assert (len(tables["verify"]) > 0) == speculative
    assert len(tables["decode"]) + len(tables["verify"]) == stats["steps"]
    assert 0 < stats["attn_blocks_live"] < stats["attn_blocks_bucket"]
    engine.close()


def _tiles_by_hand(row, first, length, pages, block_size, pool):
    """(tile stops walked, those of them whole) of one table row, column
    by column: the loop :func:`paged_attention.whole_tiles` stands for."""
    pages = min(pages, len(row))
    tile_slots = pages * block_size
    walked = whole = 0
    for tile in range(first // tile_slots, (length - 1) // tile_slots + 1):
        walked += 1
        live = [c for c in range(tile * pages, min((tile + 1) * pages, len(row)))
                if first // block_size <= c <= (length - 1) // block_size]
        page0 = row[live[0]] - live[0] % pages
        whole += (all(row[c] == page0 + c % pages for c in live)
                  and 0 <= page0 <= pool - pages)
    return walked, whole


@pytest.mark.parametrize("speculative", [False, True],
                         ids=["decode", "verify"])
def test_attn_tile_counters_follow_the_tables_the_device_saw(speculative):
    """``attn_tiles_walked`` is every tile stop the paged kernel makes
    over the tables of a decode or verify step at the tile size the
    engine was told (2 pages of 4 slots here), ``attn_tiles_whole``
    those whose live columns hold consecutive pool blocks. Three lanes
    grow a block at a time in turn, which fragmented the pool while the
    allocator handed out single blocks; told the tile, it hands out runs
    of 2, and every stop is whole. A fragmented table still reads
    fragmented, through the same rule."""
    engine = _stub_engine(
        _TickingClock(), speculative=speculative, tile_pages=(2,))
    expected = [0, 0]

    def watched(call, table_at, positions_at):
        def step(*args):
            table = np.array(args[table_at])
            positions = np.array(args[positions_at]).reshape(len(table), -1)
            for row, at in zip(table, positions):
                if row[0]:  # a live lane holds its first block
                    walked, whole = _tiles_by_hand(
                        row.tolist(), 0, int(at.max()) + 1, 2, 4, 33)
                    expected[0] += walked
                    expected[1] += whole
            seen = engine.stats()
            # booked where the table is built: this step is in already
            assert [seen["attn_tiles_walked"],
                    seen["attn_tiles_whole"]] == expected
            return call(*args)
        return step

    engine._decode = watched(engine._decode, 4, 3)
    if speculative:
        engine._decode_multi = watched(engine._decode_multi, 3, 1)
    out = _run_stub(engine, [[1, 2, 1, 2, 1, 2], [3, 3, 3, 3], [5]], 24)
    assert [len(tokens) for tokens in out] == [24, 24, 24]
    stats = engine.stats()
    assert 0 < stats["attn_tiles_whole"] == stats["attn_tiles_walked"]
    assert engine.allocator.run == 2
    engine._book_tiles(  # lane 0's second tile, lane 1's first: not whole
        np.array([[1, 2, 4, 3], [6, 5, 0, 0]]), np.array([[15], [6]]))
    stats = engine.stats()
    assert stats["attn_tiles_walked"] - stats["attn_tiles_whole"] == 2
    # an engine that was told no tile size counts none
    silent = _stub_engine(_TickingClock(), speculative=speculative)
    _run_stub(silent, [[1, 2, 3]], 4)
    assert silent.stats()["attn_tiles_walked"] == 0
    engine.close()
    silent.close()


def test_attn_tile_counters_sum_a_window_and_a_full_group():
    """Hand-made tables of a full group (tiles of 2 pages) and a window
    group (window of 10 slots over blocks of 4, tiles of 4 pages): each
    group is walked from its own first visible slot at its own tile
    size, against its own pool's size."""
    from client_tpu.models.engine_model import FULL, WINDOW, CacheGroup
    from client_tpu.models.paged_attention import count_tiles, visible_slots

    groups = (CacheGroup(FULL, (0,)), CacheGroup(WINDOW, (1,), window=10))
    engine = _stub_engine(
        _TickingClock(), tile_pages=(2, 4), cache_groups=groups,
        prefix_sharing=False, max_seq_len=32, max_active=3)
    assert engine._group_blocks == [33, 1 + 3 * 4]
    tables = np.zeros((2, 4, 8), dtype=np.int32)
    positions = np.array([[30], [17], [5]])
    tables[0, 0] = [1, 2, 3, 4, 9, 10, 20, 21]  # whole, whole, whole, whole
    tables[0, 1, :5] = [5, 6, 8, 7, 11]         # whole, not, whole (one live)
    tables[0, 2, :2] = [12, 14]                 # not
    # the window group's rows: the ring at the last columns, trash before
    tables[1, 0, 5:] = [3, 4, 1]     # 21..30: tile 1, the ring's wrap inside
    tables[1, 1, 2:5] = [5, 6, 7]    # 8..17: tiles 0 and 1, 5 at column 2
    tables[1, 2, :2] = [9, 10]       # 0..5: tile 0, whole
    engine._book_tiles(tables, positions)
    stats = engine.stats()
    assert stats["attn_tiles_walked"] == (4 + 3 + 1) + (1 + 2 + 1)
    # lane 1's window: 5, 6 at columns 2, 3 start a span at page 3; 7 at
    # column 4 one at 7, and 7 + 4 fits a pool of 13 blocks
    assert stats["attn_tiles_whole"] == (4 + 2 + 0) + (0 + 2 + 1)
    by_hand = [
        _tiles_by_hand(tables[g, lane].tolist(), first, at + 1, pages, 4, pool)
        for g, pages, pool, firsts in ((0, 2, 33, (0, 0, 0)),
                                       (1, 4, 13, (21, 8, 0)))
        for lane, (first, at) in enumerate(zip(firsts, (30, 17, 5)))
    ]
    assert [sum(column) for column in zip(*by_hand)] == [12, 9]
    # the same three lanes in blocks the engine's own allocator hands
    # out, a block at a time in turn: runs of 2 in the full group (the
    # window group's tile is its whole ring, so its run is 1), and the
    # full group's eight stops are whole where four of the hand-made
    # ones were not
    assert engine.allocator.run == 2 and engine._windows[0][2].run == 1
    held = [engine.allocator.allocate(lane, 1) for lane in range(3)]
    for column in range(1, 8):
        for lane, most in enumerate((8, 5, 2)):
            if column < most:
                held[lane].append(engine.allocator.extend(lane))
    made = np.zeros((3, 8), dtype=np.int32)
    for lane, blocks in enumerate(held):
        made[lane, : len(blocks)] = blocks
    walked, whole = count_tiles(
        made, *visible_slots(positions, None), 2, 4, 33)
    assert walked == whole == 4 + 3 + 1
    engine.close()


@pytest.mark.parametrize("windowed", [False, True], ids=["full", "windowed"])
def test_attn_slot_counters_against_a_hand_count(windowed):
    """``attn_slots_fetched`` is what the tile stops of a step bring
    into VMEM (a tile is copied whole: the stops walked times the slots
    of a tile), ``attn_slots_live`` those of them a query row can see
    (each lane's ``length - first_slot``): three lanes at positions 30,
    17 and 5 behind a full group's tiles of 2 pages of 4 slots and, with
    it, a window group's (window 10, tiles of 4 pages), counted by
    hand. A table narrower than a tile fetches tiles of its own width."""
    from client_tpu.models.engine_model import FULL, WINDOW, CacheGroup

    groups = (CacheGroup(FULL, (0,)), CacheGroup(WINDOW, (1,), window=10))
    engine = _stub_engine(
        _TickingClock(), tile_pages=(2, 4) if windowed else (2,),
        cache_groups=groups if windowed else (), prefix_sharing=False,
        max_seq_len=32, max_active=3)
    positions = np.array([[30], [17], [5]])
    full = np.zeros((3, 8), dtype=np.int32)
    full[0] = [1, 2, 3, 4, 9, 10, 20, 21]
    full[1, :5] = [5, 6, 8, 7, 11]
    full[2, :2] = [12, 14]
    # the full group: tiles of 8 slots, lengths 31, 18 and 6 from slot 0
    fetched, live = (4 + 3 + 1) * 8, 31 + 18 + 6
    tables = full
    if windowed:
        # the window group: tiles of 16 slots, each lane's last 10 slots
        # (lane 2 has only 6): 21..30 in tile 1, 8..17 in tiles 0 and 1
        tables = np.zeros((2, 3, 8), dtype=np.int32)
        tables[0] = full
        tables[1, 0, 5:] = [3, 4, 1]
        tables[1, 1, 2:5] = [5, 6, 7]
        tables[1, 2, :2] = [9, 10]
        fetched += (1 + 2 + 1) * 16
        live += 10 + 10 + 6
    engine._book_tiles(tables, positions)
    stats = engine.stats()
    assert stats["attn_slots_fetched"] == fetched
    assert stats["attn_slots_live"] == live
    assert stats["attn_tiles_walked"] == 8 + 4 * windowed
    # two live lanes behind a table of one column: a tile is that column
    engine._book_tiles(tables[..., :2, :1], np.array([[3], [0]]))
    after = engine.stats()
    groups_booked = 1 + windowed
    assert after["attn_slots_fetched"] - fetched == groups_booked * 2 * 4
    assert after["attn_slots_live"] - live == groups_booked * (4 + 1)
    silent = _stub_engine(_TickingClock())
    silent._book_tiles(full, positions)
    assert silent.stats()["attn_slots_fetched"] == 0
    assert silent.stats()["attn_slots_live"] == 0
    engine.close()
    silent.close()


def test_the_benchmark_reads_the_live_share_of_the_fetched_slots():
    """``attn.tile_slots_live_share`` is a metric file over the reader
    the benchmark has (``counters:delta_ratio``): the window's delta of
    ``attn_slots_live`` over that of ``attn_slots_fetched`` in per cent,
    listed for every cell; a parent whose ``stats()`` has no such
    counter reads nothing, and the line leaves the metric out."""
    import types

    from benchmark import run as harness

    engine = _stub_engine(_TickingClock(), tile_pages=(2,))
    before = {"engine": json.loads(json.dumps(engine.stats()))}
    engine._book_tiles(  # three tiles of 8 slots for 19 and 3 live slots
        np.array([[1, 2, 3, 4], [5, 6, 0, 0]]), np.array([[18], [2]]))
    after = {"engine": json.loads(json.dumps(engine.stats()))}
    engine.close()
    run = types.SimpleNamespace(before=before, after=after)
    value, unit = harness.read_metric(run, "attn.tile_slots_live_share")
    assert unit == "%" and value == pytest.approx(100 * 22 / 32)
    for snapshot in (before, after):
        del snapshot["engine"]["attn_slots_fetched"]
        del snapshot["engine"]["attn_slots_live"]
    assert harness.read_metric(run, "attn.tile_slots_live_share")[0] is None
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    (entry,) = [m for m in benchmark["per_layer"]
                if m["name"] == "attn.tile_slots_live_share"]
    assert entry["workloads"] == [w["name"] for w in benchmark["workloads"]]
    assert entry["layer"] == "kernels" and entry["moves"] == "out_tokens_per_s"


def _watch_dispatch(engine, decoded):
    """Call ``decoded(engine, batch, before)`` after every dispatched
    decode step, ``before`` the tile counters as they stood before it."""
    dispatch = engine._dispatch

    async def watched(batch, flight=None):
        before = (engine.attn_tiles_walked, engine.attn_tiles_whole)
        out = await dispatch(batch, flight)
        decoded(engine, batch, before)
        return out

    engine._dispatch = watched


def _generations(tile_pages, decoded, **overrides):
    """A stub engine of a full and a window group (window of 18 slots
    over blocks of 4: 6 blocks at once), 8 lanes growing in turn through
    three generations of requests, each ending at its own length, so
    that completions and re-admissions interleave: the steady state of
    a closed loop. ``decoded(engine, batch, before)`` sees every
    dispatched step with the tile counters as they stood before it."""
    from client_tpu.models.engine_model import FULL, WINDOW, CacheGroup

    sizes = dict(
        cache_groups=(CacheGroup(FULL, (0,)),
                      CacheGroup(WINDOW, (1,), window=18)),
        prefix_sharing=False, num_blocks=129, max_active=8, max_queue=32,
        max_seq_len=64)
    sizes.update(overrides)
    engine = _stub_engine(_TickingClock(), tile_pages=tile_pages, **sizes)
    _watch_dispatch(engine, decoded)
    rng = np.random.default_rng(35)

    async def run():
        seqs = [
            engine.submit(rng.integers(1, VOCAB, size=n).tolist(),
                          max_tokens=int(rng.integers(12, 64 - n)))
            for n in rng.integers(2, 22, size=24)
        ]
        return await asyncio.gather(*[_collect(s) for s in seqs])

    out = asyncio.run(run())
    stats = engine.stats()
    assert stats["completed"] == 24 and stats["preemptions"] == 0
    engine.close()
    return engine, stats, [len(tokens) for tokens in out]


def test_whole_tiles_are_the_allocators_promise_in_the_steady_state():
    """Told the kernel's tile (4 pages a group, a table of 16 columns),
    the allocators hand out runs of 4 and a ring of 8 (6 rounded up), and
    EVERY tile stop of every step is whole, in the full group and in the
    window group, with the pool as interleaved as three generations of
    8 lanes leave it. Told nothing, the engine allocates block for block
    as it did and the same traffic reads as fragmented as it did."""
    from client_tpu.models.paged_attention import count_tiles, visible_slots

    steps = []

    def all_whole(engine, batch, before):
        walked = engine.attn_tiles_walked - before[0]
        assert walked == engine.attn_tiles_whole - before[1] > 0
        steps.append(len(batch))
        for seq in batch:
            (ring,) = seq.rings
            assert len(ring) == 8 and len(seq.blocks) <= 16
        # what the open runs hold beyond their newest block: under a run
        reserved = engine.stats()["kv_blocks_reserved_by_group"]
        assert reserved[1] == 0 and 0 <= reserved[0] <= 3 * len(
            engine._running)

    engine, stats, lengths = _generations((4, 4), all_whole)
    assert engine.allocator.run == 4 and engine._group_blocks == [129, 65]
    assert max(steps) == 8 and len(steps) > 100
    assert stats["attn_tiles_walked"] == stats["attn_tiles_whole"] > 1000
    assert stats["kv_blocks_in_use_by_group"] == [0, 0]
    assert stats["kv_blocks_reserved_by_group"] == [0, 0]

    # the same traffic on an engine told nothing: runs of 1, a ring of 6,
    # no tile booked, and the tables it builds read by the same rule
    by_hand = [0, 0]

    def fragmented(engine, batch, before):
        positions = np.array(
            [[seq.position + (engine._flight is not None
                              and seq.seq_id in engine._flight.lane_of)]
             for seq in batch])
        full = np.array([seq.page_table[:16] for seq in batch])
        tables = engine._group_tables(full, batch, positions[:, 0])
        for table, window, pool in zip(tables, (None, 18), (129, 49)):
            walked, whole = count_tiles(
                table, *visible_slots(positions, window), 4, 4, pool)
            by_hand[0] += walked
            by_hand[1] += whole

    plain, silent, same = _generations((), fragmented)
    assert plain.allocator.run == 1 and plain._group_blocks == [129, 49]
    assert same == lengths  # the streams do not depend on the allocator
    assert silent["attn_tiles_walked"] == silent["attn_tiles_whole"] == 0
    assert silent["kv_blocks_reserved_by_group"] == [0, 0]
    # block for block the allocation before runs: as fragmented as the
    # commit before runs existed left the same traffic (45% whole then;
    # 46% since a lane joins the batch a step after its prefill was
    # dispatched, which moves who takes which block)
    assert by_hand == [2970, 1371]


def test_a_shared_prefixs_mixed_tile_is_the_one_stop_not_whole():
    """Prefix sharing over runs: a sequence admitted against 5 matched
    blocks (tiles of 4) references them where they lie and puts its own
    columns in runs of its own, so the tile the match ends in is its one
    stop a step that is not whole; the publisher's are all whole."""
    mixed_steps = []

    def one_mixed_tile(engine, batch, before):
        walked = engine.attn_tiles_walked - before[0]
        whole = engine.attn_tiles_whole - before[1]
        mixed = sum(1 for seq in batch if seq.shared_blocks % 4)
        assert walked - whole == mixed
        mixed_steps.append(mixed)

    from client_tpu.models.engine_model import FULL, CacheGroup

    engine = _stub_engine(
        _TickingClock(), tile_pages=(4,), num_blocks=129, max_active=8,
        max_queue=32, max_seq_len=64,
        cache_groups=(CacheGroup(FULL, (0,)),))
    _watch_dispatch(engine, one_mixed_tile)
    prefix = list(range(1, 23))  # 5 full blocks of 4 and two tokens more
    out = _run_stub(
        engine, [prefix + [7 + lane] * lane for lane in range(6)], 30)
    assert [len(tokens) for tokens in out] == [30] * 6
    stats = engine.stats()
    assert stats["prefix_cache_hits"] == 5 * 5  # five sharers of 5 blocks
    assert max(mixed_steps) == 5 and stats["kv_blocks_in_use"] == 0
    assert stats["kv_blocks_reserved_by_group"] == [0]
    engine.close()


def test_window_rings_stay_runs_of_consecutive_blocks():
    """What the whole-tile copy rests on in a window group: a ring is
    claimed whole at admission and returned whole, so whatever order
    sequences are admitted, end, are preempted and come back in, every
    ring the engine holds is ``base, base + 1, ...``."""
    from client_tpu.models.engine_model import FULL, WINDOW, CacheGroup

    groups = (CacheGroup(FULL, (0,)), CacheGroup(WINDOW, (1,), window=12))
    engine = _stub_engine(
        _TickingClock(), cache_groups=groups, prefix_sharing=False,
        num_blocks=14, max_active=3, max_queue=16, max_seq_len=48)
    rings = []

    async def watch():
        while True:
            for seq in engine._running:
                (ring,) = seq.rings
                assert ring == list(range(ring[0], ring[0] + 4)), ring
                rings.append(tuple(ring))
            await asyncio.sleep(0)

    async def run():
        watcher = asyncio.ensure_future(watch())
        rng = np.random.default_rng(7)
        seqs = []
        for n in rng.integers(2, 12, size=9):
            seqs.append(engine.submit(
                rng.integers(1, VOCAB, size=n).tolist(),
                max_tokens=int(rng.integers(3, 30))))
            await asyncio.sleep(0)
        engine.release(seqs[1])  # a cancellation among the endings
        await asyncio.gather(*[_collect(s) for s in seqs if s is not seqs[1]])
        watcher.cancel()

    asyncio.run(run())
    stats = engine.stats()
    assert stats["preemptions"] > 0 and stats["completed"] == 8
    assert len(set(rings)) == 3  # the pool's three rings, over and over
    assert stats["kv_blocks_in_use_by_group"] == [0, 0]
    engine.close()


def test_every_counter_is_in_stats_and_never_goes_back():
    engine = _stub_engine(_TickingClock(), num_blocks=5, max_seq_len=16,
                          tile_pages=(2,))
    seen = []

    async def watch():
        while True:
            seen.append(_look())
            await asyncio.sleep(0)

    def _look():
        # the loop is not parked while the watcher runs: the phases add
        # up to the last boundary at every look
        stats = engine.stats()
        _tiles(engine, stats)
        return stats

    _run_stub(engine, [[1, 2, 3], [4, 5, 6], [7, 8]], 10, watch=watch)
    seen.append(_look())
    assert len(seen) > 10
    for name in COUNTERS:
        values = [stats[name] for stats in seen]
        assert all(isinstance(v, int) for v in values), name
        assert values == sorted(values) and values[-1] > 0, name
    for phase in PHASES:
        values = [stats["phase_ns"][phase] for stats in seen]
        assert all(isinstance(v, int) for v in values), phase
        assert values == sorted(values), phase
    engine.close()


def test_debug_state_serves_the_counters():
    """``GET /v2/debug/state`` -> ``llm.<model>`` is ``engine.stats()``:
    where an operator reads the phases."""
    from client_tpu.server.core import ServerCore
    from client_tpu.server.model_repository import Model, ModelRepository
    from client_tpu.testing import InProcessServer

    class StubLlm(Model):
        name = "stub_llm"
        decoupled = True

        def __init__(self):
            self.engine = _stub_engine(_TickingClock(), tile_pages=(2,))

        def shutdown(self):
            self.engine.close()

    model = StubLlm()
    repository = ModelRepository()
    core = ServerCore(repository)
    repository.add_model(model)
    _run_stub(model.engine, [[1, 2, 3], [4, 5]], 6)
    with InProcessServer(core=core, builtin_models=False) as server:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.http_port}/v2/debug/state"
        ) as response:
            block = json.loads(response.read().decode())["llm"]["stub_llm"]
    assert block.pop("stall_log") == []
    stats = json.loads(json.dumps(model.engine.stats()))
    # the process's sums move on between two looks; the loop's do not
    for name in ("gc_ns", "gc_collections", "compile"):
        assert type(block.pop(name)) is type(stats.pop(name)), name
    assert block == stats
    _tiles(model.engine, block)
    for name in COUNTERS:
        assert isinstance(block[name], int) and block[name] > 0, name


class _Held:
    """A device result whose ``block_until_ready`` holds the loop."""

    def __init__(self, value, hold):
        self.value, self.hold = value, hold

    def block_until_ready(self):
        self.hold()

    def __array__(self, dtype=None, copy=None):
        return self.value


def test_a_second_held_in_one_wait_is_a_stall_served_and_logged():
    """The device double holds step 5's result for a second of the fake
    clock: one turn is a stall of cause ``other`` with the second in
    ``wait``, its step is not a steady step, the entry is in
    ``/v2/debug/state`` and in one log record; an engine on a fake clock
    never starts the watch."""
    from client_tpu.observability import StructuredLogger
    from client_tpu.server.core import ServerCore
    from client_tpu.server.model_repository import Model, ModelRepository
    from client_tpu.testing import InProcessServer

    clock = _TickingClock()
    engine = _stub_engine(clock)
    records = []
    engine.logger = StructuredLogger(sink=records.append)
    decode, calls = engine._decode, []

    def held_once(*args):
        ids, *rest = decode(*args)
        calls.append(engine._laps in profiling.WATCH._loops)
        if len(calls) == 5:
            ids = _Held(ids, lambda: setattr(clock, "now", clock.now + S))
        return (ids, *rest)

    S = 1_000_000_000
    engine._decode = held_once
    out = _run_stub(engine, [[1, 2, 3], [4, 5]], 12)
    assert [len(tokens) for tokens in out] == [12, 12]
    assert len(calls) > 5 and not any(calls)  # never watched
    stats = engine.stats()
    _tiles(engine, stats)
    assert stats["stalls"] == {"profiler": 0, "compile": 0, "gc": 0,
                               "other": 1}
    assert S <= stats["stall_phase_ns"]["wait"] < S + 10_000
    assert S <= stats["stall_ns"]["other"] < S + 100_000
    assert stats["steady_steps"] == stats["steps"] - 1 > 5
    assert "turns" not in stats and "profiler_sessions" not in stats
    (entry,) = engine.stall_log()
    assert entry["cause"] == "other" and entry["phase"] == "wait"
    assert entry["steps"] == 1 and entry["wall_ns"] == stats["stall_ns"]["other"]
    assert entry["phase_ns"]["wait"] == stats["stall_phase_ns"]["wait"]
    assert entry["watch_late_ns"] is None and entry["stacks"] == ""
    assert entry["watch_cpu_ns"] is None
    (record,) = [r for r in records if r["event"] == "llm_engine_stall"]
    assert record["model"] == "stub" and record["severity"] == "WARNING"
    assert {k: record[k] for k in entry} == entry

    class StubLlm(Model):
        name = "stub_llm"
        decoupled = True

        def shutdown(self):
            engine.close()

    model = StubLlm()
    model.engine = engine
    repository = ModelRepository()
    core = ServerCore(repository)
    repository.add_model(model)
    with InProcessServer(core=core, builtin_models=False) as server:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.http_port}/v2/debug/state"
        ) as response:
            state = json.loads(response.read().decode())
    assert state["llm"]["stub_llm"]["stall_log"] == [entry]
    assert state["llm"]["stub_llm"]["stalls"]["other"] == 1
    assert state["profiler_sessions"] == json.loads(
        json.dumps(list(profiling.PROCESS.sessions)))


def test_one_watch_thread_a_process_and_none_after_the_last_engine():
    """Two engines on the process's own clock share one watch thread,
    which ends once both loops have ended."""
    import time

    def watchers():
        return [t for t in threading.enumerate() if t.name == "stall-watch"]

    engines = [_stub_engine(time.monotonic_ns) for _ in range(2)]
    for engine in engines:  # on the real clock, as a served engine is
        engine._laps = LapSpans(engine._laps._names)
    seen = []

    async def run():
        seqs = [e.submit([1, 2, 3], max_tokens=6) for e in engines]
        first = asyncio.ensure_future(_collect(seqs[0]))
        await _collect(seqs[1])
        await first
        seen.append((len(watchers()), len(profiling.WATCH._loops)))
        for engine in engines:
            engine.close()
        await asyncio.sleep(0)

    asyncio.run(run())
    assert seen == [(1, 2)]
    assert not profiling.WATCH._loops
    for thread in watchers():
        thread.join(timeout=10)
    assert not watchers() and profiling.WATCH._thread is None


# -- the real engine on the float32 tiny llama ---------------------------------


@pytest.fixture(scope="module")
def spec_model():
    """Prefix sharing and n-gram speculation on, so that all four device
    programs run."""
    import jax.numpy as jnp

    from client_tpu.llm.serving import LlmEngineModel
    from client_tpu.models import llama

    model = LlmEngineModel(
        name="llm_spans",
        config=llama.LlamaConfig.tiny(max_seq_len=64, dtype=jnp.float32),
        engine_config=EngineConfig(block_size=8, num_blocks=1 + 8 * 8,
                                   max_active=8, max_queue=32, max_seq_len=64),
        speculation={"mode": "ngram", "k": 2},
    )
    model.warmup()
    yield model
    model.shutdown()


SHARED = [7, 3, 7, 3, 7, 3, 7, 3, 7, 3, 7, 3, 7, 3, 7, 3, 7, 3]
PROMPTS = [SHARED + [5], SHARED + [9, 2], [1, 2, 3, 1, 2, 3, 1, 2], [11]]


def _generate(model, max_tokens=16):
    """The first prompt until its first token, then the rest at once:
    the second shares the first's two full blocks while it still runs (a
    suffix prefill)."""
    engine = model.engine

    async def run():
        first = engine.submit(PROMPTS[0], max_tokens=max_tokens)
        head = [(await first.__anext__())[0]]
        rest = [engine.submit(p, max_tokens=max_tokens) for p in PROMPTS[1:]]
        out = await asyncio.gather(*[_collect(s) for s in [first] + rest])
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        return [head + out[0]] + out[1:]

    return asyncio.run(run())


def _delta(before, after):
    return {p: after["phase_ns"][p] - before["phase_ns"][p] for p in PHASES}


def test_a_profiler_trace_holds_the_spans_and_changes_no_token(
        spec_model, tmp_path):
    """One traced run of a few toy steps: the ``engine.*`` events sit on
    one host line and their durations are the counters' deltas; the
    programs run under their names; the tokens are the untraced run's."""
    import jax
    from jax.profiler import ProfileData

    untraced = _generate(spec_model)
    before = spec_model.engine.stats()
    with jax.profiler.trace(str(tmp_path)):
        traced = _generate(spec_model)
    after = spec_model.engine.stats()
    assert traced == untraced
    assert _generate(spec_model) == untraced

    found = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    assert len(found) == 1
    lines = collections.defaultdict(lambda: collections.defaultdict(float))
    programs = set()
    for plane in ProfileData.from_file(found[0]).planes:
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith("engine."):
                    lines[(plane.name, line.name)][event.name] += event.duration_ns
                elif event.name.startswith("PjitFunction(llm_"):
                    programs.add(event.name[len("PjitFunction("):-1])
    assert len(lines) == 1, sorted(lines)
    spans = next(iter(lines.values()))
    counted = _delta(before, after)
    assert set(spans) == {f"engine.{p}" for p in PHASES if counted[p]}
    assert sum(spans.values()) == pytest.approx(sum(counted.values()), rel=0.05)
    # `prefill` is four dispatches of a third of a millisecond since the
    # prefill is waited for under `wait`: an annotation's own few tens of
    # microseconds an event get the absolute allowance
    for phase in ("prefill", "dispatch", "wait"):
        assert spans[f"engine.{phase}"] == pytest.approx(
            counted[phase], rel=0.05, abs=200_000)
    assert programs == {"llm_prefill", "llm_prefill_suffix", "llm_decode",
                        "llm_verify"}


def test_the_pallas_kernel_carries_its_name():
    """``name=`` on the ``pallas_call`` is what XLA names the custom call
    after (``%paged_attention.N``), and so the trace's device event."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models import paged_attention

    b, h, kv, d, bs, nb = 2, 4, 2, 8, 8, 2
    _, attn = paged_attention.resolve_decode_attention(
        "pallas_interpret", "cpu")
    jaxpr = jax.make_jaxpr(attn)(
        jnp.zeros([b, 1, h, d]), jnp.zeros([4, bs, kv, d]),
        jnp.zeros([4, bs, kv, d]), jnp.zeros([b, nb], jnp.int32),
        jnp.zeros([b, 1], jnp.int32))
    assert "name=paged_attention" in str(jaxpr)
