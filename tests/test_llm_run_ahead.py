"""The LLM engine's step loop one step ahead of the device.

A greedy decode step's ids stay on the device: step k+1 is built and
dispatched from what the loop knows without step k's tokens, and only
then are k's ids read, booked and streamed. The double here is a model
that really keeps a paged cache (a token a slot, read back through the
page tables a call is handed), so a wrong lane map, a wrong position or
a block written by the wrong owner changes the tokens that follow; its
``ids`` record when the host reads them, and so do a prefill's logits:
an admission is dispatched behind the step in flight and booked an
iteration later, when its logits arrive (section 7 on). The tests of
``jit_llm_decode`` run the jitted programs themselves, on the tiny Llama.

CPU, toy sizes.
"""

import asyncio
import random

import numpy as np
import pytest

from client_tpu.llm import EngineConfig, LlmEngine
from client_tpu.llm.engine import PHASES
from client_tpu.observability import LapSpans

pytestmark = pytest.mark.llm

VOCAB = 97


def _next_token(context) -> int:
    """The model: the token after ``context`` depends on every token of
    it and on where each stands."""
    total = sum((j + 1) * 31 * int(t) for j, t in enumerate(context))
    return (total + 7 * len(context)) % VOCAB


def _logits_row(context) -> np.ndarray:
    """Peaked at the model's token, with a spread wide enough that a
    draw at temperature 1 has real choices."""
    row = np.linspace(0.0, 1.0, VOCAB, dtype=np.float32)
    row[_next_token(context)] = 3.0
    return row


def _alone(prompt, max_tokens):
    """The sequence decoded alone, greedy."""
    context = list(prompt)
    for _ in range(max_tokens):
        context.append(_next_token(context))
    return context[len(prompt):]


def _alone_sampled(prompt, max_tokens, temperature, top_k, seed):
    """The sequence decoded alone through ``_sample_rows``' scalar path:
    one row at a time, float64, the draw keyed by (seed, index)."""
    context = list(prompt)
    for index in range(max_tokens):
        scaled = _logits_row(context).astype(np.float64) / temperature
        if top_k and top_k < VOCAB:
            kth = np.partition(scaled, -top_k)[-top_k]
            scaled = np.where(scaled < kth, -np.inf, scaled)
        scaled -= scaled.max()
        probs = np.exp(scaled)
        probs /= probs.sum()
        rng = np.random.default_rng((seed, index))
        context.append(int(rng.choice(VOCAB, p=probs)))
    return context[len(prompt):]


class _DeviceIds:
    """A step's ids as the engine gets them: on the "device" until the
    host waits for them or copies them, both of which are recorded."""

    def __init__(self, values, step, events):
        self.values = values
        self.step = step
        self.events = events

    def block_until_ready(self):
        self.events.append(("wait", self.step))
        return self

    def __array__(self, dtype=None, copy=None):
        self.events.append(("read", self.step))
        return self.values


class _DeviceLogits:
    """A prefill's logits as the engine gets them: on the "device" until
    the host waits for them or copies them, both of which are recorded
    with the prefill's number. ``lost`` fails the wait, as a device that
    died under the program does."""

    def __init__(self, values, call, events, lost=False):
        self.values = values
        self.call = call
        self.events = events
        self.lost = lost

    def copy_to_host_async(self):
        self.events.append(("logits_copy", self.call))

    def block_until_ready(self):
        self.events.append(("logits_wait", self.call))
        if self.lost:
            raise RuntimeError(f"device lost under prefill {self.call}")
        return self

    def __array__(self, dtype=None, copy=None):
        self.events.append(("logits_read", self.call))
        return self.values


class _PagedModel:
    """Device functions over a paged cache of token values. Written to
    the engine's contract directly (no ``decode_fn_from_logits``): the
    previous ids are read where they are, never through the host."""

    def __init__(self, num_blocks, block_size, fail_at_call=None,
                 full_group=None):
        self.block_size = block_size
        self.pages = np.full([num_blocks, block_size], -1, dtype=np.int64)
        self.events = []
        self.calls = 0
        self.prefills = 0
        self.fail_at_call = fail_at_call
        # the prefill call that raises, and the one whose logits never
        # arrive (the wait for them raises)
        self.fail_at_prefill = None
        self.lose_logits_of = None
        # the row of stacked group tables that is the full group's (a
        # model of several cache groups keeps only that one here)
        self.full_group = full_group
        self.allocator = None  # set once the engine exists

    def _slot(self, table, position):
        return int(table[position // self.block_size]), position % self.block_size

    def _write(self, table, position, token):
        block, slot = self._slot(table, position)
        if block != 0 and self.allocator is not None:
            # a live lane writes only a block that is its own right now
            assert self.allocator.refcount(block) == 1, (block, position)
        self.pages[block, slot] = token

    def _context(self, table, last_position):
        out = []
        for position in range(last_position + 1):
            block, slot = self._slot(table, position)
            if self.allocator is not None:
                assert self.allocator.refcount(block) >= 1, (block, position)
            out.append(int(self.pages[block, slot]))
        return out

    def prefill(self, tokens, page_table, pages, last_index, start):
        self.prefills += 1
        call = self.prefills
        self.events.append(("prefill", call))
        if call == self.fail_at_prefill:
            raise RuntimeError(f"device lost at prefill call {call}")
        if self.full_group is not None:
            page_table = page_table[self.full_group]
        for j in range(last_index + 1):
            self._write(page_table, start + j, int(tokens[0, j]))
        context = self._context(page_table, start + last_index)
        logits = _DeviceLogits(
            _logits_row(context)[None], call, self.events,
            lost=call == self.lose_logits_of)
        return logits, pages

    def decode(self, prev_ids, lane_map, host_tokens, positions,
               page_tables, pages):
        self.calls += 1
        step = self.calls
        self.events.append(("dispatch", step))
        if self.fail_at_call is not None and step >= self.fail_at_call:
            raise RuntimeError(f"device lost at decode call {step}")
        prev = prev_ids.values if isinstance(prev_ids, _DeviceIds) else prev_ids
        if self.full_group is not None:
            page_tables = page_tables[self.full_group]
        bucket = lane_map.shape[0]
        logits = np.zeros([bucket, VOCAB], dtype=np.float32)
        ids = np.zeros([len(prev)], dtype=np.int32)
        for lane in range(bucket):
            table = page_tables[lane]
            if not table.any():
                continue  # a padding lane: everything in the trash block
            token = (
                prev[lane_map[lane]] if lane_map[lane] >= 0
                else host_tokens[lane]
            )
            self._write(table, int(positions[lane]), int(token))
            logits[lane] = _logits_row(
                self._context(table, int(positions[lane]))
            )
            ids[lane] = int(logits[lane].argmax())
        return _DeviceIds(ids, step, self.events), logits, pages

    def verify(self, tokens, positions, lengths, page_tables, pages):
        """The speculative engine's multi-query step: each lane's rows
        written and read in order, its logits read at once."""
        self.calls += 1
        self.events.append(("verify", self.calls))
        logits = np.zeros(tokens.shape + (VOCAB,), dtype=np.float32)
        for lane in range(tokens.shape[0]):
            table = page_tables[lane]
            for t in range(int(lengths[lane])):
                position = int(positions[lane, t])
                self._write(table, position, int(tokens[lane, t]))
                logits[lane, t] = _logits_row(self._context(table, position))
        return logits, pages


class _TrueProposer:
    """Drafts the model's own continuation: every draft is accepted."""

    def propose(self, context, k):
        context = list(context)
        for _ in range(k):
            context.append(_next_token(context))
        return context[-k:]


def _engine(model=None, clock=None, speculative=False, **overrides):
    defaults = dict(block_size=4, num_blocks=65, max_active=4, max_queue=16,
                    max_seq_len=64)
    defaults.update(overrides)
    config = EngineConfig(**defaults)
    groups = [group.kind for group in config.cache_groups]
    model = model or _PagedModel(
        config.num_blocks, config.block_size,
        full_group=groups.index("full") if groups else None)
    kwargs = {"clock_ns": clock} if clock is not None else {}
    if speculative:
        kwargs.update(decode_multi_fn=model.verify, proposer=_TrueProposer())
    engine = LlmEngine(
        model.prefill, model.decode, pages=object(), engine_config=config,
        model_name="paged", **kwargs,
    )
    model.allocator = engine.allocator
    return engine, model


async def _collect(seq, into=None):
    out = [] if into is None else into
    async for token, final in seq:
        out.append(token)
        if final:
            break
    return out


async def _settle():
    for _ in range(3):
        await asyncio.sleep(0)


# -- (1) dispatch before consume ----------------------------------------------


def test_next_step_is_dispatched_before_the_ids_in_flight_are_read():
    engine, model = _engine()

    async def run():
        seqs = [engine.submit([3, 1, 4], max_tokens=8),
                engine.submit([1, 5], max_tokens=8)]
        out = await asyncio.gather(*[_collect(s) for s in seqs])
        await _settle()
        return out

    out = asyncio.run(run())
    assert out == [_alone([3, 1, 4], 8), _alone([1, 5], 8)]
    events = model.events
    touched = {}
    for index, (kind, step) in enumerate(events):
        if kind in ("wait", "read"):
            touched.setdefault(step, index)
    dispatched = {step: i for i, (kind, step) in enumerate(events)
                  if kind == "dispatch"}
    stats = engine.stats()
    # 7 decode steps after the two prefills: every one but the first was
    # dispatched while the one before it was unread
    assert stats["steps"] == model.calls == 7
    assert stats["steps_ahead"] == 6
    for step in range(1, model.calls):
        assert dispatched[step + 1] < touched[step], (step, events)
    # and each step's ids were read exactly once, in order
    reads = [step for kind, step in events if kind == "read"]
    assert reads == list(range(1, model.calls + 1))
    engine.close()


def test_a_lone_step_is_booked_without_a_step_after_it():
    """One token to decode after the prefill's: the step is left in
    flight, finds no lane for a next step, and is consumed as it is."""
    engine, model = _engine()

    async def run():
        out = await _collect(engine.submit([2, 7, 1], max_tokens=2))
        await _settle()
        return out

    assert asyncio.run(run()) == _alone([2, 7, 1], 2)
    stats = engine.stats()
    assert stats["steps"] == 1 and stats["steps_ahead"] == 0
    assert engine._flight is None
    engine.close()


# -- (2) streams equal each sequence decoded alone ----------------------------

_TRAFFIC = [
    ([5, 3, 9, 2, 7], 13), ([8, 1], 21), ([4, 4, 4, 4, 4, 4, 4], 6),
    ([11, 2, 6], 17), ([9], 9), ([7, 7, 3, 1, 2, 9, 8, 4, 6], 11),
    ([12, 5, 1, 3], 3), ([6, 2], 15),
]


@pytest.mark.parametrize("shuffle_seed", [None, 0, 1, 2, 3],
                         ids=["in_order", "perm0", "perm1", "perm2", "perm3"])
@pytest.mark.parametrize("num_blocks", [65, 12], ids=["roomy", "dry_pool"])
def test_greedy_streams_equal_each_sequence_decoded_alone(
        shuffle_seed, num_blocks):
    """Eight sequences of unequal lengths over four lanes: they finish,
    the waiting ones are admitted (batch buckets 4, 2, 1 and back), one
    is cancelled mid-run, and with 11 usable blocks the pool runs dry
    and preempts. With a seed, ``_running`` is permuted at every yield,
    so a lane's place changes between the step in flight and the next."""
    engine, model = _engine(num_blocks=num_blocks, max_queue=16)
    rng = random.Random(shuffle_seed)
    cancel_after = 5

    async def shuffle():
        while True:
            if shuffle_seed is not None:
                rng.shuffle(engine._running)
            await asyncio.sleep(0)

    async def run():
        seqs = [engine.submit(p, max_tokens=m) for p, m in _TRAFFIC]
        shuffler = asyncio.ensure_future(shuffle())
        cancelled = seqs[1]

        async def collect_then_cancel():
            out = []
            async for token, _ in cancelled:
                out.append(token)
                if len(out) == cancel_after:
                    engine.release(cancelled)
                    break
            return out

        tasks = [
            collect_then_cancel() if s is cancelled else _collect(s)
            for s in seqs
        ]
        out = await asyncio.gather(*tasks)
        await _settle()
        shuffler.cancel()
        return out

    out = asyncio.run(run())
    for index, ((prompt, max_tokens), tokens) in enumerate(zip(_TRAFFIC, out)):
        want = _alone(prompt, max_tokens)
        if index == 1:
            want = want[:cancel_after]
        assert tokens == want, index
    stats = engine.stats()
    assert stats["steps_ahead"] > stats["steps"] // 2
    assert stats["cancelled"] == 1
    assert stats["completed"] == len(_TRAFFIC) - 1
    assert (stats["preemptions"] > 0) == (num_blocks == 12)
    assert stats["kv_blocks_in_use"] == 0 and engine._flight is None
    engine.close()


# -- (3) a dry pool with a step in flight -------------------------------------


def test_dry_pool_books_the_step_in_flight_before_it_preempts():
    # 5 usable blocks of 4 tokens: two sequences of 3 + 12 outgrow them
    engine, model = _engine(num_blocks=6, max_seq_len=16, max_active=2)
    seen = []
    preempt = engine._preempt

    def watched_preempt(victim):
        # the victim's booked tokens are all it streamed, and no step
        # that would still write its blocks is unread
        seen.append((engine._flight, victim.seq_id, list(victim.generated),
                     list(streamed[victim.seq_id])))
        blocks = list(victim.blocks)
        preempt(victim)
        assert all(engine.allocator.refcount(b) == 0 for b in blocks)

    engine._preempt = watched_preempt
    streamed = {}

    async def run():
        seqs = [engine.submit([1, 2, 3], max_tokens=12),
                engine.submit([4, 5, 6], max_tokens=12)]
        for seq in seqs:
            streamed[seq.seq_id] = []
        out = await asyncio.gather(
            *[_collect(s, streamed[s.seq_id]) for s in seqs])
        await _settle()
        return out

    out = asyncio.run(run())
    assert out == [_alone([1, 2, 3], 12), _alone([4, 5, 6], 12)]
    stats = engine.stats()
    assert stats["preemptions"] > 0 and seen
    for flight, seq_id, generated, got in seen:
        assert flight is None
        # queued for the consumer, or already with it: never more
        assert got == generated[:len(got)]
    # the loop did run ahead around the preemptions
    assert stats["steps_ahead"] > 0
    assert stats["kv_blocks_in_use"] == 0
    engine.close()


# -- (4) a sampled lane -------------------------------------------------------


def test_a_sampled_lane_holds_the_loop_to_one_step_at_a_time():
    engine, model = _engine()
    ahead_while_sampling = []

    async def run():
        greedy = [engine.submit([3, 1, 4], max_tokens=20),
                  engine.submit([1, 5, 9, 2], max_tokens=16)]
        sampled = engine.submit(
            [2, 6, 5], max_tokens=7,
            parameters={"temperature": 1.0, "top_k": 12, "seed": 1234},
        )

        async def watch():
            async for _ in _each_token(sampled):
                ahead_while_sampling.append(engine.stats()["steps_ahead"])

        out = await asyncio.gather(
            *[_collect(s) for s in greedy], watch())
        await _settle()
        return out[:2], sampled

    (greedy_out, sampled) = asyncio.run(run())
    assert greedy_out == [_alone([3, 1, 4], 20), _alone([1, 5, 9, 2], 16)]
    assert sampled.generated == _alone_sampled([2, 6, 5], 7, 1.0, 12, 1234)
    # while the sampled lane lived no step ran ahead; once it was done
    # the greedy lanes' steps did again
    assert ahead_while_sampling == [0] * 7
    stats = engine.stats()
    assert stats["steps"] == 19
    assert stats["steps_ahead"] == 19 - 6 - 1
    assert stats["phase_ns"]["sample"] > 0
    engine.close()


async def _each_token(seq):
    async for token, final in seq:
        yield token
        if final:
            break


# -- (5) a device failure a step later ----------------------------------------


def test_failed_step_drops_the_step_in_flight_and_survivors_resume():
    engine, model = _engine()
    fatal = []
    engine.on_fatal = fatal.append
    model.fail_at_call = 5
    prompts = [([3, 1, 4], 14), ([1, 5], 11), ([9, 2, 6, 5], 9)]
    streamed = [[] for _ in prompts]

    async def run():
        seqs = [engine.submit(p, max_tokens=m) for p, m in prompts]
        tasks = [asyncio.ensure_future(_collect(s, into))
                 for s, into in zip(seqs, streamed)]
        while not fatal:
            await asyncio.sleep(0)
        await _settle()
        # call 5 raised with call 4 dispatched and unread: it never is
        reads = [step for kind, step in model.events if kind == "read"]
        assert reads == [1, 2, 3]
        assert engine.recovering and engine._flight is None
        at_failure = [list(s) for s in streamed]
        for seq, got in zip(seqs, at_failure):
            # prefill's token and three booked steps, all streamed
            assert got == seq.generated and len(got) == 4
        assert engine.stats()["kv_blocks_in_use"] == 0
        # a new engine over a new cache adopts them
        successor, _ = _engine()
        successor.adopt(engine.detach_survivors())
        out = await asyncio.gather(*tasks)
        await _settle()
        assert successor.stats()["kv_blocks_in_use"] == 0
        successor.close()
        return out

    out = asyncio.run(run())
    assert out == [_alone(p, m) for p, m in prompts]
    assert isinstance(fatal[0], RuntimeError)
    engine.close()


def test_failed_step_without_a_supervisor_fails_the_streams_cleanly():
    engine, model = _engine()
    model.fail_at_call = 3

    async def run():
        seq = engine.submit([3, 1, 4], max_tokens=9)
        got = []
        with pytest.raises(Exception, match="device lost at decode call 3"):
            await _collect(seq, got)
        return got

    # prefill's token and step 1's: step 2 was in flight when 3 raised
    assert asyncio.run(run()) == _alone([3, 1, 4], 2)
    assert engine.stats()["kv_blocks_in_use"] == 0
    engine.close()


# -- (6) the phases still tile the loop's time --------------------------------


class _TickingClock:
    def __init__(self):
        self.now = 0
        self.reads = 0

    def __call__(self):
        self.reads += 1
        self.now += 1_000 + 37 * (self.reads % 11)
        return self.now


class _Boundaries:
    def __init__(self, clock):
        self.clock = clock
        self.first = self.last = None

    def __call__(self):
        self.last = self.clock()
        if self.first is None:
            self.first = self.last
        return self.last


def _witnessed_engine(**overrides):
    clock = _TickingClock()
    engine, model = _engine(clock=clock, **overrides)
    engine._laps = LapSpans(engine._laps._names, clock_ns=_Boundaries(clock))
    return engine


def _tiles(engine):
    stats = engine.stats()
    witness = engine._laps._clock_ns
    assert tuple(stats["phase_ns"]) == PHASES
    assert sum(stats["phase_ns"].values()) == witness.last - witness.first > 0
    return stats


@pytest.mark.parametrize("ending", ["park", "close", "cancel"])
def test_phases_tile_the_loop_with_a_step_in_flight(ending):
    engine = _witnessed_engine()
    in_flight_at = []

    async def run():
        seqs = [engine.submit([3, 1, 4], max_tokens=12),
                engine.submit([1, 5], max_tokens=9)]
        tasks = [asyncio.ensure_future(_collect(s)) for s in seqs]
        while engine._flight is None:
            await asyncio.sleep(0)
        # the loop is not parked, a step is in flight: the phases add up
        # to the last boundary
        in_flight_at.append(_tiles(engine)["steps"])
        if ending == "close":
            engine.close()
        elif ending == "cancel":
            engine.release(seqs[0])
        out = await asyncio.gather(*tasks, return_exceptions=True)
        await _settle()
        return out

    out = asyncio.run(run())
    stats = _tiles(engine)
    assert in_flight_at and engine._flight is None
    assert stats["phase_ns"]["propose"] == 0
    if ending == "park":
        assert out == [_alone([3, 1, 4], 12), _alone([1, 5], 9)]
        assert stats["steps_ahead"] == stats["steps"] - 1 == 10
    elif ending == "close":
        # the step in flight was dropped: failed streams, nothing booked
        assert all(isinstance(o, Exception) for o in out)
        assert stats["steps"] == in_flight_at[0]
    else:
        assert out[1] == _alone([1, 5], 9)
        assert out[0] == _alone([3, 1, 4], 12)[:len(out[0])]
        assert stats["cancelled"] == 1 and stats["kv_blocks_in_use"] == 0
    # parked: a later look finds nothing moved
    assert engine.stats()["phase_ns"] == stats["phase_ns"]
    engine.close()


# -- (7) an admission does not drain the device --------------------------------


async def _until(condition):
    while not condition():
        await asyncio.sleep(0)


def test_prefill_goes_behind_the_step_in_flight_and_the_loop_runs_ahead():
    """With step k dispatched and unread a request arrives: its prefill
    is dispatched before k's ids are read, step k+1 (without the new
    lane) before the prefill's logits are, and the lane joins step k+2
    with its token from the host."""
    engine, model = _engine()
    lanes = {}
    decode = model.decode

    def watched_decode(prev_ids, lane_map, host_tokens, *rest):
        lanes[model.calls + 1] = lane_map.tolist()
        return decode(prev_ids, lane_map, host_tokens, *rest)

    engine._decode = watched_decode

    async def run():
        first = engine.submit([3, 1, 4], max_tokens=12)
        task = asyncio.ensure_future(_collect(first))
        await _until(lambda: engine.steps >= 3 and engine._flight is not None)
        second = engine.submit([1, 5, 9], max_tokens=6)
        out = [await task, await _collect(second)]
        await _settle()
        return out

    out = asyncio.run(run())
    assert out == [_alone([3, 1, 4], 12), _alone([1, 5, 9], 6)]
    events = model.events
    at = {event: index for index, event in enumerate(events)}
    prefill = at[("prefill", 2)]
    in_flight = max(step for kind, step in events[:prefill]
                    if kind == "dispatch")
    # the copy of the logits starts with the dispatch
    assert events[prefill + 1] == ("logits_copy", 2)
    # behind the step in flight: that step's ids are still unread
    assert prefill < at[("wait", in_flight)] < at[("read", in_flight)]
    # the next step is dispatched across the prefill, without the lane
    assert prefill < at[("dispatch", in_flight + 1)] < at[("logits_wait", 2)]
    assert at[("logits_wait", 2)] < at[("logits_read", 2)]
    assert lanes[in_flight + 1] == [0]
    # and booked from the host's token in the step after
    assert at[("logits_read", 2)] < at[("dispatch", in_flight + 2)]
    assert lanes[in_flight + 2] == [0, -1]
    assert lanes[in_flight + 3] == [0, 1]
    stats = engine.stats()
    assert stats["prefills"] == 2 and stats["prefills_behind"] == 1
    assert stats["steps_ahead"] == stats["steps"] - 1
    engine.close()


def _draws(kind, index):
    sampled = kind == "sampled" or (kind == "mixed" and index % 2)
    if not sampled:
        return None
    return {"temperature": 1.0, "top_k": 12, "seed": 100 + index}


@pytest.mark.parametrize("draws", ["greedy", "sampled", "mixed"])
@pytest.mark.parametrize("num_blocks", [65, 12], ids=["roomy", "dry_pool"])
def test_streams_with_admissions_mid_run_equal_each_sequence_decoded_alone(
        draws, num_blocks):
    """Eight unequal sequences over four lanes, three there from the
    start and one more every other step: admissions fall while steps
    are in flight, lanes end under them, and at 11 usable blocks the
    pool runs dry and preempts. Greedy streams are each sequence's own
    alone; a sampled one keeps its seed's draws."""
    engine, model = _engine(num_blocks=num_blocks, max_queue=16)

    async def run():
        seqs = [engine.submit(p, max_tokens=m, parameters=_draws(draws, i))
                for i, (p, m) in enumerate(_TRAFFIC[:3])]
        tasks = [asyncio.ensure_future(_collect(s)) for s in seqs]
        for i, (p, m) in enumerate(_TRAFFIC[3:], start=3):
            after = engine.steps + 2
            await _until(lambda: engine.steps >= after)
            seq = engine.submit(p, max_tokens=m, parameters=_draws(draws, i))
            tasks.append(asyncio.ensure_future(_collect(seq)))
        out = await asyncio.gather(*tasks)
        await _settle()
        return out

    out = asyncio.run(run())
    for index, ((prompt, max_tokens), tokens) in enumerate(zip(_TRAFFIC, out)):
        parameters = _draws(draws, index)
        want = _alone(prompt, max_tokens) if parameters is None else (
            _alone_sampled(prompt, max_tokens, parameters["temperature"],
                           parameters["top_k"], parameters["seed"]))
        assert tokens == want, index
    stats = engine.stats()
    assert stats["completed"] == len(_TRAFFIC)
    assert stats["prefills"] == len(_TRAFFIC) + stats["preemptions"]
    assert (stats["preemptions"] > 0) == (num_blocks == 12)
    if draws == "greedy":
        assert stats["prefills_behind"] >= 5
        assert stats["steps_ahead"] > stats["steps"] * 3 // 4
    elif draws == "sampled":
        # a sampled lane lives from the first step to the last: every
        # step is consumed where it was dispatched, no prefill finds one
        assert stats["prefills_behind"] == stats["steps_ahead"] == 0
    assert stats["kv_blocks_in_use"] == 0 and engine._flight is None
    assert engine._admitting == []
    engine.close()


def _grouped_engine(**overrides):
    """An engine over a full, a window and a state cache group: a
    sequence holds blocks, a ring and a slot."""
    from client_tpu.models.engine_model import (
        FULL, STATE, WINDOW, CacheGroup)

    return _engine(
        cache_groups=(CacheGroup(WINDOW, (0,), window=8),
                      CacheGroup(FULL, (1,)), CacheGroup(STATE, (2,))),
        prefix_sharing=False, **overrides)


def test_cancelled_while_its_admission_is_pending_frees_all_and_streams_nothing():
    engine, model = _grouped_engine()
    held = {}

    async def run():
        first = engine.submit([3, 1, 4], max_tokens=12)
        task = asyncio.ensure_future(_collect(first))
        await _until(lambda: engine.steps >= 2 and engine._flight is not None)
        alone = engine.stats()
        second = engine.submit([1, 5, 9, 2, 6], max_tokens=6)
        await _until(lambda: engine._admitting)
        assert [p.seq for p in engine._admitting] == [second]
        pending = engine.stats()
        held["blocks"] = [
            now - before for now, before in zip(
                pending["kv_blocks_in_use_by_group"],
                alone["kv_blocks_in_use_by_group"])]
        held["slots"] = (pending["state_slots_in_use"],
                         alone["state_slots_in_use"])
        assert second.blocks and second.rings[0] and second.slots
        # neither waiting nor running while it is pending
        assert pending["waiting_sequences"] == 0
        assert pending["active_sequences"] == 1
        engine.release(second)
        await _settle()
        freed = engine.stats()
        assert engine._admitting == [] and second.state == "done"
        assert freed["state_slots_in_use"] == 1
        assert freed["kv_blocks_in_use_by_group"][0] == (
            alone["kv_blocks_in_use_by_group"][0])
        out = await task
        await _settle()
        return out, second

    out, second = asyncio.run(run())
    assert out == _alone([3, 1, 4], 12)
    # two blocks of the full group, a ring of three, one slot
    assert held == {"blocks": [3, 2, 1], "slots": (2, 1)}
    assert second.generated == [] and second.rings == [] == second.slots
    assert second._out.get_nowait()[0] == "end" and second._out.empty()
    # its prefill ran (the blocks were its own), its logits were never read
    assert ("prefill", 2) in model.events
    assert ("logits_wait", 2) not in model.events
    assert ("logits_read", 2) not in model.events
    stats = engine.stats()
    assert stats["cancelled"] == 1 and stats["completed"] == 1
    assert stats["tokens_generated"] == 12
    assert stats["kv_blocks_in_use_by_group"] == [0, 0, 0]
    assert stats["state_slots_in_use"] == 0
    engine.close()


@pytest.mark.parametrize("fails", ["at_dispatch", "at_the_logits"])
def test_failed_prefill_behind_a_step_quarantines_and_survivors_resume(fails):
    """The prefill raises (or its logits never arrive) with a decode
    step in flight: the engine is quarantined, the pending sequence has
    streamed nothing and holds nothing, and all three streams resume on
    a successor as if nothing had happened."""
    engine, model = _engine()
    fatal = []
    engine.on_fatal = fatal.append
    if fails == "at_dispatch":
        model.fail_at_prefill = 3
    else:
        model.lose_logits_of = 3
    prompts = [([3, 1, 4], 14), ([1, 5], 11), ([9, 2, 6, 5], 9)]
    streamed = [[] for _ in prompts]

    async def run():
        seqs = [engine.submit(p, max_tokens=m) for p, m in prompts[:2]]
        tasks = [asyncio.ensure_future(_collect(s, into))
                 for s, into in zip(seqs, streamed)]
        await _until(lambda: engine.steps >= 3 and engine._flight is not None)
        seqs.append(engine.submit(*prompts[2][:1], max_tokens=prompts[2][1]))
        tasks.append(asyncio.ensure_future(_collect(seqs[2], streamed[2])))
        await _until(lambda: fatal)
        await _settle()
        assert engine.recovering and engine._flight is None
        assert engine._admitting == []
        assert engine.stats()["kv_blocks_in_use"] == 0
        assert engine.stats()["recovery_survivors"] == 3
        # nothing of the pending sequence, and of the others what was
        # booked: the step in flight (and, where the logits failed, the
        # step dispatched across the prefill) was dropped unread
        assert streamed[2] == [] and seqs[2].generated == []
        for seq, got in zip(seqs[:2], streamed):
            assert got == seq.generated
        at_failure = [len(s) for s in streamed]
        successor, _ = _engine()
        successor.adopt(engine.detach_survivors())
        out = await asyncio.gather(*tasks)
        await _settle()
        assert successor.stats()["kv_blocks_in_use"] == 0
        successor.close()
        return out, at_failure

    out, at_failure = asyncio.run(run())
    assert out == [_alone(p, m) for p, m in prompts]
    assert isinstance(fatal[0], RuntimeError)
    dispatched = max(step for kind, step in model.events if kind == "dispatch")
    reads = [step for kind, step in model.events if kind == "read"]
    # the newest step was in flight, and never read
    assert reads == list(range(1, dispatched))
    after_prefill = model.events[model.events.index(("prefill", 3)):]
    across = [step for kind, step in after_prefill if kind == "dispatch"]
    if fails == "at_dispatch":
        assert "prefill call 3" in str(fatal[0])
        assert across == []
    else:
        # the failure surfaced an iteration later, at the wait: a step
        # went across the prefill and the one before it was booked
        assert "under prefill 3" in str(fatal[0])
        assert across == [dispatched]
        assert ("read", dispatched - 1) in after_prefill
        assert ("logits_read", 3) not in model.events
    assert at_failure[0] == 1 + len(reads)
    assert engine.stats()["prefills_behind"] == 1
    engine.close()


def test_two_pending_admissions_complete_in_admission_order():
    engine, model = _engine()
    first_tokens = []

    async def collect(name, seq):
        out = []
        async for token in _each_token(seq):
            if not out:
                first_tokens.append(name)
            out.append(token)
        return out

    async def run():
        first = engine.submit([3, 1, 4], max_tokens=12)
        tasks = [asyncio.ensure_future(collect("a", first))]
        await _until(lambda: engine.steps >= 2 and engine._flight is not None)
        second = engine.submit([1, 5, 9], max_tokens=5)
        third = engine.submit([2, 6], max_tokens=7)
        tasks += [asyncio.ensure_future(collect("b", second)),
                  asyncio.ensure_future(collect("c", third))]
        await _until(lambda: engine._admitting)
        assert [p.seq for p in engine._admitting] == [second, third]
        assert engine.stats()["active_sequences"] == 1
        out = await asyncio.gather(*tasks)
        await _settle()
        return out

    out = asyncio.run(run())
    assert out == [_alone([3, 1, 4], 12), _alone([1, 5, 9], 5),
                   _alone([2, 6], 7)]
    assert first_tokens == ["a", "b", "c"]
    events = model.events
    at = {event: index for index, event in enumerate(events)}
    # both prefills, then the step across them, then their logits in turn
    assert at[("prefill", 2)] < at[("prefill", 3)] < at[("logits_wait", 2)]
    between = [e for e in events[at[("prefill", 3)]:at[("logits_wait", 2)]]
               if e[0] == "dispatch"]
    assert len(between) == 1
    assert (at[("logits_wait", 2)] < at[("logits_read", 2)]
            < at[("logits_wait", 3)] < at[("logits_read", 3)])
    stats = engine.stats()
    assert stats["prefills_behind"] == 2
    assert stats["steps_ahead"] == stats["steps"] - 1
    engine.close()


def test_a_speculative_engine_books_each_admission_where_it_dispatched_it():
    """No step is ever in flight under speculation, so the order of
    calls is what it always was: a prefill, its logits, the next
    prefill, its logits, and only then the step."""
    engine, model = _engine(speculative=True, spec_k=3)
    assert engine.stats()["speculative"]

    async def run():
        seqs = [engine.submit([3, 1, 4], max_tokens=12),
                engine.submit([1, 5], max_tokens=9)]
        tasks = [asyncio.ensure_future(_collect(s)) for s in seqs]
        await _until(lambda: engine.steps >= 2)
        assert engine._admitting == [] and engine._flight is None
        tasks.append(asyncio.ensure_future(
            _collect(engine.submit([9, 2, 6, 5], max_tokens=7))))
        out = await asyncio.gather(*tasks)
        await _settle()
        return out

    out = asyncio.run(run())
    assert out == [_alone([3, 1, 4], 12), _alone([1, 5], 9),
                   _alone([9, 2, 6, 5], 7)]
    events = model.events
    calls = [(index, event) for index, event in enumerate(events)
             if event[0] in ("prefill", "dispatch", "verify")]
    assert [event for _, event in calls[:3]] == [
        ("prefill", 1), ("prefill", 2), ("verify", 1)]
    for (index, (kind, call)), (after, _) in zip(calls, calls[1:]):
        if kind == "prefill":
            assert events[index + 1:index + 4] == [
                ("logits_copy", call), ("logits_wait", call),
                ("logits_read", call)]
            assert after > index + 3
    stats = engine.stats()
    assert stats["prefills"] == 3 and stats["spec_steps"] > 0
    assert stats["prefills_behind"] == stats["steps_ahead"] == 0
    assert stats["tokens_per_step"] > 2
    engine.close()


@pytest.mark.parametrize("draws", ["greedy", "sampled"])
def test_steps_ahead_and_prefills_behind_count_what_happened(draws):
    engine, model = _engine()
    sampled = _draws(draws, 0)

    async def run():
        first = engine.submit([3, 1, 4], max_tokens=12, parameters=sampled)
        task = asyncio.ensure_future(_collect(first))
        await _until(lambda: engine.steps >= 3)
        await asyncio.gather(
            task, _collect(engine.submit([1, 5, 9], max_tokens=6)))
        await _settle()
        middle = engine.stats()
        assert engine._flight is None and not engine._running
        # an idle engine: nothing in flight to go behind
        await _collect(engine.submit([2, 6], max_tokens=4))
        await _settle()
        return middle

    middle = asyncio.run(run())
    stats = engine.stats()
    assert stats["prefills"] == 3 and middle["prefills"] == 2
    if draws == "greedy":
        assert middle["prefills_behind"] == stats["prefills_behind"] == 1
        # every step but the first of each busy spell ran ahead
        assert middle["steps_ahead"] == middle["steps"] - 1
        assert stats["steps_ahead"] == stats["steps"] - 2
    else:
        # the sampled lane's steps are consumed where they are
        # dispatched: the second prefill finds no step in flight. Once
        # the lane has ended, the greedy ones' steps run ahead again
        assert stats["prefills_behind"] == 0
        assert 0 < stats["steps_ahead"] < stats["steps"] - 11
    assert stats["steps"] == 11 + 3
    engine.close()


def test_a_prefix_published_at_dispatch_is_matched_while_its_writer_is_pending():
    """Two requests with one prefix arrive together behind a running
    lane: the second references the first's blocks while the first's
    prefill is still unread, and reads what that prefill wrote."""
    engine, model = _engine()
    prefix = [7, 3, 7, 3, 8, 1, 8, 1]  # two full blocks

    async def run():
        first = engine.submit([3, 1, 4], max_tokens=12)
        tasks = [asyncio.ensure_future(_collect(first))]
        await _until(lambda: engine.steps >= 2 and engine._flight is not None)
        writer = engine.submit(prefix + [5], max_tokens=6)
        reader = engine.submit(prefix + [9, 2], max_tokens=5)
        tasks += [asyncio.ensure_future(_collect(s)) for s in (writer, reader)]
        await _until(lambda: engine._admitting)
        assert [p.seq for p in engine._admitting] == [writer, reader]
        assert reader.shared_blocks == 2 and writer.shared_blocks == 0
        assert reader.blocks[:2] == writer.blocks[:2]
        assert engine.stats()["kv_blocks_shared"] == 2
        out = await asyncio.gather(*tasks)
        await _settle()
        return out

    out = asyncio.run(run())
    assert out == [_alone([3, 1, 4], 12), _alone(prefix + [5], 6),
                   _alone(prefix + [9, 2], 5)]
    stats = engine.stats()
    assert stats["prefix_cache_hits"] == 2
    assert stats["prefix_block_demand"] == 4
    assert stats["prefills_behind"] == 2
    assert stats["kv_blocks_in_use"] == 0
    engine.close()


@pytest.mark.parametrize("ending", ["park", "close", "cancel"])
def test_phases_tile_the_loop_with_an_admission_pending(ending):
    engine = _witnessed_engine()
    pending_at = []

    async def run():
        first = engine.submit([3, 1, 4], max_tokens=12)
        tasks = [asyncio.ensure_future(_collect(first))]
        await _until(lambda: engine.steps >= 2 and engine._flight is not None)
        second = engine.submit([1, 5, 9], max_tokens=6)
        tasks.append(asyncio.ensure_future(_collect(second)))
        await _until(lambda: engine._admitting)
        # not parked, a step in flight and a prefill unread: the phases
        # add up to the last boundary
        pending_at.append(_tiles(engine))
        if ending == "close":
            engine.close()
        elif ending == "cancel":
            engine.release(second)
        out = await asyncio.gather(*tasks, return_exceptions=True)
        await _settle()
        return out

    out = asyncio.run(run())
    stats = _tiles(engine)
    assert pending_at and engine._flight is None and engine._admitting == []
    before = pending_at[0]["phase_ns"]
    assert before["prefill"] > 0
    if ending == "park":
        assert out == [_alone([3, 1, 4], 12), _alone([1, 5, 9], 6)]
        # the second prefill was waited for and read under these two
        assert stats["phase_ns"]["wait"] > before["wait"]
        assert stats["phase_ns"]["readback"] > before["readback"]
        assert stats["prefills_behind"] == 1
    elif ending == "close":
        assert all(isinstance(o, Exception) for o in out)
        assert stats["steps"] == pending_at[0]["steps"]
        assert stats["tokens_generated"] == pending_at[0]["tokens_generated"]
    else:
        assert out == [_alone([3, 1, 4], 12), []]
        assert stats["cancelled"] == 1
    assert stats["kv_blocks_in_use"] == 0
    assert stats["phase_ns"]["prefill"] == before["prefill"]
    # parked: a later look finds nothing moved
    assert engine.stats()["phase_ns"] == stats["phase_ns"]
    engine.close()


def test_the_benchmark_reads_the_share_of_prefills_that_went_behind_a_step():
    """``engine.admits_behind_share`` is a metric file over a reader the
    benchmark has (``counters:delta_ratio``) and two keys ``stats()``
    really serves; ``BENCHMARK.json`` lists it for all eight cells; a
    parent without the counter reads nothing and leaves it out."""
    import json
    import os
    import types

    from benchmark import run as harness

    with open(os.path.join(harness.ROOT, "benchmark", "metrics",
                           "engine.admits_behind_share.json")) as f:
        metric = json.load(f)
    assert metric["reader"] == "counters:delta_ratio"
    assert metric["params"]["scale"] == 100
    engine, model = _engine()
    snapshots = []

    async def run():
        first = engine.submit([3, 1, 4], max_tokens=14)
        task = asyncio.ensure_future(_collect(first))
        await _until(lambda: engine.steps >= 2 and engine._flight is not None)
        snapshots.append({"engine": json.loads(json.dumps(engine.stats()))})
        for prompt in ([1, 5, 9], [2, 6]):
            await _collect(engine.submit(prompt, max_tokens=3))
        await task
        await _settle()
        # and one onto the idle engine: three prefills, two of them behind
        await _collect(engine.submit([7, 7], max_tokens=2))
        await _settle()
        snapshots.append({"engine": json.loads(json.dumps(engine.stats()))})

    asyncio.run(run())
    before, after = snapshots
    for key in ("numerator", "denominator"):
        source, name = metric["params"][key].split(":")
        assert source == "engine" and name in after["engine"]
        assert isinstance(after["engine"][name], int)
    window = types.SimpleNamespace(before=before, after=after)
    value, unit = harness.read_metric(window, "engine.admits_behind_share")
    assert unit == "%" and value == pytest.approx(100 * 2 / 3)
    for snapshot in (before, after):
        del snapshot["engine"]["prefills_behind"]
    assert harness.read_metric(window, "engine.admits_behind_share")[0] is None
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    (entry,) = [m for m in benchmark["per_layer"]
                if m["name"] == "engine.admits_behind_share"]
    cells = [w["name"] for w in benchmark["workloads"]]
    assert entry["workloads"] == cells and len(cells) == 8
    (ahead,) = [m for m in benchmark["per_layer"]
                if m["name"] == "engine.steps_ahead_share"]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == metric[key] == ahead[key]
    engine.close()


# -- the seam to the device: jit_llm_decode -----------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    import jax.numpy as jnp

    from client_tpu.llm.serving import LlmEngineModel
    from client_tpu.models import llama

    config = llama.LlamaConfig.tiny(max_seq_len=32, dtype=jnp.float32)
    model = LlmEngineModel(
        config=config,
        engine_config=EngineConfig(block_size=4, num_blocks=33, max_active=6,
                                   max_queue=8, max_seq_len=32),
    )
    model.warmup()
    yield model
    model.shutdown()


def test_jit_llm_decode_selects_tokens_and_returns_the_argmax(tiny_model):
    """The program picks each lane's token from the previous ids or the
    host's, and its ids are the first argmax of the float32 logits it
    also returns, zero-padded to ``ids_width`` whatever the bucket."""
    _, decode, _ = tiny_model._device_fns
    width = tiny_model.engine_config.ids_width
    assert width == 8
    pages = tiny_model.engine._pages
    table = np.zeros([4, 2], np.int32)
    table[:, 0] = [1, 2, 3, 4]
    positions = np.array([1, 2, 3, 0], np.int32)
    tokens = np.array([17, 5, 40, 9], np.int32)
    none = np.full([4], -1, np.int32)
    ids_host, logits_host, pages = decode(
        np.zeros([width], np.int32), none, tokens, positions, table, pages)
    logits_host = np.asarray(logits_host)
    assert logits_host.dtype == np.float32
    assert np.asarray(ids_host).dtype == np.int32
    assert np.asarray(ids_host).tolist() == (
        logits_host.argmax(-1).tolist() + [0] * (width - 4))
    # the same tokens, now found on the "device": lanes 0..2 through a
    # permuting map out of a previous ids vector, lane 3 from the host
    prev = np.zeros([width], np.int32)
    prev[[6, 0, 3]] = tokens[:3]
    lane_map = np.array([6, 0, 3, -1], np.int32)
    ids_dev, logits_dev, pages = decode(
        prev, lane_map, np.array([0, 0, 0, 9], np.int32), positions, table,
        pages)
    np.testing.assert_array_equal(np.asarray(logits_dev), logits_host)
    np.testing.assert_array_equal(np.asarray(ids_dev), np.asarray(ids_host))
    # handed back as the device array it is, at another batch bucket
    ids_next, logits_next, pages = decode(
        ids_dev, np.array([2, -1], np.int32), np.array([0, 23], np.int32),
        np.array([4, 1], np.int32), table[[2, 3]], pages)
    assert np.asarray(ids_next).shape == (width,)
    assert np.asarray(ids_next)[:2].tolist() == (
        np.asarray(logits_next).argmax(-1).tolist())
    tiny_model.engine._pages = pages


def test_engine_over_the_jitted_programs_runs_ahead_and_matches_alone(
        tiny_model):
    """The real programs under the loop: streams of a batch that runs
    ahead equal each prompt served alone (which runs ahead of nothing
    it shares a batch with), and sampled streams keep their seed."""
    engine = tiny_model.engine
    prompts = [[5, 9, 17, 3], [1, 2, 3], [40, 41, 42, 43, 44], [7, 8],
               [11, 12, 13], [20]]
    lengths = [9, 14, 6, 11, 4, 8]
    sampled = {"temperature": 0.8, "top_k": 20, "seed": 77}

    async def serve(indices, parameters=None):
        seqs = [engine.submit(prompts[i], max_tokens=lengths[i],
                              parameters=parameters) for i in indices]
        out = await asyncio.gather(*[_collect(s) for s in seqs])
        await _settle()
        return out

    async def run():
        alone = [(await serve([i]))[0] for i in range(len(prompts))]
        before = engine.stats()
        together = await serve(range(len(prompts)))
        after = engine.stats()
        sampled_alone = (await serve([1], sampled))[0]
        mixed = await asyncio.gather(serve([0, 3]), serve([1], sampled))
        held = engine.stats()
        return alone, together, before, after, sampled_alone, mixed, held

    alone, together, before, after, sampled_alone, mixed, held = (
        asyncio.run(run()))
    assert together == alone
    steps = after["steps"] - before["steps"]
    assert steps == max(lengths) - 1
    assert after["steps_ahead"] - before["steps_ahead"] == steps - 1
    assert mixed[0] == [alone[0], alone[3]]
    assert mixed[1] == [sampled_alone] and sampled_alone != alone[1]
    # 13 sampled steps alone, then 13 with greedy company: none ahead
    assert held["steps"] - after["steps"] == 26
    assert held["steps_ahead"] == after["steps_ahead"]
    assert held["kv_blocks_in_use"] == held["kv_blocks_shared"]


def test_jitted_prefills_go_behind_the_step_in_flight_and_match_alone(
        tiny_model):
    """The real programs with requests arriving mid-run: each prefill
    is dispatched behind a jitted step in flight, its logits an
    un-waited device array whose copy was started, and every stream is
    the prompt's own alone."""
    engine = tiny_model.engine
    prompts = [[5, 9, 17, 3], [1, 2, 3], [40, 41, 42, 43, 44], [7, 8]]
    lengths = [14, 9, 6, 8]

    async def alone(i):
        out = await _collect(engine.submit(prompts[i], max_tokens=lengths[i]))
        await _settle()
        return out

    async def run():
        want = [await alone(i) for i in range(len(prompts))]
        before = engine.stats()
        tasks = [asyncio.ensure_future(
            _collect(engine.submit(prompts[0], max_tokens=lengths[0])))]
        for i in range(1, len(prompts)):
            after = engine.steps + 2
            await _until(lambda: engine.steps >= after
                         and engine._flight is not None)
            tasks.append(asyncio.ensure_future(
                _collect(engine.submit(prompts[i], max_tokens=lengths[i]))))
        got = await asyncio.gather(*tasks)
        await _settle()
        return want, got, before, engine.stats()

    want, got, before, after = asyncio.run(run())
    assert got == want
    assert after["prefills"] - before["prefills"] == 4
    assert after["prefills_behind"] - before["prefills_behind"] == 3
    steps = after["steps"] - before["steps"]
    assert after["steps_ahead"] - before["steps_ahead"] == steps - 1
    assert after["kv_blocks_in_use"] == after["kv_blocks_shared"]
    assert engine._admitting == []


def test_every_streamed_token_comes_out_of_sample_rows():
    """The ids a greedy step leaves on the device still pass through
    ``_sample_rows`` when they are booked: it stays the one place a
    token is produced (the benchmark's broken-path control alters
    tokens there, `benchmark/lib/serving_side.py`)."""
    engine, model = _engine()
    sample, seen = engine._sample_rows, []

    def watched(items):
        picks = sample(items)
        seen.extend((seq.seq_id, index, pick)
                    for (seq, _, index), pick in zip(items, picks))
        return picks

    engine._sample_rows = watched

    async def run():
        seqs = [engine.submit([3, 1, 4], max_tokens=7),
                engine.submit([1, 5], max_tokens=5)]
        out = await asyncio.gather(*[_collect(s) for s in seqs])
        await _settle()
        return [s.seq_id for s in seqs], out

    ids, out = asyncio.run(run())
    assert engine.stats()["steps_ahead"] > 0
    for seq_id, tokens in zip(ids, out):
        assert [(i, t) for s, i, t in seen if s == seq_id] == list(
            enumerate(tokens))
