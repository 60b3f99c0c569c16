"""Lap spans in the LLM engine's step loop (``LapSpans`` + ``PHASES``).

- the helper alone, on a fake clock: phases tile the un-parked wall
  time, parked time is in no phase, no profiler is no failure;
- stub engines on a clock that ticks at every read: the sum of
  ``phase_ns`` IS the clock's time from the loop's first boundary to its
  last, over plain decode, prefills with preemption, and speculation;
  the counters beside them count what was submitted and never go back;
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
            "tokens_generated", "attn_blocks_live", "attn_blocks_bucket")


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


def _stub_engine(clock, speculative=False, **overrides):
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
    )
    # the laps read the engine's clock through a witness of their own
    engine._laps = LapSpans(engine._laps._names, clock_ns=_Boundaries(clock))
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


def test_every_counter_is_in_stats_and_never_goes_back():
    engine = _stub_engine(_TickingClock(), num_blocks=5, max_seq_len=16)
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
            self.engine = _stub_engine(_TickingClock())

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
    assert block == json.loads(json.dumps(model.engine.stats()))
    _tiles(model.engine, block)
    for name in COUNTERS:
        assert isinstance(block[name], int) and block[name] > 0, name


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
    for phase in ("prefill", "dispatch", "wait"):
        assert spans[f"engine.{phase}"] == pytest.approx(counted[phase], rel=0.05)
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
