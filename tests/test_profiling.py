"""Hot-path profiling tests (PR-6): stage-CPU accounting units and
calibration, the overhead guarantee as counts (zero clock reads while
disabled, the default; a fixed number of reads and one sample a stage a
request while enabled), the wall-stack sampler on fake clocks,
collapsed-stack/speedscope
golden exports, the /v2/debug/profile + /v2/debug/profiling endpoints,
concurrent-scrape safety with /metrics, gRPC-vs-HTTP stage-CPU agreement
on the same server, the collector/report reduction, and the
--profile-server / --flamegraph-out CLI end to end.
"""

import asyncio
import json
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import client_tpu.grpc as grpcclient
import client_tpu.http as httpclient
from client_tpu.observability.metrics import histogram_totals, parse_exposition
from client_tpu.observability import profiling
from client_tpu.observability.profiling import (
    CAUSES,
    PROCESS,
    STALL_NS,
    LapSpans,
    ProcessEvents,
    ProfileResult,
    StageCpuAccounting,
    StallWatch,
    WallProfiler,
    maybe_jax_trace,
    stage_scope,
)
from client_tpu.perf.metrics_collector import MetricsCollector
from client_tpu.perf.records import ServerMetricsSummary
from client_tpu.perf.report import format_wire_gap
from client_tpu.testing import InProcessServer

pytestmark = pytest.mark.profiling


def _simple_inputs(mod):
    in0 = np.arange(16, dtype=np.int32).reshape(1, 16)
    in1 = np.ones([1, 16], dtype=np.int32)
    a = mod.InferInput("INPUT0", [1, 16], "INT32")
    a.set_data_from_numpy(in0)
    b = mod.InferInput("INPUT1", [1, 16], "INT32")
    b.set_data_from_numpy(in1)
    return [a, b]


class _FakeClock:
    """Deterministic ns clock: advances by ``step`` per call."""

    def __init__(self, step=100, start=0):
        self.t = start
        self.step = step
        self.calls = 0

    def __call__(self):
        self.calls += 1
        self.t += self.step
        return self.t


# ---------------------------------------------------------------------------
# StageCpuAccounting units


def test_accounting_disabled_is_inert():
    cpu = _FakeClock(step=10)
    wall = _FakeClock(step=1)
    acct = StageCpuAccounting(
        cpu_clock_ns=cpu, wall_clock_ns=wall, auto_calibrate=False
    )
    assert acct.enabled is False
    # the one-branch guard: take() is False, no clock was read, nothing
    # books even if account() is called directly
    assert acct.take() is False
    acct.account("compute", 123)
    assert acct.snapshot() == {}
    assert cpu.calls == 0 and wall.calls == 0


def test_accounting_books_and_aggregates():
    cpu = _FakeClock(step=1000)
    acct = StageCpuAccounting(cpu_clock_ns=cpu, auto_calibrate=False)
    acct.enable()
    assert acct.take() is True  # stride 1 without calibration
    c0 = acct.cpu_now()
    c1 = acct.cpu_now()
    acct.account("frontend_decode", c1 - c0)
    acct.account("compute", 8000, count=4)  # merged chunk of 4 requests
    acct.account("queue_wait", 0, wall_ns=500, count=2)
    acct.account("readback", -5)  # clock anomaly clamps to 0
    snap = acct.snapshot()
    assert snap["frontend_decode"] == {"count": 1, "cpu_ns": 1000, "wall_ns": 0}
    assert snap["compute"] == {"count": 4, "cpu_ns": 8000, "wall_ns": 0}
    assert snap["queue_wait"] == {"count": 2, "cpu_ns": 0, "wall_ns": 500}
    assert snap["readback"]["cpu_ns"] == 0
    acct.disable()
    assert acct.take() is False


def test_accounting_metrics_hook_receives_bookings():
    seen = []
    acct = StageCpuAccounting(
        metrics_hook=lambda stage, cpu_ns, count: seen.append(
            (stage, cpu_ns, count)
        ),
        auto_calibrate=False,
    )
    acct.enable()
    acct.account("encode", 2500, count=5)
    assert seen == [("encode", 2500, 5)]


def test_calibration_expensive_cpu_clock_falls_back_to_wall_proxy():
    # the cpu clock "costs" 50 us per call (it advances the shared wall
    # clock when read), so calibration must reject it
    state = {"t": 0}

    def wall():
        state["t"] += 100
        return state["t"]

    def cpu():
        state["t"] += 50_000
        return state["t"] // 10_000_000 * 10_000_000

    acct = StageCpuAccounting(cpu_clock_ns=cpu, wall_clock_ns=wall)
    acct.enable()
    assert acct.clock_mode == "wall_proxy"
    assert acct.sample_stride == 1  # the wall clock itself is cheap
    # cpu_now() now reads the wall clock (+100/call), not the expensive
    # cpu clock (+50_000/call)
    assert acct.cpu_now() - acct.cpu_now() == -100


def test_calibration_coarse_cpu_clock_falls_back_to_wall_proxy():
    # cheap but tick-quantized cpu clock: never advances during the
    # bounded calibration spin -> coarse -> wall proxy
    wall = _FakeClock(step=1_000_000)

    def cpu():
        return 42

    acct = StageCpuAccounting(cpu_clock_ns=cpu, wall_clock_ns=wall)
    acct.enable()
    assert acct.clock_mode == "wall_proxy"


def test_calibration_good_cpu_clock_stays_thread_cpu():
    wall = _FakeClock(step=50)
    cpu = _FakeClock(step=200)
    acct = StageCpuAccounting(cpu_clock_ns=cpu, wall_clock_ns=wall)
    acct.enable()
    assert acct.clock_mode == "thread_cpu"
    assert acct.sample_stride == 1
    config = acct.config()
    assert config["stage_cpu"] is True
    assert config["clock"] == "thread_cpu"


def test_calibration_expensive_wall_clock_stride_samples():
    # BOTH clocks expensive: wall proxy is chosen, and the stride rises
    # so only every Nth bracket pays the read
    state = {"t": 0}

    def wall():
        state["t"] += 60_000  # 60 us per read
        return state["t"]

    def cpu():
        state["t"] += 200_000
        return state["t"]

    acct = StageCpuAccounting(cpu_clock_ns=cpu, wall_clock_ns=wall)
    acct.enable()
    assert acct.clock_mode == "wall_proxy"
    assert acct.sample_stride > 1
    # stride semantics: exactly one take() in stride consecutive calls
    fires = sum(1 for _ in range(acct.sample_stride) if acct.take())
    assert fires == 1


def test_enable_is_idempotent_never_recalibrating_mid_flight():
    # re-enabling while enabled must be a no-op: calibration swaps the
    # measurement clock, and an in-flight bracket spanning the swap
    # would book a cross-epoch delta (see MAX_BOOKING_NS)
    wall = _FakeClock(step=50)
    cpu = _FakeClock(step=200)
    acct = StageCpuAccounting(cpu_clock_ns=cpu, wall_clock_ns=wall)
    acct.enable()
    assert acct.clock_mode == "thread_cpu"
    calls_after_first = cpu.calls
    acct.enable()  # e.g. a second perf run POSTs stage_cpu=true again
    assert cpu.calls == calls_after_first  # no second calibration
    assert acct.clock_mode == "thread_cpu"
    # a cross-epoch booking (clock mix-up) is dropped, not aggregated
    acct.account("compute", acct.MAX_BOOKING_NS + 1)
    assert "compute" not in acct.snapshot()


def test_stage_scope_books_device_put():
    cpu = _FakeClock(step=700)
    acct = StageCpuAccounting(cpu_clock_ns=cpu, auto_calibrate=False)
    acct.enable()
    with stage_scope(acct, "device_put"):
        pass
    assert acct.snapshot()["device_put"] == {
        "count": 1,
        "cpu_ns": 700,
        "wall_ns": 0,
    }
    with stage_scope(None, "device_put"):
        pass  # accounting-less callers are a no-op


def test_core_disabled_hot_path_reads_no_clocks():
    """Structural half of the overhead guard: with profiling disabled
    (the default) a request through the direct hot path performs ZERO
    measurement-clock reads and books nothing."""
    from client_tpu.server.core import CoreRequest, CoreTensor, ServerCore
    from client_tpu.server.model_repository import ModelRepository
    from client_tpu.server.models import register_builtin_models

    core = ServerCore(ModelRepository())
    register_builtin_models(core.repository)
    cpu = _FakeClock(step=100)
    wall = _FakeClock(step=100)
    core.profiling = StageCpuAccounting(
        metrics_hook=core.metrics.observe_stage_cpu,
        cpu_clock_ns=cpu,
        wall_clock_ns=wall,
        auto_calibrate=False,
    )
    in0 = np.arange(16, dtype=np.int32).reshape(1, 16)
    in1 = np.ones([1, 16], dtype=np.int32)

    def request():
        return CoreRequest(
            model_name="simple",
            inputs=[
                CoreTensor("INPUT0", "INT32", [1, 16], in0),
                CoreTensor("INPUT1", "INT32", [1, 16], in1),
            ],
        )

    results = core.infer_direct([request() for _ in range(4)])
    assert all(not isinstance(r, Exception) for r in results)
    assert cpu.calls == 0 and wall.calls == 0
    assert core.profiling.snapshot() == {}
    # ...and enabling flips the same path to measuring
    core.profiling.enable()
    results = core.infer_direct([request() for _ in range(4)])
    assert all(not isinstance(r, Exception) for r in results)
    snap = core.profiling.snapshot()
    assert cpu.calls > 0
    for stage in ("queue_wait", "batch_assembly", "compute", "readback",
                  "package"):
        assert snap[stage]["count"] == 4, stage


# ---------------------------------------------------------------------------
# WallProfiler


def _parked_thread():
    """A thread parked in a known nested call chain; returns
    (thread, event) — set the event to release it."""
    release = threading.Event()

    def profiling_leaf(evt):
        evt.wait(30)

    def profiling_mid(evt):
        profiling_leaf(evt)

    def profiling_root(evt):
        profiling_mid(evt)

    thread = threading.Thread(
        target=profiling_root,
        args=(release,),
        name="parked-for-profile",
        daemon=True,
    )
    thread.start()
    # wait until the thread reaches the leaf's wait
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        frame = None
        import sys as _sys

        frame = _sys._current_frames().get(thread.ident)
        if frame is not None and frame.f_code.co_name == "wait":
            break
        time.sleep(0.005)
    return thread, release


def test_sampler_fake_clock_known_stack():
    thread, release = _parked_thread()
    try:
        clock = _FakeClock(step=2_000_000)  # 2 ms per clock read
        sleeps = []
        profiler = WallProfiler(
            hz=50, clock_ns=clock, sleep=sleeps.append
        )
        result = profiler.run(duration_s=0.2)
    finally:
        release.set()
        thread.join(timeout=5)
    assert result.sample_count >= 2
    assert all(s >= 0 for s in sleeps)
    collapsed = result.collapsed()
    target = [
        line
        for line in collapsed.splitlines()
        if "parked-for-profile" in line
    ]
    assert target, collapsed
    # root -> leaf order with the thread name as the root frame
    assert re.search(
        r"parked-for-profile;.*profiling_root;.*profiling_mid;"
        r".*profiling_leaf;.*wait.* \d+$",
        target[0],
    ), target[0]


def test_sampler_overhead_guard_lowers_rate():
    slow_clock = _FakeClock(step=5_000_000)  # every read costs "5 ms"
    profiler = WallProfiler(
        hz=1000, overhead_cap=0.1, clock_ns=slow_clock, sleep=lambda s: None
    )
    result = profiler.run(duration_s=0.5)
    assert result.hz_requested == 1000
    assert result.hz_effective < 1000
    assert result.sample_cost_ns > 0


def test_sampler_overhead_guard_adapts_to_later_expensive_samples():
    """The guard must not trust the first sample alone: when samples get
    pricier mid-run (load arrives, stacks deepen), the interval re-widens
    and the loop keeps sleeping between samples instead of busy-spinning
    back to back."""
    state = {"t": 0, "samples": 0}

    def clock():
        state["t"] += 10_000  # 10 us per clock read
        return state["t"]

    def frames():
        state["samples"] += 1
        # first sample cheap (0.1 ms); every later one costs 20 ms —
        # more than the requested 1 ms interval
        state["t"] += 100_000 if state["samples"] == 1 else 20_000_000
        return {}

    sleeps = []
    profiler = WallProfiler(
        hz=1000,
        overhead_cap=0.1,
        clock_ns=clock,
        sleep=sleeps.append,
        frames=frames,
    )
    result = profiler.run(duration_s=1.0)
    # the effective rate dropped to the expensive samples' floor
    # (~1/(20ms/0.1) = 5 Hz), far below both requested and first-sample
    assert result.hz_effective < 10
    assert result.sample_cost_ns >= 20_000_000
    # and every post-adaptation gap slept ~9x the sample cost (the
    # overhead_cap idle share) instead of busy-looping
    assert sleeps and all(s >= 0 for s in sleeps)
    assert max(sleeps) >= (20_000_000 * (1 / 0.1 - 1)) / 1e9 * 0.9


def test_collapsed_and_speedscope_golden():
    result = ProfileResult(
        duration_s=1.0,
        hz_requested=100,
        hz_effective=100.0,
        sample_count=4,
        stacks={
            ("main", "a.py:f", "b.py:g"): 3,
            ("main", "a.py:f"): 1,
        },
    )
    assert result.collapsed() == (
        "main;a.py:f 1\n"
        "main;a.py:f;b.py:g 3\n"
    )
    doc = result.speedscope(name="unit")
    assert doc["$schema"] == (
        "https://www.speedscope.app/file-format-schema.json"
    )
    frames = [f["name"] for f in doc["shared"]["frames"]]
    assert frames == ["main", "a.py:f", "b.py:g"]
    profile = doc["profiles"][0]
    assert profile["type"] == "sampled"
    assert profile["samples"] == [[0, 1], [0, 1, 2]]
    assert profile["weights"] == [1 * 0.01, 3 * 0.01]
    assert profile["endValue"] == pytest.approx(0.04)
    # a speedscope document must be JSON-serializable as-is
    json.dumps(doc)


def test_maybe_jax_trace_noop_paths(tmp_path):
    with maybe_jax_trace(None):
        pass
    with maybe_jax_trace(str(tmp_path / "trace")):
        pass  # jax profiler capture (or a silent skip) must not raise


# ---------------------------------------------------------------------------
# a record a turn: steady turns and stalls, a stall's cause, the watch


class _SetClock:
    """A clock a test sets: ``clock.at`` is what the next read returns."""

    def __init__(self, at=1_000):
        self.at = at

    def __call__(self):
        return self.at


def _laps(clock, process=None, **more):
    return LapSpans({"a": "loop.a", "b": "loop.b", "c": "loop.c"},
                    clock_ns=clock, process=process or ProcessEvents(clock),
                    **more)


def _one_turn(laps, clock, a_ns, b_ns, steps):
    """A turn of ``a_ns`` in phase a and ``b_ns`` in phase b, closed by
    the next turn's first boundary."""
    laps.enter("a")
    laps.turn(steps)
    clock.at += a_ns
    laps.enter("b")
    clock.at += b_ns
    laps.enter("c")  # the turn's last lap has no length
    laps.enter("a")
    laps.turn(steps)


@pytest.mark.parametrize("wall_ns,stalled", [
    (STALL_NS - 1, False), (STALL_NS, True), (STALL_NS + 1, True),
    (1_000, False), (40 * STALL_NS, True),
])
def test_turn_classifies_at_stall_ns(wall_ns, stalled):
    clock = _SetClock()
    seen = []
    laps = _laps(clock, on_stall=seen.append)
    _one_turn(laps, clock, 400, 600, steps=0)  # a steady turn before it
    laps.enter("a")
    clock.at += wall_ns - 7
    laps.enter("b")
    clock.at += 7
    laps.enter("a")
    laps.turn(3)
    record = laps.record()
    assert record["loop_ns"] == 1_000 + wall_ns
    assert record["stalls"]["other"] == int(stalled)
    assert sum(record["stalls"].values()) == len(seen) == int(stalled)
    assert len(laps.stall_log) == int(stalled)
    if stalled:
        assert record["stall_phase_ns"] == {"a": wall_ns - 7, "b": 7, "c": 0}
        assert record["steady_phase_ns"] == {"a": 400, "b": 600, "c": 0}
        assert record["stall_ns"]["other"] == wall_ns
        assert record["steady_steps"] == 0  # a stall's steps are not steady
        (entry,) = seen
        assert entry is laps.stall_log[-1]
        assert entry == {
            "at_ns": clock.at, "wall_ns": wall_ns, "cause": "other",
            "phase": "a", "phase_ns": {"a": wall_ns - 7, "b": 7}, "steps": 3,
            "gc_ns": 0, "compile_ns": 0, "watch_late_ns": None,
            "watch_cpu_ns": None, "stacks": ""}
        json.dumps(entry)
    else:
        assert record["steady_phase_ns"] == {
            "a": 400 + wall_ns - 7, "b": 607, "c": 0}
        assert record["stall_phase_ns"] == {"a": 0, "b": 0, "c": 0}
        assert record["steady_steps"] == 3
    assert set(record["stalls"]) == set(record["stall_ns"]) == set(CAUSES)


@pytest.mark.parametrize("seed", range(6))
def test_steady_plus_stall_is_ns_after_any_sequence(seed):
    rng = np.random.default_rng(seed)
    clock = _SetClock()
    laps = _laps(clock)
    steps = parked = 0
    last = {"loop_ns": 0, "steady_steps": 0}
    turns = 0
    for _ in range(600):
        # mostly short laps, now and then one past a stall's length
        clock.at += int(rng.choice([1, 50, 30_000, STALL_NS // 3, STALL_NS]))
        move = rng.integers(0, 10)
        if move < 6:
            laps.enter("abc"[rng.integers(0, 3)])
            continue
        steps += int(rng.integers(0, 3))
        if move < 9:
            laps.turn(steps)
        else:
            laps.park(steps)
            parked += 1
            clock.at += int(rng.integers(0, 2 * STALL_NS))  # in no phase
        # a turn boundary: the record adds up, phase for phase
        record = laps.record()
        for phase, total in laps.ns.items():
            assert (record["steady_phase_ns"][phase]
                    + record["stall_phase_ns"][phase]) == total, phase
        assert record["loop_ns"] == sum(laps.ns.values())
        assert sum(record["stall_ns"].values()) == sum(
            record["stall_phase_ns"].values())
        turns += 1
        assert sum(record["stalls"].values()) <= turns
        assert record["steady_steps"] <= steps
        for name, before in last.items():
            assert record[name] >= before, name
            last[name] = record[name]
    assert parked and sum(laps.stalls.values()) and laps.steady_steps
    assert len(laps.stall_log) <= 16


@pytest.mark.parametrize("profiler,compile_ns,gc_ns,cause", [
    (True, STALL_NS, STALL_NS, "profiler"),
    (True, 0, 0, "profiler"),
    (False, STALL_NS // 2, STALL_NS, "compile"),
    (False, STALL_NS // 2 - 1, STALL_NS // 2, "gc"),
    (False, STALL_NS // 2 - 1, STALL_NS // 2 - 1, "other"),
    (False, 0, 0, "other"),
])
def test_a_stalls_cause_is_the_first_that_fits(profiler, compile_ns, gc_ns,
                                               cause):
    clock = _SetClock()
    process = ProcessEvents(clock)
    laps = _laps(clock, process=process)
    _one_turn(laps, clock, 5, 5, steps=1)
    # what was booked before the turn opened is not the turn's
    process.on_duration(profiling._BACKEND_COMPILE, 3.0)
    process.on_gc("start", {"generation": 2})
    clock.at += STALL_NS - 20
    process.on_gc("stop", {"generation": 2})
    laps.enter("b")
    laps.turn(1)
    assert not any(laps.stalls.values())
    opened = clock.at
    clock.at += 1
    if profiler:
        session = process.open_session()
        with process.profiler_hold(session, 0):
            clock.at += 10
    process.on_duration(profiling._BACKEND_COMPILE, compile_ns // 2 / 1e9)
    process.on_duration("/jax/core/compile/jaxpr_trace_duration",
                        (compile_ns - compile_ns // 2) / 1e9)
    process.on_duration("/jax/core/something_else_duration", 9.0)
    if gc_ns:
        process.on_gc("start", {"generation": 1})
        clock.at += gc_ns
        process.on_gc("stop", {"generation": 1})
    laps.enter("a")
    clock.at = opened + STALL_NS  # the turn's wall, exactly
    laps.enter("b")
    laps.turn(2)
    assert laps.stalls == dict(dict.fromkeys(CAUSES, 0), **{cause: 1})
    entry = laps.stall_log[-1]
    assert entry["cause"] == cause and entry["wall_ns"] == STALL_NS
    assert entry["compile_ns"] == pytest.approx(compile_ns, abs=2)
    assert entry["gc_ns"] == gc_ns
    assert len(process.sessions) == int(profiler)


def test_the_profilers_stop_still_going_on_is_a_cause():
    clock = _SetClock()
    process = ProcessEvents(clock)
    laps = _laps(clock, process=process)
    session = process.open_session()
    with process.profiler_hold(session, 0):
        clock.at += 10
    clock.at += 10 * STALL_NS
    _one_turn(laps, clock, STALL_NS, 5, steps=0)  # between start and stop
    assert laps.stalls["other"] == 1
    session["monotonic_ns"][2] = clock.at  # the stop has begun and not ended
    _one_turn(laps, clock, STALL_NS, 5, steps=0)
    assert laps.stalls["profiler"] == 1
    assert not process.in_profiler(0, session["monotonic_ns"][0] - 1)


def _frame(*labels):
    """A chain of fake frames, outermost first; returns the innermost."""
    frame = None
    for label in labels:
        filename, name = label.split(":")
        code = type("code", (), {"co_filename": f"/x/{filename}",
                                 "co_name": name})
        frame = type("frame", (), {"f_code": code, "f_back": frame})
    return frame


def test_the_watch_puts_the_stack_and_its_lateness_in_the_log():
    clock = _SetClock()
    frames = {
        1: _frame("engine.py:_run", "engine.py:_consume", "array.py:_value"),
        2: _frame("thread.py:_worker"),  # idle: counted, not shown
        3: _frame("pool.py:work", "native.py:held"),
        4: _frame("threading.py:_bootstrap", "threading.py:wait"),
    }
    spawned = []
    watch = StallWatch(clock_ns=clock, frames=lambda: frames,
                       spawn=spawned.append)
    laps = _laps(clock)
    other = _laps(_SetClock())  # on another clock: not watched
    watch.register(laps)
    watch.register(other)
    assert list(watch._loops) == [laps] and len(spawned) == 1
    watch._loops[laps] = 1  # the loop's thread, as if it had registered
    laps.enter("a")
    laps.turn(0)
    clock.at += STALL_NS - 1
    watch.check(clock.at, late_ns=11)
    assert laps._sampled is None  # not yet a stall's length in one phase
    clock.at += 1
    watch.check(clock.at, late_ns=22, cpu_ns=5)
    first = laps._sampled
    clock.at += STALL_NS
    watch.check(clock.at, late_ns=33)
    assert laps._sampled is first  # ONE sample a lap
    laps.enter("b")
    laps.enter("a")
    laps.turn(0)
    (entry,) = laps.stall_log
    assert entry["cause"] == "other" and entry["watch_late_ns"] == 22
    assert entry["watch_cpu_ns"] == 5
    assert entry["stacks"].splitlines() == [
        "thread-1;engine.py:_run;engine.py:_consume;array.py:_value 1",
        "thread-3;pool.py:work;native.py:held 1",
        "(idle threads) 2",
    ]
    # the next stall has no sample of its own: the old one is not reused
    _one_turn(laps, clock, STALL_NS // 2, STALL_NS // 2, steps=0)
    assert laps.stall_log[-1]["stacks"] == ""
    assert laps.stall_log[-1]["watch_late_ns"] is None
    # parked, a loop is never sampled; and 2 KB is all a sample may take
    laps.park()
    clock.at += 3 * STALL_NS
    watch.check(clock.at)
    assert laps._sampled is first
    frames[1] = _frame(*[f"deep{i}.py:f{i}" for i in range(40)])
    frames.update({10 + i: _frame(f"m{i}.py:{'f' * 90}") for i in range(40)})
    laps.enter("a")
    clock.at += STALL_NS
    watch.check(clock.at)
    text = laps._sampled[3]
    assert len(text) <= 2048 and text.endswith(" 1\n(idle threads) 2\n")
    assert text.startswith("thread-1;deep16.py:f16;")  # the innermost 24
    watch.unregister(laps)
    watch.unregister(laps)
    assert not watch._loops and watch._wake.is_set()


def test_a_loop_registered_after_the_last_left_finds_the_wake_cleared():
    """The last loop leaves and sets the wake; another registers before
    the thread has seen the empty set (an engine restarted after a
    quarantine): the thread lives on, and its waits must wait again."""
    clock = _SetClock()
    spawned = []
    watch = StallWatch(clock_ns=clock,
                       spawn=lambda run: spawned.append(run) or "alive")
    first, second = _laps(clock), _laps(clock)
    watch.register(first)
    watch.unregister(first)
    assert watch._wake.is_set()
    watch.register(second)  # the thread of the first is still there
    assert len(spawned) == 1 and not watch._wake.is_set()
    watch.unregister(second)


def test_whoever_starts_a_waiting_thread_names_its_idle_leaf():
    clock = _SetClock()

    def _pump_loop():
        """Stands for a thread's loop that waits inside a native call."""

    frames = {
        1: _frame("engine.py:_run"),
        2: _frame("threading.py:run", "test_profiling.py:_pump_loop"),
    }
    watch = StallWatch(clock_ns=clock, frames=lambda: frames,
                       spawn=lambda run: None)
    assert "(idle threads)" not in watch._stacks(1)
    watch.idle_in(_pump_loop)
    assert watch._stacks(1).splitlines() == [
        "thread-1;engine.py:_run 1", "(idle threads) 1"]
    # the process's own watch knows the standard library's and what the
    # native front-end handed it, no server file's name by itself
    assert not any("native_frontend" in leaf for leaf in profiling._IDLE_LEAVES)


def test_the_gc_hook_books_a_collection():
    import gc

    clock = _SetClock()
    events = ProcessEvents(clock)
    events.on_gc("stop", {"generation": 0})  # a stop without its start
    for generation, took in ((0, 30), (2, 5_000), (0, 12)):
        events.on_gc("start", {"generation": generation})
        clock.at += took
        events.on_gc("stop", {"generation": generation, "collected": 1})
    assert events.gc_ns == [42, 0, 5_000]
    assert events.gc_collections == [2, 0, 1]
    assert (events.gc_total_ns, events.compile_total_ns) == (5_042, 0)
    # the process's own hears the collector itself
    PROCESS.listen()
    PROCESS.listen()
    assert gc.callbacks.count(PROCESS.on_gc) == 1
    before = PROCESS.record()
    gc.collect()
    after = PROCESS.record()
    assert after["gc_collections"][2] == before["gc_collections"][2] + 1
    assert after["gc_ns"][2] > before["gc_ns"][2]


def test_the_compile_listener_books_a_fresh_jit_once():
    import jax

    PROCESS.listen()
    salt = float(np.random.default_rng().integers(1 << 30))
    fresh = jax.jit(lambda x: x * 3 + salt)
    x = np.ones([3], dtype=np.float32)
    before = PROCESS.record()["compile"]
    fresh(x).block_until_ready()
    first = PROCESS.record()["compile"]
    assert first["backend_count"] == before["backend_count"] + 1
    assert first["backend_ns"] > before["backend_ns"]
    assert first["trace_ns"] > before["trace_ns"]
    fresh(x).block_until_ready()
    assert PROCESS.record()["compile"] == first  # nothing compiles twice
    events = ProcessEvents(_SetClock())
    events.on_event("/jax/compilation_cache/cache_hits")
    events.on_event("/jax/compilation_cache/cache_misses")
    assert events.compile["cache_hits"] == 1


@pytest.mark.parametrize("fails", [False, True], ids=["sound", "raises"])
def test_maybe_jax_trace_leaves_four_instants_on_the_laps_clock(
        monkeypatch, tmp_path, fails):
    import contextlib

    import jax

    clock = _SetClock(at=500)
    events = ProcessEvents(clock)

    @contextlib.contextmanager
    def held(log_dir):
        clock.at += 2_000  # the start holds the process
        try:
            yield
        finally:
            clock.at += 6_000  # and so does the stop

    monkeypatch.setattr(jax.profiler, "trace", held)
    with maybe_jax_trace(None, process=events):
        pass
    assert not events.sessions
    try:
        with maybe_jax_trace(str(tmp_path), process=events):
            clock.at += 100
            if fails:
                raise KeyError("inside the window")
    except KeyError:
        assert fails
    else:
        assert not fails
    (session,) = events.sessions
    assert session == {"monotonic_ns": [500, 2_500, 2_600, 8_600]}
    assert "profiler_sessions" not in events.record()  # numbers a metric reads
    assert events.in_profiler(0, 500) and events.in_profiler(2_500, 2_599)
    assert not events.in_profiler(2_501, 2_599)
    assert events.in_profiler(8_600, 9_000) and not events.in_profiler(8_601, 9_000)
    json.dumps(list(events.sessions))


# ---------------------------------------------------------------------------
# HTTP endpoints


def _http_get(url, timeout=60):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read().decode(), dict(resp.headers)


def test_profile_endpoint_end_to_end():
    thread, release = _parked_thread()
    try:
        with InProcessServer(grpc=False) as server:
            base = f"http://{server.http_url}"
            status, body, headers = _http_get(
                f"{base}/v2/debug/profile?duration_s=0.2&hz=100"
            )
            assert status == 200
            assert int(headers["X-Profile-Samples"]) >= 1
            assert "parked-for-profile" in body
            for line in body.strip().splitlines():
                assert re.match(r"^.+ \d+$", line), line
            # speedscope format round-trips as JSON
            status, body, _ = _http_get(
                f"{base}/v2/debug/profile?duration_s=0.1&hz=100"
                "&format=speedscope"
            )
            assert status == 200
            doc = json.loads(body)
            assert doc["profiles"][0]["type"] == "sampled"
            # parameter validation
            for bad in (
                "duration_s=0", "duration_s=oops", "hz=0", "format=wat"
            ):
                try:
                    urllib.request.urlopen(
                        f"{base}/v2/debug/profile?{bad}", timeout=30
                    )
                    assert False, f"{bad} should have failed"
                except urllib.error.HTTPError as e:
                    assert e.code == 400, bad
    finally:
        release.set()
        thread.join(timeout=5)


def test_profiling_config_endpoint_and_concurrent_scrapes():
    with InProcessServer(grpc=False) as server:
        base = f"http://{server.http_url}"
        status, body, _ = _http_get(f"{base}/v2/debug/profiling")
        assert status == 200
        assert json.loads(body)["stage_cpu"] is False  # default-off

        def post(payload):
            req = urllib.request.Request(
                f"{base}/v2/debug/profiling",
                data=json.dumps(payload).encode(),
                method="POST",
            )
            with urllib.request.urlopen(req, timeout=30) as resp:
                return resp.status, json.loads(resp.read())

        status, config = post({"stage_cpu": True})
        assert status == 200 and config["stage_cpu"] is True
        assert config["clock"] in ("thread_cpu", "wall_proxy")
        assert server.core.profiling.enabled is True
        # validation: unknown keys / wrong types reject with 400
        for bad in ({"stage_cpu": "yes"}, {"nope": True}):
            try:
                post(bad)
                assert False, f"{bad} should have failed"
            except urllib.error.HTTPError as e:
                assert e.code == 400
        status, config = post({"stage_cpu": False})
        assert config["stage_cpu"] is False

        # jax_trace_dir is a wire-controlled write target: anything
        # outside the system temp dir is rejected before sampling
        try:
            urllib.request.urlopen(
                f"{base}/v2/debug/profile?duration_s=0.1"
                "&jax_trace_dir=/etc/ctpu-trace",
                timeout=30,
            )
            assert False, "jax_trace_dir outside tmp should 400"
        except urllib.error.HTTPError as e:
            assert e.code == 400

        # HTTP non-inference surfaces book the "rpc" stage too (the
        # harness's own /metrics + statistics scrapes must show in the
        # attribution, matching the gRPC faces)
        server.core.profiling.enable()
        server.core.profiling.sample_stride = 1
        before = _stage_totals(server.http_url, "rpc")
        _http_get(f"{base}/v2/models/stats")
        after = _stage_totals(server.http_url, "rpc")
        server.core.profiling.disable()
        # the stats call books one rpc; the /metrics scrapes bracketing
        # it book theirs on the NEXT render, so count grows by >= 1
        assert after["count"] >= before["count"] + 1

        # concurrent /metrics scrapes and a profile run must coexist;
        # a SECOND concurrent profile gets a clean 409
        async def drive():
            import aiohttp

            async with aiohttp.ClientSession() as session:
                async def profile():
                    async with session.get(
                        f"{base}/v2/debug/profile",
                        params={"duration_s": "0.4", "hz": "50"},
                    ) as resp:
                        await resp.read()
                        return resp.status

                async def scrape():
                    async with session.get(f"{base}/metrics") as resp:
                        await resp.read()
                        return resp.status

                first = asyncio.create_task(profile())
                await asyncio.sleep(0.05)
                rest = await asyncio.gather(
                    profile(), scrape(), scrape(), scrape()
                )
                return [await first] + list(rest)

        statuses = asyncio.run(drive())
        assert statuses[0] == 200  # the first profile completed
        assert statuses[1] == 409  # the overlapping one was refused
        assert statuses[2:] == [200, 200, 200]


def test_inprocess_profile_api():
    with InProcessServer(grpc=False) as server:
        result = server.profile(duration_s=0.2, hz=100)
    assert result.sample_count >= 1
    # the server's own threads (loop thread name "client-tpu-server")
    # appear in the samples
    assert any(
        stack and stack[0] == "client-tpu-server"
        for stack in result.stacks
    ), sorted(result.stacks)[:5]


# ---------------------------------------------------------------------------
# stage-CPU end to end: gRPC vs HTTP agreement on the same server


def _stage_totals(url, stage):
    text = urllib.request.urlopen(f"http://{url}/metrics", timeout=30).read()
    families = parse_exposition(text.decode())
    return histogram_totals(
        families.get("tpu_request_cpu_seconds"), {"stage": stage}
    )


def test_grpc_and_http_stage_cpu_agree():
    with InProcessServer(grpc="aio") as server:
        prof = server.core.profiling
        prof.enable()
        prof.sample_stride = 1  # deterministic counts for the assertion
        n = 20
        with httpclient.InferenceServerClient(server.http_url) as http_client:
            inputs = _simple_inputs(httpclient)
            baseline = {
                s: _stage_totals(server.http_url, s)
                for s in ("frontend_decode", "compute", "encode")
            }
            for _ in range(n):
                http_client.infer("simple", inputs)
            after_http = {
                s: _stage_totals(server.http_url, s)
                for s in ("frontend_decode", "compute", "encode")
            }
        with grpcclient.InferenceServerClient(server.grpc_url) as grpc_client:
            ginputs = _simple_inputs(grpcclient)
            for _ in range(n):
                grpc_client.infer("simple", ginputs)
        after_grpc = {
            s: _stage_totals(server.http_url, s)
            for s in ("frontend_decode", "compute", "encode")
        }
        prof.disable()
    for stage in ("frontend_decode", "compute", "encode"):
        http_count = after_http[stage]["count"] - baseline[stage]["count"]
        grpc_count = after_grpc[stage]["count"] - after_http[stage]["count"]
        assert http_count == n, (stage, http_count)
        assert grpc_count == n, (stage, grpc_count)
    # agreement: the SHARED stage (compute — same model, same server)
    # books comparable per-request CPU on both wire paths
    http_compute = (
        after_http["compute"]["sum"] - baseline["compute"]["sum"]
    ) / n
    grpc_compute = (
        after_grpc["compute"]["sum"] - after_http["compute"]["sum"]
    ) / n
    assert http_compute > 0 and grpc_compute > 0
    ratio = max(http_compute, grpc_compute) / min(http_compute, grpc_compute)
    assert ratio < 10, (http_compute, grpc_compute)
    # ...and both protocols booked wire-only decode work
    assert after_grpc["frontend_decode"]["sum"] > 0


# ---------------------------------------------------------------------------
# overhead guard (the loopback half): clock reads and samples a request


def test_stage_accounting_costs_a_fixed_number_of_clock_reads(loopback_echo):
    """Accounting ON, an unbatched request over loopback HTTP is
    bracketed by a fixed number of reads of the injected measurement
    clock and leaves one sample in each stage it passes through;
    accounting OFF (the default) it reads no clock and books nothing
    (``test_core_disabled_hot_path_reads_no_clocks`` holds that for the
    direct path). A later change that adds a bracket a request has
    to change the numbers here."""
    from client_tpu.server.core import ServerCore
    from client_tpu.server.model_repository import ModelRepository

    n = 24
    core = ServerCore(ModelRepository())
    cpu = _FakeClock(step=100)
    wall = _FakeClock(step=100)
    prof = core.profiling = StageCpuAccounting(
        metrics_hook=core.metrics.observe_stage_cpu,
        cpu_clock_ns=cpu,
        wall_clock_ns=wall,
        auto_calibrate=False,
    )
    with loopback_echo(core) as echo:
        echo.send(n)
        assert (cpu.calls, wall.calls) == (0, 0)
        assert prof.snapshot() == {}
        prof.enable()
        echo.send(n)
        prof.disable()
        reads, snap = cpu.calls, prof.snapshot()
        echo.send(n)
        assert cpu.calls == reads and prof.snapshot() == snap
    # frontend_decode, package and encode are a bracket of two reads
    # each, compute and readback share their middle read (three), and
    # queue_wait is the core's own wall laps: no read of either clock
    assert (reads, wall.calls) == (9 * n, 0)
    assert {stage: entry["count"] for stage, entry in snap.items()} == {
        stage: n
        for stage in ("frontend_decode", "queue_wait", "compute",
                      "readback", "package", "encode")
    }
    assert {stage: entry["cpu_ns"] for stage, entry in snap.items()} == {
        stage: 0 if stage == "queue_wait" else cpu.step * n
        for stage in snap
    }


# ---------------------------------------------------------------------------
# collector + report reduction


_EXPO_T0 = """\
# TYPE tpu_request_cpu_seconds histogram
tpu_request_cpu_seconds_bucket{stage="compute",le="+Inf"} 0
tpu_request_cpu_seconds_sum{stage="compute"} 0
tpu_request_cpu_seconds_count{stage="compute"} 0
"""

_EXPO_T1 = """\
# TYPE tpu_request_cpu_seconds histogram
tpu_request_cpu_seconds_bucket{stage="compute",le="+Inf"} 40
tpu_request_cpu_seconds_sum{stage="compute"} 0.0008
tpu_request_cpu_seconds_count{stage="compute"} 40
tpu_request_cpu_seconds_bucket{stage="encode",le="+Inf"} 40
tpu_request_cpu_seconds_sum{stage="encode"} 0.0002
tpu_request_cpu_seconds_count{stage="encode"} 40
tpu_request_cpu_seconds_bucket{stage="rpc",le="+Inf"} 4
tpu_request_cpu_seconds_sum{stage="rpc"} 0.004
tpu_request_cpu_seconds_count{stage="rpc"} 4
"""


def test_collector_reduces_stage_cpu_deltas():
    docs = iter([_EXPO_T0, _EXPO_T1])

    async def fetch():
        return next(docs)

    clock = _FakeClock(step=1_000_000_000)
    collector = MetricsCollector(
        "localhost:1", fetch=fetch, clock_ns=clock
    )

    async def drive():
        await collector.scrape_now()
        await collector.scrape_now()

    asyncio.run(drive())
    summary = collector.summary()
    assert summary.stage_cpu["compute"] == {"count": 40.0, "cpu_s": 0.0008}
    assert summary.stage_cpu["encode"] == {"count": 40.0, "cpu_s": 0.0002}
    per_request = summary.stage_cpu_us()
    assert per_request["compute"] == pytest.approx(20.0)
    assert per_request["encode"] == pytest.approx(5.0)


def test_format_wire_gap_table():
    summary = ServerMetricsSummary(
        request_count=40,
        avg_queue_us=3.5,
        stage_cpu={
            "frontend_decode": {"count": 40.0, "cpu_s": 0.0004},
            "queue_wait": {"count": 40.0, "cpu_s": 0.0},
            "device_put": {"count": 40.0, "cpu_s": 0.0001},
            "compute": {"count": 40.0, "cpu_s": 0.0008},
            "encode": {"count": 40.0, "cpu_s": 0.0002},
            "rpc": {"count": 4.0, "cpu_s": 0.004},
        },
    )
    out = format_wire_gap(summary, clock_mode="wall_proxy")
    assert "Wire-gap attribution" in out
    assert "wall_proxy" in out
    assert re.search(r"frontend_decode\s+10\.0 us/req", out)
    assert re.search(r"compute\s+20\.0 us/req", out)
    # total over the inference stages: 10 + 0 + 2.5 + 20 + 5
    assert re.search(r"total\s+37\.5 us/req", out)
    # rpc reports a run total, not a per-request share
    assert re.search(r"rpc\s+4\.00 ms total \(4 non-inference calls\)", out)
    assert "[wall 3.5 us/req]" in out
    # wire-only vs shared split names the actual stage composition
    # (device_put present -> it appears in the shared label and sum)
    assert (
        "wire-only stages (frontend_decode+encode) 15.0 us/req vs "
        "shared stages (queue_wait+device_put+compute) 22.5 us/req" in out
    )
    empty = format_wire_gap(ServerMetricsSummary())
    assert "no stage-CPU samples" in empty


# ---------------------------------------------------------------------------
# CLI end to end (--profile-server / --flamegraph-out)


def test_cli_profile_server_rejects_non_kserve_by_name(capsys):
    from client_tpu.perf.cli import main

    code = main([
        "-m", "simple",
        "--service-kind", "openai",
        "--profile-server",
        "--concurrency-range", "1",
    ])
    assert code == 2
    err = capsys.readouterr().err
    # the error names the flag the user actually passed, not the
    # implied --stage-breakdown
    assert "--profile-server" in err


def test_cli_profile_server_end_to_end(tmp_path, capsys):
    from client_tpu.perf.cli import main

    flamegraph = tmp_path / "server.collapsed"
    with InProcessServer(grpc=False) as server:
        code = main([
            "-m", "simple",
            "-u", server.http_url,
            "-i", "http",
            "--concurrency-range", "2",
            "--measurement-interval", "300",
            "--stability-percentage", "60",
            "--max-trials", "3",
            "--metrics-interval", "0.1",
            "--profile-server",
            "--flamegraph-out", str(flamegraph),
            "--json-summary",
        ])
        # the run restores the server's default-off profiling
        assert server.core.profiling.enabled is False
    assert code == 0
    out = capsys.readouterr().out
    assert "Wire-gap attribution" in out
    # --profile-server implied --stage-breakdown: the client stage table
    # printed, so the attribution never reads against an empty one
    assert "Stage breakdown" in out
    assert "Server metrics" in out
    # the flamegraph file is valid collapsed-stack format
    lines = flamegraph.read_text().strip().splitlines()
    assert lines
    for line in lines:
        assert re.match(r"^.+ \d+$", line), line
    # --json-summary carries the per-stage decomposition
    summary_line = [
        line for line in out.splitlines() if line.startswith("{")
    ][-1]
    doc = json.loads(summary_line)
    stage_cpu = doc["server_stage_cpu_us"]
    assert "frontend_decode" in stage_cpu and "compute" in stage_cpu
    assert all(v >= 0 for v in stage_cpu.values())
