"""Hot-path profiling: per-stage CPU accounting + on-demand wall sampler.

The wire path serves far fewer requests per second than the in-process
path (BENCH r05: 0.349x), and wall-clock tracing alone cannot say *why*:
queue wait, GIL contention, and actual codec CPU all look like "time
passed". This module is the instrument that splits them:

:class:`StageCpuAccounting`
    Cumulative ``time.thread_time_ns`` deltas per named request stage
    (``frontend_decode``, ``queue_wait``, ``batch_assembly``,
    ``device_put``, ``compute``, ``readback``, ``package``, ``encode``,
    plus ``rpc`` for non-inference methods). Thread CPU, not wall: a
    stage that slept
    on a lock or the GIL books ~0, so the table shows where cycles go,
    not where time idles. **Default-off** — while disabled the hot paths
    take a single attribute-check branch per stage event, read no
    clocks, and book nothing. The server exports the accounting as the
    ``tpu_request_cpu_seconds{stage}`` histogram
    (:mod:`client_tpu.server.metrics`), which the perf harness's
    ``--profile-server`` reduces to the "Wire-gap attribution" report.

:class:`LapSpans`
    Wall-clock laps that tile one loop by named phase: one injected
    clock read at each phase boundary, credited to the phase that just
    ended, so the phases add up to the loop's un-parked wall time by
    construction. **Always on** (a handful of clock reads an iteration).
    Each lap is also a ``jax.profiler.TraceAnnotation``, so a
    ``jax.profiler`` trace shows the same phases on the loop thread's
    host line (against the device's lines only after alignment: see the
    class). The LLM engine's step loop runs on one
    (``engine.stats()["phase_ns"]``).
    Which of the two: ``StageCpuAccounting`` for thread CPU per request
    stage across many threads, sampled, default off; ``LapSpans`` for
    where one loop's wall time goes, every iteration, always on.

    The laps also keep **a record a turn**. A *turn* is one iteration of
    the loop, from one ``turn()`` to the next (``park()`` closes a turn
    too). A turn whose wall time is under :data:`STALL_NS` is *steady*
    and its laps go to ``steady_ns``; one of ``STALL_NS`` or more is a
    *stall* and its laps go to ``stall_phase_ns``, so that
    ``steady_ns[p] + stall_phase_ns[p] == ns[p]`` at every turn
    boundary and a mean over steady turns is served without the stalls.
    Every stall has a *cause*, the first of :data:`CAUSES` that fits:
    ``profiler`` (the turn overlaps ``jax.profiler``'s start or stop
    inside :func:`maybe_jax_trace`), ``compile`` (JAX's own reports of
    tracing, lowering and backend compiles grew by half the turn),
    ``gc`` (the collector's time did), else ``other``, for which the
    watch's one stack sample is the evidence.

:class:`ProcessEvents`
    What the whole process does to any loop's turn, each source free
    between events: the collector's time by generation (one
    ``gc.callbacks`` hook), JAX's compile reports (``jax.monitoring``
    listeners) and the instants of the profiler's sessions on the laps'
    clock. :data:`PROCESS` is the process's own.

:class:`StallWatch`
    One daemon thread a process while any loop is registered: when a
    loop has been inside one phase for ``STALL_NS`` it samples every
    thread's stack once and notes how late its own wake-up was.
    :data:`WATCH` is the process's own.

:class:`WallProfiler`
    An on-demand sampling profiler over ``sys._current_frames()``:
    samples every thread's Python stack at ``hz`` for ``duration_s``,
    aggregates identical stacks, and exports collapsed-stack text
    (flamegraph.pl) or speedscope JSON. A measured-overhead guard times
    the first sample and lowers the effective rate so sampling never
    costs more than ``overhead_cap`` of one core. Exposed as
    ``GET /v2/debug/profile`` on the HTTP front-end and
    ``InProcessServer.profile()``; nothing runs unless requested.

:func:`maybe_jax_trace`
    Optional ``jax.profiler`` trace capture around a sampling window for
    device-placed models (XLA-level timeline); a no-op when jax or its
    profiler is unavailable. The one way into the profiler: it books
    when its start and its stop began and ended (``ProcessEvents``).

Everything is clock-injectable — ``wall_ns``/``cpu_ns``/``sleep`` — and
``tools/clock_lint.py`` bans direct ``time.*()`` calls here (including
``thread_time_ns``), so the sampler and the accounting test on fake
clocks without sleeping.
"""

import collections
import contextlib
import functools
import gc
import os
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

__all__ = [
    "CAUSES",
    "PROCESS",
    "STAGES",
    "STALL_NS",
    "WATCH",
    "LapSpans",
    "ProcessEvents",
    "ProfileResult",
    "StageCpuAccounting",
    "StallWatch",
    "WallProfiler",
    "maybe_jax_trace",
    "stage_scope",
]

# Canonical stage order (report rows print in this order). The first
# eight decompose one inference request's path through the server
# ("package" = core output packaging, which the in-process path also
# pays; "encode" = front-end wire serialization, which it does not);
# "rpc" collects non-inference methods (statistics/metadata scrapes),
# which share the serving threads and are part of the wire path's CPU
# bill. Each stage has exactly ONE booker per request, so a stage's
# cpu_sum / count is its per-request mean.
STAGES = (
    "frontend_decode",
    "queue_wait",
    "batch_assembly",
    "device_put",
    "compute",
    "readback",
    "package",
    "encode",
    "rpc",
)

# Per-request stages the in-process path never executes: their sum is
# the wire gap's directly-attributable CPU (the rest of the gap is
# syscalls/transport). "rpc" is also wire-only but books per method
# call, not per request, so reports keep it out of per-request sums.
WIRE_ONLY_STAGES = ("frontend_decode", "encode")


class StageCpuAccounting:
    """Per-stage cumulative thread-CPU (and wall) accounting.

    Hot-path contract: callers guard every bracket with ``prof.take()``,
    which while disabled (the default) costs one attribute-check branch
    per stage event — no syscalls, no locks, no bookings. ``account()``
    aggregates under one lock and forwards to ``metrics_hook`` (the
    server's ``tpu_request_cpu_seconds`` histogram) outside it.

    ``enable()`` calibrates against the host's clocks, because
    ``CLOCK_THREAD_CPUTIME_ID`` is not dependable everywhere: syscall-
    trapping sandboxes make it ~1000x the cost of the vDSO wall clock,
    and some kernels quantize it to scheduler ticks (10 ms). Two
    degradations keep the instrument usable there:

    * **wall proxy** — when the CPU clock is too expensive or too coarse,
      brackets read the injected wall clock instead (``clock_mode`` flips
      to ``"wall_proxy"``). A single-threaded stage bracket's wall time
      is its CPU plus any preemption, a documented overestimate.
    * **stride sampling** — when even the chosen clock is expensive,
      only every Nth bracket measures (``sample_stride``). Each stage's
      sum/count stays an unbiased per-request mean; the stride only
      widens the confidence interval.

    ``count`` is the number of requests a booking covers (merged batch
    paths book once per chunk), so ``cpu_ns / count`` is per-request.
    """

    __slots__ = (
        "enabled",
        "clock_mode",
        "sample_stride",
        "clock_cost_ns",
        "_tick",
        "_clock",
        "_cpu_clock_ns",
        "_wall_clock_ns",
        "_auto_calibrate",
        "_metrics_hook",
        "_lock",
        "_totals",
    )

    # calibration bounds: a CPU clock pricier than this per call, or
    # coarser than this per tick, degrades to the wall proxy; a chosen
    # clock pricier than the bracket budget gets stride-sampled
    MAX_CPU_CLOCK_COST_NS = 5_000
    MAX_CPU_CLOCK_QUANTUM_NS = 1_000_000
    BRACKET_BUDGET_NS = 2_000
    MAX_STRIDE = 64
    # sanity cap per booking: a delta larger than this is a clock-epoch
    # mix-up (e.g. a disable/enable race swapping clocks mid-bracket),
    # never a real stage — drop it rather than poison the cumulative mean
    MAX_BOOKING_NS = 600_000_000_000

    def __init__(
        self,
        metrics_hook: Optional[Callable[[str, int, int], None]] = None,
        cpu_clock_ns: Callable[[], int] = time.thread_time_ns,
        wall_clock_ns: Callable[[], int] = time.monotonic_ns,
        auto_calibrate: bool = True,
    ):
        self.enabled = False
        self.clock_mode = "thread_cpu"
        self.sample_stride = 1
        self.clock_cost_ns = 0
        self._tick = 0
        self._cpu_clock_ns = cpu_clock_ns
        self._wall_clock_ns = wall_clock_ns
        self._clock = cpu_clock_ns
        self._auto_calibrate = auto_calibrate
        self._metrics_hook = metrics_hook
        self._lock = threading.Lock()
        # stage -> [count, cpu_ns, wall_ns]
        self._totals: Dict[str, List[int]] = {}

    def enable(self) -> None:
        # idempotent: re-enabling while enabled must NOT re-calibrate —
        # calibration swaps self._clock, and an in-flight bracket that
        # read c0 on the old clock would book c1-c0 across unrelated
        # epochs (monotonic minus thread-CPU is hours of phantom CPU)
        if self.enabled:
            return
        if self._auto_calibrate:
            self._calibrate()
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def _calibrate(self) -> None:
        """Pick the measurement clock and stride for THIS host (see the
        class docstring); runs once per enable(), bounded ~20 ms."""
        wall = self._wall_clock_ns
        cpu = self._cpu_clock_ns
        w0 = wall()
        for _ in range(8):
            cpu()
        cpu_cost_ns = max(0, wall() - w0) // 8
        coarse = False
        if cpu_cost_ns <= self.MAX_CPU_CLOCK_COST_NS:
            # affordable clock: check its granularity (bounded spin — a
            # tick-quantized clock moves within ~2 scheduler ticks)
            q0 = cpu()
            deadline = wall() + 20_000_000
            quantum_ns = None
            while wall() < deadline:
                q1 = cpu()
                if q1 != q0:
                    quantum_ns = q1 - q0
                    break
            coarse = (
                quantum_ns is None
                or quantum_ns > self.MAX_CPU_CLOCK_QUANTUM_NS
            )
        if cpu_cost_ns > self.MAX_CPU_CLOCK_COST_NS or coarse:
            self.clock_mode = "wall_proxy"
            self._clock = wall
            w1 = wall()
            for _ in range(8):
                wall()
            clock_cost_ns = max(0, wall() - w1) // 8
        else:
            self.clock_mode = "thread_cpu"
            self._clock = cpu
            clock_cost_ns = cpu_cost_ns
        self.clock_cost_ns = clock_cost_ns
        # ~2 clock reads per bracket; keep the average bracket cost under
        # BRACKET_BUDGET_NS by measuring only every Nth occurrence
        self.sample_stride = max(
            1,
            min(self.MAX_STRIDE, round(2 * clock_cost_ns / self.BRACKET_BUDGET_NS)),
        )

    def take(self) -> bool:
        """One stage-bracket admission: True when this occurrence should
        measure. THE hot-path gate — while disabled it is a single
        attribute-check branch; enabled, a counter tick per stride."""
        if not self.enabled:
            return False
        tick = self._tick + 1
        if tick >= self.sample_stride:
            self._tick = 0
            return True
        # benign data race across threads: a lost tick skews the stride
        # by one occurrence, never corrupts a measurement
        self._tick = tick
        return False

    def cpu_now(self) -> int:
        """Current measurement-clock ns (thread CPU, or the wall proxy on
        degraded hosts). Only call behind a ``take()`` — the whole point
        of default-off is not paying this read."""
        return self._clock()

    def account(
        self, stage: str, cpu_ns: int, wall_ns: int = 0, count: int = 1
    ) -> None:
        """Book ``count`` requests' worth of one stage. No-op while
        disabled (so a race with disable() mid-request stays cheap)."""
        if not self.enabled or count <= 0:
            return
        if cpu_ns < 0:
            cpu_ns = 0  # thread clock anomaly; never book negative CPU
        elif cpu_ns > self.MAX_BOOKING_NS:
            return  # cross-epoch clock mix-up, not a real measurement
        with self._lock:
            entry = self._totals.get(stage)
            if entry is None:
                entry = self._totals[stage] = [0, 0, 0]
            entry[0] += count
            entry[1] += cpu_ns
            entry[2] += wall_ns
        if self._metrics_hook is not None:
            self._metrics_hook(stage, cpu_ns, count)

    def snapshot(self) -> Dict[str, Dict[str, int]]:
        """Cumulative totals: stage -> {count, cpu_ns, wall_ns}."""
        with self._lock:
            return {
                stage: {"count": e[0], "cpu_ns": e[1], "wall_ns": e[2]}
                for stage, e in self._totals.items()
            }

    def config(self) -> Dict[str, object]:
        """The debug-endpoint view: enabled + calibration outcome."""
        return {
            "stage_cpu": self.enabled,
            "clock": self.clock_mode,
            "sample_stride": self.sample_stride,
            "clock_cost_ns": self.clock_cost_ns,
        }


@contextlib.contextmanager
def stage_scope(accounting: Optional[StageCpuAccounting], stage: str):
    """Bracket a code region as one stage booking (public hook — models
    that do their own explicit host->device transfers wrap them in
    ``stage_scope(core.profiling, "device_put")``)."""
    if accounting is None or not accounting.take():
        yield
        return
    c0 = accounting.cpu_now()
    try:
        yield
    finally:
        accounting.account(stage, accounting.cpu_now() - c0)


# -- what the whole process does to a loop ------------------------------------

#: A turn of a loop that takes this long or longer is a stall. Ten steady
#: turns of the LLM engine's step loop, three admission turns (a step, a
#: 512-token prefill and its tail), a tenth of the shortest stall on
#: record (PERF.md section 7).
STALL_NS = 250_000_000

#: A stall's causes, in the order they are tried; the first that fits
#: is booked.
CAUSES = ("profiler", "compile", "gc", "other")

# jax.monitoring's names for what a compile is made of (jax/_src/dispatch.py,
# jax/_src/compiler.py): the backend's part holds a persistent-cache load
# where there was one
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_JAXPR_TRACE = frozenset((
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
))
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class ProcessEvents:
    """The collector, JAX's compiles and the profiler's sessions, as the
    process saw them: what a loop's stalled turn is held against.

    Monotone sums, each booked where its source reports, none costing
    anything between events. ``gc_ns[g]`` / ``gc_collections[g]``: the
    wall time and count of generation ``g``'s collections, two reads of
    the injected clock a collection (``gc.callbacks``). ``compile``:
    ``backend_ns`` / ``backend_count`` sum JAX's
    ``backend_compile_duration`` reports (a compile, or the load of one
    from the persistent cache), ``trace_ns`` its ``jaxpr_trace_duration``
    and ``jaxpr_to_mlir_module_duration``, ``cache_hits`` its
    ``/jax/compilation_cache/cache_hits`` events (``backend_count``
    less ``cache_hits`` is what a start compiled cold: what tells a
    set-up of minutes from a warm one). ``sessions``: for each of the
    last profiler sessions the four instants at which ``jax.profiler``'s
    start and its stop began and ended, as ``monotonic_ns`` on the
    injected clock (the clock of the laps, of a load generator's window
    and of the stall log). A trace's own zero lies some 30 us after the
    first of them (PERF.md section 7), which is what lays these records
    on a trace's host line; the trace carries no clock of the system's.
    An instant not reached yet is None.

    :meth:`listen` hooks the sources, once; nothing is hooked by
    constructing one, so a test feeds ``on_gc`` / ``on_duration`` /
    ``on_event`` itself.
    """

    def __init__(self, clock_ns: Callable[[], int] = time.monotonic_ns):
        self._clock_ns = clock_ns
        self._lock = threading.Lock()
        self._listening = False
        self._jax = False
        self._gc_since = 0
        # the two sums a closing turn is held against, kept as plain
        # attributes so that a turn reads them without a call
        self.gc_total_ns = 0
        self.compile_total_ns = 0
        self.gc_ns = [0, 0, 0]
        self.gc_collections = [0, 0, 0]
        self.compile = {"backend_ns": 0, "backend_count": 0, "trace_ns": 0,
                        "cache_hits": 0}
        self.sessions: collections.deque = collections.deque(maxlen=4)

    def listen(self) -> None:
        """Hook the collector, and JAX's reports if JAX can be imported
        (it is tried again at the next call where it could not: a
        process meets this module before it meets JAX). Idempotent."""
        if not self._listening:
            self._listening = True
            gc.callbacks.append(self.on_gc)
        if not self._jax:
            try:
                from jax import monitoring
            except Exception:  # noqa: BLE001 - optional source, never fatal
                return
            self._jax = True
            monitoring.register_event_duration_secs_listener(self.on_duration)
            monitoring.register_event_listener(self.on_event)

    # -- the sources' callbacks ---------------------------------------------

    def on_gc(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self._gc_since = self._clock_ns()
        elif self._gc_since:
            generation = min(info.get("generation", 2), 2)
            took = self._clock_ns() - self._gc_since
            self.gc_ns[generation] += took
            self.gc_total_ns += took
            self.gc_collections[generation] += 1
            self._gc_since = 0

    def on_duration(self, event: str, duration_secs: float, **_: Any) -> None:
        if event == _BACKEND_COMPILE:
            name = "backend_ns"
        elif event in _JAXPR_TRACE:
            name = "trace_ns"
        else:
            return
        took = int(duration_secs * 1e9)
        with self._lock:  # any thread compiles, two may at once
            self.compile[name] += took
            self.compile["backend_count"] += name == "backend_ns"
            self.compile_total_ns += took

    def on_event(self, event: str, **_: Any) -> None:
        if event == _CACHE_HIT:
            with self._lock:
                self.compile["cache_hits"] += 1

    def open_session(self) -> Dict[str, List[Optional[int]]]:
        """A profiler session begins: its record, instants still None."""
        session = {"monotonic_ns": [None] * 4}
        self.sessions.append(session)
        return session

    @contextlib.contextmanager
    def profiler_hold(self, session: Dict[str, List[Optional[int]]],
                      first: int):
        """Book instants ``first`` and ``first + 1`` of ``session``
        around the block: the profiler's start (0) or its stop (2)."""
        at = session["monotonic_ns"]
        at[first] = self._clock_ns()
        try:
            yield
        finally:
            at[first + 1] = self._clock_ns()

    # -- what a closing turn asks -------------------------------------------

    def in_profiler(self, since_ns: int, until_ns: int) -> bool:
        """Whether ``[since_ns, until_ns]`` (on ``clock_ns``) overlaps a
        start or a stop of the profiler, one still going on included."""
        for session in self.sessions:
            at = session["monotonic_ns"]
            for began, ended in ((at[0], at[1]), (at[2], at[3])):
                if began is not None and began <= until_ns and (
                        ended is None or ended >= since_ns):
                    return True
        return False

    def record(self) -> Dict[str, Any]:
        """The sums, as a loop serves them beside its own."""
        return {
            "gc_ns": list(self.gc_ns),
            "gc_collections": list(self.gc_collections),
            "compile": dict(self.compile),
        }


#: The process's own. It listens from the first ``LapSpans`` on, from an
#: entry point that calls ``PROCESS.listen()`` before its first compile
#: (``python -m client_tpu.server``), and from here where JAX is loaded
#: already: importing this module must not be what loads JAX.
PROCESS = ProcessEvents()
if "jax" in sys.modules:
    PROCESS.listen()


# -- lap spans ----------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, resolved once; None where jax
    or its profiler is missing (laps then keep their counters only)."""
    try:
        from jax.profiler import TraceAnnotation
    except Exception:  # noqa: BLE001 - optional capture, never fatal
        return None
    return TraceAnnotation


class LapSpans:
    """Laps of one loop by named phase, tiling its wall time, and a
    record of its turns.

    ``names`` maps each phase to its trace annotation's name.
    ``enter(phase)`` is a phase boundary: ONE read of the injected clock,
    and the interval since the previous boundary is credited to the
    phase that just ended. Neighbours share their boundary read, so
    ``sum(ns.values())`` is exactly the wall time from the first
    ``enter`` to the last boundary, less what was parked: what no phase
    names shows up in the phase that surrounds it, never in a hole.
    ``park()`` ends the open phase without opening another (the loop
    sleeps until there is work); parked time is in no phase.

    **Turns.** ``turn(steps)``, called once an iteration right after the
    ``enter`` that begins it, closes the turn that ended at that
    boundary; ``park(steps)`` closes the open turn too. ``steps`` is the
    loop's own monotone count of units of work (the engine's decode
    steps). A turn's wall time is the sum of its laps. Under
    :data:`STALL_NS` the turn is *steady*: its laps count to
    ``steady_ns`` and its steps to ``steady_steps``. At ``STALL_NS`` or
    more it is a *stall*: its laps are added to ``stall_phase_ns``, its
    wall time to ``stall_ns[cause]`` and one to ``stalls[cause]``, an
    entry is kept in ``stall_log`` (the last 16) and handed to
    ``on_stall``. So ``steady_ns[p] + stall_phase_ns[p] == ns[p]`` for
    every phase at every turn boundary, ``loop_ns`` is the sum of all of
    them there, and ``steady_ns`` over ``steady_steps`` is the loop's
    pace without its stalls. Between two boundaries the record trails
    ``ns`` by the laps the open turn has booked so far, which are
    neither yet. Everything served (:meth:`record`) is monotone.

    **Causes** (:data:`CAUSES`, first match): ``profiler`` if the turn
    overlaps a start or stop of ``jax.profiler`` booked by
    :func:`maybe_jax_trace`; ``compile`` if ``process``'s compile sums
    grew by half the turn's wall time or more while it was open; ``gc``
    if the collector's did; else ``other``. Whatever the cause, if the
    :class:`StallWatch` sampled the threads' stacks during the turn, the
    entry holds the sample, how late the watch was and the CPU time the
    process burned meanwhile: late, and the whole process stood still
    (with about the lateness of CPU time, a thread computing under the
    interpreter lock; with next to none, the process stopped or every
    thread blocked); punctual, and the stacks are what was running.

    Every lap also opens a ``TraceAnnotation`` on the calling thread and
    closes the previous one, so a ``jax.profiler`` trace carries the
    phases as events on that thread's host line. Their durations are the
    counters'. Their place against the trace's DEVICE lines is not to be
    trusted raw: on a TPU v5e the device's line ran 1.65 and 2.13 ms
    ahead of the host's in two traces (PERF.md section 6, PR 25), so
    overlap with device gaps needs the alignment that
    ``benchmark/lib/span_reduce.py`` fits first. A lap may cross an
    ``await``: the annotation is closed at the next boundary, on the
    loop's own thread, whatever else ran on it in between. With no
    profiler session the annotation is a flag test.

    One loop, one thread: boundaries are not locked. Readers on other
    threads (``ns`` is a dict of ints) see each counter whole and the
    set at most one lap apart; the watch reads the open phase and its
    boundary, and leaves its sample in one assignment.
    """

    __slots__ = ("ns", "stall_phase_ns", "loop_ns", "steady_steps",
                 "stalls", "stall_ns", "stall_log", "on_stall", "_clock_ns",
                 "_names", "_phase", "_since", "_annotation", "_open",
                 "_process", "_closed", "_turn_since", "_turn_gc",
                 "_turn_compile", "_steps", "_sampled")

    def __init__(self, names: Dict[str, str],
                 clock_ns: Callable[[], int] = time.monotonic_ns,
                 process: Optional[ProcessEvents] = None,
                 on_stall: Optional[Callable[[Dict[str, Any]], None]] = None):
        self.ns: Dict[str, int] = dict.fromkeys(names, 0)
        self.stall_phase_ns: Dict[str, int] = dict.fromkeys(names, 0)
        self.loop_ns = 0
        self.steady_steps = 0
        self.stalls: Dict[str, int] = dict.fromkeys(CAUSES, 0)
        self.stall_ns: Dict[str, int] = dict.fromkeys(CAUSES, 0)
        self.stall_log: collections.deque = collections.deque(maxlen=16)
        self.on_stall = on_stall
        self._clock_ns = clock_ns
        self._names = dict(names)
        self._phase: Optional[str] = None  # None: parked
        self._since = 0
        self._annotation = _trace_annotation()
        self._open = None
        if process is None:
            process = PROCESS
            process.listen()
        self._process = process
        # ``ns`` as it stood where the last turn closed: the closed
        # turns' laps, steady and stalled (a steady turn costs one copy)
        self._closed = dict(self.ns)
        # the open turn: where it opened (None: no turn is open) and the
        # process's sums there; the loop's steps so far
        self._turn_since: Optional[int] = None
        self._turn_gc = self._turn_compile = 0
        self._steps = 0
        # the watch's last sample: (the lap's boundary, how late the
        # watch woke, the process's CPU time since the wake-up before,
        # the collapsed stacks)
        self._sampled: Optional[Tuple[int, int, int, str]] = None

    @property
    def steady_ns(self) -> Dict[str, int]:
        """The steady turns' laps: the closed turns' less the stalls'."""
        stalled = self.stall_phase_ns
        return {phase: total - stalled[phase]
                for phase, total in self._closed.items()}

    def enter(self, phase: str) -> int:
        """End the open phase here and open ``phase``; returns the
        boundary's instant. Entering the open phase again is no boundary
        (and returns the last one's instant)."""
        if phase == self._phase:
            return self._since
        now = self._clock_ns()
        if self._phase is not None:
            self.ns[self._phase] += now - self._since
        else:
            self._turn_since = now
            self._turn_gc = self._process.gc_total_ns
            self._turn_compile = self._process.compile_total_ns
        self._phase, self._since = phase, now
        if self._annotation is not None:
            if self._open is not None:
                self._open.__exit__(None, None, None)
            self._open = self._annotation(self._names[phase])
            self._open.__enter__()
        return now

    def park(self, steps: Optional[int] = None) -> None:
        """End the open phase and close the open turn; nothing is open
        until the next ``enter``."""
        if self._phase is None:
            return
        now = self._clock_ns()
        self.ns[self._phase] += now - self._since
        self._phase, self._since = None, now
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None
        self.turn(steps)
        self._turn_since = None

    def turn(self, steps: Optional[int] = None) -> None:
        """Close the open turn at the last boundary and open the next
        one there; ``steps`` is the loop's count of steps so far."""
        since, until = self._turn_since, self._since
        took = 0 if steps is None else steps - self._steps
        self._steps += took
        if since is None or until == since:
            return
        wall = until - since
        process = self._process
        gc_ns, compile_ns = process.gc_total_ns, process.compile_total_ns
        if wall >= STALL_NS:
            self._stall(since, until, took, gc_ns - self._turn_gc,
                        compile_ns - self._turn_compile)
        else:
            self.steady_steps += took
        self._closed = self.ns.copy()
        self.loop_ns += wall
        self._turn_since, self._turn_gc, self._turn_compile = (
            until, gc_ns, compile_ns)

    def _stall(self, since: int, until: int, steps: int, gc_ns: int,
               compile_ns: int) -> None:
        wall = until - since
        closed, stalled, spent = self._closed, self.stall_phase_ns, {}
        for phase, total in self.ns.items():
            if total != closed[phase]:
                spent[phase] = total - closed[phase]
                stalled[phase] += spent[phase]
        if self._process.in_profiler(since, until):
            cause = "profiler"
        elif 2 * compile_ns >= wall:
            cause = "compile"
        elif 2 * gc_ns >= wall:
            cause = "gc"
        else:
            cause = "other"
        self.stalls[cause] += 1
        self.stall_ns[cause] += wall
        sampled = self._sampled
        if sampled is None or not since <= sampled[0] <= until:
            sampled = (None, None, None, "")
        entry = {
            "at_ns": until,
            "wall_ns": wall,
            "cause": cause,
            "phase": max(spent, key=spent.get),
            "phase_ns": spent,
            "steps": steps,
            "gc_ns": gc_ns,
            "compile_ns": compile_ns,
            "watch_late_ns": sampled[1],
            "watch_cpu_ns": sampled[2],
            "stacks": sampled[3],
        }
        self.stall_log.append(entry)
        if self.on_stall is not None:
            self.on_stall(entry)

    def record(self) -> Dict[str, Any]:
        """What a loop serves of its turns beside ``ns``, numbers only
        and all monotone, with the process's sums."""
        return {
            "loop_ns": self.loop_ns,
            "steady_steps": self.steady_steps,
            "steady_phase_ns": self.steady_ns,
            "stall_phase_ns": dict(self.stall_phase_ns),
            "stalls": dict(self.stalls),
            "stall_ns": dict(self.stall_ns),
            **self._process.record(),
        }


# -- sampling profiler --------------------------------------------------------


@dataclass
class ProfileResult:
    """One sampling run's aggregate: unique stacks -> sample counts.

    Stacks are root->leaf frame-label tuples, prefixed with the thread
    name, exactly as the collapsed exporter prints them.
    """

    duration_s: float = 0.0
    hz_requested: float = 0.0
    hz_effective: float = 0.0
    sample_count: int = 0
    sample_cost_ns: int = 0
    stacks: Dict[Tuple[str, ...], int] = field(default_factory=dict)

    # -- exporters ----------------------------------------------------------

    def collapsed(self) -> str:
        """flamegraph.pl collapsed-stack format: ``f1;f2;f3 count``."""
        lines = [
            ";".join(stack) + f" {count}"
            for stack, count in sorted(self.stacks.items())
        ]
        return "\n".join(lines) + ("\n" if lines else "")

    def speedscope(self, name: str = "client-tpu-server") -> Dict:
        """The speedscope.app JSON document (type "sampled"); weights are
        seconds per sample at the effective rate."""
        frame_index: Dict[str, int] = {}
        frames: List[Dict[str, str]] = []
        samples: List[List[int]] = []
        weights: List[float] = []
        period_s = 1.0 / self.hz_effective if self.hz_effective > 0 else 0.0
        for stack, count in sorted(self.stacks.items()):
            indices = []
            for label in stack:
                index = frame_index.get(label)
                if index is None:
                    index = frame_index[label] = len(frames)
                    frames.append({"name": label})
                indices.append(index)
            samples.append(indices)
            weights.append(count * period_s)
        total = sum(weights)
        return {
            "$schema": "https://www.speedscope.app/file-format-schema.json",
            "shared": {"frames": frames},
            "profiles": [
                {
                    "type": "sampled",
                    "name": name,
                    "unit": "seconds",
                    "startValue": 0.0,
                    "endValue": total,
                    "samples": samples,
                    "weights": weights,
                }
            ],
            "name": name,
            "activeProfileIndex": 0,
            "exporter": "client-tpu-profiler",
        }


def _code_label(code) -> str:
    return f"{os.path.basename(code.co_filename)}:{code.co_name}"


def _frame_label(frame) -> str:
    return _code_label(frame.f_code)


class WallProfiler:
    """Wall-clock stack sampler over ``sys._current_frames()``.

    One :meth:`run` samples every OTHER thread's Python stack at ``hz``
    for ``duration_s``. The measured-overhead guard times the first
    sample pass and widens the interval so sampling never exceeds
    ``overhead_cap`` of one core's time — a pathological process (many
    threads, deep stacks) degrades to a slower profile, never to a
    profiler-induced outage. All time sources are injectable (tests run
    on fake clocks; no direct ``time.*()`` calls — clock_lint enforced).
    """

    def __init__(
        self,
        hz: float = 99.0,
        max_depth: int = 64,
        overhead_cap: float = 0.1,
        clock_ns: Callable[[], int] = time.monotonic_ns,
        sleep: Callable[[float], None] = time.sleep,
        frames: Callable[[], Dict] = sys._current_frames,
    ):
        if hz <= 0:
            raise ValueError(f"hz must be > 0, got {hz}")
        if not 0 < overhead_cap <= 1:
            raise ValueError(f"overhead_cap must be in (0, 1], got {overhead_cap}")
        self.hz = float(hz)
        self.max_depth = max_depth
        self.overhead_cap = overhead_cap
        self._clock_ns = clock_ns
        self._sleep = sleep
        self._frames = frames

    def _thread_names(self) -> Dict[int, str]:
        return {
            t.ident: t.name for t in threading.enumerate() if t.ident is not None
        }

    def _sample(self, result: ProfileResult, skip_ident: int) -> None:
        names = self._thread_names()
        for ident, frame in self._frames().items():
            if ident == skip_ident:
                continue
            stack: List[str] = []
            depth = 0
            while frame is not None and depth < self.max_depth:
                stack.append(_frame_label(frame))
                frame = frame.f_back
                depth += 1
            stack.append(names.get(ident, f"thread-{ident}"))
            key = tuple(reversed(stack))  # root -> leaf, thread name first
            result.stacks[key] = result.stacks.get(key, 0) + 1
        result.sample_count += 1

    def run(self, duration_s: float) -> ProfileResult:
        """Sample for ``duration_s`` seconds; returns the aggregate."""
        if duration_s <= 0:
            raise ValueError(f"duration_s must be > 0, got {duration_s}")
        own = threading.get_ident()
        result = ProfileResult(duration_s=duration_s, hz_requested=self.hz)
        interval_ns = int(1e9 / self.hz)
        # Overhead guard: EVERY sample is timed and the interval widens
        # so (worst sample cost / interval) stays under overhead_cap.
        # The first sample alone is not enough — it can land while the
        # process has few/shallow threads, and a later, pricier sample
        # (load arrived, stacks deepened) must not turn the loop into a
        # back-to-back busy spin.
        start_ns = self._clock_ns()
        self._sample(result, own)
        now_ns = self._clock_ns()
        result.sample_cost_ns = max(0, now_ns - start_ns)
        interval_ns = max(
            interval_ns, int(result.sample_cost_ns / self.overhead_cap), 1
        )
        result.hz_effective = 1e9 / interval_ns
        deadline_ns = start_ns + int(duration_s * 1e9)
        next_ns = start_ns + interval_ns
        while now_ns < deadline_ns:
            if next_ns > now_ns:
                self._sleep((next_ns - now_ns) / 1e9)
            sample_start_ns = self._clock_ns()
            self._sample(result, own)
            now_ns = self._clock_ns()
            cost_ns = max(0, now_ns - sample_start_ns)
            if cost_ns > result.sample_cost_ns:
                result.sample_cost_ns = cost_ns
                floor_ns = int(cost_ns / self.overhead_cap)
                if floor_ns > interval_ns:
                    interval_ns = floor_ns
                    result.hz_effective = 1e9 / interval_ns
            # never schedule the next sample closer than the idle gap
            # the cap demands (interval >= cost/cap >= cost, so the gap
            # is non-negative) — a lagging next_ns must not busy-loop
            next_ns = max(
                next_ns + interval_ns, now_ns + (interval_ns - cost_ns)
            )
        return result


@contextlib.contextmanager
def maybe_jax_trace(log_dir: Optional[str],
                    process: Optional[ProcessEvents] = None):
    """``jax.profiler.trace`` around a sampling window when available.

    The wall sampler sees Python frames only; device-placed models hide
    their time inside XLA. Passing ``jax_trace_dir`` to the profile
    endpoint captures the device timeline alongside — silently skipped
    when jax (or its profiler) is missing, so the sampler never fails
    because the optional extra isn't installed.

    The profiler's start and its stop hold the whole process for
    seconds, so each is booked: ``process`` (:data:`PROCESS`) keeps the
    instants at which they began and ended on its clock, and a
    loop's turn that overlaps one is a stall of cause ``profiler``.
    """
    if not log_dir:
        yield
        return
    try:
        import jax

        trace_ctx = jax.profiler.trace(log_dir)
    except Exception:  # noqa: BLE001 - optional capture, never fatal
        yield
        return
    process = PROCESS if process is None else process
    session = process.open_session()
    with process.profiler_hold(session, 0):
        trace_ctx.__enter__()
    try:
        yield
    finally:
        with process.profiler_hold(session, 2):
            trace_ctx.__exit__(*sys.exc_info())


# -- the watch ----------------------------------------------------------------

# a thread whose innermost Python frame is one of these is waiting for
# work, not doing any: left out of a stall's stacks (but counted). The
# standard library's; whoever starts a thread that waits inside a native
# call of its own says so (``StallWatch.idle_in``)
_IDLE_LEAVES = (
    "threading.py:wait", "threading.py:_wait_for_tstate_lock",
    "selectors.py:select", "queue.py:get", "thread.py:_worker",
    "socket.py:accept",
)
_STACKS_CAP = 2000  # and a line for the idle threads' count
_STACK_DEPTH = 24


def _daemon(run: Callable[[], None]) -> threading.Thread:
    thread = threading.Thread(target=run, name="stall-watch", daemon=True)
    thread.start()
    return thread


class StallWatch:
    """The evidence for a stall of cause ``other``: one daemon thread a
    process that wakes every ``STALL_NS / 2`` and, when a registered
    loop has been inside one phase for ``STALL_NS`` or more and is not
    parked, takes ONE sample of every thread's stack (once a lap) and
    notes how late its own wake-up was and how much CPU time the process
    burned since the one before. The loop's ``LapSpans`` puts all three
    into the stall's log entry when the turn closes.

    A late watch means the whole process stood still (the interpreter
    lock held in native code, the machine); a punctual one has the stack
    that was running. The thread starts with the first loop registered
    and ends when none is left. A loop on another clock than the watch's
    (a test double on a fake clock) is not registered: the watch could
    not tell how long it has been anywhere.

    :meth:`check` is the whole of a wake-up and takes the instant and
    the lateness, so a test drives it with injected frames, and with a
    ``spawn`` that starts nothing, without a thread.
    """

    def __init__(self, clock_ns: Callable[[], int] = time.monotonic_ns,
                 frames: Callable[[], Dict] = sys._current_frames,
                 spawn: Callable[[Callable[[], None]], Any] = _daemon,
                 cpu_ns: Callable[[], int] = time.process_time_ns):
        self._clock_ns = clock_ns
        self._cpu_ns = cpu_ns
        self._sampler = WallProfiler(max_depth=_STACK_DEPTH, frames=frames)
        self._spawn = spawn
        self._lock = threading.Lock()
        self._loops: Dict[LapSpans, int] = {}  # -> its thread's ident
        self._idle_leaves = set(_IDLE_LEAVES)
        self._thread: Any = None
        self._wake = threading.Event()

    def register(self, laps: LapSpans) -> None:
        """Watch ``laps``, whose loop runs on the calling thread."""
        if laps._clock_ns is not self._clock_ns:
            return
        with self._lock:
            self._loops[laps] = threading.get_ident()
            # whatever an ``unregister`` set and the thread has not seen
            # yet: left set, every wait would return at once
            self._wake.clear()
            if self._thread is None:
                self._thread = self._spawn(self._run)

    def unregister(self, laps: LapSpans) -> None:
        with self._lock:
            self._loops.pop(laps, None)
            if not self._loops:
                self._wake.set()

    def idle_in(self, function: Callable[..., Any]) -> None:
        """A thread whose innermost Python frame is ``function`` waits
        for work there (inside a native call): a stall's stacks count
        it among the idle threads."""
        self._idle_leaves.add(_code_label(function.__code__))

    def _run(self) -> None:
        period_ns = STALL_NS // 2
        due_ns = self._clock_ns() + period_ns
        cpu_ns = self._cpu_ns()
        while True:
            self._wake.wait(max(0, due_ns - self._clock_ns()) / 1e9)
            with self._lock:
                if not self._loops:
                    self._thread = None
                    return
            now_ns, cpu_before, cpu_ns = self._clock_ns(), cpu_ns, self._cpu_ns()
            self.check(now_ns, now_ns - due_ns, cpu_ns - cpu_before)
            due_ns = now_ns + period_ns

    def check(self, now_ns: int, late_ns: int = 0, cpu_ns: int = 0) -> None:
        """One wake-up at ``now_ns``, ``late_ns`` after it was due, the
        process having burned ``cpu_ns`` of CPU (all its threads) since
        the wake-up before: beside a long lateness, next to none says
        the process was not running at all (stopped, or every thread
        blocked), about as much says one thread computed while it held
        the interpreter lock, more says several did."""
        with self._lock:
            loops = list(self._loops.items())
        for laps, ident in loops:
            phase, since = laps._phase, laps._since
            sampled = laps._sampled
            if (phase is None or now_ns - since < STALL_NS
                    or (sampled is not None and sampled[0] == since)):
                continue
            laps._sampled = (since, late_ns, cpu_ns, self._stacks(ident))

    def _stacks(self, loop_ident: int) -> str:
        """Collapsed stacks of every thread but this one, the loop's own
        first and the idle ones only counted; a stack that would take
        the text past ``_STACKS_CAP`` bytes is left out whole."""
        result = ProfileResult()
        self._sampler._sample(result, threading.get_ident())
        loop = self._sampler._thread_names().get(
            loop_ident, f"thread-{loop_ident}") + ";"
        text, idle = "", 0
        for line in sorted(result.collapsed().splitlines(),
                           key=lambda line: not line.startswith(loop)):
            leaf = line.rsplit(" ", 1)[0].rsplit(";", 1)[-1]
            if leaf in self._idle_leaves and not line.startswith(loop):
                idle += 1
            elif len(text) + len(line) < _STACKS_CAP:
                text += line + "\n"
        if idle:
            text += f"(idle threads) {idle}\n"
        return text


#: The process's own watch.
WATCH = StallWatch()
