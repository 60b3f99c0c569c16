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
    profiler is unavailable.

Everything is clock-injectable — ``wall_ns``/``cpu_ns``/``sleep`` — and
``tools/clock_lint.py`` bans direct ``time.*()`` calls here (including
``thread_time_ns``), so the sampler and the accounting test on fake
clocks without sleeping.
"""

import contextlib
import functools
import os
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

__all__ = [
    "STAGES",
    "LapSpans",
    "ProfileResult",
    "StageCpuAccounting",
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
    """Laps of one loop by named phase, tiling its wall time.

    ``names`` maps each phase to its trace annotation's name.
    ``enter(phase)`` is a phase boundary: ONE read of the injected clock,
    and the interval since the previous boundary is credited to the
    phase that just ended. Neighbours share their boundary read, so
    ``sum(ns.values())`` is exactly the wall time from the first
    ``enter`` to the last boundary, less what was parked: what no phase
    names shows up in the phase that surrounds it, never in a hole.
    ``park()`` ends the open phase without opening another (the loop
    sleeps until there is work); parked time is in no phase.

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
    set at most one lap apart.
    """

    __slots__ = ("ns", "_clock_ns", "_names", "_phase", "_since",
                 "_annotation", "_open")

    def __init__(self, names: Dict[str, str],
                 clock_ns: Callable[[], int] = time.monotonic_ns):
        self.ns: Dict[str, int] = dict.fromkeys(names, 0)
        self._clock_ns = clock_ns
        self._names = dict(names)
        self._phase: Optional[str] = None  # None: parked
        self._since = 0
        self._annotation = _trace_annotation()
        self._open = None

    def enter(self, phase: str) -> int:
        """End the open phase here and open ``phase``; returns the
        boundary's instant. Entering the open phase again is no boundary
        (and returns the last one's instant)."""
        if phase == self._phase:
            return self._since
        now = self._clock_ns()
        if self._phase is not None:
            self.ns[self._phase] += now - self._since
        self._phase, self._since = phase, now
        if self._annotation is not None:
            if self._open is not None:
                self._open.__exit__(None, None, None)
            self._open = self._annotation(self._names[phase])
            self._open.__enter__()
        return now

    def park(self) -> None:
        """End the open phase; nothing is open until the next ``enter``."""
        if self._phase is None:
            return
        self.ns[self._phase] += self._clock_ns() - self._since
        self._phase = None
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


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


def _frame_label(frame) -> str:
    code = frame.f_code
    return f"{os.path.basename(code.co_filename)}:{code.co_name}"


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
def maybe_jax_trace(log_dir: Optional[str]):
    """``jax.profiler.trace`` around a sampling window when available.

    The wall sampler sees Python frames only; device-placed models hide
    their time inside XLA. Passing ``jax_trace_dir`` to the profile
    endpoint captures the device timeline alongside — silently skipped
    when jax (or its profiler) is missing, so the sampler never fails
    because the optional extra isn't installed.
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
    with trace_ctx:
        yield
