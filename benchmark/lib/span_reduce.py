"""Device idle gaps split by overlap with the engine's lap spans.

The LLM engine writes its step loop's phases into the profiler's trace
as ``engine.<phase>`` events on the engine thread's host line.
`trace_reduce.reduce` gives a whole gap to the Python frame at its
midpoint; here every gap of 20 us or more is cut where the phases change
and each piece goes to the phase that was open (``outside_engine`` where
none was). Arithmetic on neutral events (`trace_reduce.load_xplane`),
tested on a recorded trace.

The host's line and the device's are on one clock only to a millisecond
or two: on the chip the device's line has shown a decode program
starting before the ``engine.dispatch`` that launched it began, 2.13 ms
out in one trace and 1.65 ms in the next (`PERF.md` section 6, PR 25),
of an idle gap of 5-6 ms. So the split is given twice: as the trace has
it, which is NOT to be read (it booked 28-43% of the idle seconds on
``wait``), and with the device's line moved by ``device_lead_ms``, the
median time by which an ``engine.wait`` ends after the decode program it
waited for. That fit takes the host to learn of the end at once, so
``wait`` gets next to nothing by construction: the aligned split says
how the other phases share the gaps, not whether ``wait`` ends late.
"""

import bisect
import statistics

from benchmark.lib import trace_reduce

SPAN_PREFIX = "engine."
OUTSIDE = "outside_engine"
#: the decode program's module, whose starts are the steps
STEP_MODULE = "llm_decode"


def engine_spans(events: list) -> tuple:
    """([(start, end, phase)] sorted, {(plane, line)} they sit on)."""
    spans, lines = [], set()
    for plane, line, name, start, duration in events:
        if (name.startswith(SPAN_PREFIX)
                and not trace_reduce.DEVICE_PLANE.match(plane)):
            spans.append((start, start + duration, name[len(SPAN_PREFIX):]))
            lines.add((plane, line))
    return sorted(spans), lines


def first_device(events: list) -> str:
    devices = {e[0] for e in events if trace_reduce.DEVICE_PLANE.match(e[0])}
    if not devices:
        raise ValueError("the trace holds no device plane")
    return min(devices)


def device_gaps(events: list) -> tuple:
    """([(start, end)] of the first device's idle gaps of MIN_GAP_NS or
    more, its busy ns, (first operation's start, last one's end))."""
    device0 = first_device(events)
    busy_ns, merged = trace_reduce.union_ns(
        [(start, start + duration) for plane, line, _, start, duration in events
         if plane == device0 and line == trace_reduce.OPS_LINE
         and duration > 0])
    if not merged:
        raise ValueError("no operation ran on the device in the trace")
    gaps = [(a_end, b_start) for (_, a_end), (b_start, _)
            in zip(merged, merged[1:])
            if b_start - a_end >= trace_reduce.MIN_GAP_NS]
    return gaps, busy_ns, (merged[0][0], merged[-1][1])


def overlap_by_phase(spans: list, intervals: list) -> dict:
    """{phase: ns of ``intervals`` covered by that phase's spans}, with
    what no span covers under ``outside_engine``. The spans tile their
    line (one open at a time), so the pieces add up to the intervals."""
    starts = [s[0] for s in spans]
    out = {}
    for lo, hi in intervals:
        covered = 0
        i = max(0, bisect.bisect_right(starts, lo) - 1)
        while i < len(spans) and spans[i][0] < hi:
            start, end, phase = spans[i]
            piece = min(end, hi) - max(start, lo)
            if piece > 0:
                out[phase] = out.get(phase, 0) + piece
                covered += piece
            i += 1
        out[OUTSIDE] = out.get(OUTSIDE, 0) + (hi - lo) - covered
    return out


def waits_of_steps(spans: list, steps: list) -> list:
    """[(wait start, wait end, step start, step end)]: each decode
    program on the device's line with the ``wait`` span that overlaps it
    most, where that is more than half of it."""
    waits = [(start, end) for start, end, phase in spans if phase == "wait"]
    starts = [w[0] for w in waits]
    pairs = []
    for step_start, step_end in steps:
        i = bisect.bisect_right(starts, step_start)
        near = waits[max(0, i - 2): i + 2]
        if not near:
            continue
        wait = max(near, key=lambda w: min(w[1], step_end) - max(w[0], step_start))
        if 2 * (min(wait[1], step_end) - max(wait[0], step_start)) > (
                step_end - step_start):
            pairs.append((wait[0], wait[1], step_start, step_end))
    return pairs


def split(events: list) -> dict:
    """The summary:

    ``gaps_s``       {phase: seconds of device idle gaps under it}, the
                     two lines taken as the trace has them
    ``idle_s``       their sum: idle time in gaps of 20 us or more
    ``named_share``  the part of it under a named phase
    ``device_lead_ms``  median over the steps of how long after a decode
                     program's end on the device's line its ``wait``
                     span ends on the host's: the offset of the two
                     lines' clocks plus the time the host takes to learn
                     of the end (None without a step and its wait)
    ``aligned_gaps_s``  ``gaps_s`` with the device's line moved by it
    ``wait_minus_step_ms``  mean of (a ``wait`` span's length - its
                     decode program's on the device): no clock offset in
                     it
    ``spans_s``      {phase: seconds of that phase's spans inside the
                     device's own window}: the step loop's phase table
                     on the trace's clock
    ``span_lines``   [[plane, line]] the spans sit on (one: the thread
                     that runs the step loop)
    ``steps``, ``step_gap_ms``  decode programs started in the window,
                     and the mean start-to-start gap between them
    ``window_s``, ``busy_s``    as `trace_reduce.reduce` has them
    """
    spans, lines = engine_spans(events)
    gaps, busy_ns, (first, last) = device_gaps(events)
    by_phase = overlap_by_phase(spans, gaps)
    idle_ns = sum(hi - lo for lo, hi in gaps)
    inside = overlap_by_phase(spans, [(first, last)])
    inside.pop(OUTSIDE)
    device0 = first_device(events)
    steps = sorted(
        (start, start + duration) for plane, line, name, start, duration in events
        if plane == device0 and line == trace_reduce.MODULES_LINE
        and STEP_MODULE in name)
    step_gap_ms = None
    if len(steps) > 1:
        step_gap_ms = (steps[-1][0] - steps[0][0]) / 1e6 / (len(steps) - 1)
    pairs = waits_of_steps(spans, steps)
    lead_ns = aligned = wait_minus_step_ms = None
    if pairs:
        lead_ns = int(statistics.median(w_end - s_end
                                        for _, w_end, _, s_end in pairs))
        aligned = overlap_by_phase(
            spans, [(lo + lead_ns, hi + lead_ns) for lo, hi in gaps])
        wait_minus_step_ms = statistics.fmean(
            (w_end - w_start) - (s_end - s_start)
            for w_start, w_end, s_start, s_end in pairs) / 1e6
    return {
        "gaps_s": {phase: ns / 1e9 for phase, ns in by_phase.items()},
        "idle_s": idle_ns / 1e9,
        "named_share": (1.0 - by_phase.get(OUTSIDE, 0) / idle_ns
                        if idle_ns else None),
        "device_lead_ms": None if lead_ns is None else lead_ns / 1e6,
        "aligned_gaps_s": (None if aligned is None else
                           {phase: ns / 1e9 for phase, ns in aligned.items()}),
        "wait_minus_step_ms": wait_minus_step_ms,
        "spans_s": {phase: ns / 1e9 for phase, ns in inside.items()},
        "span_lines": sorted(list(pair) for pair in lines),
        "steps": len(steps),
        "step_gap_ms": step_gap_ms,
        "window_s": (last - first) / 1e9,
        "busy_s": busy_ns / 1e9,
    }


def sample(events: list, span_ns: int = 110_000_000,
           min_host_ns: int = 10_000) -> list:
    """The first device's operations and modules in the ``span_ns`` after
    the middle of the trace, the ``engine.*`` spans that touch it, and
    the step loop's Python frames of ``min_host_ns`` or more in it (for
    the midpoint table beside), starts rebased, names cut short: a
    recorded trace small enough to keep beside the tests."""
    device0 = first_device(events)
    on_device = [e for e in events if e[0] == device0]
    middle = (min(e[3] for e in on_device) + max(e[3] for e in on_device)) // 2
    end = middle + span_ns
    python_line = trace_reduce._python_line(events)

    def short(name):
        head = name.split(" = ")[0][:48]
        return (head + " = " + trace_reduce.PALLAS
                if trace_reduce.PALLAS in name else head)

    keep = []
    for event in events:
        plane, line, name, start, duration = event
        touches = start < end and start + duration > middle
        if plane == device0:
            if (line in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE)
                    and middle <= start and start + duration <= end):
                keep.append(event)
        elif name.startswith(SPAN_PREFIX):
            if touches:
                keep.append(event)
        elif ((plane, line) == python_line and touches
              and duration >= min_host_ns):
            keep.append(event)
    keep.sort(key=lambda e: e[3])
    return [[e[0], e[1], short(e[2]), e[3] - middle, e[4]] for e in keep]
