"""From a profiler trace to the few numbers the readers need.

`load_xplane` turns an ``.xplane.pb`` into neutral events; `reduce` is
arithmetic on those events alone, so it is tested on a small recorded
trace kept in `benchmark/tests/` and every PR computes the same numbers
the same way. Device events carry XLA's own names (the program has no
`named_scope` yet), normalised by `op_family`.
"""

import bisect
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
#: a gap shorter than this between two device operations is not idle time
#: worth naming (it is still idle time in `busy_s`)
MIN_GAP_NS = 20_000


def load_xplane(path: str) -> list:
    """[(plane, line, name, start_ns, duration_ns)] of every event."""
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for event in line.events:
                events.append((plane.name, line.name, event.name,
                               int(event.start_ns), int(event.duration_ns)))
    return events


PALLAS = 'custom_call_target="tpu_custom_call"'
#: host frames that only wait or belong to the event loop and the profiler:
#: an idle gap is named by the innermost frame that is none of these
PLUMBING = re.compile(
    r"^(base_events|events|selectors|selector_events|threading|queues|"
    r"profiling|_profiler|profiler|runners|futures|tasks|thread)\.py_")


def op_family(name: str) -> str:
    """``%fusion.123 = ...`` -> ``fusion``: XLA's name without the
    instance number, in the characters a metric name may have. A Pallas
    kernel (a ``tpu_custom_call``) keeps that mark behind its name:
    ``_lambda_.tpu_custom_call``."""
    head = name.split(" = ")[0].strip().lstrip("%$")
    head = re.sub(r"\.\d+$", "", head)
    head = re.sub(r"\(.*$", "", head)
    head = re.sub(r"[^A-Za-z0-9_.-]", "_", head)[:48] or "unnamed"
    return head + ".tpu_custom_call" if PALLAS in name else head


def union_ns(intervals: list) -> tuple:
    """(total covered ns, merged [start, end] list) of [start, end] pairs."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return sum(end - start for start, end in merged), merged


def _owner(host_starts, host_events, at_ns, lookback=4000) -> str:
    """The innermost host frame that covers ``at_ns`` and is not
    plumbing; failing that the innermost of any."""
    i = bisect.bisect_right(host_starts, at_ns) - 1
    fallback = "no_host_event"
    for j in range(i, max(-1, i - lookback), -1):
        start, end, name = host_events[j]
        if start <= at_ns < end:
            if not PLUMBING.match(name) and ".py_" in name:
                return name
            if fallback == "no_host_event":
                fallback = name
    return fallback


def _python_line(events) -> tuple:
    """(plane, line) of the host thread with most Python frames: the
    server's event loop, where the engine's step loop runs."""
    counts = {}
    for plane, line, name, _, _ in events:
        if name.startswith("$") and not DEVICE_PLANE.match(plane):
            counts[(plane, line)] = counts.get((plane, line), 0) + 1
    return max(counts, key=counts.get) if counts else (None, None)


def reduce(events: list) -> dict:
    """The trace's summary:

    ``window_s``   first device operation's start to the last one's end:
                   the profiler's own start and stop (the device idles
                   for most of a second while ``stop_trace`` holds the
                   interpreter) are not part of the window
    ``busy_s``     union of device-operation intervals, mean over devices
    ``ops``        {op family: seconds} summed over devices
    ``module_runs`` [[module family, seconds, {op family: seconds}], ...]
                   one entry an execution of a compiled program on device 0
    ``idle_gaps``  {innermost host frame at the gap's middle: seconds}
    """
    devices = sorted({e[0] for e in events if DEVICE_PLANE.match(e[0])})
    if not devices:
        raise ValueError("the trace holds no device plane")
    on_device = [e for e in events if e[0] in devices and e[1] == OPS_LINE
                 and e[4] > 0]
    if not on_device:
        raise ValueError("no operation ran on the device in the trace")
    first = min(e[3] for e in on_device)
    last = max(e[3] + e[4] for e in on_device)
    ops, busy, merged0 = {}, [], []
    for device in devices:
        intervals = []
        for plane, line, name, start, duration in events:
            if plane == device and line == OPS_LINE and duration > 0:
                intervals.append((start, start + duration))
                family = op_family(name)
                ops[family] = ops.get(family, 0.0) + duration / 1e9
        total, merged = union_ns(intervals)
        busy.append(total / 1e9)
        if device == devices[0]:
            merged0 = merged
    # executions of compiled programs on the first device, with their ops
    modules = sorted((start, start + duration, op_family(name))
                     for plane, line, name, start, duration in events
                     if plane == devices[0] and line == MODULES_LINE)
    op_events = sorted((start, duration, op_family(name))
                       for plane, line, name, start, duration in events
                       if plane == devices[0] and line == OPS_LINE)
    op_starts = [e[0] for e in op_events]
    module_runs = []
    for start, end, family in modules:
        inside = {}
        i = bisect.bisect_left(op_starts, start)
        while i < len(op_events) and op_events[i][0] < end:
            inside[op_events[i][2]] = (inside.get(op_events[i][2], 0.0)
                                       + op_events[i][1] / 1e9)
            i += 1
        module_runs.append([family, (end - start) / 1e9, inside])
    python_line = _python_line(events)
    host = sorted((start, start + duration, op_family(name))
                  for plane, line, name, start, duration in events
                  if (plane, line) == python_line and duration > 0)
    host_starts = [e[0] for e in host]
    idle = {}
    edges = merged0
    for (_, gap_start), (gap_end, _) in zip(edges, edges[1:]):
        if gap_end - gap_start >= MIN_GAP_NS:
            name = _owner(host_starts, host, (gap_start + gap_end) // 2)
            idle[name] = idle.get(name, 0.0) + (gap_end - gap_start) / 1e9
    return {
        "window_s": (last - first) / 1e9,
        "busy_s": sum(busy) / len(busy),
        "devices": len(devices),
        "ops": ops,
        "module_runs": module_runs,
        "idle_gaps": idle,
    }


def sample(events: list, span_ns: int = 120_000_000, limit: int = 6000) -> list:
    """The events of the ``span_ns`` after the middle of the trace, at
    most ``limit`` of them, starts rebased: a recorded trace small
    enough to keep beside the tests."""
    middle = (min(e[3] for e in events) + max(e[3] for e in events)) // 2
    inside = sorted((e for e in events if middle <= e[3] < middle + span_ns),
                    key=lambda e: e[3])
    device = [e for e in inside if DEVICE_PLANE.match(e[0])]
    host = [e for e in inside if not DEVICE_PLANE.match(e[0])]
    keep = device[: limit * 3 // 4] + host[: limit // 4]

    def short(name):
        head = name.split(" = ")[0][:80]
        return head + " = " + PALLAS if PALLAS in name else head

    return [[e[0], e[1], short(e[2]), e[3] - middle, e[4]] for e in keep]


def top(table: dict, n: int = 10) -> list:
    return [[name, seconds] for name, seconds in
            sorted(table.items(), key=lambda item: -item[1])[:n]]
