#!/usr/bin/env python3
"""Where the device's idle gaps go, by the engine's own lap spans.

    python3 benchmark/tools/span_gaps.py --xplane <file.xplane.pb>

Reduces a trace that is there (taken from a serving process through
``GET /v2/debug/profile?jax_trace_dir=<dir>``, under
``<dir>/plugins/profile/*/``): every device idle gap of 20 us or more
split by overlap with the ``engine.*`` events
(`benchmark/lib/span_reduce.py`), as ``{phase: seconds}``, as the trace
has its lines and with the device's line moved by ``device_lead_ms``,
beside `trace_reduce`'s table, which gives each whole gap to the Python
frame at its midpoint. Needs no chip. One JSON object on stdout; with
``--record``, a slice of the trace small enough to keep beside the
tests.
"""

import argparse
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


@functools.lru_cache(maxsize=None)
def host_phases() -> tuple:
    """The phases with the device idle, as `engine.step_ms.host` adds them."""
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           "engine.step_ms.host.json")) as f:
        return tuple(json.load(f)["params"]["phases"])


def reduce_xplane(path: str, record: str = None) -> dict:
    from benchmark.lib import span_reduce, trace_reduce

    events = trace_reduce.load_xplane(path)
    summary = span_reduce.split(events)
    summary["midpoint_gaps_s"] = dict(trace_reduce.top(
        trace_reduce.reduce(events)["idle_gaps"]))
    summary["device_modules"] = sorted({
        trace_reduce.op_family(name) for plane, line, name, _, _ in events
        if trace_reduce.DEVICE_PLANE.match(plane)
        and line == trace_reduce.MODULES_LINE})
    if record:
        with open(record, "w") as f:
            json.dump(span_reduce.sample(events), f)
    return summary


def host_shares(by_phase: dict) -> dict:
    """Each host phase's share (%) of the host phases' sum."""
    phases = host_phases()
    total = sum(by_phase.get(p, 0) for p in phases)
    return {p: 100.0 * by_phase.get(p, 0) / total for p in phases} if total else {}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--xplane", required=True, help="the trace to reduce")
    parser.add_argument("--record", help="write a small slice of the events "
                        "here (a recorded trace for the tests)")
    args = parser.parse_args()
    summary = reduce_xplane(args.xplane, args.record)
    for table in ("gaps_s", "aligned_gaps_s"):
        if summary.get(table):
            summary[table.replace("_s", "_host_shares")] = host_shares(
                summary[table])
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
