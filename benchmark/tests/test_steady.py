"""CPU tests of what reads the engine's record of its turns: the `steady`
readers on two hand-made snapshots, a parent without the record, and
`BENCHMARK.json`'s entries for them.
"""

import json
import os
import types

import pytest

from benchmark import run as harness
from benchmark.readers import steady

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MS = 1_000_000
PHASES = ("schedule", "prefill", "propose", "dispatch", "wait", "readback",
          "sample", "emit", "yield")
CAUSES = ("profiler", "compile", "gc", "other")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
NEW = [m["name"] for m in BENCHMARK["per_layer"]
       if m["name"].endswith(".steady")
       or m["name"].startswith(("engine.stall_share.", "engine.gc_ms",
                                "setup.compile_", "setup.jaxpr_"))]


def snapshot(at_ms, steps, steady_steps, steady_ms, stall_ms, gc_ms, compile_ms,
             trace_ms):
    steady = {p: int(steady_ms.get(p, 0) * MS) for p in PHASES}
    stalled = {c: int(stall_ms.get(c, 0) * MS) for c in CAUSES}
    engine = {
        "steps": steps, "steady_steps": steady_steps,
        "steady_phase_ns": steady, "stall_ns": stalled,
        "loop_ns": sum(steady.values()) + sum(stalled.values()),
        "gc_ns": [int(g * MS) for g in gc_ms],
        "compile": {"backend_ns": int(compile_ms * MS), "backend_count": 15,
                    "trace_ns": int(trace_ms * MS), "cache_hits": 15},
    }
    return {"at": at_ms * MS, "engine": engine}


def made_up_run():
    """Between the edges: 50 steps of which 40 in steady turns that took
    800 ms, and 4,200 ms of stalls (4,000 the profiler's, 200 of no known
    cause); set-up compiled for 90 s and traced for 30."""
    before = snapshot(5_000, 100, 100, {"wait": 700, "yield": 100}, {},
                      (5, 1, 40), 90_000, 30_000)
    after = snapshot(
        10_000, 150, 140,
        {"schedule": 40, "prefill": 80, "dispatch": 120, "wait": 700 + 240,
         "readback": 40, "sample": 20, "emit": 20, "yield": 100 + 240},
        {"profiler": 4_000, "other": 200}, (5 + 10, 1 + 5, 40), 90_000, 30_000)
    return types.SimpleNamespace(before=before, after=after)


def read(run, name):
    return harness.read_metric(run, name)[0]


EXPECTED = {
    "engine.step_gap_ms.steady": 800 / 40,
    "engine.step_ms.host.steady": (40 + 120 + 40 + 20 + 20 + 240) / 40,
    "engine.step_ms.wait.steady": 240 / 40,
    "engine.step_ms.dispatch.steady": 120 / 40,
    "engine.step_ms.yield.steady": 240 / 40,
    "engine.step_ms.schedule_alone.steady": 40 / 40,
    "engine.stall_share.profiler": 100 * 4_000 / 5_000,
    "engine.stall_share.program": 100 * 200 / 5_000,
    "engine.gc_ms_per_step": 15 / 50,
    "setup.compile_ms_in_window": 0.0,
    "setup.compile_s": 90.0,
    "setup.jaxpr_trace_s": 30.0,
}


def test_every_new_metric_has_its_expectation():
    assert sorted(NEW) == sorted(EXPECTED)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_reads_the_made_up_snapshots(name):
    assert read(made_up_run(), name) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_is_left_out_where_the_engine_keeps_no_record(name):
    """The parent serves `stats()` without the record: the reader returns
    None and does not raise."""
    run = made_up_run()
    for edge in (run.before, run.after):
        edge["engine"] = {"steps": edge["engine"]["steps"]}
    assert read(run, name) is None
    run.before["engine"] = run.after["engine"] = None  # no LLM engine at all
    assert read(run, name) is None


def test_a_compile_inside_the_window_is_read_in_milliseconds():
    run = made_up_run()
    run.after["engine"]["compile"]["backend_ns"] += 1_500 * MS
    assert read(run, "setup.compile_ms_in_window") == pytest.approx(1_500.0)
    assert read(run, "setup.compile_s") == pytest.approx(90.0)  # set-up's


def test_no_steady_step_no_mean():
    run = made_up_run()
    run.after["engine"]["steady_steps"] = run.before["engine"]["steady_steps"]
    assert steady.ms_per_step(run, ["wait"]) is None
    assert steady.ms_per_step(run) is None
    run.after["engine"]["loop_ns"] = run.before["engine"]["loop_ns"]
    assert steady.stall_share_pct(run, ["profiler"]) is None
