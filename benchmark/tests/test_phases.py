"""CPU tests of what reads the engine's lap spans: the `phases` readers on
made-up snapshots, the overlap reduction on hand-made events and on a
small trace recorded on the chip, and one traced rehearsal whose line
has to hold every new metric.
"""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import run as harness
from benchmark.lib import span_reduce, trace_reduce
from benchmark.readers import phases

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000
PHASES = ("schedule", "prefill", "propose", "dispatch", "wait", "readback",
          "sample", "emit", "yield")


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


BENCHMARK = load(ROOT, "BENCHMARK.json")
NEW = [m["name"] for m in BENCHMARK["per_layer"]
       if m["source"] == "program_span" or m["name"] == "engine.phase_coverage"]


def snapshot(at_ms, steps, per_phase_ms, **more):
    engine = {"steps": steps, **more,
              "phase_ns": {p: int(per_phase_ms.get(p, 0) * MS) for p in PHASES}}
    return {"at": at_ms * MS, "engine": engine}


def made_up_run():
    """1,000 ms, 20 steps, 2 prefills, 4 admissions between the edges."""
    before = snapshot(5_000, 100, {"wait": 7.0, "yield": 1.0}, prefills=3,
                      admitted=3, queue_wait_ns=50 * MS)
    after = snapshot(
        6_000, 120,
        {"schedule": 10, "prefill": 80, "dispatch": 30, "wait": 7 + 700,
         "readback": 60, "sample": 20, "emit": 10, "yield": 1 + 80},
        prefills=5, admitted=7, queue_wait_ns=(50 + 100) * MS)
    return types.SimpleNamespace(before=before, after=after)


def read(run, name):
    return harness.read_metric(run, name)[0]


@pytest.mark.parametrize("name,expected", [
    ("engine.step_ms.host", (10 + 30 + 60 + 20 + 10 + 80) / 20),
    ("engine.step_ms.wait", 700 / 20),
    ("engine.step_ms.readback", 60 / 20),
    ("engine.step_ms.sample", 20 / 20),
    ("engine.step_ms.emit", 10 / 20),
    ("engine.step_ms.yield", 80 / 20),
    ("engine.step_ms.schedule", (10 + 30) / 20),
    ("engine.step_ms.dispatch", 30 / 20),
    ("engine.prefill_ms.mean", 80 / 2),
    ("engine.queue_wait_ms.mean", 100 / 4),
    ("engine.phase_coverage", 99.0),
])
def test_new_metric_reads_the_made_up_snapshots(name, expected):
    assert name in NEW
    assert read(made_up_run(), name) == pytest.approx(expected)


@pytest.mark.parametrize("name", NEW)
def test_new_metric_is_left_out_where_the_engine_has_no_spans(name):
    """The parent of the PR that added the spans serves `stats()` without
    them: the reader returns None and does not raise."""
    run = made_up_run()
    for edge in (run.before, run.after):
        for key in ("phase_ns", "prefills", "admitted", "queue_wait_ns"):
            del edge["engine"][key]
    assert read(run, name) is None
    run.before["engine"] = run.after["engine"] = None  # no LLM engine at all
    assert read(run, name) is None


def test_the_parts_add_up_to_the_host_time():
    run = made_up_run()
    parts = ("readback", "sample", "emit", "yield", "schedule")
    assert sum(read(run, f"engine.step_ms.{p}") for p in parts) == (
        pytest.approx(read(run, "engine.step_ms.host")))
    # `.dispatch` is the larger part of `.schedule`, under its own name
    assert read(run, "engine.step_ms.dispatch") < read(run, "engine.step_ms.schedule")
    assert phases.ms_per_step(run, ["wait"]) == read(run, "engine.step_ms.wait")
    run.after["engine"]["steps"] = run.before["engine"]["steps"]
    assert phases.ms_per_step(run, ["wait"]) is None  # no step, no mean


def test_prefill_on_the_device_is_selected_by_its_module_name():
    from benchmark.readers import trace

    spec = load(BENCH, "metrics", "step.prefill_ms.mean.json")
    run = types.SimpleNamespace(trace={"module_runs": [
        ["jit_llm_decode", 0.027, {"paged_attention.tpu_custom_call": 0.011}],
        ["jit_llm_prefill", 0.040, {"fusion": 0.039}],
        ["jit_llm_prefill_suffix", 0.020, {"fusion": 0.019}],
        ["jit_llm_decode", 0.027, {"paged_attention.tpu_custom_call": 0.011}],
    ]})
    assert trace.module_mean_ms(run, **spec["params"]) == pytest.approx(30.0)
    # the parent's trace knows no such module: nothing to read
    run.trace["module_runs"] = [["jit__lambda", 0.027, {}]]
    assert trace.module_mean_ms(run, **spec["params"]) is None
    # the kernel's new name keeps the mark the other readers select by
    assert trace_reduce.op_family(
        '%paged_attention.1 = bf16[16,8,4,128] custom-call(...), '
        'custom_call_target="tpu_custom_call"'
    ) == "paged_attention.tpu_custom_call"


# -- idle gaps by overlap with the spans ------------------------------------------


def test_gaps_split_by_overlap_on_hand_made_events():
    d, h = "/device:TPU:0", "/host:CPU"
    events = [
        (d, "XLA Modules", "jit_llm_decode(1)", 0, 300_000),
        (d, "XLA Ops", "%fusion.1 = x", 0, 300_000),
        (d, "XLA Modules", "jit_llm_decode(1)", 1_000_000, 300_000),
        (d, "XLA Ops", "%fusion.1 = x", 1_000_000, 300_000),
        (d, "XLA Ops", "%fusion.2 = x", 1_310_000, 50_000),  # a 10 us gap: not named
        (h, "loop", "engine.wait", 50_000, 300_000),          # ends 50 us late
        (h, "loop", "engine.readback", 350_000, 400_000),
        (h, "loop", "engine.sample", 750_000, 100_000),
        # 850..900 us: no span open
        (h, "loop", "engine.dispatch", 900_000, 200_000),
        (h, "loop", "$engine.py:1 _plain_decode", 0, 1_400_000),
        (h, "loop", "$array.py:2 _value", 350_000, 400_000),
    ]
    summary = span_reduce.split(events)
    assert summary["gaps_s"] == pytest.approx({
        "wait": 50e-6, "readback": 400e-6, "sample": 100e-6,
        "dispatch": 100e-6, "outside_engine": 50e-6})
    assert summary["idle_s"] == pytest.approx(700e-6)
    assert sum(summary["gaps_s"].values()) == pytest.approx(summary["idle_s"])
    assert summary["named_share"] == pytest.approx(650 / 700)
    assert summary["span_lines"] == [[h, "loop"]]
    assert summary["steps"] == 2 and summary["step_gap_ms"] == pytest.approx(1.0)
    assert summary["spans_s"]["dispatch"] == pytest.approx(200e-6)
    assert summary["busy_s"] == pytest.approx(650e-6)
    # the first step's wait ends 50 us after the step does on the device's
    # line: with that line moved by as much, the gap is 350..1050 us
    assert summary["device_lead_ms"] == pytest.approx(0.05)
    assert summary["wait_minus_step_ms"] == pytest.approx(0.0)
    assert summary["aligned_gaps_s"] == pytest.approx({
        "readback": 400e-6, "sample": 100e-6, "dispatch": 150e-6,
        "outside_engine": 50e-6})
    # the midpoint rule gives the whole gap (middle 650 us) to one frame
    assert trace_reduce.reduce(events)["idle_gaps"] == pytest.approx(
        {"array.py_2__value": 700e-6})


def test_gaps_split_on_the_trace_recorded_on_the_chip():
    """`recorded_spans.json`: a slice of a trace taken on the chip through
    ``GET /v2/debug/profile?jax_trace_dir=`` (`tools/span_gaps.py`)."""
    events = [tuple(e) for e in load(HERE, "recorded_spans.json")]
    summary = span_reduce.split(events)
    # the spans sit on one host line, beside the device's on the
    # profiler's clock: the phases that surround a step are found around
    # the steps
    assert len(summary["span_lines"]) == 1
    assert summary["steps"] >= 2
    assert 25.0 < summary["step_gap_ms"] < 45.0
    assert sum(summary["gaps_s"].values()) == pytest.approx(summary["idle_s"])
    assert summary["idle_s"] <= summary["window_s"] - summary["busy_s"] + 1e-9
    assert summary["named_share"] >= 0.9
    assert set(summary["gaps_s"]) - {"outside_engine"} <= set(PHASES)
    # the two lines' clocks are a millisecond or two apart (in this trace
    # the device's line runs 1.5 ms ahead: a step begins on it before the
    # dispatch that launched it has got far), which the raw split books
    # on `wait`
    assert 0.0 < summary["device_lead_ms"] < 5.0
    assert summary["gaps_s"]["wait"] > 0.2 * summary["idle_s"]
    assert abs(summary["wait_minus_step_ms"]) < 1.0
    aligned = summary["aligned_gaps_s"]
    assert sum(aligned.values()) == pytest.approx(summary["idle_s"])
    assert aligned.get("wait", 0.0) < 0.1 * summary["idle_s"]
    assert aligned.get("outside_engine", 0.0) < 0.1 * summary["idle_s"]
    # the device is idle while the host reads back, samples, emits,
    # yields and dispatches the next step
    for phase in ("readback", "sample", "emit", "yield", "dispatch"):
        assert aligned[phase] > 0, phase
    assert summary["spans_s"]["wait"] > 0.5 * summary["busy_s"]
    modules = {trace_reduce.op_family(e[2]) for e in events
               if e[1] == trace_reduce.MODULES_LINE}
    assert "jit_llm_decode" in modules and "jit__lambda" not in modules
    assert any(trace_reduce.op_family(e[2]) == "paged_attention.tpu_custom_call"
               for e in events if e[1] == trace_reduce.OPS_LINE)


# -- the whole harness, traced -------------------------------------------------------


def test_a_traced_rehearsal_holds_every_new_metric():
    if not os.path.exists(os.path.join(ROOT, "build", "_native_frontend.so")):
        pytest.skip("build/ has no native front-end (run.py builds it on "
                    "its first run; a test does not)")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "mistral7b.batch", "--seed", str(2 ** 31 + 25), "--seconds", "6",
         "--trace", "1", "--rehearse-cpu"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    metrics = {name: m["value"] for name, m in line["metrics"].items()}
    assert set(NEW) <= set(metrics)
    assert metrics["engine.phase_coverage"] >= 97.0
    assert metrics["engine.phase_coverage"] <= 101.0
    parts = ("readback", "sample", "emit", "yield", "schedule")
    assert sum(metrics[f"engine.step_ms.{p}"] for p in parts) == (
        pytest.approx(metrics["engine.step_ms.host"]))
    # wait + host + prefill's part of a step is the step gap
    assert (metrics["engine.step_ms.wait"] + metrics["engine.step_ms.host"]
            <= metrics["engine.step_gap_ms.mean"] * 1.01)
