"""CPU tests of the benchmark's own yardstick (`pytest benchmark/tests`).

Counting, traffic, reducer and byte functions are arithmetic and run in
milliseconds. The rehearsals of the whole harness take some tens of
seconds each at toy size: sound runs print ``correct: true``; the control
(the int8 reference in the program's place) and the broken timed path (a
token altered where the program produces it) print ``correct: false``.
"""

import importlib
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from benchmark.lib import bytes_ops, serving_config, trace_reduce, traffic, window

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
HERE = os.path.dirname(os.path.abspath(__file__))
S = 1_000_000_000


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


BENCHMARK = load(ROOT, "BENCHMARK.json")

# -- window-exact counting ------------------------------------------------------


def stream(due_s, first_s, gap_s, n, **extra):
    times = [int((first_s + k * gap_s) * S) for k in range(n)]
    return {"due": int(due_s * S), "times": times, "error": None,
            "complete": True, **extra}


@pytest.mark.parametrize("name,requests,expected", [
    # begins before the window, ends inside: only responses inside count
    ("straddles_start", [stream(8.0, 8.5, 0.5, 8)], 5),   # 10.0 .. 12.0
    # begins inside, ends after: the same
    ("straddles_end", [stream(18.0, 18.5, 0.5, 8)], 3),    # 18.5, 19.0, 19.5
    # spans the whole window: counted though it neither began nor ended in it
    ("spans", [stream(5.0, 5.5, 1.0, 30)], 10),
    # the right edge is open, the left closed
    ("edges", [{"due": 0, "times": [10 * S, 20 * S], "error": None}], 1),
])
def test_rate_counts_every_response_inside_the_window(name, requests, expected):
    assert window.responses_in_window(requests, 10 * S, 20 * S) == expected
    assert window.rate_per_s(requests, 10 * S, 20 * S) == expected / 10


def test_a_stall_inside_the_window_moves_the_rate_and_the_tail():
    steady = [stream(0.0, 0.0, 0.1, 400)]
    stalled = [dict(steady[0], times=[t + (2 * S if t >= 15 * S else 0)
                                      for t in steady[0]["times"]])]
    t0, t1 = 10 * S, 20 * S
    assert window.rate_per_s(steady, t0, t1) == 10.0
    assert window.rate_per_s(stalled, t0, t1) == 8.0
    assert max(window.gaps_ms(steady, t0, t1)) == pytest.approx(100.0)
    assert max(window.gaps_ms(stalled, t0, t1)) == pytest.approx(2100.0)


def test_a_gap_counts_where_it_ended_and_requests_where_they_were_due():
    requests = [stream(9.0, 9.9, 0.2, 3), stream(19.5, 19.9, 0.3, 2)]
    gaps = window.gaps_ms(requests, 10 * S, 20 * S)
    assert gaps == pytest.approx([200.0, 200.0])  # 10.1 and 10.3; 20.2 is outside
    failed = dict(stream(12.0, 0, 0, 0), error="boom")
    assert window.attempted_failed(requests + [failed], 10 * S, 20 * S) == (2, 1)


@pytest.mark.parametrize("q,expected", [(0, 1.0), (50, 3.0), (95, 4.8), (100, 5.0)])
def test_percentile_is_numpys(q, expected):
    values = [5.0, 1.0, 4.0, 2.0, 3.0]
    assert window.percentile(values, q) == pytest.approx(expected)
    assert window.percentile(values, q) == pytest.approx(np.percentile(values, q))


# -- traffic ---------------------------------------------------------------------

MIXES = sorted(name[:-5] for name in os.listdir(os.path.join(BENCH, "traffic")))


@pytest.mark.parametrize("mix_name", MIXES)
def test_every_seed_offers_the_same_multiset_of_lengths(mix_name):
    mix = traffic.load_mix(traffic.mix_path(ROOT, mix_name))
    n = int(mix.get("multiset", 64))

    def drawn(seed):
        lengths = traffic.Lengths(mix, seed)
        return [lengths.next() for _ in range(2 * n)]

    a, b = drawn(1), drawn(2 ** 31 + 7)
    assert sorted(p for p, _ in a) == sorted(p for p, _ in b)
    assert sorted(o for _, o in a) == sorted(o for _, o in b)
    assert sum(p + o for p, o in a) == sum(p + o for p, o in b)
    if len(set(a)) > 1:
        assert a != b  # the order is the seed's


def test_quantile_lengths_are_a_fixed_multiset():
    spec = {"dist": "lognormal", "median": 256, "sigma": 0.7, "min": 64,
            "max": 1024}
    values = traffic.quantile_values(spec, 64)
    assert values == sorted(values) and 64 <= values[0] and values[-1] <= 1024
    assert abs(values[32] - 256) < 16


def test_the_stagger_spreads_completions_one_every_step_of_it():
    mix = traffic.load_mix(traffic.mix_path(ROOT, "batch"))
    lengths = traffic.Lengths(mix, 3)
    firsts = [lengths.first(i) for i in range(mix["clients"])]
    assert firsts[0] == (512, 512) and firsts[15] == (992, 32)
    assert {p + o for p, o in firsts} == {1024}
    assert sorted(o for _, o in firsts) == list(range(32, 513, 32))
    # the warm-up's longest lane stays in the page-table bucket the
    # window's longest context (993..1024 tokens: 64 blocks) uses
    longest = mix["warm"]["decode_longest_prompts"][0]
    assert 56 * 16 < longest + 1 and longest + 6 * mix["warm"]["lanes"] <= 1024


def test_prompts_are_the_seeds_and_unshared():
    a = traffic.prompt_ids(2 ** 31 + 5, 0, 64, 32768)
    assert a == traffic.prompt_ids(2 ** 31 + 5, 0, 64, 32768)
    assert a != traffic.prompt_ids(2 ** 31 + 5, 1, 64, 32768)
    assert a[:16] != traffic.prompt_ids(2 ** 31 + 6, 0, 64, 32768)[:16]
    assert min(a) >= 1 and max(a) < 32768


# -- the trace reducer on a recorded trace -------------------------------------------


def test_union_and_op_family():
    assert trace_reduce.union_ns([(0, 10), (5, 20), (30, 40)]) == (
        30, [[0, 20], [30, 40]])
    assert trace_reduce.op_family("%fusion.123 = bf16[16,4096] fusion(...)") == "fusion"
    assert trace_reduce.op_family("jit__lambda_(1234)") == "jit__lambda_"
    assert trace_reduce.op_family(
        '%_lambda_.29 = bf16[16] custom-call(...), custom_call_target='
        '"tpu_custom_call"') == "_lambda_.tpu_custom_call"
    assert trace_reduce.op_family(
        '%custom-call.3 = custom-call(), custom_call_target="ConcatBitcast"'
    ) == "custom-call"


def test_reducer_on_the_recorded_trace():
    events = [tuple(e) for e in load(HERE, "recorded_trace.json")]
    summary = trace_reduce.reduce(events)
    device = [e for e in events if trace_reduce.DEVICE_PLANE.match(e[0])
              and e[1] == trace_reduce.OPS_LINE]
    assert device, "the recorded trace holds device operations"
    # busy is the union of the device's operations: no more than their
    # sum, no more than the window, and above zero
    assert 0 < summary["busy_s"] <= summary["window_s"]
    assert summary["busy_s"] <= sum(e[4] for e in device) / 1e9 + 1e-12
    assert sum(summary["ops"].values()) == pytest.approx(
        sum(e[4] for e in device) / 1e9)
    # every execution of a compiled program holds the operations inside it
    runs = summary["module_runs"]
    assert runs and all(seconds > 0 for _, seconds, _ in runs)
    decode = [r for r in runs if any("tpu_custom_call" in op for op in r[2])]
    assert decode, "a decode step (with the Pallas kernel) is in the trace"
    for _, seconds, ops in decode:
        assert sum(ops.values()) <= seconds * 1.001
    # idle time is named by host frames and adds up to at most the idle
    idle = summary["window_s"] - summary["busy_s"]
    assert sum(summary["idle_gaps"].values()) <= idle + 1e-9
    assert len(trace_reduce.top(summary["ops"])) <= 10


def test_reducer_on_hand_made_events():
    d, h = "/device:TPU:0", "/host:CPU"
    events = [
        (d, "XLA Modules", "jit_step(1)", 100_000, 300_000),
        (d, "XLA Ops", "%fusion.1 = x", 100_000, 100_000),
        (d, "XLA Ops", '%kernel.7 = x custom_call_target="tpu_custom_call"',
         250_000, 100_000),
        (d, "XLA Ops", "%fusion.2 = x", 900_000, 100_000),
        (h, "python", "$base_events.py:9 _run_once", 0, 1_000_000),
        (h, "python", "$engine.py:1 _plain_decode", 50_000, 900_000),
        (h, "python", "$array.py:2 _value", 400_000, 450_000),
        (h, "python", "$selectors.py:3 select", 600_000, 50_000),
        (h, "sampler", "$profiling.py:4 _sample", 0, 1_000_000),
    ]
    summary = trace_reduce.reduce(events)
    # the window is the device's own span: the profiler's start and stop
    # (before the first operation, after the last) are not in it
    assert summary["window_s"] == pytest.approx(900e-6)
    assert summary["busy_s"] == pytest.approx(300e-6)
    assert summary["ops"] == pytest.approx({"fusion": 200e-6,
                                            "kernel.tpu_custom_call": 100e-6})
    assert summary["module_runs"] == [["jit_step", pytest.approx(300e-6), {
        "fusion": pytest.approx(100e-6),
        "kernel.tpu_custom_call": pytest.approx(100e-6)}]]
    # gaps: 200-250us (middle 225us: _plain_decode) and 350-900us (middle
    # 625us: select is innermost but plumbing, so _value owns it)
    assert summary["idle_gaps"] == pytest.approx({
        "engine.py_1__plain_decode": 50e-6, "array.py_2__value": 550e-6})


# -- byte functions against hand sums ---------------------------------------------------


def test_bytes_against_hand_sums():
    model = serving_config.load_config(
        os.path.join(BENCH, "configs", "mistral_7b_v03"))["model"]
    layer = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336 + 2 * 4096
    assert bytes_ops.decoder_layer_params(model) == layer == 218_112_000
    assert bytes_ops.decoder_step_weight_bytes(model) == 2 * (
        24 * layer + 4096 * 32768 + 4096)
    assert bytes_ops.kv_bytes_per_token_per_layer(model) == 4096  # 2*8*128*2
    assert bytes_ops.paged_attention_bytes(model, [512, 1024]) == 4096 * 1536
    peak = load(BENCH, "lib", "peaks.json")["TPU v5 lite"]
    share, bound = bytes_ops.roofline_share(819e9 * 0.002, 0.0, 0.004, peak)
    assert (share, bound) == (pytest.approx(50.0), "hbm")
    share, bound = bytes_ops.roofline_share(1.0, 197e12 * 0.001, 0.004, peak)
    assert (share, bound) == (pytest.approx(25.0), "mxu")


# -- BENCHMARK.json ---------------------------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def each(key):
    return [pytest.param(entry, id=entry["name"]) for entry in BENCHMARK[key]]


@pytest.mark.parametrize("metric", each("end_to_end") + each("per_layer"))
def test_metric_is_well_formed_and_has_its_reader(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    spec = load(BENCH, "metrics", f"{metric['name']}.json")
    # which cells report it is BENCHMARK.json's alone to say, so that a
    # new cell edits no metric file
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec.get(key) == metric.get(key), key
    assert "workloads" not in spec
    module, function = spec["reader"].split(":")
    __import__(f"benchmark.readers.{module}")
    assert callable(getattr(sys.modules[f"benchmark.readers.{module}"], function))
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


def cells_of(metric):
    return metric.get("workloads", [w["name"] for w in BENCHMARK["workloads"]])


@pytest.mark.parametrize("metric", each("per_layer"))
def test_moves_names_a_metric_its_cells_report(metric):
    moved = {m["name"]: m for m in BENCHMARK["end_to_end"]}[metric["moves"]]
    assert set(cells_of(metric)) <= set(cells_of(moved))
    assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200


@pytest.mark.parametrize("cell", each("workloads"))
def test_cell_is_well_formed_and_finds_its_files(cell):
    body = BENCHMARK
    assert NAME.match(cell["name"]) and cell["chips"] == 1
    assert len(cell["why"]) <= 200
    config = {c["name"]: c for c in body["configs"]}[cell["config"]]
    stated = load(ROOT, config["file"])
    assert stated["reduced"] == config["reduced"]
    assert stated["source"] == config["source"]
    for key in config["reduced"]:
        assert not re.search(r"(_dim|_rank|hidden_size|intermediate_size)$", key)
        assert stated["model"][key] != stated["published"][key]
    assert os.path.isdir(os.path.join(os.path.dirname(os.path.join(
        ROOT, config["file"])), "model_repository", cell["config"]))
    assert all(c["name"] in {w["config"] for w in body["workloads"]}
               for c in body["configs"])
    mix = traffic.load_mix(traffic.mix_path(ROOT, cell["traffic"]))
    # the load driver and the check are found by the names the files give
    driver = importlib.import_module(f"benchmark.drivers.{mix['driver']}")
    assert all(callable(getattr(driver.Load, name))
               for name in ("warm", "ramp", "hold", "close"))
    check = importlib.import_module(f"benchmark.checks.{stated['check']}")
    assert callable(check.sample) and callable(check.numbers)
    assert stated["limits"]
    reported = [m for m in body["end_to_end"] if cell["name"] in cells_of(m)]
    assert {"setup_s"} < {m["name"] for m in reported}
    assert any(cell["name"] in cells_of(m) for m in body["per_layer"])
    assert stated["engine"]["speculation"] is None


def test_bounds_and_run_seconds():
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    for metric in BENCHMARK["end_to_end"]:
        assert 0.01 <= metric["bound"] <= 0.1
    cells = 24
    assert ((2 + 14 * cells) * (BENCHMARK["run_seconds"] + 60) + cells * 180
            + 1200) <= 43200


# -- the whole harness: sound, under the control, and broken underneath ---------------------------


def toy(config_name):
    return serving_config.load_config(
        os.path.join(BENCH, "configs", config_name), toy=True)


def test_the_reference_judges_its_own_choice_sound():
    """A program that serves the reference's own first choice reads gap
    0 at that position."""
    from benchmark.checks import decoder

    config = toy("mistral_7b_v03")
    tokens = traffic.prompt_ids(5, 0, 64, config["model"]["vocab_size"])
    job = {"seed": 5, "model": config["model"],
           "sequences": [{"prompt": tokens[:8], "served": tokens[8:]}]}
    first = reference_first(job)
    job["sequences"] = [{"prompt": tokens[:40], "served": [first[40 - 8]]}]
    numbers = decoder.numbers(job, control=False)
    assert numbers["served_gap_max"] == 0.0 and numbers["served_tokens"] == 1


def reference_first(job):
    from benchmark.lib import reference_llm

    return reference_llm.served_token_gaps(
        job["seed"], job["model"], job["sequences"])[0]["reference_first"]


def rehearse(seed, *flags, **env):
    """`run.py --rehearse-cpu` skips the look for a chip and drives the
    rest of a run at the toy sizes; returns (result line, stderr)."""
    if not os.path.exists(os.path.join(ROOT, "build", "_native_frontend.so")):
        pytest.skip("build/ has no native front-end (run.py builds it on "
                    "its first run; a test does not)")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         BENCHMARK["workloads"][0]["name"], "--seed", str(seed), "--seconds",
         "6", "--trace", "0", "--rehearse-cpu", *flags],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu", **env),
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "compared" and line["failed"] == 0
    assert re.search(r"compared \w+: .* \(limit .*\)\n\[bench\] correct: "
                     + str(line["correct"]) + r"\n$", done.stderr)
    return line


def over_their_limits(line):
    return [k for k, c in line["compared"].items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 11])
def test_sound_run_is_correct_and_its_control_is_not(seed):
    """The same run twice: as the driver runs it, and with ``--control``,
    which puts the int8 reference's first choices in the program's place
    in the harness's own comparison. (At these sizes, over eight seeds,
    the program's widest gap reads 0.007-0.024 and the control's
    0.049-0.087; the mean gap 0.00003-0.00015 and 0.00052-0.0013.)"""
    sound = rehearse(seed)
    assert sound["correct"] is True and over_their_limits(sound) == []
    control = rehearse(seed, "--control")
    assert control["correct"] is False and control["control"] is True
    assert over_their_limits(control) == ["served_gap_max", "served_gap_mean"]
    # the program's own readings, beside them, are still sound
    reference = control["diagnostics"]["reference"]
    for number in ("max", "mean"):
        assert (reference[f"program_gap_{number}"]
                <= control["compared"][f"served_gap_{number}"]["limit"])


def test_a_broken_timed_path_is_not_correct():
    """Every fifth decoded token altered where the program produces it
    (`BENCH_BREAK=token`, `lib/serving_side.py`)."""
    line = rehearse(2 ** 31 + 11, BENCH_BREAK="token")
    assert line["correct"] is False
    assert over_their_limits(line) == ["served_gap_max", "served_gap_mean"]
