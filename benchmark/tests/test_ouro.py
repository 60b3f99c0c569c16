"""CPU tests of what `ouro_2_6b` adds to the yardstick: the byte and
operation counts against hand arithmetic, the readers of its per-layer
metrics on hand-made summaries and on a recorded chip run, its
configuration file and traffic, and the whole harness at toy size
(sound: ``correct: true``; the int8 control and a timed path with every
fifth token altered: ``correct: false``)."""

import json
import os
import re
import subprocess
import sys
import types

import pytest

from benchmark.lib import bytes_ops_ouro, serving_config
from benchmark.readers import ouro

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "ouro_2_6b.reason384_16"
NEW_METRICS = (
    "attn.mha_roofline", "step.llm_decode.loop_roofline_share",
    "loop.kv_share_of_bytes", "loop.weight_stream_share")
LOOP, HEAD, ROW = 19_733_151_744, 201_326_592, 8192


def config(toy=False):
    return serving_config.load_config(
        os.path.join(BENCH, "configs", "ouro_2_6b"), toy=toy)


# -- bytes and operations ---------------------------------------------------------


def test_bytes_and_operations_against_hand_sums():
    model = config()["model"]
    # a layer: q, k, v, o of 2,048 x 2,048; the SwiGLU's three; four norms
    assert bytes_ops_ouro.layer_params(model) == (
        4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048) == 51_388_416
    assert bytes_ops_ouro.cache_pairs(model) == 4 * 48 == 192
    whole = 48 * 51_388_416 + 2 * 49152 * 2048 + 2 * 2048 + 1
    assert bytes_ops_ouro.model_params(model) == whole == 2_667_974_657
    # the layers' weights cross HBM once a PASS: 19.73 GB a step
    assert bytes_ops_ouro.loop_weight_bytes(model, 192) == (
        4 * 48 * 51_388_416 * 2) == LOOP
    assert round(LOOP / 1e9, 2) == 19.73
    assert bytes_ops_ouro.loop_weight_bytes(model, 96) == LOOP / 2
    assert bytes_ops_ouro.head_bytes(model) == 2 * 2048 * 49152 == HEAD
    assert round(HEAD / 1e9, 2) == 0.20
    # a cached token: K and V of 16 heads of 128 a pair, 192 pairs
    assert bytes_ops_ouro.kv_row_bytes(model) == 2 * 16 * 128 * 2 == ROW
    assert bytes_ops_ouro.kv_bytes_per_token(model) == 192 * ROW == 1_572_864
    # 16 query heads score (2 x 128) and weigh (2 x 128) a row: 1 FLOP a byte
    assert bytes_ops_ouro.kv_row_flops(model) == 16 * 4 * 128 == ROW
    # the issue's step: 16 lanes, 4,096 cached tokens held
    step = bytes_ops_ouro.step_bytes(model, 192, 4096 * 192)
    assert step == (LOOP, HEAD, 4096 * 1_572_864)
    assert round(step[2] / 1e9, 2) == 6.44
    assert round(sum(step) / 1e9, 1) == 26.4
    assert round(100 * step[2] / sum(step)) == 24
    assert round(1e3 * sum(step) / 819e9, 1) == 32.2


# -- the readers ---------------------------------------------------------------------


def made_run(with_counters=True, with_trace=True):
    """100 steps of 16 lanes holding 4,096 cached tokens between them."""
    run = types.SimpleNamespace()
    run.config = config()
    run.peak = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}

    def engine(steps, **more):
        return {"at": 0, "engine": dict(steps=steps, **more)}

    more = dict(loop_layer_passes=192 * 100,
                loop_kv_rows_read=192 * 4096 * 100) if with_counters else {}
    run.before = engine(1000, **{k: 0 for k in more})
    run.after = engine(1100, **more)
    run.requests = []
    run.trace = None
    if with_trace:
        decode = ["jit_llm_decode", 0.040,
                  {"paged_attention.tpu_custom_call": 0.009, "fusion": 0.030,
                   "while": 0.040}]
        prefill = ["jit_llm_prefill", 0.03, {"fusion": 0.02, "while": 0.03}]
        run.trace = {"module_runs": [decode, prefill, decode]}
    return run


KV = 4096 * 192 * ROW


def test_counter_readers_need_no_trace():
    run = made_run(with_trace=False)
    assert ouro.kv_share_of_bytes_pct(run) == pytest.approx(
        100 * KV / (LOOP + HEAD + KV))
    assert 24 < ouro.kv_share_of_bytes_pct(run) < 25
    assert ouro.mha_roofline_pct(run, "paged_attention") is None
    assert ouro.loop_roofline_share_pct(run) is None
    assert ouro.weight_stream_share_pct(run, "paged_attention") is None


def test_roofline_readers_on_a_made_trace():
    run = made_run()
    # two decode executions: 2 x 9 ms of the paged kernel, which has to
    # read every live row of every pair once; bytes bind, not FLOPs
    assert KV / 819e9 > KV / 197e12
    assert ouro.mha_roofline_pct(run, "paged_attention") == pytest.approx(
        100 * (2 * KV / 819e9) / 0.018)
    assert ouro.loop_roofline_share_pct(run) == pytest.approx(
        100 * ((LOOP + HEAD + KV) / 819e9) / 0.040)
    # the weights and the head over the step LESS the kernel
    assert ouro.weight_stream_share_pct(run, "paged_attention") == (
        pytest.approx(100 * (2 * (LOOP + HEAD) / 819e9) / (0.080 - 0.018)))
    for share in (ouro.mha_roofline_pct(run, "paged_attention"),
                  ouro.loop_roofline_share_pct(run),
                  ouro.weight_stream_share_pct(run, "paged_attention")):
        assert 0 < share < 100
    # an early exit that ran half the layer bodies would halve the loop's
    # bytes: the counts are the program's, the bytes a body the model's
    run.after["engine"]["loop_layer_passes"] = 96 * 100
    assert ouro.loop_roofline_share_pct(run) == pytest.approx(
        100 * ((LOOP / 2 + HEAD + KV) / 819e9) / 0.040)


def test_a_program_without_the_counters_reports_nothing():
    """A program from before this configuration (the parent, on which the
    driver lays these files): every reader gives None and raises
    nothing, so the line leaves the metric out."""
    run = made_run(with_counters=False)
    for value in (ouro.kv_share_of_bytes_pct(run),
                  ouro.loop_roofline_share_pct(run),
                  ouro.mha_roofline_pct(run, "paged_attention"),
                  ouro.weight_stream_share_pct(run, "paged_attention")):
        assert value is None
    run = made_run()
    run.trace = {"module_runs": [["jit_llm_decode", 0.02, {"fusion": 0.02}]]}
    assert ouro.mha_roofline_pct(run, "paged_attention") is None
    assert ouro.weight_stream_share_pct(run, "paged_attention") is None


def test_the_readers_on_a_recorded_chip_run():
    """`recorded_ouro.json`: the engine's `stats()` at the two edges of a
    traced chip run's window, six of its traced decode executions and a
    prefill as the trace reduction gave them, and the per-layer metrics
    of the line that run printed. The readers, given the snapshots and
    the whole trace, gave the line's numbers; given the excerpt they give
    the counter metric exactly and the trace's within what six
    executions differ from all of them."""
    with open(os.path.join(BENCH, "tests", "recorded_ouro.json")) as f:
        recorded = json.load(f)
    run = made_run(with_trace=False)
    run.before, run.after = recorded["before"], recorded["after"]
    run.peak = recorded["peak"]
    steps = {k: recorded[k]["engine"] for k in ("before", "after")}

    def delta(name):
        return steps["after"][name] - steps["before"][name]

    assert delta("steps") > 0
    assert ouro.kv_share_of_bytes_pct(run) == pytest.approx(
        recorded["metrics"]["loop.kv_share_of_bytes"])
    run.trace = recorded["trace"]
    decodes = [m for m in recorded["trace"]["module_runs"]
               if "llm_decode" in m[0]]
    assert len(decodes) == 6
    assert all(any(name.startswith("paged_attention") for name in m[2])
               for m in decodes)
    for name, value in (
            ("attn.mha_roofline",
             ouro.mha_roofline_pct(run, "paged_attention")),
            ("step.llm_decode.loop_roofline_share",
             ouro.loop_roofline_share_pct(run)),
            ("loop.weight_stream_share",
             ouro.weight_stream_share_pct(run, "paged_attention"))):
        assert 0 < value <= 100
        assert value == pytest.approx(recorded["metrics"][name], rel=0.1)
    # the program's own row bytes: a cached token over the 192 pairs
    assert steps["after"]["kv_row_bytes_by_group"] == [
        {"stored": 1_572_864, "counted": 1_572_864}]
    # 192 layer bodies a step, and every live lane's whole context read
    # in each of the 192 pairs (the engine books a step's contexts when
    # it dispatches it, the model's counter arrives with its result: the
    # two differ by what the steps in flight at the two edges differ)
    assert delta("loop_layer_passes") == 192 * delta("steps")
    assert delta("loop_kv_rows_read") == pytest.approx(
        192 * delta("attn_tokens_full"), rel=1e-3)
    assert delta("preemptions") == 0
    # 16 lanes hold 4,096 tokens: 72 runs of 4 blocks, and no fewer
    assert 16 * 128 // 16 <= steps["after"][
        "kv_blocks_in_use_by_group"][0] <= 336


def test_every_new_metric_has_its_file_and_lists_the_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    listed = {m["name"]: m for m in benchmark["per_layer"]
              if CELL in m.get("workloads", [])}
    for name in NEW_METRICS:
        assert listed[name]["workloads"] == [CELL]
        with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
            spec = json.load(f)
        module, function = spec["reader"].split(":")
        assert module == "ouro" and callable(getattr(ouro, function))
        assert {k: spec[k] for k in ("unit", "better", "source", "layer",
                                     "moves")} == {
            k: listed[name][k] for k in ("unit", "better", "source",
                                         "layer", "moves")}
    assert [m["name"] for m in benchmark["per_layer"][-4:]] == list(
        NEW_METRICS)
    # the readers written for the other models' keys are not this cell's
    assert not {n for n in listed if n.startswith((
        "moe.", "afmoe.", "dsv3.", "qwen3next.", "gdn.", "ssm.", "kv."))}
    assert not {"paged_attn_roofline", "attn.mixed_roofline",
                "attn.window_full_roofline", "attn.latent_roofline",
                "attn.gated_full_roofline", "attn.mqa_roofline",
                "attn.shared_kv_roofline", "step.decode_ms.mean",
                "step.llm_decode.roofline_share",
                "step.llm_decode.sambay_roofline_share"} & set(listed)
    # every model-independent one is
    assert {"engine.step_ms.host", "device.idle_share.llm",
            "setup.compiles_in_window", "step.llm_decode_ms.mean",
            "step.prefill_ms.mean", "attn.tiles_whole_share",
            "attn.tile_slots_live_share", "engine.attn_blocks_live_share",
            "engine.steps_ahead_share", "engine.admits_behind_share",
            "engine.step_ms.host.steady", "engine.stall_share.program",
            "setup.compile_s", "setup.jaxpr_trace_s"} <= set(listed)
    for metric in benchmark["end_to_end"]:
        assert CELL in metric.get("workloads", [CELL])
    assert benchmark["workloads"][-1]["name"] == CELL
    cell = benchmark["workloads"][-1]
    assert cell["chips"] == 1 and cell["traffic"] == "reason384_16"
    assert "192 layer-passes a token" in cell["why"]
    assert len(cell["why"]) <= 200
    entry = benchmark["configs"][-1]
    assert entry["name"] == "ouro_2_6b"
    assert entry["reduced"] == ["max_position_embeddings"]
    assert len(entry["why"]) <= 200


# -- the configuration file and the traffic -------------------------------------------


def test_config_states_that_nothing_is_cut_but_the_positions():
    with open(os.path.join(BENCH, "configs", "ouro_2_6b",
                           "config.json")) as f:
        stated = json.load(f)
    model, published = stated["model"], stated["published"]
    assert {k: model[k] for k in model if k != "torch_dtype"} == {
        k: stated[k] for k in model if k != "torch_dtype"}
    assert stated["reduced"] == list(published) == list(
        stated["reduced_why"]) == ["max_position_embeddings"]
    assert published == {"max_position_embeddings": 65536}
    assert model["max_position_embeddings"] == 512
    # every layer, every pass, every row, every width
    assert (model["num_hidden_layers"], model["total_ut_steps"],
            model["vocab_size"], model["hidden_size"],
            model["intermediate_size"], model["num_attention_heads"],
            model["num_key_value_heads"], model["head_dim"],
            model["early_exit_threshold"], model["tie_word_embeddings"]) == (
        48, 4, 49152, 2048, 5632, 16, 16, 128, 1, False)
    engine = stated["engine"]
    # the trash block and 84 runs of the kernel's tile of 4 pages
    assert engine["num_blocks"] == 1 + 84 * 4 and engine["max_active"] == 16
    assert engine["prefix_sharing"] is False and engine["speculation"] is None
    assert "one v5e chip holds Ouro-2.6B whole" in stated["deployment"]
    for item in ("sandwich_norms", "norm_closes_every_pass", "pass_count",
                 "exit_gate"):
        assert "as recalled" in stated["assumed"][item]
    for item in ("pass_caches", "rope", "kv_rows", "norms", "weight_layout",
                 "norm_scales", "weights", "carried_unused"):
        assert stated["assumed"][item]
    assert set(stated["limits"]) == {
        "served_step_share", "served_gap_mean", "undecided_share"}
    # the catalog's entry, key for key, but for the one reduced key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            (entry,) = [row for row in map(json.loads, f)
                        if row["name"] == "Ouro-2.6B"]
        assert stated["source"] == entry["source_url"]
        differing = {k for k, v in entry["config"].items() if stated[k] != v}
        assert differing == set(stated["reduced"])


def test_the_program_config_is_the_files_and_refuses_what_it_lacks():
    from benchmark.lib.serving_ouro import ouro_config

    model = config()["model"]
    made = ouro_config(model)
    assert (made.n_layers, made.ut_steps, made.cache_pairs, made.n_heads,
            made.n_kv_heads, made.head_dim, made.d_ff, made.vocab_size,
            made.max_seq_len, made.rope_theta, made.norm_eps) == (
        48, 4, 192, 16, 16, 128, 5632, 49152, 512, 1e6, 1e-6)
    for key, value in (("tie_word_embeddings", True), ("sliding_window", 4096),
                       ("use_sliding_window", True),
                       ("rope_scaling", {"rope_type": "yarn", "factor": 4}),
                       ("early_exit_threshold", 0.9), ("hidden_act", "gelu"),
                       ("layer_types", ["sliding_attention"] * 48)):
        with pytest.raises(ValueError, match="does not implement"):
            ouro_config({**model, key: value})
    toy = ouro_config(config(toy=True)["model"])
    assert (toy.n_layers, toy.ut_steps, toy.n_heads, toy.head_dim,
            toy.d_model) == (4, 4, 4, 32, 128)


def test_the_mix_staggers_sixteen_streams_over_the_pool():
    """`traffic/reason384_16.json`: stream i's first request is 128 + 16i
    in and 256 - 16i out, so every stream ends at a context of 384 and
    the contexts lie evenly over 128-384 from the first step; the 16
    lanes then hold 72 of the pool's 84 runs of 64 tokens at the most,
    and the warm-up's walk through the batch buckets fits too."""
    from benchmark.lib import traffic

    mix = traffic.load_mix(traffic.mix_path(ROOT, "reason384_16"))
    stated = config()
    lengths = traffic.Lengths(mix, 3)
    firsts = [lengths.first(i) for i in range(mix["clients"])]
    assert mix["clients"] == 16 == stated["engine"]["max_active"]
    assert firsts[0] == (128, 256) and firsts[15] == (368, 16)
    assert {p + o for p, o in firsts} == {384}
    assert [lengths.next() for _ in range(20)] == [(128, 256)] * 20
    assert 384 <= stated["model"]["max_position_embeddings"]
    runs = (stated["engine"]["num_blocks"] - 1) // 4
    assert runs == 84
    # every phase of the stagger: lane j at a context of 128 + 16 j + s
    held = max(sum(-(-(128 + 16 * j + s) // 64) for j in range(16))
               for s in range(1, 17))
    assert held == 72 <= runs
    warm = mix["warm"]
    walk = (-(-(warm["decode_longest_prompts"][0] + 6 * warm["lanes"]) // 64)
            + (warm["lanes"] - 1)
            * -(-(warm["lane_prompt"] + 6 * warm["lanes"]) // 64))
    assert walk == 68 <= runs
    assert warm["decode_longest_prompts"][0] + 6 * warm["lanes"] <= 512
    assert warm["prefill_prompts"] == [128, 256, 376]
    ids = traffic.prompt_ids(3, 0, 4096, stated["model"]["vocab_size"])
    assert 0 < min(ids) and 48000 < max(ids) < 49152
    assert mix["trace_seconds"] == 3 and mix["compare_requests"] == 3
    toy = traffic.load_mix(traffic.mix_path(ROOT, "reason384_16"), toy=True)
    first = traffic.Lengths(toy, 3).first(3)
    assert toy["clients"] == 4 and first == (40, 8)
    assert sum(first) <= config(toy=True)["model"]["max_position_embeddings"]


# -- the check -------------------------------------------------------------------------


def test_the_check_reads_its_three_numbers_and_its_control(monkeypatch):
    """`checks/ouro_decoder.py` on made gaps and margins: the mean over
    all served tokens, the share of DECIDED positions (the reference's
    best 0.1 or more over its second) whose served token is not the
    reference's best, and the share left undecided; under ``control`` the
    same of the int8 forward's choices, the program's own beside them."""
    from benchmark.checks import ouro_decoder
    from benchmark.lib import reference_ouro

    made = [{"gaps": [0.0, 0.02, 0.0, 0.3], "margins": [0.5, 0.02, 0.09, 0.3],
             "control_gaps": [0.5, 0.02, 0.05, 0.0],
             "reference_first": [1, 2, 3, 4]}]
    monkeypatch.setattr(reference_ouro, "served_token_gaps",
                        lambda seed, model, sequences, control=False: made)
    job = {"seed": 1, "model": {}, "sequences": []}
    sound = ouro_decoder.numbers(job, False)
    assert sound["served_tokens"] == 4 and sound["undecided_share"] == 0.5
    assert sound["served_gap_mean"] == pytest.approx(0.08)
    assert sound["served_step_share"] == 0.5 and sound["served_gap_max"] == 0.3
    control = ouro_decoder.numbers(job, True)
    assert control["served_gap_mean"] == pytest.approx(0.1425)
    assert control["served_step_share"] == 0.5
    assert control["program_gap_mean"] == pytest.approx(0.08)
    assert ouro_decoder.DECIDED_MARGIN == 0.1


def test_the_reference_reads_the_tokens_the_program_would_serve():
    """`served_token_gaps` at the toy sizes on the reference's OWN greedy
    continuation: every gap is 0, and the int8 control's choices are not
    all the reference's."""
    import numpy as np

    from benchmark.lib import reference_ouro, weights_ouro

    model = config(toy=True)["model"]
    prompt = np.random.default_rng(0).integers(1, 1024, size=12).tolist()
    top = weights_ouro.top(7, model)
    served = []
    for _ in range(6):
        logits = reference_ouro.forward(
            prompt + served, top,
            lambda i: weights_ouro.layer(7, i, model), model)
        served.append(int(np.argmax(np.asarray(logits[-1]))))
    (entry,) = reference_ouro.served_token_gaps(
        7, model, [{"prompt": prompt, "served": served}], control=True)
    assert entry["gaps"] == [0.0] * 6 and entry["reference_first"] == served
    assert len(entry["control_gaps"]) == len(entry["margins"]) == 6
    assert min(entry["margins"]) > 0


# -- the whole harness at toy size ---------------------------------------------------


def rehearse(seed, *flags, **env):
    if not os.path.exists(os.path.join(ROOT, "build", "_native_frontend.so")):
        pytest.skip("build/ has no native front-end (run.py builds it on "
                    "its first run; a test does not)")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(seed), "--seconds", "6", "--trace", "0",
         "--rehearse-cpu", *flags],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu", **env),
        capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "compared" and line["failed"] == 0
    assert re.search(r"\[bench\] correct: " + str(line["correct"]) + r"\n$",
                     done.stderr)
    return line


def over_their_limits(line):
    return [k for k, c in line["compared"].items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 11])
def test_sound_toy_run_is_correct_and_its_control_is_not(seed):
    sound = rehearse(seed)
    assert sound["correct"] is True and over_their_limits(sound) == []
    assert {"out_tokens_per_s", "itl_ms.p95", "setup_s"} == set(sound["metrics"])
    control = rehearse(seed, "--control")
    assert control["correct"] is False and control["control"] is True
    assert 0 < len(over_their_limits(control)) and set(
        over_their_limits(control)) <= {"served_step_share", "served_gap_mean"}


def test_a_broken_timed_path_is_not_correct():
    """Every fifth decoded token altered where the program produces it."""
    line = rehearse(2 ** 31 + 11, BENCH_BREAK="token")
    assert line["correct"] is False
    assert set(over_their_limits(line)) == {"served_step_share",
                                            "served_gap_mean"}
