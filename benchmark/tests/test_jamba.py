"""CPU tests of what `jamba2_3b` adds to the yardstick: the byte and
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

from benchmark.lib import bytes_ops_jamba, serving_config
from benchmark.readers import jamba

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "jamba2_3b.reason8k_128"
S = 1_000_000_000
NEW_METRICS = (
    "ssm.step_roofline", "attn.mqa_roofline",
    "step.llm_decode.ssm_roofline_share", "ssm.state_share_of_bytes")


def config(toy=False):
    return serving_config.load_config(
        os.path.join(BENCH, "configs", "jamba2_3b"), toy=toy)


# -- bytes and operations ---------------------------------------------------------


def test_bytes_and_operations_against_hand_sums():
    model = config()["model"]
    assert bytes_ops_jamba.mamba_layers(model) == 26
    assert bytes_ops_jamba.attention_layers(model) == 2
    assert bytes_ops_jamba.d_inner(model) == 5120
    # a lane's slot in one Mamba layer: 16 x 5,120 float32 and the last 3
    # convolution inputs of 5,120 channels in bf16
    assert bytes_ops_jamba.state_bytes(model) == 16 * 5120 * 4 == 327_680
    assert bytes_ops_jamba.conv_state_bytes(model) == 3 * 5120 * 2 == 30_720
    assert bytes_ops_jamba.slot_bytes(model) == 358_400
    # a cached token in one attention layer: K and V of 1 head of 128
    assert bytes_ops_jamba.kv_bytes_per_token(model) == 512
    assert bytes_ops_jamba.kv_flops_per_token(model) == 20 * 2 * 2 * 128
    # a Mamba layer's slot is an attention layer's K/V at 700 tokens
    assert 358_400 // 512 == 700
    # the issue's reckoning: 128 lanes, 26 layers, in and out: 2.39 GB
    assert bytes_ops_jamba.step_state_bytes(128 * 26, model) == (
        128 * 26 * 2 * 358_400)
    assert round(128 * 26 * 2 * 358_400 / 1e9, 2) == 2.39
    assert bytes_ops_jamba.kernel_state_bytes(128 * 26, model) == (
        128 * 26 * 2 * 327_680)
    moved, flops = bytes_ops_jamba.decode_attention_work(model, 128 * 4352)
    assert moved == 2 * 128 * 4352 * 512 and round(moved / 1e9, 2) == 0.57
    assert flops == 2 * 128 * 4352 * 10_240
    # the issue's arithmetic: the mixers, the MLP, the whole model
    assert bytes_ops_jamba.mamba_mixer_params(model) == 41_241_792
    assert bytes_ops_jamba.attention_mixer_params(model) == 13_762_560
    assert bytes_ops_jamba.mlp_params(model) == 62_914_560
    whole = (26 * 41_241_792 + 2 * 13_762_560 + 28 * (62_914_560 + 5120)
             + 65536 * 2560 + 2560)
    assert bytes_ops_jamba.model_params(model) == whole
    assert round(whole / 1e6) == 3029
    # bf16 but A_log, D and b_dt: what jax.eval_shape counts of the
    # program's own parameters (`tests/test_mosaic_compile.py`)
    weights = bytes_ops_jamba.decode_step_weight_bytes(model)
    assert weights == 2 * whole + 2 * 26 * (16 + 2) * 5120 == 6_063_467_264
    # the issue's step: 9.0 GB, of which the Mamba mixers 4.5
    step = weights + 128 * 26 * 2 * 358_400 + moved
    assert round(step / 1e9, 1) == 9.0
    mixers = 2 * 26 * 41_241_792 + 128 * 26 * 2 * 358_400
    assert round(mixers / 1e9, 1) == 4.5


# -- the readers on hand-made summaries ---------------------------------------------


def engine(steps, **counted):
    return {"engine": {"steps": steps, **counted}, "at": steps * S}


def made_run(with_counters=True, with_trace=True):
    run = types.SimpleNamespace()
    run.config = config()
    run.peak = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    run.t0, run.t1 = 10 * S, 20 * S
    # 100 steps of 128 lanes in the window; a step's lanes held 557,056
    # tokens of context and turned 3,328 states
    more = dict(attn_tokens_full=557_056 * 100, lane_steps=128 * 100,
                ssm_state_updates=3328 * 100) if with_counters else {}
    run.before = engine(1000, **{k: 0 for k in more})
    run.after = engine(1100, **more)
    run.requests = []
    run.trace = None
    if with_trace:
        decode = ["jit_llm_decode", 0.016,
                  {"selective_scan_step.tpu_custom_call": 0.004,
                   "paged_attention.tpu_custom_call": 0.001, "fusion": 0.011}]
        prefill = ["jit_llm_prefill", 0.06, {"fusion": 0.05, "while": 0.01}]
        run.trace = {"module_runs": [decode, prefill, decode]}
    return run


def test_counter_readers_need_no_trace():
    run = made_run(with_trace=False)
    state, cache = 3328 * 2 * 358_400, 2 * 557_056 * 512
    assert jamba.state_share_of_bytes_pct(run) == pytest.approx(
        100 * state / (6_063_467_264 + state + cache))
    assert 26 < jamba.state_share_of_bytes_pct(run) < 27  # 2.39 of 9.0
    assert jamba.ssm_step_roofline_pct(run, "selective_scan_step") is None
    assert jamba.mqa_roofline_pct(run, "paged_attention") is None
    assert jamba.ssm_roofline_share_pct(run) is None


def test_roofline_readers_on_a_made_trace():
    run = made_run()
    # two decode executions: 2 x 4 ms of the scan kernel, which has to
    # move every live lane's float32 state in and out
    assert jamba.ssm_step_roofline_pct(run, "selective_scan_step") == (
        pytest.approx(100 * (2 * 3328 * 2 * 327_680 / 819e9) / 0.008))
    cache = 2 * 557_056 * 512
    assert cache / 819e9 > 2 * 557_056 * 10_240 / 197e12  # bytes bind
    assert jamba.mqa_roofline_pct(run, "paged_attention") == pytest.approx(
        100 * (2 * cache / 819e9) / 0.002)
    # the whole step: every weight, states in and out with their
    # convolution inputs, the K/V, over the program's 16 ms
    least = (6_063_467_264 + 3328 * 2 * 358_400 + cache) / 819e9
    assert jamba.ssm_roofline_share_pct(run) == pytest.approx(
        100 * least / 0.016)
    for share in (jamba.ssm_step_roofline_pct(run, "selective_scan_step"),
                  jamba.mqa_roofline_pct(run, "paged_attention"),
                  jamba.ssm_roofline_share_pct(run)):
        assert 0 < share < 100


def test_a_program_without_the_counters_reports_nothing():
    """A program from before this configuration (the parent, on which the
    driver lays these files): every reader gives None and raises
    nothing, so the line leaves the metric out."""
    run = made_run(with_counters=False)
    assert jamba.state_share_of_bytes_pct(run) is None
    assert jamba.ssm_roofline_share_pct(run) is None
    assert jamba.ssm_step_roofline_pct(run, "selective_scan_step") is None
    assert jamba.mqa_roofline_pct(run, "paged_attention") is None
    run = made_run()
    run.trace = {"module_runs": [["jit_llm_decode", 0.02, {"fusion": 0.02}]]}
    assert jamba.ssm_step_roofline_pct(run, "selective_scan_step") is None
    assert jamba.mqa_roofline_pct(run, "paged_attention") is None


def test_the_readers_on_a_recorded_chip_run():
    """`recorded_jamba.json`: the engine's `stats()` at the two edges of
    a traced chip run's window, six of its traced decode executions and
    a prefill as the trace reduction gave them, and the per-layer metrics
    of the line that run printed. The readers, given the snapshots and
    the whole trace, gave the line's numbers; given the excerpt they give
    the counter metric exactly and the trace's within what six executions
    differ from all of them."""
    with open(os.path.join(BENCH, "tests", "recorded_jamba.json")) as f:
        recorded = json.load(f)
    run = made_run(with_trace=False)
    run.before, run.after = recorded["before"], recorded["after"]
    run.peak = recorded["peak"]
    assert recorded["after"]["engine"]["steps"] > (
        recorded["before"]["engine"]["steps"])
    assert jamba.state_share_of_bytes_pct(run) == pytest.approx(
        recorded["metrics"]["ssm.state_share_of_bytes"])
    run.trace = recorded["trace"]
    kernels = recorded["trace"]["module_runs"][0][2]
    assert any(name.startswith("selective_scan_step") for name in kernels)
    for name, value in (
            ("ssm.step_roofline",
             jamba.ssm_step_roofline_pct(run, "selective_scan_step")),
            ("attn.mqa_roofline",
             jamba.mqa_roofline_pct(run, "paged_attention")),
            ("step.llm_decode.ssm_roofline_share",
             jamba.ssm_roofline_share_pct(run))):
        assert 0 < value <= 100
        assert value == pytest.approx(recorded["metrics"][name], rel=0.1)
    # the program's own row bytes: a cached token, and a slot
    assert recorded["after"]["engine"]["kv_row_bytes_by_group"] == [
        {"stored": 512, "counted": 512},
        {"stored": 358_400, "counted": 358_400}]
    # every live lane turns 26 states a step
    steps = {k: recorded[k]["engine"] for k in ("before", "after")}
    assert (steps["after"]["ssm_state_updates"]
            - steps["before"]["ssm_state_updates"]) == 26 * (
        steps["after"]["lane_steps"] - steps["before"]["lane_steps"])


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
        assert module == "jamba" and callable(getattr(jamba, function))
        assert {k: spec[k] for k in ("unit", "better", "source", "layer",
                                     "moves")} == {
            k: listed[name][k] for k in ("unit", "better", "source",
                                         "layer", "moves")}
    # the readers written for the other models' keys are not this cell's
    assert not {"moe.experts_roofline", "attn.mixed_roofline",
                "afmoe.experts_roofline", "attn.window_full_roofline",
                "paged_attn_roofline", "kv.window_unheld_share",
                "attn.latent_roofline", "dsv3.experts_roofline",
                "gdn.step_roofline", "attn.gated_full_roofline",
                "moe.resident_calls_per_step",
                "step.llm_decode.roofline_share",
                "step.llm_decode.state_roofline_share",
                "step.llm_decode.hbm_roofline_share"} & set(listed)
    # and every model-independent metric is
    assert {"engine.step_ms.host", "device.idle_share.llm",
            "setup.compiles_in_window", "step.llm_decode_ms.mean",
            "step.prefill_ms.mean", "attn.tiles_whole_share",
            "attn.tile_slots_live_share", "engine.attn_blocks_live_share",
            "engine.steps_ahead_share", "engine.step_ms.host.steady",
            "engine.stall_share.program", "setup.compile_s"} <= set(listed)
    for metric in benchmark["end_to_end"]:
        assert CELL in metric.get("workloads", [CELL])
    (cell,) = [w for w in benchmark["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "reason8k_128"
    assert cell == benchmark["workloads"][-1]
    assert "Mamba mixers 4.5 of 9.0 GB a step" in cell["why"]
    (entry,) = [c for c in benchmark["configs"] if c["name"] == "jamba2_3b"]
    assert entry["reduced"] == ["max_position_embeddings"]


# -- the configuration file and the traffic -------------------------------------------


def test_config_states_that_nothing_is_cut_but_the_positions():
    with open(os.path.join(BENCH, "configs", "jamba2_3b",
                           "config.json")) as f:
        stated = json.load(f)
    model, published = stated["model"], stated["published"]
    assert {k: model[k] for k in model if k != "torch_dtype"} == {
        k: stated[k] for k in model if k != "torch_dtype"}
    assert stated["reduced"] == list(published) == list(
        stated["reduced_why"]) == ["max_position_embeddings"]
    assert published == {"max_position_embeddings": 262144}
    assert model["max_position_embeddings"] == 8192
    # every layer, every row, every width
    assert (model["num_hidden_layers"], model["vocab_size"],
            model["hidden_size"], model["intermediate_size"],
            model["num_attention_heads"], model["num_key_value_heads"],
            model["mamba_expand"], model["mamba_d_state"],
            model["mamba_d_conv"], model["mamba_dt_rank"],
            model["attn_layer_period"], model["attn_layer_offset"],
            model["num_experts"], model["num_experts_per_tok"]) == (
        28, 65536, 2560, 8192, 20, 1, 2, 16, 4, 160, 14, 7, 1, 1)
    engine = stated["engine"]
    # the full group: 128 lanes of 8,192 tokens; the state group is the
    # program's own 1 + max_active slots
    assert engine["num_blocks"] == 128 * 512 + 1 and engine["max_active"] == 128
    assert engine["prefix_sharing"] is False and engine["speculation"] is None
    assert "one v5e chip holds AI21-Jamba2-3B whole" in stated["deployment"]
    for item in ("layer_order", "no_position_signal", "norms", "mamba"):
        assert "as recalled" in stated["assumed"][item]
    assert "0.04-0.999" in stated["assumed"]["step_draw"]
    assert "7:1" in stated["assumed"]["layer_order"]
    assert set(stated["limits"]) == {
        "served_step_share", "served_gap_mean", "undecided_share"}
    # the catalog's entry, key for key, but for the one reduced key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            (entry,) = [row for row in map(json.loads, f)
                        if row["name"] == "AI21-Jamba2-3B"]
        assert stated["source"] == entry["source_url"]
        differing = {k for k, v in entry["config"].items() if stated[k] != v}
        assert differing == set(stated["reduced"])


def test_the_program_config_is_the_files_and_refuses_what_it_lacks():
    from benchmark.lib.serving_jamba import jamba_config

    model = config()["model"]
    made = jamba_config(model)
    assert (made.n_layers, made.d_inner, made.head_dim, made.d_state,
            made.dt_rank, made.vocab_size, made.max_seq_len) == (
        28, 5120, 128, 16, 160, 65536, 8192)
    assert made.layer_kinds == ((1,) * 7 + (0,) + (1,) * 6) * 2
    for key, value in (("num_experts", 16), ("tie_word_embeddings", False),
                       ("sliding_window", 4096), ("mamba_proj_bias", True),
                       ("mamba_conv_bias", False), ("hidden_act", "gelu")):
        with pytest.raises(ValueError, match="does not implement"):
            jamba_config({**model, key: value})


def test_the_mix_is_gigachats_own_and_fits_the_configuration():
    """`traffic/reason8k_128.json` as it stands (`test_gigachat3.py` has
    the stagger's test): its ids are drawn from this configuration's
    whole vocabulary, its longest lane fills the 512-column table, and
    the pool holds every lane at its longest."""
    from benchmark.lib import traffic

    mix = traffic.load_mix(traffic.mix_path(ROOT, "reason8k_128"))
    lengths = traffic.Lengths(mix, 3)
    firsts = [lengths.first(i) for i in range(mix["clients"])]
    assert mix["clients"] == 128 == config()["engine"]["max_active"]
    assert firsts[0] == (512, 7680) and {p + o for p, o in firsts} == {8192}
    assert max(p + o for p, o in firsts) <= config()["model"][
        "max_position_embeddings"]
    assert config()["engine"]["num_blocks"] - 1 >= 128 * 8192 // 16
    ids = traffic.prompt_ids(3, 0, 4096, config()["model"]["vocab_size"])
    assert 0 < min(ids) and 60000 < max(ids) < 65536
    assert mix["warm"]["prefill_prompts"] == [512, 1000, 2000, 4000, 8000]
    assert mix["trace_seconds"] == 3 and mix["compare_requests"] == 3


# -- the check -------------------------------------------------------------------------


def test_the_check_reads_its_three_numbers_and_its_control(monkeypatch):
    """`checks/jamba_decoder.py` on made gaps and margins: the mean over
    all served tokens, the share of DECIDED positions (the reference's
    best 0.1 or more over its second) whose served token is not the
    reference's best, and the share left undecided; under ``control`` the
    same of the int8 forward's choices, the program's own beside them."""
    from benchmark.checks import jamba_decoder
    from benchmark.lib import reference_jamba

    made = [{"gaps": [0.0, 0.02, 0.0, 0.3], "margins": [0.5, 0.02, 0.09, 0.3],
             "control_gaps": [0.5, 0.02, 0.05, 0.0],
             "reference_first": [1, 2, 3, 4]}]
    monkeypatch.setattr(reference_jamba, "served_token_gaps",
                        lambda seed, model, sequences, control=False: made)
    job = {"seed": 1, "model": {}, "sequences": []}
    sound = jamba_decoder.numbers(job, False)
    assert sound["served_tokens"] == 4 and sound["undecided_share"] == 0.5
    assert sound["served_gap_mean"] == pytest.approx(0.08)
    assert sound["served_step_share"] == 0.5 and sound["served_gap_max"] == 0.3
    control = jamba_decoder.numbers(job, True)
    assert control["served_gap_mean"] == pytest.approx(0.1425)
    assert control["served_step_share"] == 0.5
    assert control["program_gap_mean"] == pytest.approx(0.08)
    assert jamba_decoder.DECIDED_MARGIN == 0.1


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
