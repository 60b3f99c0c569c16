"""CPU tests of what `qwen3_next_80b` adds to the yardstick: the byte and
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

from benchmark.lib import bytes_ops_qwen3next, serving_config
from benchmark.readers import qwen3next

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "qwen3_next_80b.reason2k_128"
S = 1_000_000_000
NEW_METRICS = (
    "gdn.step_roofline", "qwen3next.experts_roofline",
    "attn.gated_full_roofline", "step.llm_decode.state_roofline_share",
    "gdn.state_share_of_bytes", "qwen3next.experts_touched_share",
    "qwen3next.load_max_over_mean")


def config(toy=False):
    return serving_config.load_config(
        os.path.join(BENCH, "configs", "qwen3_next_80b"), toy=toy)


# -- bytes and operations ---------------------------------------------------------


def test_bytes_and_operations_against_hand_sums():
    model = config()["model"]
    assert bytes_ops_qwen3next.delta_layers(model) == 12
    assert bytes_ops_qwen3next.full_layers(model) == 4
    assert bytes_ops_qwen3next.conv_channels(model) == 8192
    # a lane's slot in one DeltaNet layer: 32 x 128 x 128 float32 and the
    # last 3 convolution inputs of 8,192 channels in bf16
    assert bytes_ops_qwen3next.state_bytes(model) == 32 * 128 * 128 * 4 == 2_097_152
    assert bytes_ops_qwen3next.conv_state_bytes(model) == 3 * 8192 * 2 == 49_152
    assert bytes_ops_qwen3next.slot_bytes(model) == 2_146_304
    # a cached token in one full layer: K and V of 2 heads of 256 in bf16
    assert bytes_ops_qwen3next.kv_bytes_per_token(model) == 2048
    assert bytes_ops_qwen3next.kv_flops_per_token(model) == 16 * 2 * 2 * 256
    # a DeltaNet layer's slot is a full layer's K/V at 2,096 tokens
    assert 2 * 2_146_304 // 2048 == 2096
    assert bytes_ops_qwen3next.expert_bytes(model) == 3 * 2048 * 512 * 2 == 6_291_456
    assert bytes_ops_qwen3next.pair_flops(model) == 6 * 2048 * 512
    # the issue's reckoning: 128 lanes, 12 layers, in and out
    assert bytes_ops_qwen3next.step_state_bytes(128 * 12, model) == (
        128 * 12 * 2 * 2_146_304)
    assert round(128 * 12 * 2 * 2_146_304 / 1e9, 1) == 6.6
    assert bytes_ops_qwen3next.kernel_state_bytes(128 * 12, model) == (
        128 * 12 * 2 * 2_097_152)
    moved, flops = bytes_ops_qwen3next.decode_attention_work(model, 128 * 1280)
    assert moved == 4 * 128 * 1280 * 2048 and round(moved / 1e9, 1) == 1.3
    assert flops == 4 * 128 * 1280 * 16_384
    # the issue's arithmetic: the mixers and the expert block
    assert bytes_ops_qwen3next.delta_mixer_params(model) == 33_718_464
    assert bytes_ops_qwen3next.full_mixer_params(model) == 27_263_488
    block = 2048 * 512 + 3 * 2048 * 512 + 2048  # router, shared, its gate
    other = (12 * 33_718_464 + 4 * 27_263_488 + 16 * (2 * 2048 + block)
             + 2048 * 18992 + 2048)
    assert bytes_ops_qwen3next.decode_step_weight_bytes(model, 0) == 2 * other
    assert bytes_ops_qwen3next.decode_step_weight_bytes(model, 470.5) == (
        2 * other + 470.5 * 6_291_456)
    # all 512 held experts touched: 4.46 GB, all but the embedding
    whole = bytes_ops_qwen3next.decode_step_weight_bytes(model, 16 * 32)
    assert round(whole / 1e9, 2) == 4.46
    # with 92% of them: the issue's 4.2 GB, and its 12.1 GB a step
    step = (bytes_ops_qwen3next.decode_step_weight_bytes(model, 0.92 * 512)
            + 128 * 12 * 2 * 2_146_304 + moved)
    assert round(step / 1e9, 1) == 12.1


# -- the readers on hand-made summaries ---------------------------------------------


def engine(steps, **counted):
    return {"engine": {"steps": steps, **counted}, "at": steps * S}


def made_run(with_counters=True, with_trace=True):
    run = types.SimpleNamespace()
    run.config = config()
    run.peak = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    run.t0, run.t1 = 10 * S, 20 * S
    # 100 steps of 128 lanes in the window; a step's lanes held 163,840
    # tokens of context, turned 1,536 states, touched 470 of the 512
    # held experts and routed 320 pairs to them
    more = dict(attn_tokens_full=163_840 * 100, lane_steps=128 * 100,
                gdn_state_updates=1536 * 100,
                moe_experts_touched=470 * 100, moe_pairs=320 * 100,
                moe_load_max=20 * 100) if with_counters else {}
    run.before = engine(1000, **{k: 0 for k in more})
    run.after = engine(1100, **more)
    run.requests = []
    run.trace = None
    if with_trace:
        decode = ["jit_llm_decode", 0.020,
                  {"gated_delta_step.tpu_custom_call": 0.010,
                   "moe_experts.tpu_custom_call": 0.005,
                   "paged_attention.tpu_custom_call": 0.002, "fusion": 0.003}]
        prefill = ["jit_llm_prefill", 0.06,
                   {"moe_experts.tpu_custom_call": 0.02, "fusion": 0.04}]
        run.trace = {"module_runs": [decode, prefill, decode]}
    return run


def test_counter_readers_need_no_trace():
    run = made_run(with_trace=False)
    model = run.config["model"]
    weights = bytes_ops_qwen3next.decode_step_weight_bytes(model, 470)
    state, cache = 1536 * 2 * 2_146_304, 4 * 163_840 * 2048
    assert qwen3next.state_share_of_bytes_pct(run) == pytest.approx(
        100 * state / (weights + state + cache))
    assert 50 < qwen3next.state_share_of_bytes_pct(run) < 58  # ~6.6 of 12.1
    assert qwen3next.experts_touched_share_pct(run) == pytest.approx(
        100 * 470 / 512)
    assert qwen3next.load_max_over_mean(run) == pytest.approx(20 * 32 / 320)
    assert qwen3next.gdn_step_roofline_pct(run, "gated_delta_step") is None
    assert qwen3next.experts_roofline_pct(run, "moe_experts") is None
    assert qwen3next.gated_full_roofline_pct(run, "paged_attention") is None
    assert qwen3next.state_roofline_share_pct(run) is None


def test_roofline_readers_on_a_made_trace():
    run = made_run()
    model = run.config["model"]
    # two decode executions: 2 x 10 ms of the state-update kernel, which
    # has to move every live lane's float32 state in and out
    assert qwen3next.gdn_step_roofline_pct(run, "gated_delta_step") == (
        pytest.approx(100 * (2 * 1536 * 2 * 2_097_152 / 819e9) / 0.020))
    # 2 x 5 ms of the expert kernel; the prefill's 20 ms is not a step's
    assert qwen3next.experts_roofline_pct(run, "moe_experts") == pytest.approx(
        100 * (2 * 470 * 6_291_456 / 819e9) / 0.010)
    cache = 4 * 163_840 * 2048
    assert cache / 819e9 > 4 * 163_840 * 16_384 / 197e12  # bytes bind
    assert qwen3next.gated_full_roofline_pct(run, "paged_attention") == (
        pytest.approx(100 * (2 * cache / 819e9) / 0.004))
    # the whole step: weights with the 470 experts touched, states in and
    # out with their convolution inputs, the K/V, over the program's 20 ms
    least = (bytes_ops_qwen3next.decode_step_weight_bytes(model, 470)
             + 1536 * 2 * 2_146_304 + cache) / 819e9
    assert qwen3next.state_roofline_share_pct(run) == pytest.approx(
        100 * least / 0.020)
    for share in (qwen3next.gdn_step_roofline_pct(run, "gated_delta_step"),
                  qwen3next.experts_roofline_pct(run, "moe_experts"),
                  qwen3next.gated_full_roofline_pct(run, "paged_attention"),
                  qwen3next.state_roofline_share_pct(run)):
        assert 0 < share < 100


def test_a_program_without_the_counters_reports_nothing():
    """A program from before this configuration (the parent, on which the
    driver lays these files): every reader gives None and raises
    nothing, so the line leaves the metric out."""
    run = made_run(with_counters=False)
    for reader in (qwen3next.state_share_of_bytes_pct,
                   qwen3next.experts_touched_share_pct,
                   qwen3next.load_max_over_mean,
                   qwen3next.state_roofline_share_pct):
        assert reader(run) is None
    assert qwen3next.gdn_step_roofline_pct(run, "gated_delta_step") is None
    assert qwen3next.experts_roofline_pct(run, "moe_experts") is None
    assert qwen3next.gated_full_roofline_pct(run, "paged_attention") is None
    run = made_run()
    run.trace = {"module_runs": [["jit_llm_decode", 0.02, {"fusion": 0.02}]]}
    assert qwen3next.gdn_step_roofline_pct(run, "gated_delta_step") is None
    assert qwen3next.experts_roofline_pct(run, "moe_experts") is None
    assert qwen3next.gated_full_roofline_pct(run, "paged_attention") is None


def test_the_readers_on_a_recorded_chip_run():
    """`recorded_qwen3next.json`: the engine's `stats()` at the two edges
    of a traced chip run's window, six of its traced decode executions
    and a prefill as the trace reduction gave them, and the per-layer
    metrics of the line that run printed. The readers, given the
    snapshots and the whole trace, gave the line's numbers; given the
    excerpt they give the counter metrics exactly and the trace's within
    what six executions differ from all of them."""
    with open(os.path.join(BENCH, "tests", "recorded_qwen3next.json")) as f:
        recorded = json.load(f)
    run = made_run(with_trace=False)
    run.before, run.after = recorded["before"], recorded["after"]
    run.peak = recorded["peak"]
    assert recorded["after"]["engine"]["steps"] > (
        recorded["before"]["engine"]["steps"])
    for name, reader in (
            ("gdn.state_share_of_bytes", qwen3next.state_share_of_bytes_pct),
            ("qwen3next.experts_touched_share",
             qwen3next.experts_touched_share_pct),
            ("qwen3next.load_max_over_mean", qwen3next.load_max_over_mean)):
        assert reader(run) == pytest.approx(recorded["metrics"][name])
    run.trace = recorded["trace"]
    kernels = recorded["trace"]["module_runs"][0][2]
    assert any(name.startswith("gated_delta_step") for name in kernels)
    for name, value in (
            ("gdn.step_roofline",
             qwen3next.gdn_step_roofline_pct(run, "gated_delta_step")),
            ("qwen3next.experts_roofline",
             qwen3next.experts_roofline_pct(run, "moe_experts")),
            ("attn.gated_full_roofline",
             qwen3next.gated_full_roofline_pct(run, "paged_attention")),
            ("step.llm_decode.state_roofline_share",
             qwen3next.state_roofline_share_pct(run))):
        assert 0 < value <= 100
        assert value == pytest.approx(recorded["metrics"][name], rel=0.1)
    # the program's own row bytes: a cached token, and a slot
    assert recorded["after"]["engine"]["kv_row_bytes_by_group"] == [
        {"stored": 2048, "counted": 2048},
        {"stored": 2_146_304, "counted": 2_146_304}]


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
        assert module == "qwen3next" and callable(getattr(qwen3next, function))
        assert {k: spec[k] for k in ("unit", "better", "source", "layer",
                                     "moves")} == {
            k: listed[name][k] for k in ("unit", "better", "source",
                                         "layer", "moves")}
    # the readers written for the other models' keys are not this cell's
    assert not {"moe.experts_roofline", "attn.mixed_roofline",
                "afmoe.experts_roofline", "attn.window_full_roofline",
                "paged_attn_roofline", "kv.window_unheld_share",
                "attn.latent_roofline", "dsv3.experts_roofline",
                "step.llm_decode.roofline_share",
                "step.llm_decode.hbm_roofline_share"} & set(listed)
    # and every model-independent metric is
    assert {"engine.step_ms.host", "device.idle_share.llm",
            "setup.compiles_in_window", "step.llm_decode_ms.mean",
            "step.prefill_ms.mean", "moe.resident_calls_per_step",
            "attn.tiles_whole_share", "attn.tile_slots_live_share",
            "engine.attn_blocks_live_share", "engine.steps_ahead_share"
            } <= set(listed)
    for metric in benchmark["end_to_end"]:
        assert CELL in metric.get("workloads", [CELL])
    (cell,) = [w for w in benchmark["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "reason2k_128"
    assert "state r/w 6.6 GB of a 12.1 GB step" in cell["why"]


# -- the configuration file and the traffic -------------------------------------------


def test_config_states_its_cuts_and_every_width_is_the_published_one():
    with open(os.path.join(BENCH, "configs", "qwen3_next_80b",
                           "config.json")) as f:
        stated = json.load(f)
    model, published = stated["model"], stated["published"]
    extra = {"experts_routed_over", "experts_held_first", "torch_dtype"}
    assert {k: model[k] for k in model if k not in extra} == {
        k: stated[k] for k in model if k not in extra}
    assert stated["reduced"] == list(published) == list(stated["reduced_why"])
    assert published == {
        "num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936,
        "max_position_embeddings": 262144}
    assert model["experts_routed_over"] == 512 and model["num_experts"] == 32
    assert model["vocab_size"] * 8 == published["vocab_size"]
    # four whole periods of 3 DeltaNet : 1 full
    assert model["num_hidden_layers"] == 4 * model["full_attention_interval"]
    # no width is cut
    assert (model["hidden_size"], model["head_dim"],
            model["num_attention_heads"], model["num_key_value_heads"],
            model["linear_key_head_dim"], model["linear_value_head_dim"],
            model["linear_num_key_heads"], model["linear_num_value_heads"],
            model["linear_conv_kernel_dim"], model["moe_intermediate_size"],
            model["shared_expert_intermediate_size"],
            model["num_experts_per_tok"], model["partial_rotary_factor"],
            model["intermediate_size"]) == (
        2048, 256, 16, 2, 128, 128, 16, 32, 4, 512, 512, 10, 0.25, 5120)
    engine = stated["engine"]
    # the full group: 128 lanes of 2,048 tokens; the state group is the
    # program's own 1 + max_active slots
    assert engine["num_blocks"] == 128 * 128 + 1 and engine["max_active"] == 128
    assert engine["prefix_sharing"] is False and engine["speculation"] is None
    assert "16 v5e chips" in stated["deployment"]
    for item in ("state_float32", "norms", "gated_attention", "delta_rule",
                 "routing"):
        assert "as recalled" in stated["assumed"][item]
    assert "0.9-0.999" in stated["assumed"]["decay_draw"]
    # the catalog's entry, key for key, but for the four reduced keys
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            (entry,) = [row for row in map(json.loads, f)
                        if row["name"] == "Qwen3-Next-80B-A3B-Instruct"]
        assert stated["source"] == entry["source_url"]
        differing = {k for k, v in entry["config"].items() if stated[k] != v}
        assert differing == set(stated["reduced"])


def test_the_program_config_is_the_files_and_refuses_what_it_lacks():
    from benchmark.lib.serving_qwen3next import qwen3next_config

    model = config()["model"]
    made = qwen3next_config(model)
    assert (made.n_layers, made.held, made.n_experts, made.rotary_dim,
            made.conv_dim, made.value_dim, made.vocab_size) == (
        16, (0, 32), 512, 64, 8192, 4096, 18992)
    assert made.layer_kinds == (1, 1, 1, 0) * 4
    for key, value in (("norm_topk_prob", False), ("use_sliding_window", True),
                       ("mlp_only_layers", [0]), ("decoder_sparse_step", 2),
                       ("rope_scaling", {"rope_type": "yarn"})):
        with pytest.raises(ValueError, match="does not implement"):
            qwen3next_config({**model, key: value})


def test_the_stagger_spreads_contexts_over_512_to_2048():
    from benchmark.lib import traffic

    mix = traffic.load_mix(traffic.mix_path(ROOT, "reason2k_128"))
    lengths = traffic.Lengths(mix, 3)
    assert mix["clients"] == 128 and mix["trace_seconds"] == 2
    firsts = [lengths.first(i) for i in range(mix["clients"])]
    assert firsts[0] == (512, 1536) and firsts[127] == (2036, 12)
    assert {p + o for p, o in firsts} == {2048}
    assert lengths.next() == (512, 1536)
    # the widest lane keeps the page table at its 128-column bucket
    assert -(-firsts[127][0] // 16) > 120
    warm = mix["warm"]
    assert warm["prefill_prompts"] == [512, 1000, 2000]
    # the walk's longest lane stays in that bucket and inside max_seq_len,
    # and passes the 64-lane bucket (the 128-lane one compiles in the ramp)
    assert -(-warm["decode_longest_prompts"][0] // 16) > 64
    assert warm["decode_longest_prompts"][0] + 6 * warm["lanes"] <= 2048
    assert 32 < warm["lanes"] <= 64
    # the steady state holds 62.5% of the full group's pool
    assert 128 * 1280 // 16 == 10240 and 10240 / 16384 == 0.625


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
