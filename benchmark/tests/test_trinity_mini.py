"""CPU tests of what `trinity_mini` adds to the yardstick: the byte and
operation counts, the readers of its per-layer metrics on hand-made
summaries, its configuration file and traffic, and the whole harness at
toy size (sound: ``correct: true``; the int8 control: ``correct:
false``)."""

import json
import os
import re
import subprocess
import sys
import types

import pytest

from benchmark.lib import bytes_ops_afmoe, serving_config
from benchmark.readers import afmoe

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "trinity_mini.reason8k"
S = 1_000_000_000


def config(toy=False):
    return serving_config.load_config(
        os.path.join(BENCH, "configs", "trinity_mini"), toy=toy)


# -- bytes and operations ---------------------------------------------------------


def test_bytes_and_operations_against_hand_sums():
    model = config()["model"]
    assert bytes_ops_afmoe.expert_bytes(model) == 3 * 2048 * 1024 * 2 == 12_582_912
    assert bytes_ops_afmoe.pair_flops(model) == 6 * 2048 * 1024
    assert bytes_ops_afmoe.layer_counts(model) == (4, 12, 14)
    assert bytes_ops_afmoe.kv_bytes_per_token(model) == 2048
    # the issue's reckoning: 64 lanes even over 512-8,192, a window of 2,048
    full, window = bytes_ops_afmoe.decode_attention_bytes(
        model, 64 * 4352, 64 * 1894)
    assert (full, window) == (4 * 2048 * 64 * 4352, 12 * 2048 * 64 * 1894)
    assert round(window / (full + window), 2) == 0.57
    # a decode step's weights: 16 attention layers (wq, the gate and wo
    # of 8.39M, wk and wv of 1.05M, two head norms), four norms a layer,
    # two dense MLPs, 14 routers over 128 and 14 shared experts, the head
    # and its norm; and 12.58 MB for each routed expert touched
    attention = 3 * 2048 * 4096 + 2 * 2048 * 512 + 256
    other = (16 * (attention + 4 * 2048) + 2 * 3 * 2048 * 6144
             + 14 * (2048 * 128 + 3 * 2048 * 1024)
             + 2048 * 25024 + 2048)
    assert other == 654_841_856
    assert bytes_ops_afmoe.decode_step_weight_bytes(model, 0) == 2 * other
    assert bytes_ops_afmoe.decode_step_weight_bytes(model, 220.5) == (
        2 * other + 220.5 * 12_582_912)


# -- the readers on hand-made summaries ---------------------------------------------


def engine(steps, **counted):
    return {"engine": {"steps": steps, **counted}, "at": steps * S}


def made_run(with_counters=True, with_trace=True):
    run = types.SimpleNamespace()
    run.config = config()
    run.peak = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    run.t0, run.t1 = 10 * S, 20 * S
    # 100 steps in the window; a step's lanes held 278,528 tokens of
    # context of which the window reached 121,216, touched 15 of each
    # layer's 16 experts and routed 64 pairs to them
    more = dict(attn_tokens_full=278_528 * 100, attn_tokens_window=121_216 * 100,
                moe_experts_touched=210 * 100, moe_pairs=896 * 100,
                moe_load_max=100 * 100) if with_counters else {}
    run.before = engine(1000, **{k: 0 for k in more})
    run.after = engine(1100, **more)
    run.requests = []
    run.trace = None
    if with_trace:
        decode = ["jit_llm_decode", 0.018,
                  {"moe_experts.tpu_custom_call": 0.004,
                   "paged_attention.tpu_custom_call": 0.009, "fusion": 0.005}]
        prefill = ["jit_llm_prefill", 0.06,
                   {"moe_experts.tpu_custom_call": 0.02, "fusion": 0.04}]
        run.trace = {"module_runs": [decode, prefill, decode]}
    return run


def test_counter_readers_need_no_trace():
    run = made_run(with_trace=False)
    full, window = 4 * 2048 * 278_528, 12 * 2048 * 121_216
    assert afmoe.window_share_of_kv_pct(run) == pytest.approx(
        100 * window / (full + window))
    assert afmoe.experts_touched_share_pct(run) == pytest.approx(
        100 * 210 / (16 * 14))
    # the fullest held expert of each layer took 100 / 14 pairs a step,
    # the mean held expert 896 / 14 / 16
    assert afmoe.load_max_over_mean(run) == pytest.approx(100 * 16 / 896)
    assert afmoe.window_full_roofline_pct(run, "paged_attention") is None
    assert afmoe.experts_roofline_pct(run, "moe_experts") is None
    assert afmoe.hbm_roofline_share_pct(run) is None


def test_roofline_readers_count_the_decode_programs_kernels_only():
    run = made_run()
    kv = 4 * 2048 * 278_528 + 12 * 2048 * 121_216
    # two decode executions: 2 x 9 ms of the attention kernel
    assert afmoe.window_full_roofline_pct(run, "paged_attention") == (
        pytest.approx(100 * (2 * kv / 819e9) / 0.018))
    # 2 x 4 ms of the expert kernel; the prefill's 20 ms is not a step's
    assert afmoe.experts_roofline_pct(run, "moe_experts") == pytest.approx(
        100 * (2 * 210 * 12_582_912 / 819e9) / 0.008)
    # the whole step: the other weights, the 210 experts touched and the
    # K/V, over the decode program's 18 ms (the prefill's 60 ms left out)
    least = (2 * 654_841_856 + 210 * 12_582_912 + kv) / 819e9
    assert afmoe.hbm_roofline_share_pct(run) == pytest.approx(
        100 * least / 0.018)
    for share in (afmoe.window_full_roofline_pct(run, "paged_attention"),
                  afmoe.experts_roofline_pct(run, "moe_experts"),
                  afmoe.hbm_roofline_share_pct(run)):
        assert 0 < share < 100


def test_a_program_without_the_counters_reports_nothing():
    """A program from before this configuration: every reader gives None
    and raises nothing, so the line leaves the metric out."""
    run = made_run(with_counters=False)
    assert afmoe.window_share_of_kv_pct(run) is None
    assert afmoe.experts_touched_share_pct(run) is None
    assert afmoe.load_max_over_mean(run) is None
    assert afmoe.window_full_roofline_pct(run, "paged_attention") is None
    assert afmoe.experts_roofline_pct(run, "moe_experts") is None
    assert afmoe.hbm_roofline_share_pct(run) is None
    run = made_run()
    run.trace = {"module_runs": [["jit_llm_decode", 0.02, {"fusion": 0.02}]]}
    assert afmoe.window_full_roofline_pct(run, "paged_attention") is None
    assert afmoe.experts_roofline_pct(run, "moe_experts") is None


def test_every_new_metric_has_its_file_and_lists_the_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    listed = {m["name"]: m for m in benchmark["per_layer"]
              if CELL in m.get("workloads", [])}
    for name in ("attn.window_full_roofline", "afmoe.experts_roofline",
                 "step.llm_decode.hbm_roofline_share",
                 "attn.window_share_of_kv", "afmoe.experts_touched_share",
                 "afmoe.load_max_over_mean"):
        assert listed[name]["workloads"] == [CELL]
        with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
            spec = json.load(f)
        module, function = spec["reader"].split(":")
        assert module == "afmoe" and callable(getattr(afmoe, function))
        assert {k: spec[k] for k in ("unit", "better", "source", "layer",
                                     "moves")} == {
            k: listed[name][k] for k in ("unit", "better", "source",
                                         "layer", "moves")}
    # the readers written for `mimo_v2_flash`'s keys are not this cell's
    assert not {"moe.experts_roofline", "attn.mixed_roofline",
                "step.llm_decode.weight_stream_share", "paged_attn_roofline",
                "moe.experts_touched_share", "moe.load_max_over_mean"} & set(
                    listed)


# -- the configuration file and the traffic -------------------------------------------


def test_config_states_its_cuts_and_its_top_level_is_the_model_group():
    with open(os.path.join(BENCH, "configs", "trinity_mini", "config.json")) as f:
        stated = json.load(f)
    model, published = stated["model"], stated["published"]
    extra = {"experts_routed_over", "experts_held_first", "torch_dtype"}
    assert {k: model[k] for k in model if k not in extra} == {
        k: stated[k] for k in model if k not in extra}
    assert set(stated["reduced"]) == set(published) == set(stated["reduced_why"])
    assert model["experts_routed_over"] == published["num_experts"] == 128
    assert model["num_experts"] == 16 and model["num_experts_per_tok"] == 8
    assert model["layer_types"] == published["layer_types"][:16]
    assert model["layer_types"].count("full_attention") == 4
    assert model["vocab_size"] * 8 == published["vocab_size"]
    # no width is cut
    assert (model["hidden_size"], model["head_dim"], model["intermediate_size"],
            model["moe_intermediate_size"], model["sliding_window"],
            model["num_attention_heads"], model["num_key_value_heads"],
            model["route_scale"]) == (2048, 128, 6144, 1024, 2048, 32, 4, 2.826)
    engine = stated["engine"]
    # the full group's pool for 64 lanes of 5,120 tokens (the traffic
    # holds 4,352 a lane); the window group's is the engine's to work out
    assert engine["num_blocks"] == 64 * 320 + 1
    assert engine["max_active"] == 64 and "window_num_blocks" not in engine
    assert "8 v5e chips" in stated["deployment"]
    for item in ("qk_norm", "rope_in_sliding_layers_only", "output_gate",
                 "four_norms", "router_bias"):
        assert "as recalled" in stated["assumed"][item]


def test_the_stagger_spreads_contexts_over_512_to_8192():
    from benchmark.lib import traffic

    mix = traffic.load_mix(traffic.mix_path(ROOT, "reason8k"))
    lengths = traffic.Lengths(mix, 3)
    firsts = [lengths.first(i) for i in range(mix["clients"])]
    assert firsts[0] == (512, 7680) and firsts[63] == (8072, 120)
    assert {p + o for p, o in firsts} == {8192}
    assert lengths.next() == (512, 7680)
    # the widest lane keeps the page table at its 512-column bucket
    assert -(-firsts[63][0] // 16) > 504
    warm = mix["warm"]
    assert warm["decode_longest_prompts"][0] + 6 * warm["lanes"] == 8192


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
