"""CPU tests of what `gigachat3_702b` adds to the yardstick: the byte and
operation counts, the readers of its per-layer metrics on hand-made
summaries, its configuration file and traffic, and the whole harness at
toy size (sound: ``correct: true``; the int8 control and a timed path
with every fifth token altered: ``correct: false``)."""

import json
import os
import re
import subprocess
import sys
import types

import pytest

from benchmark.lib import bytes_ops_dsv3, serving_config
from benchmark.readers import dsv3

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "gigachat3_702b.reason8k_128"
S = 1_000_000_000
NEW_METRICS = (
    "attn.latent_roofline", "dsv3.experts_roofline",
    "step.llm_decode.roofline_share", "attn.latent_share_of_bytes",
    "dsv3.experts_touched_share", "dsv3.load_max_over_mean",
    "dsv3.lanes_here_share")


def config(toy=False):
    return serving_config.load_config(
        os.path.join(BENCH, "configs", "gigachat3_702b"), toy=toy)


# -- bytes and operations ---------------------------------------------------------


def test_bytes_and_operations_against_hand_sums():
    model = config()["model"]
    assert bytes_ops_dsv3.latent_bytes_per_token(model) == 576 * 2 == 1152
    assert bytes_ops_dsv3.latent_flops_per_token(model) == (
        64 * (576 + 512) * 2) == 139_264
    # 121 FLOP a byte, half the v5e's ridge of 240
    assert round(139_264 / 1152) == 121 and round(197e12 / 819e9) == 241
    assert bytes_ops_dsv3.expert_bytes(model) == 3 * 7168 * 2048 * 2 == 88_080_384
    assert bytes_ops_dsv3.pair_flops(model) == 6 * 7168 * 2048
    assert bytes_ops_dsv3.expert_layers(model) == 4
    # the issue's reckoning: 128 lanes even over 512-8,192
    moved, flops = bytes_ops_dsv3.decode_attention_work(model, 128 * 4352)
    assert moved == 5 * 128 * 4352 * 1152 and flops == 5 * 128 * 4352 * 139_264
    # the issue's arithmetic, a layer's attention: 132.58M parameters
    attention = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576
                 + 512 * 64 * 320 + 64 * 192 * 7168)
    assert round(attention / 1e6, 2) == 132.58
    assert bytes_ops_dsv3.attention_params(model) == attention + 1536 + 512
    other = (5 * (attention + 1536 + 512 + 2 * 7168) + 3 * 7168 * 18432
             + 4 * (7168 * 256 + 3 * 7168 * 2048) + 7168 * 16032 + 7168)
    assert bytes_ops_dsv3.decode_step_weight_bytes(model, 0) == 2 * other
    assert bytes_ops_dsv3.decode_step_weight_bytes(model, 62.5) == (
        2 * other + 62.5 * 88_080_384)
    # all 64 held experts touched: 8.3 GB a step, all but the embedding
    assert round(bytes_ops_dsv3.decode_step_weight_bytes(model, 64) / 1e9, 1) == 8.4


# -- the readers on hand-made summaries ---------------------------------------------


def engine(steps, **counted):
    return {"engine": {"steps": steps, **counted}, "at": steps * S}


def made_run(with_counters=True, with_trace=True):
    run = types.SimpleNamespace()
    run.config = config()
    run.peak = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    run.t0, run.t1 = 10 * S, 20 * S
    # 100 steps of 128 lanes in the window; a step's lanes held 557,056
    # tokens of context, touched 15 of each layer's 16 experts, routed
    # 256 pairs to them from 200 (lane, layer) pairs
    more = dict(attn_tokens_full=557_056 * 100, lane_steps=128 * 100,
                moe_experts_touched=60 * 100, moe_pairs=256 * 100,
                moe_load_max=30 * 100, moe_lanes_here=200 * 100,
                ) if with_counters else {}
    run.before = engine(1000, **{k: 0 for k in more})
    run.after = engine(1100, **more)
    run.requests = []
    run.trace = None
    if with_trace:
        decode = ["jit_llm_decode", 0.020,
                  {"moe_experts.tpu_custom_call": 0.008,
                   "paged_attention.tpu_custom_call": 0.007, "fusion": 0.005}]
        prefill = ["jit_llm_prefill", 0.06,
                   {"moe_experts.tpu_custom_call": 0.02, "fusion": 0.04}]
        run.trace = {"module_runs": [decode, prefill, decode]}
    return run


def test_counter_readers_need_no_trace():
    run = made_run(with_trace=False)
    model = run.config["model"]
    weights = bytes_ops_dsv3.decode_step_weight_bytes(model, 60)
    moved = 5 * 557_056 * 1152
    assert dsv3.latent_share_of_bytes_pct(run) == pytest.approx(
        100 * moved / (weights + moved))
    assert 25 < dsv3.latent_share_of_bytes_pct(run) < 30  # the issue's 30%
    assert dsv3.experts_touched_share_pct(run) == pytest.approx(100 * 60 / 64)
    assert dsv3.load_max_over_mean(run) == pytest.approx(30 * 16 / 256)
    assert dsv3.lanes_here_share_pct(run) == pytest.approx(
        100 * 200 / (128 * 4))
    assert dsv3.latent_roofline_pct(run, "paged_attention") is None
    assert dsv3.experts_roofline_pct(run, "moe_experts") is None
    assert dsv3.roofline_share_pct(run) is None


def test_roofline_readers_take_the_longer_of_bytes_and_flops():
    run = made_run()
    model = run.config["model"]
    moved, flops = 5 * 557_056 * 1152, 5 * 557_056 * 139_264
    assert moved / 819e9 > flops / 197e12  # bytes bind at the peak
    # two decode executions: 2 x 7 ms of the attention kernel
    assert dsv3.latent_roofline_pct(run, "paged_attention") == (
        pytest.approx(100 * (2 * moved / 819e9) / 0.014))
    # a slower HBM... a faster one: the FLOPs win, and the share is theirs
    run.peak = {"hbm_bytes_per_s": 4 * 819e9, "bf16_flops_per_s": 197e12}
    assert dsv3.latent_roofline_pct(run, "paged_attention") == (
        pytest.approx(100 * (2 * flops / 197e12) / 0.014))
    run.peak = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    # 2 x 8 ms of the expert kernel; the prefill's 20 ms is not a step's
    assert dsv3.experts_roofline_pct(run, "moe_experts") == pytest.approx(
        100 * (2 * 60 * 88_080_384 / 819e9) / 0.016)
    # the whole step: the weights with the 60 experts touched, and the
    # attention's longer side, over the decode program's 20 ms
    least = (bytes_ops_dsv3.decode_step_weight_bytes(model, 60) / 819e9
             + moved / 819e9)
    assert dsv3.roofline_share_pct(run) == pytest.approx(100 * least / 0.020)
    for share in (dsv3.latent_roofline_pct(run, "paged_attention"),
                  dsv3.experts_roofline_pct(run, "moe_experts"),
                  dsv3.roofline_share_pct(run)):
        assert 0 < share < 100


def test_a_program_without_the_counters_reports_nothing():
    """A program from before this configuration (the parent, on which the
    driver lays these files): every reader gives None and raises
    nothing, so the line leaves the metric out."""
    run = made_run(with_counters=False)
    for reader in (dsv3.latent_share_of_bytes_pct,
                   dsv3.experts_touched_share_pct, dsv3.load_max_over_mean,
                   dsv3.lanes_here_share_pct, dsv3.roofline_share_pct):
        assert reader(run) is None
    assert dsv3.latent_roofline_pct(run, "paged_attention") is None
    assert dsv3.experts_roofline_pct(run, "moe_experts") is None
    run = made_run()
    run.trace = {"module_runs": [["jit_llm_decode", 0.02, {"fusion": 0.02}]]}
    assert dsv3.latent_roofline_pct(run, "paged_attention") is None
    assert dsv3.experts_roofline_pct(run, "moe_experts") is None


def test_the_readers_on_a_recorded_line():
    """`recorded_gigachat3.json`: the engine's `stats()` at the two edges
    of a toy rehearsal's window and the counter metrics of the line that
    run printed. The readers, given the snapshots, give the line's
    numbers; the program's own row bytes are served beside the counters."""
    with open(os.path.join(BENCH, "tests", "recorded_gigachat3.json")) as f:
        recorded = json.load(f)
    run = made_run(with_trace=False)
    run.config = config(toy=True)
    run.before, run.after = recorded["before"], recorded["after"]
    assert recorded["after"]["engine"]["steps"] > (
        recorded["before"]["engine"]["steps"])
    for name, reader in (
            ("attn.latent_share_of_bytes", dsv3.latent_share_of_bytes_pct),
            ("dsv3.experts_touched_share", dsv3.experts_touched_share_pct),
            ("dsv3.load_max_over_mean", dsv3.load_max_over_mean),
            ("dsv3.lanes_here_share", dsv3.lanes_here_share_pct)):
        assert reader(run) == pytest.approx(recorded["metrics"][name])
    # the toy's row: 96 + 16 numbers held, 128 stored, in bf16
    assert recorded["after"]["engine"]["kv_row_bytes_by_group"] == [
        {"stored": 256, "counted": 224}]
    model = run.config["model"]
    assert bytes_ops_dsv3.latent_bytes_per_token(model) == 224


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
        assert module == "dsv3" and callable(getattr(dsv3, function))
        assert {k: spec[k] for k in ("unit", "better", "source", "layer",
                                     "moves")} == {
            k: listed[name][k] for k in ("unit", "better", "source",
                                         "layer", "moves")}
    # the readers written for the other models' keys are not this cell's
    assert not {"moe.experts_roofline", "attn.mixed_roofline",
                "afmoe.experts_roofline", "attn.window_full_roofline",
                "paged_attn_roofline", "kv.window_unheld_share",
                "step.llm_decode.hbm_roofline_share"} & set(listed)
    # and every model-independent metric is
    assert {"engine.step_ms.host", "device.idle_share.llm",
            "setup.compiles_in_window", "step.llm_decode_ms.mean",
            "moe.resident_calls_per_step", "attn.tiles_whole_share",
            "engine.attn_blocks_live_share"} <= set(listed)
    (cell,) = [w for w in benchmark["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "reason8k_128"
    assert "largest share the chip allows under the floors" in cell["why"]


# -- the configuration file and the traffic -------------------------------------------


def test_config_states_its_cuts_and_every_width_is_the_published_one():
    with open(os.path.join(BENCH, "configs", "gigachat3_702b",
                           "config.json")) as f:
        stated = json.load(f)
    model, published = stated["model"], stated["published"]
    extra = {"experts_routed_over", "experts_held_first", "torch_dtype"}
    assert {k: model[k] for k in model if k not in extra} == {
        k: stated[k] for k in model if k not in extra}
    assert stated["reduced"] == list(published) == list(stated["reduced_why"])
    assert len(stated["reduced"]) == 6
    assert published == {
        "num_hidden_layers": 64, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 128256,
        "max_position_embeddings": 262144, "num_nextn_predict_layers": 1}
    assert model["experts_routed_over"] == 256 and model["n_routed_experts"] == 16
    assert model["vocab_size"] * 8 == published["vocab_size"]
    assert model["num_hidden_layers"] - model["first_k_dense_replace"] == 4
    # no width is cut
    assert (model["hidden_size"], model["intermediate_size"],
            model["moe_intermediate_size"], model["num_attention_heads"],
            model["q_lora_rank"], model["kv_lora_rank"],
            model["qk_nope_head_dim"], model["qk_rope_head_dim"],
            model["v_head_dim"], model["num_experts_per_tok"],
            model["n_group"], model["topk_group"],
            model["routed_scaling_factor"]) == (
        7168, 18432, 2048, 64, 1536, 512, 128, 64, 192, 8, 8, 4, 2.5)
    assert model["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "rope_type": "yarn"}
    engine = stated["engine"]
    # one full group: 128 lanes of 5,120 tokens (the traffic holds 4,352)
    assert engine["num_blocks"] == 128 * 320 + 1 and engine["max_active"] == 128
    assert "16 v5e chips" in stated["deployment"]
    for item in ("yarn", "latent_norms", "group_limited_routing"):
        assert "as recalled" in stated["assumed"][item]
    # the catalog's entry, key for key, but for the six reduced keys
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            (entry,) = [row for row in map(json.loads, f)
                        if row["name"] == "GigaChat3.1-702B-A36B"]
        assert stated["source"] == entry["source_url"]
        differing = {k for k, v in entry["config"].items() if stated[k] != v}
        assert differing == set(stated["reduced"])


def test_the_program_config_is_the_files_and_refuses_what_it_lacks():
    from benchmark.lib.serving_dsv3 import dsv3_config

    model = config()["model"]
    made = dsv3_config(model)
    assert (made.n_layers, made.n_dense_layers, made.held, made.n_experts,
            made.row, made.row_width) == (5, 1, (0, 16), 256, 576, 640)
    assert abs(made.softmax_scale - 0.14468) < 1e-5
    for key, value in (("num_nextn_predict_layers", 1),
                       ("scoring_func", "softmax"), ("topk_method", "greedy")):
        with pytest.raises(ValueError, match="does not implement"):
            dsv3_config({**model, key: value})


def test_the_stagger_spreads_contexts_over_512_to_8192():
    from benchmark.lib import traffic

    mix = traffic.load_mix(traffic.mix_path(ROOT, "reason8k_128"))
    lengths = traffic.Lengths(mix, 3)
    assert mix["clients"] == 128
    firsts = [lengths.first(i) for i in range(mix["clients"])]
    assert firsts[0] == (512, 7680) and firsts[127] == (8132, 60)
    assert {p + o for p, o in firsts} == {8192}
    assert lengths.next() == (512, 7680)
    # the widest lane keeps the page table at its 512-column bucket
    assert -(-firsts[127][0] // 16) > 504
    warm = mix["warm"]
    # the walk's longest lane stays in that bucket and inside max_seq_len,
    # and passes the 64-lane bucket (the 128-lane one compiles in the ramp)
    assert -(-warm["decode_longest_prompts"][0] // 16) >= 497
    assert warm["decode_longest_prompts"][0] + 6 * warm["lanes"] <= 8192
    assert 32 < warm["lanes"] <= 64
    # the steady state holds 85% of the pool
    assert 128 * 4352 // 16 == 34816 and 34816 / 40960 == 0.85


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
