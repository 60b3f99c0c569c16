"""CPU tests of what `mimo_v2_flash` adds to the yardstick: the byte and
operation counts, the readers of its per-layer metrics on hand-made
summaries, its configuration file, and the whole harness at toy size
(sound: ``correct: true``; the int8 control and a broken timed path:
``correct: false``)."""

import json
import os
import re
import subprocess
import sys
import types

import pytest

from benchmark.lib import bytes_ops_mimo, serving_config
from benchmark.readers import counters, moe

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "mimo_v2_flash.reason"
S = 1_000_000_000


def config(toy=False):
    return serving_config.load_config(
        os.path.join(BENCH, "configs", "mimo_v2_flash"), toy=toy)


# -- bytes and operations ---------------------------------------------------------


def test_bytes_and_operations_against_hand_sums():
    model = config()["model"]
    assert bytes_ops_mimo.expert_bytes(model) == 3 * 4096 * 2048 * 2 == 50_331_648
    assert bytes_ops_mimo.pair_flops(model) == 6 * 4096 * 2048
    assert bytes_ops_mimo.layer_counts(model) == (2, 9, 10)
    # K rows of 192 and V rows of 128 over 4 and over 8 kv heads
    assert bytes_ops_mimo.kv_bytes_per_token(model, window=False) == 2560
    assert bytes_ops_mimo.kv_bytes_per_token(model, window=True) == 5120
    # one lane inside the window, one far past it
    assert bytes_ops_mimo.decode_attention_bytes(model, [100, 2000]) == (
        2 * 2560 * 2100 + 9 * 5120 * (100 + 128))
    # a decode step's weights: two full and nine window attention
    # layers, the dense MLP, ten routers over 256, 22 norms, the head and
    # its norm; and 50.3 MB for each expert touched
    q, wo = 4096 * 64 * 192, 64 * 128 * 4096
    other = (2 * (q + 4096 * 4 * 320 + wo) + 9 * (q + 4096 * 8 * 320 + wo)
             + 3 * 4096 * 16384 + 10 * 4096 * 256 + 22 * 4096
             + 4096 * 19072 + 4096)
    assert other == 1_317_629_952
    assert bytes_ops_mimo.decode_step_weight_bytes(model, 0) == 2 * other
    assert bytes_ops_mimo.decode_step_weight_bytes(model, 132.5) == (
        2 * other + 132.5 * 50_331_648)


# -- the readers on hand-made summaries ---------------------------------------------


def engine(steps, **moe_counters):
    return {"engine": {"steps": steps, **moe_counters}, "at": steps * S}


def made_run(with_counters=True, with_trace=True):
    run = types.SimpleNamespace()
    run.config = config()
    run.peak = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    run.t0, run.t1 = 10 * S, 20 * S
    # 100 steps in the window; a step touched 14 of each layer's 16
    # experts, routed 32 pairs to them, the fullest expert took 6
    more = dict(moe_experts_touched=140 * 100, moe_pairs=320 * 100,
                moe_load_max=60 * 100, window_blocks_whole=8000,
                window_blocks_unheld=7100) if with_counters else {}
    zero = {k: 0 for k in more}
    run.before, run.after = engine(1000, **zero), engine(1100, **more)
    # two lanes decoding through the window: the first token (of the
    # prefill) just before it, then 100 decoded tokens inside it, at
    # contexts 101..200 and 1901..2000
    times = [int((9.9 + 0.1 * k) * S) for k in range(101)]
    run.requests = [{"prompt": [0] * 100, "times": times},
                    {"prompt": [0] * 1900, "times": times}]
    run.trace = None
    if with_trace:
        decode = ["jit_llm_decode", 0.016,
                  {"moe_experts.tpu_custom_call": 0.009,
                   "paged_attention.tpu_custom_call": 0.003, "fusion": 0.004}]
        prefill = ["jit_llm_prefill", 0.06,
                   {"moe_experts.tpu_custom_call": 0.02, "fusion": 0.04}]
        run.trace = {"module_runs": [decode, prefill, decode]}
    return run


def test_counter_readers_divide_by_held_layers_and_steps():
    run = made_run(with_trace=False)
    assert moe.experts_touched_share_pct(run) == pytest.approx(
        100 * 140 / (16 * 10))
    assert moe.load_max_over_mean(run) == pytest.approx(60 / (320 / 16))
    assert counters.delta_ratio(
        run, "engine:window_blocks_unheld", "engine:window_blocks_whole",
        scale=100) == pytest.approx(88.75)
    # the trace readers say nothing without a trace
    assert moe.experts_roofline_pct(run, "moe_experts") is None
    assert moe.mixed_attention_roofline_pct(run, "paged_attention") is None
    assert moe.weight_stream_share_pct(run) is None


def test_roofline_readers_count_the_decode_programs_kernels_only():
    run = made_run()
    # two decode executions: 2 x 9 ms of the expert kernel; the prefill's
    # 20 ms of the same kernel is not a decode step's
    moved = 2 * 140 * 50_331_648
    assert moe.experts_roofline_pct(run, "moe_experts") == pytest.approx(
        100 * (moved / 819e9) / 0.018)
    assert moe.experts_roofline_pct(run, "moe_experts") < 100
    contexts = list(range(101, 201)) + list(range(1901, 2001))
    a_step = bytes_ops_mimo.decode_attention_bytes(
        run.config["model"], contexts) / 100
    assert moe.mixed_attention_roofline_pct(run, "paged_attention") == (
        pytest.approx(100 * (2 * a_step / 819e9) / 0.006))
    # the whole step: the other weights and the 140 experts touched,
    # over the decode program's 16 ms (the prefill's 60 ms left out)
    least = (2 * 1_317_629_952 + 140 * 50_331_648) / 819e9
    assert moe.weight_stream_share_pct(run) == pytest.approx(
        100 * least / 0.016)
    assert moe.weight_stream_share_pct(run) < 100


def test_a_program_without_the_counters_reports_nothing():
    """The parent of the PR that added them: every reader gives None,
    and the line leaves the metric out."""
    run = made_run(with_counters=False)
    assert moe.experts_touched_share_pct(run) is None
    assert moe.load_max_over_mean(run) is None
    assert moe.experts_roofline_pct(run, "moe_experts") is None
    assert moe.weight_stream_share_pct(run) is None
    assert counters.delta_ratio(
        run, "engine:window_blocks_unheld", "engine:window_blocks_whole",
        scale=100) is None
    run.trace = {"module_runs": [["jit_llm_decode", 0.02, {"fusion": 0.02}]]}
    assert moe.mixed_attention_roofline_pct(run, "paged_attention") is None


# -- the check: gaps where the reference's routing stands clear -------------------------


def test_the_routers_margin_is_the_least_move_of_a_held_expert_across_the_edge():
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import reference_mimo

    # s + b of six experts for three tokens; experts 0 and 1 are held,
    # two a token are chosen
    biased = np.array([
        [0.90, 0.10, 0.80, 0.70, 0.3, 0.2],  # 0 chosen: 0.90 - 0.70 to leave
        [0.20, 0.69, 0.80, 0.70, 0.3, 0.1],  # 1 left out: 0.70 - 0.69 to enter
        [0.20, 0.10, 0.80, 0.79, 0.3, 0.3],  # a tie of others: 0.79 - 0.20
    ])
    w = {"router": jnp.asarray(np.log(biased / (1 - biased)), jnp.float32),
         "router_bias": jnp.zeros(6)}
    chosen, weight, margin = reference_mimo.route(
        jnp.eye(3), w, {"num_experts_per_tok": 2}, (0, 2))
    assert np.asarray(chosen).tolist() == [[0, 2], [2, 3], [2, 3]]
    assert np.asarray(weight[0]) == pytest.approx([0.9 / 1.7, 0.8 / 1.7])
    assert np.asarray(margin) == pytest.approx([0.20, 0.01, 0.59], abs=1e-6)


def test_the_check_reads_the_gaps_of_decided_positions_only(monkeypatch):
    from benchmark.checks import moe_decoder
    from benchmark.lib import reference_mimo

    clear, tie = moe_decoder.DECIDED_MARGIN * 2, moe_decoder.DECIDED_MARGIN / 2
    results = [{"gaps": [0.0, 0.4, 0.01, 0.0], "margins": [clear, tie, clear, tie],
                "control_gaps": [0.2, 0.4, 0.0, 0.0]}]
    monkeypatch.setattr(reference_mimo, "served_token_gaps",
                        lambda *a, **k: results)
    job = {"seed": 1, "model": {}, "sequences": []}
    sound = moe_decoder.numbers(job, control=False)
    assert sound["served_tokens"] == 4 and sound["undecided_share"] == 0.5
    assert (sound["served_gap_max"], sound["served_gap_mean"]) == (0.01, 0.005)
    assert (sound["all_gap_max"], sound["served_off_best"]) == (0.4, 1)
    control = moe_decoder.numbers(job, control=True)
    assert (control["served_gap_max"], control["program_gap_max"]) == (0.2, 0.01)


# -- the configuration file ---------------------------------------------------------


def test_config_states_its_cuts_and_its_top_level_is_the_model_group():
    with open(os.path.join(BENCH, "configs", "mimo_v2_flash", "config.json")) as f:
        stated = json.load(f)
    model, published = stated["model"], stated["published"]
    extra = {"experts_routed_over", "experts_held_first", "torch_dtype"}
    # the published keys stand at the top level as they are run, and the
    # ``model`` group the harness reads is the same numbers
    assert {k: model[k] for k in model if k not in extra} == {
        k: stated[k] for k in model if k not in extra}
    assert set(stated["reduced"]) == set(published) == set(stated["reduced_why"])
    assert model["experts_routed_over"] == published["n_routed_experts"] == 256
    assert model["n_routed_experts"] == 16 and model["num_experts_per_tok"] == 8
    assert model["hybrid_layer_pattern"] == published["hybrid_layer_pattern"][:11]
    assert model["moe_layer_freq"] == published["moe_layer_freq"][:11]
    assert model["vocab_size"] * 8 == published["vocab_size"]
    # no width is cut
    assert (model["hidden_size"], model["head_dim"], model["v_head_dim"],
            model["intermediate_size"], model["moe_intermediate_size"],
            model["sliding_window"]) == (4096, 192, 128, 16384, 2048, 128)
    engine = stated["engine"]
    # the full group's pool for 64 sequences of 2,048; the window
    # group's is the engine's to work out (64 rings of 9, and the trash)
    assert engine["num_blocks"] == 64 * 128 + 1
    assert engine["max_active"] == 64 and "window_num_blocks" not in engine
    assert "16 v5e chips" in stated["deployment"]


def test_the_stagger_spreads_contexts_over_512_to_2048():
    from benchmark.lib import traffic

    mix = traffic.load_mix(traffic.mix_path(ROOT, "reason"))
    lengths = traffic.Lengths(mix, 3)
    firsts = [lengths.first(i) for i in range(mix["clients"])]
    assert firsts[0] == (512, 1536) and firsts[63] == (2024, 24)
    assert {p + o for p, o in firsts} == {2048}
    assert lengths.next() == (512, 1536)


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
    """`PERF.md` section 2 has the toy's readings: over the positions
    where the reference's routing stands clear the toy's control lies
    over the sound runs in the widest and in the mean gap, as the cell's
    does, by less (some 450 decided tokens a run)."""
    sound = rehearse(seed)
    assert sound["correct"] is True and over_their_limits(sound) == []
    assert {"out_tokens_per_s", "itl_ms.p95", "setup_s"} == set(sound["metrics"])
    control = rehearse(seed, "--control")
    assert control["correct"] is False and control["control"] is True
    assert 0 < len(over_their_limits(control)) and set(
        over_their_limits(control)) <= {"served_gap_max", "served_gap_mean"}


def test_a_broken_timed_path_is_not_correct():
    """Every fifth decoded token altered where the program produces it."""
    line = rehearse(2 ** 31 + 11, BENCH_BREAK="token")
    assert line["correct"] is False
    assert over_their_limits(line) == ["served_gap_max", "served_gap_mean"]
