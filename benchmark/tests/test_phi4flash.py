"""CPU tests of what `phi4_mini_flash` adds to the yardstick: the byte and
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

from benchmark.lib import bytes_ops_phi4flash, serving_config
from benchmark.readers import jamba, phi4flash

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "phi4_mini_flash.reason8k"
S = 1_000_000_000
NEW_METRICS = (
    "attn.shared_kv_roofline", "step.llm_decode.sambay_roofline_share",
    "attn.shared_kv_share_of_bytes")
WEIGHTS = 7_706_792_960


def config(toy=False):
    return serving_config.load_config(
        os.path.join(BENCH, "configs", "phi4_mini_flash"), toy=toy)


# -- bytes and operations ---------------------------------------------------------


def test_bytes_and_operations_against_hand_sums():
    model = config()["model"]
    kinds = bytes_ops_phi4flash.layer_kinds(model)
    assert kinds[:18] == ["mamba", "window"] * 8 + ["mamba", "full"]
    assert kinds[18:] == ["gmu", "cross"] * 7
    assert bytes_ops_phi4flash.shared_readers(model) == 8
    # a cached token in a storing layer: K 20 heads of 64, V 10 of 128
    assert bytes_ops_phi4flash.kv_bytes_per_token(model) == (
        20 * 64 * 2 + 10 * 128 * 2) == 5120
    # 40 query heads: a key head of 64 scored, a value head of 128 weighed
    assert bytes_ops_phi4flash.kv_flops_per_token(model) == (
        40 * (2 * 64 + 2 * 128)) == 15_360 == 3 * 5120
    # a lane's slot in one Mamba layer, as Jamba's
    assert bytes_ops_phi4flash.slot_bytes(model) == 358_400
    assert bytes_ops_phi4flash.state_bytes(model) == 16 * 5120 * 4
    # the issue's step at 64 lanes and a mean context of 4,352
    shared, rings, flops = bytes_ops_phi4flash.decode_attention_work(
        model, 64 * 4352 * 8, 64 * 512)
    assert shared == 64 * 4352 * 5120 * 8 and round(shared / 1e9, 2) == 11.41
    assert rings == 64 * 512 * 5120 * 8 and round(rings / 1e9, 2) == 1.34
    assert flops == 3 * (shared + rings)
    states = bytes_ops_phi4flash.step_state_bytes(64 * 9, model)
    assert states == 64 * 9 * 2 * 358_400 and round(states / 1e9, 2) == 0.41
    # the issue's arithmetic: the mixers, the MLP, the whole model
    assert bytes_ops_phi4flash.mamba_mixer_params(model) == 41_241_600
    assert bytes_ops_phi4flash.attention_mixer_params(model) == 19_668_864
    assert bytes_ops_phi4flash.cross_mixer_params(model) == 13_112_704
    assert bytes_ops_phi4flash.gmu_params(model) == 26_214_400
    assert bytes_ops_phi4flash.mlp_params(model) == 78_643_200
    whole = (9 * 41_241_600 + 9 * 19_668_864 + 7 * 13_112_704
             + 7 * 26_214_400 + 32 * (78_643_200 + 10_240)
             + 200064 * 2560 + 5120)
    assert bytes_ops_phi4flash.model_params(model) == whole
    assert round(whole / 1e6) == 3853
    # bf16 but A_log, D, b_dt and the lambdas: what jax.eval_shape counts
    # of the program's own parameters (`tests/test_mosaic_compile.py`)
    weights = bytes_ops_phi4flash.decode_step_weight_bytes(model)
    assert weights == 2 * whole + 2 * (9 * 18 * 5120 + 16 * 256) == WEIGHTS
    step = weights + shared + rings + states
    assert round(step / 1e9, 1) == 20.9
    assert round(1e3 * step / 819e9, 1) == 25.5
    assert round(100 * shared / step) == 55
    assert round(100 * (shared + rings) / step) == 61


# -- the readers on hand-made summaries ---------------------------------------------


def engine(steps, **counted):
    return {"engine": {"steps": steps, **counted}, "at": steps * S}


def made_run(with_counters=True, with_trace=True):
    run = types.SimpleNamespace()
    run.config = config()
    run.peak = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    run.t0, run.t1 = 10 * S, 20 * S
    # 100 steps of 64 lanes in the window; a step's lanes held 278,528
    # tokens of context, 32,768 of them inside a window, turned 576
    # states and read 8 x 278,528 rows of the shared pool
    more = dict(attn_tokens_full=278_528 * 100, lane_steps=64 * 100,
                attn_tokens_window=32_768 * 100,
                ssm_state_updates=576 * 100,
                shared_kv_rows_read=8 * 278_528 * 100
                ) if with_counters else {}
    run.before = engine(1000, **{k: 0 for k in more})
    run.after = engine(1100, **more)
    run.requests = []
    run.trace = None
    if with_trace:
        decode = ["jit_llm_decode", 0.040,
                  {"selective_scan_step.tpu_custom_call": 0.001,
                   "paged_attention.tpu_custom_call": 0.026, "fusion": 0.013}]
        prefill = ["jit_llm_prefill", 0.06, {"fusion": 0.05, "while": 0.01}]
        run.trace = {"module_runs": [decode, prefill, decode]}
    return run


SHARED, RINGS = 8 * 278_528 * 5120, 8 * 32_768 * 5120
STATES = 576 * 2 * 358_400


def test_counter_readers_need_no_trace():
    run = made_run(with_trace=False)
    assert phi4flash.shared_kv_share_of_bytes_pct(run) == pytest.approx(
        100 * SHARED / (WEIGHTS + STATES + RINGS + SHARED))
    assert 54 < phi4flash.shared_kv_share_of_bytes_pct(run) < 56
    assert phi4flash.shared_kv_roofline_pct(run, "paged_attention") is None
    assert phi4flash.sambay_roofline_share_pct(run) is None


def test_roofline_readers_on_a_made_trace():
    run = made_run()
    # two decode executions: 2 x 26 ms of the paged kernel, which has to
    # read the shared pool once a reader and every ring once
    assert (SHARED + RINGS) / 819e9 > 3 * (SHARED + RINGS) / 197e12
    assert phi4flash.shared_kv_roofline_pct(run, "paged_attention") == (
        pytest.approx(100 * (2 * (SHARED + RINGS) / 819e9) / 0.052))
    least = (WEIGHTS + STATES + RINGS + SHARED) / 819e9
    assert phi4flash.sambay_roofline_share_pct(run) == pytest.approx(
        100 * least / 0.040)
    # Jamba's reader of the scan kernel reads this model's keys too
    assert jamba.ssm_step_roofline_pct(run, "selective_scan_step") == (
        pytest.approx(100 * (2 * 576 * 2 * 327_680 / 819e9) / 0.002))
    for share in (phi4flash.shared_kv_roofline_pct(run, "paged_attention"),
                  phi4flash.sambay_roofline_share_pct(run),
                  jamba.ssm_step_roofline_pct(run, "selective_scan_step")):
        assert 0 < share < 100


def test_a_program_without_the_counters_reports_nothing():
    """A program from before this configuration (the parent, on which the
    driver lays these files): every reader gives None and raises
    nothing, so the line leaves the metric out."""
    run = made_run(with_counters=False)
    assert phi4flash.shared_kv_share_of_bytes_pct(run) is None
    assert phi4flash.sambay_roofline_share_pct(run) is None
    assert phi4flash.shared_kv_roofline_pct(run, "paged_attention") is None
    run = made_run()
    run.trace = {"module_runs": [["jit_llm_decode", 0.02, {"fusion": 0.02}]]}
    assert phi4flash.shared_kv_roofline_pct(run, "paged_attention") is None


def test_the_readers_on_a_recorded_chip_run():
    """`recorded_phi4flash.json`: the engine's `stats()` at the two edges
    of a traced chip run's window, six of its traced decode executions
    and a prefill as the trace reduction gave them, and the per-layer
    metrics of the line that run printed. The readers, given the
    snapshots and the whole trace, gave the line's numbers; given the
    excerpt they give the counter metric exactly and the trace's within
    what six executions differ from all of them."""
    with open(os.path.join(BENCH, "tests", "recorded_phi4flash.json")) as f:
        recorded = json.load(f)
    run = made_run(with_trace=False)
    run.before, run.after = recorded["before"], recorded["after"]
    run.peak = recorded["peak"]
    assert recorded["after"]["engine"]["steps"] > (
        recorded["before"]["engine"]["steps"])
    assert phi4flash.shared_kv_share_of_bytes_pct(run) == pytest.approx(
        recorded["metrics"]["attn.shared_kv_share_of_bytes"])
    run.trace = recorded["trace"]
    kernels = recorded["trace"]["module_runs"][0][2]
    assert any(name.startswith("paged_attention") for name in kernels)
    assert any(name.startswith("selective_scan_step") for name in kernels)
    for name, value in (
            ("attn.shared_kv_roofline",
             phi4flash.shared_kv_roofline_pct(run, "paged_attention")),
            ("step.llm_decode.sambay_roofline_share",
             phi4flash.sambay_roofline_share_pct(run)),
            ("ssm.step_roofline",
             jamba.ssm_step_roofline_pct(run, "selective_scan_step"))):
        assert 0 < value <= 100
        assert value == pytest.approx(recorded["metrics"][name], rel=0.1)
    # the program's own row bytes: a cached token in the full layer and
    # in a window layer, and a slot
    assert recorded["after"]["engine"]["kv_row_bytes_by_group"] == [
        {"stored": 5120, "counted": 5120}, {"stored": 5120, "counted": 5120},
        {"stored": 358_400, "counted": 358_400}]
    steps = {k: recorded[k]["engine"] for k in ("before", "after")}

    def delta(name):
        return steps["after"][name] - steps["before"][name]

    # every live lane turns 9 states a step, and its whole context is
    # read by 8 layers (the engine books a step's contexts when it
    # dispatches it, the model's counter arrives with its result: the two
    # differ by what the steps in flight at the two edges differ)
    assert delta("ssm_state_updates") == 9 * delta("lane_steps")
    assert delta("shared_kv_rows_read") == pytest.approx(
        8 * delta("attn_tokens_full"), rel=1e-4)
    # the full group's blocks in use are ONE layer's: 5,120 B a token
    full = steps["after"]["kv_blocks_in_use_by_group"][0]
    assert 64 * 512 // 16 <= full <= 32768


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
        assert module == "phi4flash" and callable(getattr(phi4flash, function))
        assert {k: spec[k] for k in ("unit", "better", "source", "layer",
                                     "moves")} == {
            k: listed[name][k] for k in ("unit", "better", "source",
                                         "layer", "moves")}
    # the readers written for the other models' keys are not this cell's
    assert not {"moe.experts_roofline", "attn.mixed_roofline",
                "afmoe.experts_roofline", "attn.window_full_roofline",
                "paged_attn_roofline", "attn.latent_roofline",
                "dsv3.experts_roofline", "gdn.step_roofline",
                "attn.gated_full_roofline", "attn.mqa_roofline",
                "moe.resident_calls_per_step", "ssm.state_share_of_bytes",
                "step.llm_decode.ssm_roofline_share",
                "step.llm_decode.roofline_share",
                "step.llm_decode.state_roofline_share",
                "step.llm_decode.hbm_roofline_share"} & set(listed)
    # Jamba's reader of the scan kernel and every model-independent one are
    assert {"ssm.step_roofline", "engine.step_ms.host",
            "device.idle_share.llm", "setup.compiles_in_window",
            "step.llm_decode_ms.mean", "step.prefill_ms.mean",
            "attn.tiles_whole_share", "attn.tile_slots_live_share",
            "engine.attn_blocks_live_share", "engine.steps_ahead_share",
            "engine.admits_behind_share", "engine.step_ms.host.steady",
            "engine.stall_share.program", "setup.compile_s"} <= set(listed)
    for metric in benchmark["end_to_end"]:
        assert CELL in metric.get("workloads", [CELL])
    (cell,) = [w for w in benchmark["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "reason8k"
    assert "ONE full K/V pool read by 8 layers" in cell["why"]
    assert len(cell["why"]) <= 200
    (entry,) = [c for c in benchmark["configs"]
                if c["name"] == "phi4_mini_flash"]
    assert entry["reduced"] == ["max_position_embeddings"]
    assert len(entry["why"]) <= 200


# -- the configuration file and the traffic -------------------------------------------


def test_config_states_that_nothing_is_cut_but_the_positions():
    with open(os.path.join(BENCH, "configs", "phi4_mini_flash",
                           "config.json")) as f:
        stated = json.load(f)
    model, published = stated["model"], stated["published"]
    assumed_sizes = {"mamba_d_state", "mamba_d_conv", "mamba_expand",
                     "mamba_dt_rank", "torch_dtype"}
    assert {k: model[k] for k in model if k not in assumed_sizes} == {
        k: stated[k] for k in model if k not in assumed_sizes}
    assert assumed_sizes - {"torch_dtype"} <= set(stated["assumed"])
    assert stated["reduced"] == list(published) == list(
        stated["reduced_why"]) == ["max_position_embeddings"]
    assert published == {"max_position_embeddings": 262144}
    assert model["max_position_embeddings"] == 8192
    # every layer, every row, every width
    assert (model["num_hidden_layers"], model["vocab_size"],
            model["hidden_size"], model["intermediate_size"],
            model["num_attention_heads"], model["num_key_value_heads"],
            model["sliding_window"], model["mb_per_layer"],
            model["mamba_expand"], model["mamba_d_state"],
            model["mamba_d_conv"], model["mamba_dt_rank"]) == (
        32, 200064, 2560, 10240, 40, 20, 512, 2, 2, 16, 4, 160)
    engine = stated["engine"]
    # the full group: 64 lanes of 8,192 tokens; the window group's rings
    # and the state group's slots are the program's own
    assert engine["num_blocks"] == 64 * 512 + 1 and engine["max_active"] == 64
    assert engine["prefix_sharing"] is False and engine["speculation"] is None
    assert ("one v5e chip holds Phi-4-mini-flash-reasoning whole"
            in stated["deployment"])
    for item in ("layer_order", "differential_attention", "layer_norm",
                 "no_position_signal", "gmu_memory", "mamba", "biases",
                 "mamba_d_state", "mamba_dt_rank"):
        assert "as recalled" in stated["assumed"][item]
    for item in ("yoco_prefill", "window_counts_self", "column_layout",
                 "state_float32", "head_dim", "step_draw", "weights"):
        assert stated["assumed"][item]
    assert set(stated["limits"]) == {
        "served_step_share", "served_gap_mean", "undecided_share"}
    # the catalog's entry, key for key, but for the one reduced key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            (entry,) = [row for row in map(json.loads, f)
                        if row["name"] == "Phi-4-mini-flash-reasoning"]
        assert stated["source"] == entry["source_url"]
        differing = {k for k, v in entry["config"].items() if stated[k] != v}
        assert differing == set(stated["reduced"])


def test_the_program_config_is_the_files_and_refuses_what_it_lacks():
    from benchmark.lib.serving_phi4flash import phi4flash_config

    model = config()["model"]
    made = phi4flash_config(model)
    assert (made.n_layers, made.d_inner, made.head_dim, made.d_state,
            made.dt_rank, made.vocab_size, made.max_seq_len, made.window,
            made.kv_pairs, made.norm_eps) == (
        32, 5120, 64, 16, 160, 200064, 8192, 512, 10, 1e-5)
    assert list(made.layer_kinds) == bytes_ops_phi4flash.layer_kinds(model)
    for key, value in (("mb_per_layer", 4), ("tie_word_embeddings", False),
                       ("mlp_bias", True), ("lm_head_bias", True),
                       ("hidden_act", "gelu")):
        with pytest.raises(ValueError, match="does not implement"):
            phi4flash_config({**model, key: value})
    toy = phi4flash_config(config(toy=True)["model"])
    assert toy.layer_kinds == ("mamba", "window") * 2 + (
        "mamba", "full", "gmu", "cross")


def test_the_mix_is_trinitys_own_and_fits_the_configuration():
    """`traffic/reason8k.json` as it stands (`test_trinity_mini.py` has the
    stagger's test): its ids are drawn from this configuration's whole
    vocabulary, its longest lane fills the 512-column table, and the pool
    holds every lane at its longest."""
    from benchmark.lib import traffic

    mix = traffic.load_mix(traffic.mix_path(ROOT, "reason8k"))
    lengths = traffic.Lengths(mix, 3)
    firsts = [lengths.first(i) for i in range(mix["clients"])]
    assert mix["clients"] == 64 == config()["engine"]["max_active"]
    assert firsts[0] == (512, 7680) and {p + o for p, o in firsts} == {8192}
    assert firsts[1] == (632, 7560)
    assert max(p + o for p, o in firsts) <= config()["model"][
        "max_position_embeddings"]
    assert config()["engine"]["num_blocks"] - 1 >= 64 * 8192 // 16
    ids = traffic.prompt_ids(3, 0, 4096, config()["model"]["vocab_size"])
    assert 0 < min(ids) and 190000 < max(ids) < 200064
    assert mix["warm"]["prefill_prompts"] == [512, 1000, 2000, 4000, 8000]
    assert mix["trace_seconds"] == 3 and mix["compare_requests"] == 3


# -- the check -------------------------------------------------------------------------


def test_the_check_reads_its_three_numbers_and_its_control(monkeypatch):
    """`checks/phi4flash_decoder.py` on made gaps and margins: the mean
    over all served tokens, the share of DECIDED positions (the
    reference's best 0.1 or more over its second) whose served token is
    not the reference's best, and the share left undecided; under
    ``control`` the same of the int8 forward's choices, the program's own
    beside them."""
    from benchmark.checks import phi4flash_decoder
    from benchmark.lib import reference_phi4flash

    made = [{"gaps": [0.0, 0.02, 0.0, 0.3], "margins": [0.5, 0.02, 0.09, 0.3],
             "control_gaps": [0.5, 0.02, 0.05, 0.0],
             "reference_first": [1, 2, 3, 4]}]
    monkeypatch.setattr(reference_phi4flash, "served_token_gaps",
                        lambda seed, model, sequences, control=False: made)
    job = {"seed": 1, "model": {}, "sequences": []}
    sound = phi4flash_decoder.numbers(job, False)
    assert sound["served_tokens"] == 4 and sound["undecided_share"] == 0.5
    assert sound["served_gap_mean"] == pytest.approx(0.08)
    assert sound["served_step_share"] == 0.5 and sound["served_gap_max"] == 0.3
    control = phi4flash_decoder.numbers(job, True)
    assert control["served_gap_mean"] == pytest.approx(0.1425)
    assert control["served_step_share"] == 0.5
    assert control["program_gap_mean"] == pytest.approx(0.08)
    assert phi4flash_decoder.DECIDED_MARGIN == 0.1


def test_the_memory_is_of_visible_size_beside_the_gate_at_the_toy_sizes():
    """The seeded weights put a gated memory unit's two factors within an
    order of each other: layer N/2's ungated sums and ``silu(a W_in)``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import reference_phi4flash as ref
    from benchmark.lib import weights_phi4flash as weights

    model = config(toy=True)["model"]
    tokens = np.random.default_rng(0).integers(1, 1024, size=96)
    with jax.default_matmul_precision("highest"):
        top = weights.top(5, model)
        x, carry = ref.embed(tokens, top), ref.start(len(tokens), model)
        for index in range(7):
            w = weights.layer(5, index, model)
            if weights.layer_kind(model, index) == weights.GMU:
                gate = jax.nn.silu(jnp.dot(
                    ref.norm(x, w["ln1_w"], w["ln1_b"], model),
                    w["w_in"].astype(jnp.float32)))
            x, carry = ref.layer(
                x, carry, w, ref.lambda_init(index), model,
                weights.layer_kind(model, index),
                index == weights.memory_layer(model))
    memory, gate = (float(jnp.sqrt(jnp.mean(jnp.square(a))))
                    for a in (carry["memory"], gate))
    assert 0.1 < memory < 10 and 0.1 < gate < 10


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
