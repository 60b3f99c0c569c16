"""Per-layer numbers of `trinity_mini` (``afmoe``): the engine's count of
what a decode step's attention reads (``engine:attn_tokens_full`` the
lanes' contexts summed, ``engine:attn_tokens_window`` what of each the
window reaches), its routing counters (``engine:moe_*``), and the device
trace's two kernels inside the decode program. Counters are window deltas
over the window's steps, times are the traced decode executions'. A
program without the counters, or a trace without the kernels, gives None
and the metric is left out of the line.

``engine:attn_tokens_window`` is one sum over all window groups and is
booked by plain decode steps only: the attention readers here are right
for a model with one window group served with ``speculation=None`` (the
engine refuses window groups with speculation), which `trinity_mini`
is."""

from benchmark.lib import bytes_ops, bytes_ops_afmoe
from benchmark.readers import counters, trace
from benchmark.readers.moe import DECODE, _kernel_seconds


def _per_step(run, name):
    return counters.delta_ratio(run, f"engine:{name}", "engine:steps")


def _attention_bytes(run):
    """(full layers', window layers') K/V bytes of the window's mean step."""
    full = _per_step(run, "attn_tokens_full")
    window = _per_step(run, "attn_tokens_window")
    if full is None or window is None:
        return None
    return bytes_ops_afmoe.decode_attention_bytes(
        run.config["model"], full, window)


def window_full_roofline_pct(run, op):
    """K/V bytes the decode steps' attention has to read (a full layer
    each lane's whole context, a window layer what the window reaches of
    it) over the attention kernel's time, against HBM bandwidth."""
    if run.trace is None:
        return None
    count, seconds = _kernel_seconds(run, op)
    moved = _attention_bytes(run)
    if not count or not seconds or moved is None:
        return None
    share, _ = bytes_ops.roofline_share(
        count * sum(moved), 0.0, seconds, run.peak)
    return share


def window_share_of_kv_pct(run):
    """Window layers' bytes over all K/V bytes a step reads: the
    traffic's and the model's, not the program's."""
    moved = _attention_bytes(run)
    if moved is None or not sum(moved):
        return None
    return 100.0 * moved[1] / sum(moved)


def experts_touched_share_pct(run):
    """Held experts some lane of the step chose, over all the held
    experts of all expert layers (`readers/moe.py` has what it means)."""
    model = run.config["model"]
    touched = _per_step(run, "moe_experts_touched")
    if touched is None:
        return None
    held = int(model["num_experts"]) * bytes_ops_afmoe.layer_counts(model)[2]
    return 100.0 * touched / held


def load_max_over_mean(run):
    """The fullest held expert's pairs over the mean held expert's
    (`readers/moe.py`'s count under this configuration's key)."""
    most = counters.delta(run, "engine:moe_load_max")
    pairs = counters.delta(run, "engine:moe_pairs")
    if most is None or not pairs:
        return None
    return most * int(run.config["model"]["num_experts"]) / pairs


def experts_roofline_pct(run, op):
    """The touched routed experts' bytes against HBM bandwidth, or the
    routed pairs' FLOPs against the MXU's peak if that is the longer,
    over the expert kernel's time in the traced decode steps (the shared
    expert is plain XLA, outside the kernel, and not counted here)."""
    if run.trace is None:
        return None
    model = run.config["model"]
    count, seconds = _kernel_seconds(run, op)
    touched = _per_step(run, "moe_experts_touched")
    pairs = _per_step(run, "moe_pairs")
    if not count or not seconds or touched is None or pairs is None:
        return None
    share, _ = bytes_ops.roofline_share(
        count * touched * bytes_ops_afmoe.expert_bytes(model),
        count * pairs * bytes_ops_afmoe.pair_flops(model), seconds, run.peak)
    return share


def hbm_roofline_share_pct(run):
    """The least time HBM needs for every byte a decode step must move
    (all weights but the embedding, with the experts the window's mean
    step touched, and the K/V its attention reads) over the decode
    program's device time: the share of the whole step, under 100 by
    construction."""
    step_ms = trace.module_mean_ms(run, module=DECODE)
    touched = _per_step(run, "moe_experts_touched")
    moved = _attention_bytes(run)
    if step_ms is None or touched is None or moved is None:
        return None
    least_s = ((bytes_ops_afmoe.decode_step_weight_bytes(
        run.config["model"], touched) + sum(moved))
        / run.peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (step_ms / 1e3)
