"""Per-layer numbers of `gigachat3_702b` (``deepseek_v3``): the engine's
count of what a decode step's attention reads (``engine:attn_tokens_full``,
the lanes' contexts summed), its routing counters (``engine:moe_*``, with
the model's own ``moe_lanes_here``), and the device trace's two kernels
inside the decode program. Counters are window deltas over the window's
steps, times are the traced decode executions'. A program without the
counters, or a trace without the kernels, gives None and the metric is
left out of the line.

Attention is weighed by the LONGER of its bytes and its FLOPs
(`lib/bytes_ops_dsv3.py`): a latent row is read once for 64 heads, so
the kernel sits at 121 FLOP a byte, half the chip's ridge, and a fetch
that got faster would leave the MXU's time standing."""

from benchmark.lib import bytes_ops, bytes_ops_dsv3
from benchmark.readers import counters, trace
# the fullest held expert's pairs over the mean held expert's: the count
# and the configuration's key (``n_routed_experts``) are `mimo_v2_flash`'s
from benchmark.readers.moe import (  # noqa: F401 - a metric's reader
    DECODE, _kernel_seconds, load_max_over_mean,
)


def _per_step(run, name):
    return counters.delta_ratio(run, f"engine:{name}", "engine:steps")


def _attention_work(run):
    """(bytes, FLOPs) of the window's mean step's attention over the cache."""
    full = _per_step(run, "attn_tokens_full")
    if full is None:
        return None
    return bytes_ops_dsv3.decode_attention_work(run.config["model"], full)


def latent_roofline_pct(run, op):
    """The longer of the cached rows' bytes against HBM bandwidth and the
    heads' FLOPs over them against the MXU's peak, over the attention
    kernel's time in the traced decode steps."""
    if run.trace is None:
        return None
    count, seconds = _kernel_seconds(run, op)
    work = _attention_work(run)
    if not count or not seconds or work is None:
        return None
    share, _ = bytes_ops.roofline_share(
        count * work[0], count * work[1], seconds, run.peak)
    return share


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
        count * touched * bytes_ops_dsv3.expert_bytes(model),
        count * pairs * bytes_ops_dsv3.pair_flops(model), seconds, run.peak)
    return share


def _step_bytes(run):
    """(weights' bytes, attention's (bytes, FLOPs)) of the window's mean
    decode step, or None."""
    touched = _per_step(run, "moe_experts_touched")
    work = _attention_work(run)
    if touched is None or work is None:
        return None
    return (bytes_ops_dsv3.decode_step_weight_bytes(
        run.config["model"], touched), work)


def roofline_share_pct(run):
    """The least time the chip needs for a decode step (every weight but
    the embedding streamed once, with the experts the window's mean step
    touched; the attention's longer of bytes and FLOPs) over the decode
    program's device time: the share of the whole step, under 100 by
    construction (the parts cannot overlap better than perfectly, and
    the dense FLOPs ride under the weights' bytes at 128 lanes)."""
    step_ms = trace.module_mean_ms(run, module=DECODE)
    read = _step_bytes(run)
    if step_ms is None or read is None:
        return None
    weights, (moved, flops) = read
    least_s = (weights / run.peak["hbm_bytes_per_s"]
               + max(moved / run.peak["hbm_bytes_per_s"],
                     flops / run.peak["bf16_flops_per_s"]))
    return 100.0 * least_s / (step_ms / 1e3)


def latent_share_of_bytes_pct(run):
    """The cache's bytes over all bytes a decode step must move: the
    traffic's and the model's, not the program's."""
    read = _step_bytes(run)
    if read is None:
        return None
    weights, (moved, _) = read
    return 100.0 * moved / (weights + moved)


def experts_touched_share_pct(run):
    """Held experts some lane of the step chose, over all the held
    experts of all expert layers (`readers/moe.py` has what it means)."""
    model = run.config["model"]
    touched = _per_step(run, "moe_experts_touched")
    if touched is None:
        return None
    held = int(model["n_routed_experts"]) * bytes_ops_dsv3.expert_layers(model)
    return 100.0 * touched / held


def lanes_here_share_pct(run):
    """(lane, expert layer) pairs of the decode steps with a routed pair
    on a held expert, over lanes x expert layers x steps: what
    group-limited routing leaves one chip of a group's half (a lane
    either kept group 0, 4 of 8, or has nothing here). Lanes are the
    window's mean step's batch."""
    here = counters.delta(run, "engine:moe_lanes_here")
    lane_steps = counters.delta(run, "engine:lane_steps")
    if here is None or not lane_steps:
        return None
    layers = bytes_ops_dsv3.expert_layers(run.config["model"])
    return 100.0 * here / (lane_steps * layers)
