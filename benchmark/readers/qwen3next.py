"""Per-layer numbers of `qwen3_next_80b` (``qwen3_next``): the model's
count of the recurrent states a decode step turned
(``engine:gdn_state_updates``, (lane, DeltaNet layer) pairs), the
engine's count of what its attention reads (``engine:attn_tokens_full``,
the lanes' contexts summed), its routing counters (``engine:moe_*``),
and the device trace's three kernels inside the decode program. Counters
are window deltas over the window's steps, times are the traced decode
executions'. A program without the counters, or a trace without the
kernels, gives None and the metric is left out of the line."""

from benchmark.lib import bytes_ops, bytes_ops_qwen3next
from benchmark.readers import counters, trace
# the fullest held expert's pairs over the mean held expert's: the count
# and the configuration's key (``num_experts``) are `trinity_mini`'s
from benchmark.readers.afmoe import (  # noqa: F401 - a metric's reader
    load_max_over_mean,
)
from benchmark.readers.moe import DECODE, _kernel_seconds


def _per_step(run, name):
    return counters.delta_ratio(run, f"engine:{name}", "engine:steps")


def gdn_step_roofline_pct(run, op):
    """The live lanes' states, in and out once, against HBM bandwidth,
    over the state-update kernel's time in the traced decode steps. The
    rule's arithmetic is the VPU's (a head's [128, 128] state is
    multiplied and summed three times over, no matmul), so no FLOP peak
    is held against it: a share well under 100 with the DMAs hidden
    means the vector unit binds."""
    if run.trace is None:
        return None
    count, seconds = _kernel_seconds(run, op)
    updates = _per_step(run, "gdn_state_updates")
    if not count or not seconds or updates is None:
        return None
    share, _ = bytes_ops.roofline_share(
        count * bytes_ops_qwen3next.kernel_state_bytes(
            updates, run.config["model"]), 0.0, seconds, run.peak)
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
        count * touched * bytes_ops_qwen3next.expert_bytes(model),
        count * pairs * bytes_ops_qwen3next.pair_flops(model), seconds,
        run.peak)
    return share


def _attention_work(run):
    full = _per_step(run, "attn_tokens_full")
    if full is None:
        return None
    return bytes_ops_qwen3next.decode_attention_work(run.config["model"], full)


def gated_full_roofline_pct(run, op):
    """The cached K/V bytes the full layers' attention has to read (or
    the heads' FLOPs over them, which at 8 FLOP a byte never is the
    longer) over the paged kernel's time in the traced decode steps."""
    if run.trace is None:
        return None
    count, seconds = _kernel_seconds(run, op)
    work = _attention_work(run)
    if not count or not seconds or work is None:
        return None
    share, _ = bytes_ops.roofline_share(
        count * work[0], count * work[1], seconds, run.peak)
    return share


def _step_bytes(run):
    """(weights', states', K/V) bytes of the window's mean decode step."""
    model = run.config["model"]
    touched = _per_step(run, "moe_experts_touched")
    updates = _per_step(run, "gdn_state_updates")
    work = _attention_work(run)
    if touched is None or updates is None or work is None:
        return None
    return (bytes_ops_qwen3next.decode_step_weight_bytes(model, touched),
            bytes_ops_qwen3next.step_state_bytes(updates, model), work[0])


def state_roofline_share_pct(run):
    """The least time the chip needs for a decode step (every weight but
    the embedding streamed once, with the experts the window's mean step
    touched; every live lane's state and convolution inputs in and out;
    the cached K/V read once) over the decode program's device time: the
    share of the whole step, under 100 by construction (everything is
    bound by bytes here, and the parts cannot overlap better than
    perfectly)."""
    step_ms = trace.module_mean_ms(run, module=DECODE)
    read = _step_bytes(run)
    if step_ms is None or read is None:
        return None
    return 100.0 * (sum(read) / run.peak["hbm_bytes_per_s"]) / (step_ms / 1e3)


def state_share_of_bytes_pct(run):
    """The recurrent states' bytes over all bytes a decode step must
    move: the traffic's and the model's, not the program's."""
    read = _step_bytes(run)
    if read is None:
        return None
    return 100.0 * read[1] / sum(read)


def experts_touched_share_pct(run):
    """Held experts some lane of the step chose, over all the held
    experts of all layers (`readers/moe.py` has what it means)."""
    model = run.config["model"]
    touched = _per_step(run, "moe_experts_touched")
    if touched is None:
        return None
    held = int(model["num_experts"]) * int(model["num_hidden_layers"])
    return 100.0 * touched / held

