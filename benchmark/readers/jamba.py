"""Per-layer numbers of `jamba2_3b` (``jamba``): the model's count of the
recurrent states a decode step turned (``engine:ssm_state_updates``,
(lane, Mamba layer) pairs), the engine's count of what its attention
reads (``engine:attn_tokens_full``, the lanes' contexts summed), and the
device trace's two kernels inside the decode program. Counters are
window deltas over the window's steps, times are the traced decode
executions'. A program without the counters, or a trace without the
kernels, gives None and the metric is left out of the line."""

from benchmark.lib import bytes_ops, bytes_ops_jamba
from benchmark.readers import counters, trace
from benchmark.readers.moe import DECODE, _kernel_seconds


def _per_step(run, name):
    return counters.delta_ratio(run, f"engine:{name}", "engine:steps")


def ssm_step_roofline_pct(run, op):
    """The live lanes' states, in and out once, against HBM bandwidth,
    over the scan kernel's time in the traced decode steps. The rule's
    arithmetic is the VPU's and the EUP's (a lane's [16, 5120] state is
    multiplied, added to and summed, one exponential an element, no
    matmul), so no FLOP peak is held against it: a share well under 100
    is what a stop costs beyond its bytes, or the vector unit."""
    if run.trace is None:
        return None
    count, seconds = _kernel_seconds(run, op)
    updates = _per_step(run, "ssm_state_updates")
    if not count or not seconds or updates is None:
        return None
    share, _ = bytes_ops.roofline_share(
        count * bytes_ops_jamba.kernel_state_bytes(
            updates, run.config["model"]), 0.0, seconds, run.peak)
    return share


def _attention_work(run):
    full = _per_step(run, "attn_tokens_full")
    if full is None:
        return None
    return bytes_ops_jamba.decode_attention_work(run.config["model"], full)


def mqa_roofline_pct(run, op):
    """The cached K/V bytes the attention layers have to read (or the 20
    heads' FLOPs over them, which at 20 FLOP a byte never is the longer)
    over the paged kernel's time in the traced decode steps."""
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
    updates = _per_step(run, "ssm_state_updates")
    work = _attention_work(run)
    if updates is None or work is None:
        return None
    return (bytes_ops_jamba.decode_step_weight_bytes(model),
            bytes_ops_jamba.step_state_bytes(updates, model), work[0])


def ssm_roofline_share_pct(run):
    """The least time the chip needs for a decode step (every weight
    streamed once, the embedding as the tied head; every live lane's
    state and convolution inputs in and out; the cached K/V read once)
    over the decode program's device time: the share of the whole step,
    under 100 by construction (everything is bound by bytes here, and the
    parts cannot overlap better than perfectly)."""
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
