"""Per-layer numbers of `phi4_mini_flash` (``phi4flash``, SambaY): the
model's counts of the rows of the ONE full pool a decode step read
(``engine:shared_kv_rows_read``: the live lanes' contexts x the eight
layers that read it) and of the recurrent states it turned
(``engine:ssm_state_updates``), the engine's count of what a window
layer's lanes see (``engine:attn_tokens_window``), and the device trace's
paged kernel inside the decode program. Counters are window deltas over
the window's steps, times are the traced decode executions'. A program
without the counters, or a trace without the kernel, gives None and the
metric is left out of the line."""

from benchmark.lib import bytes_ops, bytes_ops_phi4flash
from benchmark.readers import counters, trace
from benchmark.readers.moe import DECODE, _kernel_seconds


def _per_step(run, name):
    return counters.delta_ratio(run, f"engine:{name}", "engine:steps")


def _attention_work(run):
    """(the shared pool's bytes, the rings' bytes, FLOPs) a step."""
    shared = _per_step(run, "shared_kv_rows_read")
    window = _per_step(run, "attn_tokens_window")
    if shared is None or window is None:
        return None
    return bytes_ops_phi4flash.decode_attention_work(
        run.config["model"], shared, window)


def shared_kv_roofline_pct(run, op):
    """The bytes the sixteen differential calls of a step have to read
    (every row of the shared pool once a reader, every ring row once; or
    their FLOPs, which at 3 FLOP a byte never are the longer) over the
    paged kernel's time in the traced decode steps, whatever implements
    the pairing of two key heads with one value head."""
    if run.trace is None:
        return None
    count, seconds = _kernel_seconds(run, op)
    work = _attention_work(run)
    if not count or not seconds or work is None:
        return None
    share, _ = bytes_ops.roofline_share(
        count * (work[0] + work[1]), count * work[2], seconds, run.peak)
    return share


def _step_bytes(run):
    """(weights', states', rings', the shared pool's) bytes of the
    window's mean decode step."""
    model = run.config["model"]
    updates = _per_step(run, "ssm_state_updates")
    work = _attention_work(run)
    if updates is None or work is None:
        return None
    return (bytes_ops_phi4flash.decode_step_weight_bytes(model),
            bytes_ops_phi4flash.step_state_bytes(updates, model),
            work[1], work[0])


def sambay_roofline_share_pct(run):
    """The least time the chip needs for a decode step (every weight
    streamed once, the embedding as the tied head; every live lane's
    state and convolution inputs in and out; the rings; the shared pool
    once a reader) over the decode program's device time: the share of
    the whole step, under 100 by construction (everything is bound by
    bytes here, and the parts cannot overlap better than perfectly)."""
    step_ms = trace.module_mean_ms(run, module=DECODE)
    read = _step_bytes(run)
    if step_ms is None or read is None:
        return None
    return 100.0 * (sum(read) / run.peak["hbm_bytes_per_s"]) / (step_ms / 1e3)


def shared_kv_share_of_bytes_pct(run):
    """The shared pool's reads over all bytes a decode step must move: the
    traffic's and the model's, not the program's."""
    read = _step_bytes(run)
    if read is None:
        return None
    return 100.0 * read[3] / sum(read)
