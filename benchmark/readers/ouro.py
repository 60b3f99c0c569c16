"""Per-layer numbers of `ouro_2_6b` (``ouro``, a looped decoder): the
model's counts of the layer bodies a decode step ran
(``engine:loop_layer_passes``) and of the (token, pair) rows of K and V
its attention read (``engine:loop_kv_rows_read``: the live lanes'
contexts x the 192 pairs), and the device trace's decode program with
the paged kernel inside it. Counters are window deltas over the window's
steps, times are the traced decode executions'. A program without the
counters, or a trace without the kernel, gives None and the metric is
left out of the line."""

from benchmark.lib import bytes_ops, bytes_ops_ouro
from benchmark.readers import counters, trace
from benchmark.readers.moe import DECODE, _kernel_seconds


def _step_bytes(run):
    """(layer weights', the head's, K/V rows') bytes of the window's mean
    decode step."""
    passes = counters.delta_ratio(
        run, "engine:loop_layer_passes", "engine:steps")
    rows = counters.delta_ratio(
        run, "engine:loop_kv_rows_read", "engine:steps")
    if passes is None or rows is None:
        return None
    return bytes_ops_ouro.step_bytes(run.config["model"], passes, rows)


def mha_roofline_pct(run, op):
    """The bytes the step's paged calls have to read (every live row of
    every pair once; or their FLOPs, which at 1 FLOP a byte never are the
    longer) over the paged kernel's time in the traced decode steps,
    whatever implements the loop: the kernel's share of its roofline at
    KV 16, one query row a KV head, tiles of 4 pages."""
    if run.trace is None:
        return None
    count, seconds = _kernel_seconds(run, op)
    read = _step_bytes(run)
    if not count or not seconds or read is None:
        return None
    model = run.config["model"]
    flops = (read[2] / bytes_ops_ouro.kv_row_bytes(model)
             * bytes_ops_ouro.kv_row_flops(model))
    share, _ = bytes_ops.roofline_share(
        count * read[2], count * flops, seconds, run.peak)
    return share


def loop_roofline_share_pct(run):
    """The least time the chip needs for a decode step (a layer's weights
    streamed once a layer body run, the head once, every live K/V row
    once) over the decode program's device time: the share of the whole
    step, under 100 by construction (everything is bound by bytes here,
    and the parts cannot overlap better than perfectly)."""
    step_ms = trace.module_mean_ms(run, module=DECODE)
    read = _step_bytes(run)
    if step_ms is None or read is None:
        return None
    return 100.0 * (sum(read) / run.peak["hbm_bytes_per_s"]) / (step_ms / 1e3)


def kv_share_of_bytes_pct(run):
    """K/V bytes read over all bytes a decode step must move: the
    traffic's and the model's, not the program's."""
    read = _step_bytes(run)
    if read is None:
        return None
    return 100.0 * read[2] / sum(read)


def weight_stream_share_pct(run, op):
    """The least time the passes' layer weights and the head take from
    HBM over the decode program's device time LESS the paged kernel's:
    how near the rolled loop's matmuls stream to an unrolled dense
    decoder's (`trace.weight_stream_share_pct`, over its whole step)."""
    if run.trace is None:
        return None
    steps = trace._runs(run, module=DECODE, with_op=op)
    _, kernel_s = _kernel_seconds(run, op)
    read = _step_bytes(run)
    rest_s = sum(seconds for seconds, _ in steps) - kernel_s
    if not steps or rest_s <= 0 or read is None:
        return None
    least_s = len(steps) * (read[0] + read[1]) / run.peak["hbm_bytes_per_s"]
    return 100.0 * least_s / rest_s
