"""Per-layer numbers of a sparse-expert decoder with window and full
attention layers (`mimo_v2_flash`): the engine's routing counters
(``engine:moe_*``, summed over expert layers and decode steps) and the
device trace's two kernels inside the decode program. A program without
the counters, or a trace without the kernels, gives None and the metric
is left out of the line."""

import re

from benchmark.lib import bytes_ops, bytes_ops_mimo
from benchmark.readers import counters, trace

DECODE = "llm_decode"


def experts_touched_share_pct(run):
    """Held experts some token of the step chose, over all the held
    experts of all expert layers: what of its experts' weights a decode
    step has to stream. The router's and the traffic's, not the
    program's: uniform routing over 64 lanes reads 87%, a deployment's 32
    tokens an expert 100%, and routing collapsed onto few experts reads
    low and flatters the rate (so ``better`` is ``higher``)."""
    model = run.config["model"]
    touched = counters.delta(run, "engine:moe_experts_touched")
    steps = counters.delta(run, "engine:steps")
    if touched is None or not steps:
        return None
    layers = bytes_ops_mimo.layer_counts(model)[2]
    return 100.0 * touched / (int(model["n_routed_experts"]) * layers * steps)


def load_max_over_mean(run):
    """The fullest held expert's pairs over the mean held expert's."""
    most = counters.delta(run, "engine:moe_load_max")
    pairs = counters.delta(run, "engine:moe_pairs")
    if most is None or not pairs:
        return None
    return most * int(run.config["model"]["n_routed_experts"]) / pairs


def _kernel_seconds(run, op):
    """(decode executions in the trace, seconds they spent in ``op``)."""
    steps = trace._runs(run, module=DECODE, with_op=op)
    seconds = sum(s for _, ops in steps for name, s in ops.items()
                  if re.search(op, name))
    return len(steps), seconds


def experts_roofline_pct(run, op):
    """The touched experts' bytes against HBM bandwidth, or the routed
    pairs' FLOPs against the MXU's peak if that is the longer, over the
    expert kernel's time in the traced decode steps. Touched experts and
    pairs a step are the window's means."""
    if run.trace is None:
        return None
    model = run.config["model"]
    count, seconds = _kernel_seconds(run, op)
    touched = counters.delta_ratio(run, "engine:moe_experts_touched",
                                   "engine:steps")
    pairs = counters.delta_ratio(run, "engine:moe_pairs", "engine:steps")
    if not count or not seconds or touched is None or pairs is None:
        return None
    share, _ = bytes_ops.roofline_share(
        count * touched * bytes_ops_mimo.expert_bytes(model),
        count * pairs * bytes_ops_mimo.pair_flops(model), seconds, run.peak)
    return share


def weight_stream_share_pct(run):
    """The least time a decode step's weights take from HBM (all but the
    experts, and the experts the window's mean step touched) over the
    decode program's device time: what of a step is weight streaming at
    best. The model's own count, as `trace.weight_stream_share_pct` has
    the dense decoder's."""
    step_ms = trace.module_mean_ms(run, module=DECODE)
    touched = counters.delta_ratio(run, "engine:moe_experts_touched",
                                   "engine:steps")
    if step_ms is None or touched is None:
        return None
    least_s = (bytes_ops_mimo.decode_step_weight_bytes(
        run.config["model"], touched) / run.peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (step_ms / 1e3)


def step_contexts(run):
    """The contexts of one mean decode step of the window: every decoded
    token's context (its prompt plus the tokens before it), as many of
    them a step as the window's tokens over its steps, spread evenly."""
    contexts = [len(r.get("prompt", ())) + j
                for r in run.requests for j, t in enumerate(r["times"])
                if j and run.t0 <= t < run.t1]
    steps = counters.delta(run, "engine:steps")
    if not contexts or not steps:
        return None
    return contexts, steps


def mixed_attention_roofline_pct(run, op):
    """K/V bytes the decode steps' attention has to read (full layers the
    whole context, window layers its last ``sliding_window`` tokens) over
    the attention kernel's time, against HBM bandwidth."""
    if run.trace is None:
        return None
    count, seconds = _kernel_seconds(run, op)
    read = step_contexts(run)
    if not count or not seconds or read is None:
        return None
    contexts, steps = read
    moved = (count / steps) * bytes_ops_mimo.decode_attention_bytes(
        run.config["model"], contexts)
    share, _ = bytes_ops.roofline_share(moved, 0.0, seconds, run.peak)
    return share
