"""Per-layer numbers from the reduced device trace (`--trace 1` only)."""

import re

from benchmark.lib import bytes_ops


def _runs(run, module=None, with_op=None, without_op=None):
    out = []
    for family, seconds, ops in run.trace["module_runs"]:
        if module and not re.search(module, family):
            continue
        has = lambda pattern: any(re.search(pattern, op) for op in ops)
        if with_op and not has(with_op):
            continue
        if without_op and has(without_op):
            continue
        out.append((seconds, ops))
    return out


def mean_step_contexts(run):
    """[mean summed context of one decode step in the window]: each
    decoded token's context is its prompt plus the tokens before it."""
    total = 0
    for r in run.requests:
        n_prompt = len(r.get("prompt", ()))
        for j, t in enumerate(r["times"]):
            if j and run.t0 <= t < run.t1:
                total += n_prompt + j
    if not (run.before and run.before.get("engine")):
        return None
    steps = run.after["engine"]["steps"] - run.before["engine"]["steps"]
    return [total / steps] if steps else None


def module_mean_ms(run, module=None, with_op=None, without_op=None,
                   min_ms=0.0):
    """Mean device time of one execution of the programs selected."""
    if run.trace is None:
        return None
    runs = [s for s, _ in _runs(run, module, with_op, without_op)
            if s * 1e3 >= min_ms]
    return 1e3 * sum(runs) / len(runs) if runs else None


def idle_share_pct(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def weight_stream_share_pct(run, with_op):
    """The least time the step's weights take from HBM, over the decode
    program's device time: what of a step is weight streaming at best."""
    step_ms = module_mean_ms(run, with_op=with_op)
    if step_ms is None:
        return None
    least_s = (bytes_ops.decoder_step_weight_bytes(run.config["model"])
               / run.peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (step_ms / 1e3)


def paged_attention_roofline_pct(run, op):
    """K/V bytes of the live contexts over the kernel's device time,
    against HBM bandwidth (the kernel is bound by bytes at decode)."""
    if run.trace is None:
        return None
    steps = _runs(run, with_op=op)
    kernel_s = sum(s for _, ops in steps for name, s in ops.items()
                   if re.search(op, name))
    contexts = mean_step_contexts(run)
    if not steps or not kernel_s or not contexts:
        return None
    layers = int(run.config["model"]["num_hidden_layers"])
    moved = (len(steps) * layers
             * bytes_ops.paged_attention_bytes(run.config["model"], contexts))
    share, _ = bytes_ops.roofline_share(moved, 0.0, kernel_s, run.peak)
    return share
