"""The LLM engine's record of its turns, read without the stalls.

The engine books every turn of its step loop either to the steady turns
or, at 250 ms or more, to the stalls, each stall under a cause
(``stats()``: ``steady_phase_ns``, ``steady_steps``, ``stall_ns.<cause>``,
``loop_ns``; beside them the process's sums: ``gc_ns`` by generation,
``compile.*``). A mean over steady turns is the loop's pace without the
seconds the profiler's stop holds it in a traced run. A program without
the record (the parent of the PR that added it) gives None, and the
metric is left out of the line.

Here is only what ``counters`` cannot say: a mean over several phases, a
share over several causes, a sum over a list, a counter as set-up left
it, and a plain delta in another unit (``counters.delta`` has no
scale). One phase over ``steady_steps`` and one cause over ``loop_ns``
are ``counters:delta_ratio`` in their metric files.
"""

from benchmark.readers import counters


def _sum_delta(run, keys):
    deltas = [counters.delta(run, key) for key in keys]
    return None if not deltas or None in deltas else sum(deltas)


def ms_per_step(run, phases=None):
    """Mean milliseconds a decode step of a steady turn spends in the
    phases named (all of them if None)."""
    if phases is None:
        phases = (run.after.get("engine") or {}).get("steady_phase_ns", ())
    spent = _sum_delta(run, [f"engine:steady_phase_ns.{p}" for p in phases])
    steps = counters.delta(run, "engine:steady_steps")
    if spent is None or not steps:
        return None
    return spent / 1e6 / steps


def stall_share_pct(run, causes):
    """What of the loop's time between the snapshots went to stalls of
    the causes named."""
    stalled = _sum_delta(run, [f"engine:stall_ns.{c}" for c in causes])
    loop = counters.delta(run, "engine:loop_ns")
    if stalled is None or not loop:
        return None
    return 100.0 * stalled / loop


def gc_ms_per_step(run):
    """Milliseconds of the collector (all generations, the whole
    process) a decode step."""
    before = counters._value(run.before, "engine:gc_ns")
    after = counters._value(run.after, "engine:gc_ns")
    steps = counters.delta(run, "engine:steps")
    if before is None or after is None or not steps:
        return None
    return (sum(after) - sum(before)) / 1e6 / steps


def delta_scaled(run, key, scale=1.0):
    """The counter's growth between the snapshots, times ``scale``."""
    grown = counters.delta(run, key)
    return None if grown is None else scale * grown


def at_before(run, key, scale=1.0):
    """The counter as the ``before`` snapshot has it (what set-up booked),
    times ``scale``."""
    value = counters._value(run.before, key)
    return None if value is None else scale * value
