"""Deltas of the LLM engine's lap spans over the window.

The engine tiles its step loop by named phase and exports the laps as
``stats()["phase_ns"]`` (``engine:phase_ns.<phase>`` in the snapshots).
A program without them (the parent of the PR that added them) gives
None, and the metric is left out of the line.
"""

from benchmark.readers import counters


def _phases_delta_ns(run, phases=None):
    """Nanoseconds the window spent in ``phases`` (all of them if None)."""
    if phases is None:
        phases = ((run.after or {}).get("engine") or {}).get("phase_ns")
        if phases is None:
            return None
    deltas = [counters.delta(run, f"engine:phase_ns.{name}") for name in phases]
    return None if None in deltas else sum(deltas)


def ms_per_step(run, phases):
    """Mean milliseconds a decode step spends in the phases named."""
    spent, steps = _phases_delta_ns(run, phases), counters.delta(run, "engine:steps")
    if spent is None or not steps:
        return None
    return spent / 1e6 / steps


def coverage_pct(run):
    """The phases' sum over the wall time between the two snapshots: the
    tiling's own check (100 when the loop never parked and no time is
    outside a phase)."""
    spent = _phases_delta_ns(run)
    if spent is None:
        return None
    return 100.0 * spent / (run.after["at"] - run.before["at"])
