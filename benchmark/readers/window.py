"""End-to-end numbers, counted by window from the load generator's own
response timestamps (`benchmark/lib/window.py`)."""

from benchmark.lib import window


def responses_per_s(run):
    return window.rate_per_s(run.requests, run.t0, run.t1)


def gap_percentile_ms(run, q):
    gaps = window.gaps_ms(run.requests, run.t0, run.t1)
    return window.percentile(gaps, q) if gaps else None


def setup_seconds(run):
    return run.setup_s
