"""Window-exact counting on one monotonic clock.

The window is ``[t0, t0 + seconds)``. A rate is every response that
arrived inside it over exactly ``seconds``, whether or not its request
began or ended inside. A tail is over every gap that ENDED inside it.
Nothing here looks at whether a request completed.

A request record is ``{"due": ns, "times": [ns, ...], "error": str|None,
...}``: ``due`` the instant it was due to be sent, ``times`` the arrival
instant of each of its responses.
"""


def in_window(t, t0, t1) -> bool:
    return t0 <= t < t1


def responses_in_window(requests, t0, t1) -> int:
    return sum(in_window(t, t0, t1) for r in requests for t in r["times"])


def rate_per_s(requests, t0, t1) -> float:
    return responses_in_window(requests, t0, t1) / ((t1 - t0) / 1e9)


def gaps_ms(requests, t0, t1) -> list:
    """Every gap between consecutive responses of one request whose
    later response arrived inside the window."""
    out = []
    for r in requests:
        times = r["times"]
        for before, after in zip(times, times[1:]):
            if in_window(after, t0, t1):
                out.append((after - before) / 1e6)
    return out


def attempted_failed(requests, t0, t1) -> tuple:
    """Requests due inside the window, and those of them that failed."""
    due = [r for r in requests if in_window(r["due"], t0, t1)]
    return len(due), sum(r.get("error") is not None for r in due)


def percentile(values, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        raise ValueError("no samples inside the window")
    ordered = sorted(values)
    rank = (len(ordered) - 1) * q / 100.0
    low = int(rank)
    high = min(low + 1, len(ordered) - 1)
    return ordered[low] + (ordered[high] - ordered[low]) * (rank - low)
