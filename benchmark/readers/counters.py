"""Deltas of the program's own counters over the window.

A key is ``<source>:<path>``: ``engine:`` the LLM engine's `stats()`,
``stats:`` the statistics extension's snapshot of the driven model,
``prom:`` the sum over labels of one `/metrics` family. Snapshots are
taken at the window's edges and carry their own instants.
"""


def _value(snapshot: dict, key: str):
    source, path = key.split(":", 1)
    node = snapshot.get(source)
    if node is None:
        return None
    if source == "prom":
        return node.get(path)
    for part in path.split("."):
        if not isinstance(node, dict) or part not in node:
            return None
        node = node[part]
    return node


def delta(run, key: str):
    before, after = _value(run.before, key), _value(run.after, key)
    if before is None or after is None:
        return None
    return after - before


def delta_ratio(run, numerator, denominator, scale=1.0):
    top, bottom = delta(run, numerator), delta(run, denominator)
    if top is None or not bottom:
        return None
    return scale * top / bottom


def ms_per_delta(run, key):
    """Milliseconds between the two snapshots over the counter's delta
    (the mean gap between two of whatever it counts)."""
    count = delta(run, key)
    if not count:
        return None
    return (run.after["at"] - run.before["at"]) / 1e6 / count


def compiles_in_window(run):
    """Programs that entered compilation (or a cache load) inside the
    window, from the server's own ``Compiling ...`` log lines."""
    return float(sum(run.t0 <= t < run.t1 for t in run.compile_times))
