"""Reduce a profiler trace in a child that never touches the chip.

    python3 -m benchmark.lib.trace_child <trace dir> <out.json>
"""

import glob
import json
import os
import sys

from benchmark.lib import trace_reduce


def main(argv) -> int:
    trace_dir, out_path = argv
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        print(f"trace_child: no .xplane.pb under {trace_dir}", file=sys.stderr)
        return 1
    events = trace_reduce.load_xplane(found[-1])
    summary = trace_reduce.reduce(events)
    with open(out_path, "w") as f:
        json.dump(summary, f)
    # a slice of the raw events beside it, small enough to keep as a
    # recorded trace for the reducer's test
    with open(out_path + ".sample", "w") as f:
        json.dump(trace_reduce.sample(events), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
