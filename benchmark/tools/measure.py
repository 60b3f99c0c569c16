#!/usr/bin/env python3
"""Several runs of one cell in one call, as the bound is set from.

    chiprun -- python3 benchmark/tools/measure.py --workload mistral7b.batch \
        --seeds 11,12,13,14,15,16 --sets 2 --seconds 30 --label batch_sets

Runs `benchmark/run.py` once a seed and set, one after another (one
process may hold the chip), appends each result line to
``chiprun_out/<label>.jsonl`` and prints, for every metric, each set's
median and its spread: the distance between the first and third quartile
(`statistics.quantiles(values, n=4)`) as a share of the median. A bound
is about five times the widest spread over the cells, never under 1%.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--sets", type=int, default=1)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, default=0)
    parser.add_argument("--label", required=True)
    parser.add_argument("--control", action="store_true",
                        help="passed on to run.py: every run has to come "
                        "out `correct: false`")
    args = parser.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    sets = []
    with open(os.path.join(out_dir, f"{args.label}.jsonl"), "a") as log:
        for set_index in range(args.sets):
            lines = []
            for seed in seeds:
                argv = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
                        "--workload", args.workload, "--seed", str(seed),
                        "--seconds", str(args.seconds), "--trace", str(args.trace)]
                if args.control:
                    argv.append("--control")
                began = time.monotonic()
                done = subprocess.run(argv, cwd=ROOT, capture_output=True,
                                      text=True)
                took = time.monotonic() - began
                last = (done.stdout.strip().splitlines() or [""])[-1]
                try:
                    line = json.loads(last)
                except ValueError:
                    line = None
                record = {"set": set_index, "seed": seed, "rc": done.returncode,
                          "wall_s": took, "line": line}
                if (line is None or done.returncode != 0
                        or line["correct"] == args.control):
                    record["stderr"] = done.stderr[-6000:]
                    print(f"run seed {seed} rc {done.returncode}:\n"
                          f"{done.stderr[-3000:]}", flush=True)
                log.write(json.dumps(record) + "\n")
                log.flush()
                if line is not None:
                    lines.append(line)
                    flat = {k: round(v["value"], 4)
                            for k, v in line["metrics"].items()}
                    print(f"set {set_index} seed {seed} wall {took:.0f}s "
                          f"correct {line['correct']} {flat} "
                          f"compared {line['compared']} "
                          f"reference {line['diagnostics']['reference']}",
                          flush=True)
            sets.append(lines)
    for set_index, lines in enumerate(sets):
        names = sorted({k for line in lines for k in line["metrics"]})
        for name in names:
            values = [line["metrics"][name]["value"] for line in lines
                      if name in line["metrics"]]
            if len(values) >= 2:
                print(f"set {set_index} {name}: n {len(values)} median "
                      f"{statistics.median(values):.6g} spread "
                      f"{100 * spread(values):.3f}% min {min(values):.6g} "
                      f"max {max(values):.6g}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
