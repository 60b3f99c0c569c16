"""The comparison that decides `correct`, run as a child of its own once
the window has closed and the server (and its state) has gone.

    python3 -m benchmark.lib.check_child <job.json> <out.json> [--control]

It imports nothing of the program. The job names the configuration's
check (`benchmark/checks/<name>.py`), the seed and what the timed path
produced; the answer is each number that is compared. ``--control`` puts
the lower-precision reference in the program's place.
"""

import importlib
import json
import os
import sys


def main(argv) -> int:
    control = "--control" in argv
    job_path, out_path = [a for a in argv if not a.startswith("--")]
    with open(job_path) as f:
        job = json.load(f)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(job["root"], ".jax_cache"))
    check = importlib.import_module(f"benchmark.checks.{job['check']}")
    with open(out_path, "w") as f:
        json.dump(check.numbers(job, control), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
