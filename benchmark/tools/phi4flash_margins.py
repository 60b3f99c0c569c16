#!/usr/bin/env python3
"""Position by position, what `checks/phi4flash_decoder.py` reads of one run:
each served token's gap, the int8 control's gap and the margin by which
the reference's best logit stands over its second there, for choosing
`DECIDED_MARGIN` and the limits (`PERF.md` section 2).

    python3 benchmark/tools/phi4flash_margins.py <check_job.json> <out.json>

``check_job.json`` is what `run.py` leaves in `.bench_work/` (copy it
before the next run overwrites it). One plain and one int8 forward of
the reference, minutes on the chip for three requests of 8,192 tokens,
hours on the CPU. Measures nothing of the program's speed."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(job_path: str, out_path: str) -> int:
    with open(job_path) as f:
        job = json.load(f)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(job["root"], ".jax_cache"))
    from benchmark.lib import reference_phi4flash

    results = reference_phi4flash.served_token_gaps(
        job["seed"], job["model"], job["sequences"], control=True)
    out = {"seed": job["seed"],
           "lengths": [[len(s["prompt"]), len(s["served"])]
                       for s in job["sequences"]]}
    for key in ("gaps", "control_gaps", "margins"):
        out[key] = [value for r in results for value in r[key]]
    with open(out_path, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
