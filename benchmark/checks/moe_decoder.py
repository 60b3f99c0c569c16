"""A served sparse-expert decoder against `lib/reference_mimo.py`: the
two numbers of `checks/decoder.py` (the widest and the mean gap by which
a served greedy token's reference logit lies below the reference's
best), over the same sample of the window's requests, the reference
given the same held experts and vocabulary slice as the program, and
read **where the reference's routing stands clear**.

A routed model has one more way to be off the reference's best, and it
is no fault: where the last expert chosen and the first left out lie
closer in ``s + b`` than bf16 carries the router's input, the program
may take the other one, and a whole held expert's output at a weight of
about 1/8 comes or goes with it. Every wide gap of a sound run sits at
such a position (`PERF.md` section 2 has the readings), and the int8
control reads no wider there, so over all positions the widest gap
cannot tell the two. A position is therefore *decided* when, in every
expert layer of the reference, the selection's edge stands clear of a
held expert by :data:`DECIDED_MARGIN` (`reference_mimo.route`); the two
compared numbers are read over the decided positions, and
``undecided_share`` is compared too, so that the check cannot go blind.
The readings over all positions go beside them, uncompared."""

from benchmark.checks.decoder import sample  # noqa: F401 - the harness's hook

#: in ``s + b``. Over seven sound chip runs the tokens that left the
#: reference's best by more than 0.03 numbered 130, 24, 4, 0, 0 at
#: margins of 0-1, 1-2, 2-3, 3-4, 4-5 thousandths (the widest 0.0026), as
#: a Gaussian tail of 0.0010 falls (`PERF.md` section 2): this is five
#: of those, and keeps two fifths of the positions
DECIDED_MARGIN = 0.005


def numbers(job: dict, control: bool) -> dict:
    from benchmark.lib import reference_mimo

    results = reference_mimo.served_token_gaps(
        job["seed"], job["model"], job["sequences"], control=control)
    decided = [m >= DECIDED_MARGIN for r in results for m in r["margins"]]

    def read(key):
        gaps = [g for r in results for g in r[key]]
        clear = [g for g, d in zip(gaps, decided) if d]
        return {"served_tokens": len(gaps),
                "undecided_share": 1 - len(clear) / len(gaps),
                "served_gap_max": max(clear),
                "served_gap_mean": sum(clear) / len(clear),
                "served_off_best": sum(g > 0 for g in clear),
                "all_gap_max": max(gaps),
                "all_gap_mean": sum(gaps) / len(gaps)}

    out = read("gaps")
    if control:
        program = out
        out = read("control_gaps")
        out.update(program_gap_max=program["served_gap_max"],
                   program_gap_mean=program["served_gap_mean"])
    return out
