"""A served ``deepseek_v3`` decoder (GigaChat3.1-702B-A36B) against
`lib/reference_dsv3.py`: the three numbers of `checks/afmoe_decoder.py`
(``served_gap_mean``, the mean gap by which a served greedy token's
reference logit lies below the reference's best; ``served_step_share``,
the share of tokens more than :data:`STEP` under it; both over the
positions where the reference's routing stands clear; and
``undecided_share``, the share of positions where it does not), over the
same kind of sample of the window's requests, the reference given the
same held experts, shared expert and vocabulary slice as the program.
The program's decode attends in the absorbed form over the latent
cache, the reference in the plain form over keys and values expanded
from nothing cached: their agreement here is the absorption's test at
the published widths.

A position is *decided* when, in every expert layer of the reference,
BOTH of the router's selections stand clear by :data:`DECIDED_MARGIN`
where they concern this chip (`reference_dsv3.route`): the top-8's edge
of every held expert, and the group selection's edge (a swap of groups
changes whom the held experts compete with; where no group with a held
expert is kept, what the best of them lacks). Under the margin bf16's
rounding of the router's input may choose otherwise, and a whole held
expert's output at a weight of ``2.5 / 8`` beside a shared expert of
weight 1 comes or goes with the choice. The widest gap is reported and
held to no limit, as for `trinity_mini`: one flip in a run sets it.
`PERF.md` section 2 has this model's readings, sound runs beside int8
control runs."""

from benchmark.checks.decoder import sample  # noqa: F401 - the harness's hook

#: in ``c = s + b`` for the experts and in ``g`` (a sum of two ``c``) for
#: the groups: `trinity_mini`'s, whose flips' tail it was measured on;
#: `PERF.md` section 2 has what it leaves decided here
DECIDED_MARGIN = 0.005

#: a gap that only a routing flip or a fault opens (`checks/afmoe_decoder.py`)
STEP = 0.1


def numbers(job: dict, control: bool) -> dict:
    from benchmark.lib import reference_dsv3

    results = reference_dsv3.served_token_gaps(
        job["seed"], job["model"], job["sequences"], control=control)
    decided = [m >= DECIDED_MARGIN for r in results for m in r["margins"]]

    def read(key):
        gaps = [g for r in results for g in r[key]]
        clear = [g for g, d in zip(gaps, decided) if d]
        return {"served_tokens": len(gaps),
                "undecided_share": 1 - len(clear) / len(gaps),
                "served_gap_max": max(clear),
                "served_gap_mean": sum(clear) / len(clear),
                "served_step_share": sum(g > STEP for g in clear) / len(clear),
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
