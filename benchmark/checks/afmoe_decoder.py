"""A served ``afmoe`` decoder (Trinity-Mini) against
`lib/reference_afmoe.py`: the numbers of `checks/moe_decoder.py` (the
widest and the mean gap by which a served greedy token's reference logit
lies below the reference's best, over the positions where the
reference's routing stands clear, and the share of positions where it
does not), over the same kind of sample of the window's requests, the
reference given the same held experts, shared expert and vocabulary
slice as the program; and, in the widest gap's place among the limits,
``served_step_share``: the share of decided tokens that lie more than
:data:`STEP` under the reference's best.

Why over decided positions: where the last expert chosen and the first
left out lie closer in ``s + b`` than bf16 carries the router's input,
the program may take the other one, and a whole held expert's output
comes or goes with it. Here that is a larger step than in
`mimo_v2_flash`: an expert weighs ``route_scale / 8`` = 0.35 beside a
shared expert of weight 1, where MiMo's weighed 1/8. A position is
*decided* when, in every expert layer of the reference, the selection's
edge stands clear of every held expert by :data:`DECIDED_MARGIN`
(`reference_afmoe.route`). `PERF.md` section 2 has this model's
readings, sound runs beside int8 control runs."""

from benchmark.checks.decoder import sample  # noqa: F401 - the harness's hook

#: in ``s + b``. Over six sound chip runs' 29,640 served tokens those
#: that left the reference's best by more than 0.1 numbered 395, 142,
#: 35, 11, 1 at margins of 0-1, 1-2, 2-3, 3-4, 4-5 thousandths and 1 of
#: 5,482 beyond (`PERF.md` section 2): the tail is wider than
#: `mimo_v2_flash`'s, and a flip still passes this margin in one run of
#: six (two of twelve sound runs read 0.098 and 0.129), which is why
#: the widest gap is reported and not held to a limit; a flip's step
#: shrinks as its margin grows (none over 0.25 past 0.004). A fifth of
#: the positions are decided
DECIDED_MARGIN = 0.005

#: a gap that only a routing flip or a fault opens: twice the widest of
#: a sound run's other decided gaps (0.047 over twelve chip runs). One
#: flip in a run of 900 decided tokens is a share of 0.001; the int8
#: control puts 3-6% of them past it (`PERF.md` section 2)
STEP = 0.1


def numbers(job: dict, control: bool) -> dict:
    from benchmark.lib import reference_afmoe

    results = reference_afmoe.served_token_gaps(
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
