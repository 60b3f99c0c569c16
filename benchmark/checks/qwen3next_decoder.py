"""A served ``qwen3_next`` decoder (Qwen3-Next-80B-A3B-Instruct) against
`lib/reference_qwen3next.py`: the three numbers of
`checks/afmoe_decoder.py` (``served_gap_mean``, the mean gap by which a
served greedy token's reference logit lies below the reference's best;
``served_step_share``, the share of tokens more than :data:`STEP` under
it; both over the positions where the reference's routing stands clear;
and ``undecided_share``, the share of positions where it does not), over
the same kind of sample of the window's requests, the reference given
the same held experts, gated shared expert and vocabulary slice as the
program. The program's prefill runs the DeltaNet layers by the chunked
rule and its decode turns each lane's state in its slot, a token a step;
the reference runs the recurrence token by token from a zero state over
the whole sequence and caches nothing: their agreement over some
thousand decoded tokens a request is the carried state's test at the
published widths (a state lost, reset or rounded at any step moves every
later token of the request).

A position is *decided* when, in every layer of the reference, the
top-10's edge stands clear of every held expert by
:data:`DECIDED_MARGIN` in the router's logits
(`reference_qwen3next.route`). Under the margin bf16's rounding of the
router's input may choose otherwise, and a whole held expert's output at
a weight of about a tenth of the routed sum comes or goes with the
choice. The widest gap is reported and held to no limit, as for
`trinity_mini`: one flip in a run sets it. `PERF.md` section 2 has this
model's readings, sound runs beside int8 control runs."""

from benchmark.checks.decoder import sample  # noqa: F401 - the harness's hook

#: in the router's logits (unit size). Here the margin hardly tells: a
#: held expert weighs about a tenth of the routed sum beside a shared
#: expert of weight one, so a flip's step is small, and over a sound chip
#: run's 1,992 served tokens those that left the reference's best by
#: more than 0.1 numbered 2 of 86 at margins under 0.001 and 1 of 1,906
#: beyond, the mean gap 0.0017 at every margin from 0.002 to 0.02, where
#: the int8 control's is 0.031-0.034 and 12-14% of its tokens lie past
#: 0.1 (`PERF.md` section 2). Five of those thousandths, `trinity_mini`'s
#: number: four fifths of the positions are decided
DECIDED_MARGIN = 0.005

#: a gap that only a routing flip or a fault opens (`checks/afmoe_decoder.py`)
STEP = 0.1


def numbers(job: dict, control: bool) -> dict:
    from benchmark.lib import reference_qwen3next

    results = reference_qwen3next.served_token_gaps(
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
