"""A served ``ouro`` decoder (Ouro-2.6B) against `lib/reference_ouro.py`,
over the same kind of sample of the window's requests as
`checks/decoder.py`: the gap by which a served (greedy) token's
reference logit lies below the reference's best. The program runs its
192 layer-passes as two rolled loops over stacked weights and keeps 192
caches in one pool pair, a pass's at an offset; the reference loops in
Python over one layer's weights at a time and caches nothing: their
agreement over the 256 decoded tokens of a request is the test of the
offsets (a pass that read another pass's rows, or a layer another
layer's, moves every later token) and of the loop (a pass or a norm
dropped moves every token).

The three numbers of `checks/jamba_decoder.py`, read the same way: a
dense model has no routing to flip, so every position tells.
``served_gap_mean`` is the mean gap over ALL served tokens compared;
``served_step_share`` is read over the positions where the reference's
best logit lies :data:`DECIDED_MARGIN` or more over its second (there a
served token that is not the reference's best lies at least that margin
under it); ``undecided_share`` is compared too, so that the check cannot
go blind. The widest gap is reported and held to no limit. `PERF.md`
section 2 has this model's readings, sound runs beside int8 control
runs: 192 normed layer-passes in bf16 are not 24 layers."""

from benchmark.checks.decoder import sample  # noqa: F401 - the harness's hook
from benchmark.checks.jamba_decoder import DECIDED_MARGIN


def numbers(job: dict, control: bool) -> dict:
    from benchmark.lib import reference_ouro

    results = reference_ouro.served_token_gaps(
        job["seed"], job["model"], job["sequences"], control=control)
    decided = [m >= DECIDED_MARGIN for r in results for m in r["margins"]]

    def read(key):
        gaps = [g for r in results for g in r[key]]
        clear = [g for g, d in zip(gaps, decided) if d]
        return {"served_tokens": len(gaps),
                "undecided_share": 1 - len(clear) / len(gaps),
                "served_gap_max": max(gaps),
                "served_gap_mean": sum(gaps) / len(gaps),
                "served_step_share": sum(g > 0 for g in clear) / len(clear),
                "served_off_best": sum(g > 0 for g in gaps)}

    out = read("gaps")
    if control:
        program = out
        out = read("control_gaps")
        out.update(program_gap_max=program["served_gap_max"],
                   program_gap_mean=program["served_gap_mean"],
                   program_step_share=program["served_step_share"])
    return out
