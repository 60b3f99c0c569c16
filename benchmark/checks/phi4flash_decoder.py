"""A served ``phi4flash`` decoder (Phi-4-mini-flash-reasoning) against
`lib/reference_phi4flash.py`, over the same kind of sample of the
window's requests as `checks/decoder.py`: the gap by which a served
(greedy) token's reference logit lies below the reference's best. The
program prefills the self-decoder alone and decodes through one full
pool that eight layers read, eight rings and nine slots; the reference
runs every layer over every position and caches nothing: their agreement
over some thousand decoded tokens a request is the test of the shared
pool, the memory and the carried states at the published widths (a row
read from the wrong pool, a memory from the wrong layer, a state lost or
rounded moves every later token of the request).

Three numbers are compared, as for `jamba2_3b`. ``served_gap_mean`` is the
mean gap over ALL served tokens compared (a dense model has no routing to
flip, so every position tells; it grows with the square of the logits'
error, which is what sets bf16 and int8 apart). ``served_step_share`` is
read over the positions where the reference's top stands clear: a
position is *decided* when the reference's best logit lies
:data:`DECIDED_MARGIN` or more over its second, and there a served token
that is not the reference's best lies at least that margin under it: the
share of decided positions with such a token is what only a precision
below bf16, or a fault, makes more than a rarity. ``undecided_share`` is
compared too, so that the check cannot go blind. The widest gap is
reported and held to no limit. `PERF.md` section 2 has this model's
readings, sound runs beside int8 control runs."""

from benchmark.checks.decoder import sample  # noqa: F401 - the harness's hook

#: in the logits (unit size: the tied head reads a normed stream through
#: an embedding drawn at 1 / sqrt(hidden)); `PERF.md` section 2 has the
#: measured error of a sound run's logits and of the control's beside it
DECIDED_MARGIN = 0.1


def numbers(job: dict, control: bool) -> dict:
    from benchmark.lib import reference_phi4flash

    results = reference_phi4flash.served_token_gaps(
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
