"""A served decoder against `lib/reference_llm.py`: the gap by which a
served (greedy) token's reference logit lies below the reference's best,
over a seeded sample of the requests the window finished, the one with
most served tokens in it. Two numbers are compared: the widest gap, and
the mean gap over all served tokens, which is steady from seed to seed
where the widest swings (`PERF.md` section 2)."""


def sample(requests, t0, t1, mix, rng) -> dict:
    done = [r for r in requests if r["complete"] and r["phase"] != "warmup"
            and t0 <= r["times"][-1] < t1]
    if not done:
        raise ValueError("no request finished inside the window")
    # the longest: most served tokens, and of those the longest prompt
    longest = max(done, key=lambda r: (len(r["tokens"]), len(r["prompt"])))
    others = [r for r in done if r is not longest]
    count = min(len(others), int(mix["compare_requests"]) - 1)
    picked = [longest] + [others[i] for i in
                          rng.choice(len(others), size=count, replace=False)]
    return {"sequences": [{"prompt": r["prompt"], "served": r["tokens"]}
                          for r in picked]}


def numbers(job: dict, control: bool) -> dict:
    """Under ``control`` both numbers are read, at each position of the
    same prompts and served tokens, for the token the int8 forward puts
    first there, and the program's own readings go beside them as
    ``program_gap_max`` and ``program_gap_mean``."""
    from benchmark.lib import reference_llm

    results = reference_llm.served_token_gaps(
        job["seed"], job["model"], job["sequences"], control=control)
    served = [g for r in results for g in r["gaps"]]
    def read(gaps):
        return {"served_tokens": len(gaps), "served_gap_max": max(gaps),
                "served_gap_mean": sum(gaps) / len(gaps),
                "served_off_best": sum(g > 0 for g in gaps)}

    out = read(served)
    if control:
        program = out
        out = read([g for r in results for g in r["control_gaps"]])
        out.update(program_gap_max=program["served_gap_max"],
                   program_gap_mean=program["served_gap_mean"])
    return out
