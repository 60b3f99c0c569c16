"""The one general traffic generator: a mix is a JSON file of parameters.

The seed changes content and order, never work: lengths are the
QUANTILES of the stated distribution (a fixed multiset), permuted by the
seed, so every seed offers the same tokens in another order.

Mix keys (see `benchmark/README.md`):
  driver    the load driver, `benchmark/drivers/<driver>.py`, which reads
            its own keys (clients, warm, ...) besides these
  prompt_tokens / output_tokens   {"dist": "fixed", "value": n} or
            {"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}
  multiset  how many quantiles make the multiset (default 64)
  first_request_stagger {"prompt_step": p, "output_step": o}: client i's
            FIRST request carries prompt + i*p and asks for output + i*o
  toy       keys that replace the above in a CPU rehearsal
"""

import json
import math
import os
from statistics import NormalDist

import numpy as np


def load_mix(path: str, toy: bool = False) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if toy:
        mix = {**mix, **mix.get("toy", {})}
    mix.pop("toy", None)
    return mix


def quantile_values(spec: dict, n: int) -> list:
    """``n`` values at the quantiles (i + 0.5) / n of ``spec``: the same
    multiset whatever the seed."""
    if spec["dist"] == "fixed":
        return [int(spec["value"])] * n
    if spec["dist"] == "lognormal":
        normal = NormalDist()
        out = []
        for i in range(n):
            z = normal.inv_cdf((i + 0.5) / n)
            value = float(spec["median"]) * math.exp(float(spec["sigma"]) * z)
            out.append(int(min(max(round(value), spec["min"]), spec["max"])))
        return out
    raise ValueError(f"unknown distribution {spec['dist']!r}")


class Lengths:
    """Request k of the run takes entry k of the multiset's seeded
    permutation (cycled, each cycle permuted anew)."""

    def __init__(self, mix: dict, seed: int):
        n = int(mix.get("multiset", 64))
        self._prompts = quantile_values(mix["prompt_tokens"], n)
        self._outputs = quantile_values(mix["output_tokens"], n)
        self._rng = np.random.default_rng([int(seed), 1])
        self._order = []
        stagger = mix.get("first_request_stagger") or {}
        self._first = (int(stagger.get("prompt_step", 0)),
                       int(stagger.get("output_step", 0)))
        self._base = (int(mix["prompt_tokens"].get("value", 0)),
                      int(mix["output_tokens"].get("value", 0)))

    def first(self, client: int) -> tuple:
        """(prompt, output) lengths of client ``client``'s first request."""
        if self._first == (0, 0):
            return self.next()
        return (self._base[0] + client * self._first[0],
                self._base[1] + client * self._first[1])

    def next(self) -> tuple:
        if not self._order:
            # prompts and outputs permute independently
            n = len(self._prompts)
            self._order = list(zip(self._rng.permutation(n),
                                   self._rng.permutation(n)))
        i, j = self._order.pop()
        return self._prompts[i], self._outputs[j]


def prompt_ids(seed: int, index: int, length: int, vocab: int) -> list:
    """Token ids of request ``index``: unshared content from the seed."""
    rng = np.random.default_rng([int(seed), 2, int(index)])
    return rng.integers(1, vocab, size=length, dtype=np.int64).tolist()


def mix_path(root: str, name: str) -> str:
    return os.path.join(root, "benchmark", "traffic", f"{name}.json")
