"""Closed loop of decoupled token streams: ``clients`` callers, each with
one greedy streamed request in flight, its next one due the instant the
last one ended; all multiplexed over one gRPC stream of the repo's public
`client_tpu.grpc` client (the client library is part of what is measured).

One process, few threads. Every response is stamped with
``time.monotonic_ns()`` in the client's reader thread as it arrives; the
harness counts by window afterwards (`benchmark/lib/window.py`).

Mix keys this driver reads (besides `lib/traffic.py`'s lengths):
  clients   callers in the loop
  warm      {"prefill_prompts": [prompt lengths, one request each],
             "decode_longest_prompts": [longest context of a walk through
             the batch buckets 1..``lanes``], "lanes", "lane_prompt"}
"""

import queue
import threading
import time

import numpy as np

import client_tpu.grpc as grpcclient

from benchmark.lib import traffic


class LoadError(Exception):
    """The load could not be offered as the mix states."""


class Load:
    """What `run.py` drives: ``warm()``, ``ramp()``, ``hold(until_ns)``,
    ``close()``; ``requests`` and ``errors`` are what it reads."""

    def __init__(self, url: str, config: dict, mix: dict, seed: int):
        self.model, self.seed, self.mix = config["name"], seed, mix
        self.vocab = int(config["model"]["vocab_size"])
        self.requests = []          # records, in order of sending
        self.errors = []
        self._by_id = {}
        self._events = queue.SimpleQueue()
        self._lengths = traffic.Lengths(mix, seed)
        self._client = grpcclient.InferenceServerClient(url)
        self._client.start_stream(callback=self._on_response)
        self._closed_loop_until = None

    # -- the client's reader thread ------------------------------------------

    def _on_response(self, result, error) -> None:
        now = time.monotonic_ns()
        if error is not None:
            self.errors.append((now, str(error)))
            self._events.put(None)
            return
        record = self._by_id[result.get_response().id]
        record["times"].append(now)
        record["tokens"].append(int(result.as_numpy("OUTPUT_IDS")[0]))
        if len(record["times"]) == record["max_tokens"]:
            record["complete"] = True
            self._events.put(record)
        elif len(record["times"]) == 1:
            self._events.put(record)

    # -- sending -------------------------------------------------------------

    def send(self, prompt_len: int, max_tokens: int, client: int = -1,
             phase: str = "window") -> dict:
        index = len(self.requests)
        prompt = traffic.prompt_ids(self.seed, index, prompt_len, self.vocab)
        now = time.monotonic_ns()
        record = {
            "id": f"r{index}", "client": client, "phase": phase,
            "due": now,
            "prompt": prompt, "max_tokens": max_tokens, "times": [],
            "tokens": [], "complete": False, "error": None,
        }
        self.requests.append(record)
        self._by_id[record["id"]] = record
        tensor = grpcclient.InferInput("INPUT_IDS", [prompt_len], "INT32")
        tensor.set_data_from_numpy(np.asarray(prompt, np.int32))
        self._client.async_stream_infer(
            self.model, [tensor], request_id=record["id"],
            parameters={"max_tokens": max_tokens, "temperature": 0.0},
        )
        return record

    def _wait(self, predicate, timeout_s: float, what: str):
        """Pump reader events until ``predicate()``; closed-loop clients
        whose request completed get their next one meanwhile."""
        deadline = time.monotonic() + timeout_s
        while not predicate():
            left = deadline - time.monotonic()
            if left <= 0:
                raise LoadError(f"timed out after {timeout_s:.0f}s: {what}")
            try:
                record = self._events.get(timeout=min(left, 0.5))
            except queue.Empty:
                continue
            if record is None:
                raise LoadError(f"stream error: {self.errors[-1][1]}")
            self._on_event(record)

    def _on_event(self, record) -> None:
        if (record["complete"] and record["client"] >= 0
                and self._closed_loop_until is not None
                and time.monotonic_ns() < self._closed_loop_until):
            self.send(*self._lengths.next(), client=record["client"])

    def warm(self) -> None:
        """Set-up: every prefill and decode shape the mix can reach."""
        warm = self.mix["warm"]
        for prompt_len in warm["prefill_prompts"]:
            self.run_one(int(prompt_len), 1)
        for prompt_len in warm["decode_longest_prompts"]:
            self.warm_batch_buckets(int(prompt_len), int(warm["lanes"]),
                                    int(warm["lane_prompt"]))

    def run_one(self, prompt_len: int, max_tokens: int, timeout_s=900.0):
        """One request to its end (warms a prefill shape)."""
        record = self.send(prompt_len, max_tokens, phase="warmup")
        self._wait(lambda: record["complete"], timeout_s,
                   f"warm-up request of {prompt_len}+{max_tokens} tokens")
        return record

    def warm_batch_buckets(self, prompt_len: int, lanes: int,
                           lane_prompt: int, timeout_s=1500.0) -> None:
        """Walk the decode batch through 1..``lanes`` live lanes under a
        longest context of ``prompt_len``: lane n+1 is sent only once
        lane n has decoded a step beside the others, so every batch
        bucket below ``lanes`` runs (and compiles) here, in set-up, in
        the same order every run."""
        live = []
        budget = 6 * lanes
        for n in range(lanes):
            length = prompt_len if n == 0 else lane_prompt
            record = self.send(length, budget, phase="warmup")
            live.append(record)
            self._wait(
                lambda: all(len(r["times"]) >= 2 + (len(live) - 1 - i)
                            or r["complete"] for i, r in enumerate(live)),
                timeout_s, f"warm-up of batch bucket for {n + 1} lanes")
        self._wait(lambda: all(r["complete"] for r in live), timeout_s,
                   "warm-up lanes to finish")

    def ramp(self, timeout_s=600.0) -> None:
        """The longest first request goes first (so the page-table
        bucket of the batch is its own from the first step), then the
        rest; returns once every client's stream is decoding."""
        clients = int(self.mix["clients"])
        self._closed_loop_until = 1 << 62
        firsts = [(self._lengths.first(c), c) for c in range(clients)]
        firsts.sort(key=lambda item: -item[0][0])
        records = []
        for k, ((prompt_len, max_tokens), client) in enumerate(firsts):
            records.append(self.send(prompt_len, max_tokens, client=client,
                                     phase="ramp"))
            if k == 0:
                self._wait(lambda: records[0]["times"], timeout_s,
                           "the first stream's first token")
        self._wait(lambda: all(len(r["times"]) >= 2 or r["complete"]
                               for r in records),
                   timeout_s, "every client's stream to decode")

    def hold(self, until_ns: int) -> None:
        """Keep the closed loop going until ``until_ns``."""
        self._closed_loop_until = until_ns
        self._wait(lambda: time.monotonic_ns() >= until_ns,
                   (until_ns - time.monotonic_ns()) / 1e9 + 30.0, "the window")

    def close(self) -> None:
        self._client.stop_stream(cancel_requests=True)
        self._client.close()
