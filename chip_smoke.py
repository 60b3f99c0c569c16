#!/usr/bin/env python3
"""The quickest proof that the serving path still starts on the chip.

    python3 chip_smoke.py

drives the main path once, through the documented entry points, at full
width: it builds ``native/``, starts ``python -m client_tpu.server
--grpc-frontend native --model-repository examples/model_repository``
(ResNet-50/224 and a decoder at the Llama-2-7B widths, random weights
from a seed, depth cut to fit one chip) and drives it with
``build/perf_analyzer``, the Python HTTP/gRPC clients and
``python -m client_tpu.genai_perf``. Every claim it checks is read from
the server over the wire. It exits nonzero, with the reason, unless all
of it held; on success the last line of stdout is one JSON object naming
the device.

One process may hold the chip, so this parent never imports JAX and runs
its phases as SEQUENTIAL children:

  probe   JAX/libtpu versions, the device, a warm device_get latency;
          no accelerator -> exit here
  build   configure + build the native targets the drive uses
  cold    first server start, the drive, SIGTERM drain
  warm    second start of the same server: the compile cache must be hit
  pytest  the ``-m tpu`` test tier (compiled Pallas vs fused_xla parity)
  tp4     with >= 4 devices, the same decoder sharded tp=4

It claims nothing about speed: the seconds it prints are set-up facts.
``--rehearse-cpu`` walks the same phases on the CPU at toy sizes (Pallas
in interpret mode) to debug the script itself; it proves nothing about
the chip and says so in its result line.
"""

import argparse
import json
import os
import queue
import re
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
MODEL_REPOSITORY = os.path.join(REPO, "examples", "model_repository")
BUILD = os.path.join(REPO, "build")
RESNET, LLM = "resnet50", "llama_7b_width"
#: the contract is 1200 s, compilation included
DEADLINE_S = 1150
PROMPT_TOKENS, STREAMS = 272, 4
#: genai-perf's generations are short; the smoke's own streams are long,
#: because the n-gram proposer only drafts once a generated token has
#: occurred before, and over random weights (32000-way argmax, no
#: structure) that takes a birthday's worth of tokens: the chance that
#: none of 4 streams of 496 repeats a token is below 1e-6
GENAI_TOKENS, STREAM_TOKENS = 40, 496
PHASES = ("cold", "warm", "pytest", "tp4")

PROBE = r"""
import importlib.metadata as md, json, os, time
import jax, jaxlib, numpy as np
from client_tpu.models.paged_attention import resolve_decode_attention

devices = jax.devices()
double = jax.jit(lambda a: a * 2)

def device_get_ms(n):
    ready = jax.block_until_ready(double(np.ones([n], np.float32)))
    jax.device_get(ready)
    samples = []
    for _ in range(21):
        ready = jax.block_until_ready(double(np.ones([n], np.float32)))
        start = time.perf_counter()
        jax.device_get(ready)
        samples.append((time.perf_counter() - start) * 1e3)
    return round(sorted(samples)[len(samples) // 2], 3)

try:
    libtpu = md.version("libtpu")
except md.PackageNotFoundError:
    libtpu = "not installed"
print(json.dumps({
    "jax": jax.__version__, "jaxlib": jaxlib.__version__, "libtpu": libtpu,
    "platform": devices[0].platform, "kind": devices[0].device_kind,
    "count": len(devices),
    "device_get_ms": {"4 KB": device_get_ms(1000),
                      "1 MB": device_get_ms(256 * 1024)},
    "kernel": resolve_decode_attention(
        os.environ.get("CLIENT_TPU_LLM_KERNEL"), jax.default_backend())[0],
}))
"""


class SmokeFailure(Exception):
    """A check did not hold; the message is the reason printed."""


def say(message: str) -> None:
    print(f"[smoke] {message}", flush=True)


def check(condition, reason: str) -> None:
    if not condition:
        raise SmokeFailure(reason)


_children = []


def spawn(argv, env, **kwargs) -> subprocess.Popen:
    """Start a child in its own session so the final sweep can kill its
    whole group, whatever it started itself."""
    proc = subprocess.Popen(argv, env=env, cwd=REPO, start_new_session=True,
                            **kwargs)
    _children.append(proc)
    return proc


def stop_all() -> None:
    for proc in _children:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()


def run_child(label, argv, env, timeout):
    """Run one child to completion; returns (rc, stdout, stderr)."""
    proc = spawn(argv, env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                 text=True)
    try:
        out, err = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        out, err = proc.communicate()
        raise SmokeFailure(
            f"{label} did not finish in {timeout:.0f}s:\n{(out + err)[-2000:]}"
        ) from None
    return proc.returncode, out, err


def last_json_line(text: str):
    for line in reversed(text.splitlines()):
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                continue
    return None


def http_json(port: int, path: str, post: bool = False):
    request = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=b"{}" if post else None
    )
    with urllib.request.urlopen(request, timeout=30) as response:
        return json.loads(response.read())


def metric_samples(port: int, name: str):
    """{labels: value} for one family of the Prometheus exposition."""
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=30
    ) as response:
        text = response.read().decode()
    samples = {}
    for line in text.splitlines():
        match = re.match(rf"{name}(\{{[^}}]*\}})? (\S+)$", line)
        if match:
            samples[match.group(1) or ""] = float(match.group(2))
    return samples


def cache_entries() -> int:
    from client_tpu.compile_cache import compile_cache_dir

    try:
        return sum(
            name.endswith("-cache") for name in os.listdir(compile_cache_dir())
        )
    except FileNotFoundError:
        return 0


class Server:
    """One ``python -m client_tpu.server`` child on the smoke repository."""

    def __init__(self, label: str, env, start_timeout: float):
        from client_tpu.testing import parse_server_started

        self.label = label
        self.lines = []
        self.ports = None
        self._announced = threading.Event()
        # the compiler's debug log is how the cache hits are counted
        env = dict(env, JAX_DEBUG_LOG_MODULES="jax._src.compiler")
        cache_before = cache_entries()
        began = time.monotonic()
        self.proc = spawn(
            [sys.executable, "-m", "client_tpu.server", "--host", "127.0.0.1",
             "--http-port", "0", "--grpc-port", "0", "--grpc-frontend",
             "native", "--no-builtin-models", "--model-repository",
             MODEL_REPOSITORY],
            env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )

        def read():
            for line in self.proc.stdout:
                self.lines.append(line)
                event = parse_server_started(line)
                if event is not None:
                    self.ports = event
                    self._announced.set()
            self._announced.set()  # exited: wake the waiter either way

        self._reader = threading.Thread(target=read, daemon=True)
        self._reader.start()
        self._announced.wait(start_timeout)
        self.start_s = time.monotonic() - began
        check(
            self.ports is not None,
            f"{label}: server did not announce server_started in "
            f"{start_timeout:.0f}s (rc {self.proc.poll()}):\n{self.tail()}",
        )
        self.http, self.grpc = self.ports["http_port"], self.ports["grpc_port"]
        self.cache_new = cache_entries() - cache_before
        self.cache_hits = self.count("Persistent compilation cache hit")
        self.cache_misses = self.count("PERSISTENT COMPILATION CACHE MISS")
        say(f"{label}: server_started after {self.start_s:.1f}s "
            f"(http {self.http}, grpc {self.grpc}); compile cache: "
            f"{self.cache_hits} hits, {self.cache_misses} misses, "
            f"{self.cache_new} entries written")

    def count(self, needle: str) -> int:
        return sum(needle in line for line in self.lines)

    def tail(self, n: int = 40) -> str:
        keep = [line for line in self.lines if "jax._src.compiler" not in line]
        return "".join(keep[-n:])

    def assert_ready(self, models) -> None:
        index = {m["name"]: m for m in
                 http_json(self.http, "/v2/repository/index", post=True)}
        for name in models:
            entry = index.get(name, {"state": "MISSING", "reason": ""})
            check(entry["state"] == "READY",
                  f"{self.label}: model {name} is {entry['state']}: "
                  f"{entry['reason']}")

    def stop(self) -> None:
        """SIGTERM must drain and exit 0."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=90)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"{self.label}: server ignored SIGTERM for 90s:\n{self.tail()}"
            ) from None
        self._reader.join(timeout=10)
        check(rc == 0 and self.count('"drain_completed"') == 1,
              f"{self.label}: server exit code {rc} after SIGTERM:\n"
              f"{self.tail()}")
        say(f"{self.label}: SIGTERM drained, server exit code 0")


# -- the drive ---------------------------------------------------------------


def drive_resnet_clients(server: Server) -> None:
    """One fixed image over four transports; the logits must be the same
    finite [1000] vector each time."""
    import numpy as np

    import client_tpu.grpc as grpcclient
    import client_tpu.http as httpclient
    import client_tpu.utils.shared_memory as sysshm
    import client_tpu.utils.tpu_shared_memory as tpushm

    image = np.random.default_rng(0).random((1, 224, 224, 3), dtype=np.float32)
    shape, out_bytes = list(image.shape), 1000 * 4
    logits = {}

    with httpclient.InferenceServerClient(f"127.0.0.1:{server.http}") as http:
        tensor = httpclient.InferInput("INPUT", shape, "FP32")
        tensor.set_data_from_numpy(image)
        logits["http inline"] = http.infer(RESNET, [tensor]).as_numpy("OUTPUT")

    with grpcclient.InferenceServerClient(f"127.0.0.1:{server.grpc}") as grpc:
        tensor = grpcclient.InferInput("INPUT", shape, "FP32")
        tensor.set_data_from_numpy(image)
        logits["grpc inline"] = grpc.infer(RESNET, [tensor]).as_numpy("OUTPUT")

        def over_shm(kind, create, module, register, unregister):
            regions = [create("smoke_in", image.nbytes),
                       create("smoke_out", out_bytes)]
            try:
                module.set_shared_memory_region(regions[0], [image])
                register("smoke_in", regions[0], image.nbytes)
                register("smoke_out", regions[1], out_bytes)
                tensor = grpcclient.InferInput("INPUT", shape, "FP32")
                tensor.set_shared_memory("smoke_in", image.nbytes)
                wanted = grpcclient.InferRequestedOutput("OUTPUT")
                wanted.set_shared_memory("smoke_out", out_bytes)
                result = grpc.infer(RESNET, [tensor], outputs=[wanted])
                check(result.as_numpy("OUTPUT") is None,
                      f"{kind}: output came back inline, not through shm")
                logits[f"grpc {kind}"] = module.get_contents_as_numpy(
                    regions[1], np.float32, [1, 1000]).copy()
            finally:
                unregister()
                for region in regions:
                    module.destroy_shared_memory_region(region)

        over_shm(
            "system shm",
            lambda name, size: sysshm.create_shared_memory_region(
                name, f"/{name}_{os.getpid()}", size),
            sysshm,
            lambda name, region, size: grpc.register_system_shared_memory(
                name, f"/{name}_{os.getpid()}", size),
            grpc.unregister_system_shared_memory,
        )
        over_shm(
            "tpu shm",
            tpushm.create_shared_memory_region,
            tpushm,
            lambda name, region, size: grpc.register_tpu_shared_memory(
                name, tpushm.get_raw_handle(region), 0, size),
            grpc.unregister_tpu_shared_memory,
        )

    reference = logits["http inline"]
    check(reference.shape == (1, 1000) and np.isfinite(reference).all()
          and reference.std() > 0,
          f"{RESNET}: logits are not a finite, non-constant [1000] vector")
    for transport, got in logits.items():
        check(got.shape == reference.shape and np.array_equal(got, reference),
              f"{RESNET}: {transport} disagrees with http inline by "
              f"{np.abs(got - reference).max():.3g}")
    say(f"{RESNET}: ResNet-50/224 answered over {', '.join(logits)}: the same "
        f"finite logits [1000] (min {reference.min():.3f}, "
        f"max {reference.max():.3f})")


def drive_perf_analyzer(server: Server) -> None:
    for transport in ("none", "system", "tpu"):
        rc, out, err = run_child(
            f"perf_analyzer --shared-memory {transport}",
            [os.path.join(BUILD, "perf_analyzer"), "-m", RESNET, "-u",
             f"127.0.0.1:{server.grpc}", "-i", "grpc", "--shared-memory",
             transport, "--concurrency-range", "1", "--measurement-mode",
             "count_windows", "--measurement-request-count", "10",
             "--max-trials", "3", "--json-summary"],
            dict(os.environ), timeout=180,
        )
        summary = last_json_line(out)
        check(rc == 0 and summary is not None,
              f"perf_analyzer --shared-memory {transport} rc {rc}:\n"
              f"{(out + err)[-1500:]}")
        check(summary["errors"] == 0 and summary["count"] > 0,
              f"perf_analyzer --shared-memory {transport}: {summary}")
        say(f"{RESNET}: perf_analyzer --shared-memory {transport}: "
            f"{summary['count']} requests, 0 errors")


def drive_llm_streams(server: Server, speculation: str):
    """STREAMS concurrent streamed generations over one gRPC
    ModelStreamInfer stream; returns each stream's tokens."""
    import numpy as np

    import client_tpu.grpc as grpcclient

    rng = np.random.default_rng(7)
    responses = queue.Queue()
    tokens = {f"g{i}": [] for i in range(STREAMS)}
    with grpcclient.InferenceServerClient(f"127.0.0.1:{server.grpc}") as grpc:
        grpc.start_stream(callback=lambda r, e: responses.put((r, e)))
        for request_id in tokens:
            # a phrase of its own per stream, repeated: no two streams
            # share a block (ids below 250 exist in the toy vocabulary too)
            phrase = rng.integers(1, 250, size=17)
            prompt = np.resize(phrase, PROMPT_TOKENS).astype(np.int32)
            tensor = grpcclient.InferInput("INPUT_IDS", [PROMPT_TOKENS],
                                           "INT32")
            tensor.set_data_from_numpy(prompt)
            grpc.async_stream_infer(
                LLM, [tensor], request_id=request_id,
                parameters={"max_tokens": STREAM_TOKENS,
                            "speculation": speculation},
            )
        while any(len(t) < STREAM_TOKENS for t in tokens.values()):
            try:
                result, error = responses.get(timeout=300)
            except queue.Empty:
                raise SmokeFailure(
                    f"{LLM}: stream stalled (speculation {speculation}): "
                    f"{ {k: len(v) for k, v in tokens.items()} }"
                ) from None
            check(error is None,
                  f"{LLM}: stream error (speculation {speculation}): {error}")
            tokens[result.get_response().id].append(
                int(result.as_numpy("OUTPUT_IDS")[0]))
        grpc.stop_stream()
    check(all(len(t) == STREAM_TOKENS for t in tokens.values()),
          f"{LLM}: a stream overran max_tokens: "
          f"{ {k: len(v) for k, v in tokens.items()} }")
    say(f"{LLM}: {STREAMS} concurrent streamed generations, speculation "
        f"{speculation}: {PROMPT_TOKENS}-token prompts, {STREAM_TOKENS} "
        f"tokens each")
    return tokens


def drive_genai_perf(server: Server, speculation: str, env) -> None:
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as artifacts:
        rc, out, err = run_child(
            f"genai-perf --speculation {speculation}",
            [sys.executable, "-m", "client_tpu.genai_perf", "profile", "-m",
             LLM, "-u", f"127.0.0.1:{server.grpc}", "--num-prompts", "8",
             "--synthetic-input-tokens-mean", str(PROMPT_TOKENS),
             "--output-tokens-mean", str(GENAI_TOKENS), "--concurrency",
             str(STREAMS), "--speculation", speculation,
             "--measurement-interval", "8000", "--max-trials", "3",
             "--artifact-dir", artifacts, "--json-summary"],
            env, timeout=300,
        )
        summary = last_json_line(out)
        check(rc == 0 and summary is not None,
              f"genai-perf --speculation {speculation} rc {rc}:\n"
              f"{(out + err)[-1500:]}")
        with open(os.path.join(artifacts, "profile_export.json")) as f:
            requests = [r for e in json.load(f)["experiments"]
                        for r in e["requests"]]
    errors = sum(not r["success"] for r in requests)
    check(errors == 0 and summary["request_count"] > 0,
          f"genai-perf --speculation {speculation}: {errors} errors in "
          f"{len(requests)} requests; summary {summary}")
    say(f"{LLM}: genai-perf --speculation {speculation}: "
        f"{summary['request_count']} requests, 0 errors, "
        f"{summary['output_tokens_avg']:.0f} tokens each, "
        f"{summary.get('tokens_per_step', 1.0)} tokens/step")


def llm_parameters(server: Server):
    config = http_json(server.http, f"/v2/models/{LLM}/config")
    return {k: v["string_value"] for k, v in config["parameters"].items()}


def llm_weight_bytes() -> int:
    """bf16 bytes of the decoder's weights at the published widths and
    the depth its model.py declares."""
    with open(os.path.join(MODEL_REPOSITORY, LLM, "model.py")) as f:
        layers = int(re.search(r"^N_LAYERS = (\d+)$", f.read(), re.M)[1])
    d, ff, v = 4096, 11008, 32000
    return 2 * (layers * (4 * d * d + 3 * d * ff + 2 * d) + 2 * v * d + d)


def assert_nothing_absorbed(server: Server, models=(RESNET, LLM)) -> None:
    """Production absorbs device failures (UNAVAILABLE models, engine
    quarantine, background reloads); a smoke checks none of it fired."""
    recoveries = metric_samples(server.http, "tpu_recovery_total")
    check(not any(recoveries.values()),
          f"tpu_recovery_total is not zero: {recoveries}")
    state = http_json(server.http, "/v2/debug/state")
    engine = state["llm"][LLM]
    check(not engine["recovering"] and engine["recovery"]["recoveries"] == 0
          and engine["recovery"]["failures"] == 0
          and server.count("llm_engine_loop_failed") == 0,
          f"{LLM}: the engine was quarantined: {engine['recovery']}\n"
          f"{server.tail()}")
    for name in models:
        stats = http_json(server.http, f"/v2/models/{name}/stats")
        infer = stats["model_stats"][0]["inference_stats"]
        check(infer["fail"]["count"] == 0 and infer["success"]["count"] > 0,
              f"{name}: server statistics: success "
              f"{infer['success']['count']}, fail {infer['fail']['count']}")
    say("absorbed failures: none (0 recoveries, 0 quarantines, 0 failed "
        "requests in the server's statistics)")


def phase_cold(env, probe, rehearsal: bool):
    server = Server("cold start", env, start_timeout=600)
    topology = http_json(server.http, "/v2")["devices"]
    kinds = sorted({d["kind"] for d in topology["devices"]})
    say(f"/v2 devices: platform {topology['platform']}, kind {kinds}, "
        f"count {topology['device_count']}")
    check(topology["platform"] == probe["platform"]
          and topology["device_count"] == probe["count"],
          f"server sees {topology['platform']} x{topology['device_count']}, "
          f"the probe saw {probe['platform']} x{probe['count']}")
    server.assert_ready((RESNET, LLM))
    kernel = llm_parameters(server)["decode_kernel"]
    check(kernel == probe["kernel"],
          f"{LLM}: decode_kernel={kernel}, but resolve_decode_attention "
          f"chose {probe['kernel']}")
    say(f"{LLM}: READY with decode_kernel={kernel}; {RESNET}: READY")

    drive_resnet_clients(server)
    drive_perf_analyzer(server)
    with_spec = drive_llm_streams(server, "on")
    without = drive_llm_streams(server, "off")
    same = sum(with_spec[k] == without[k] for k in with_spec)
    say(f"{LLM}: {same}/{STREAMS} streams token-identical with speculation "
        f"on and off (not required: argmax over random weights may flip on "
        f"rounding between the two kernels)")
    drive_genai_perf(server, "on", env)
    drive_genai_perf(server, "off", env)
    spec = json.loads(llm_parameters(server)["speculation_stats"])
    check(spec["spec_proposed"] > 0 and spec["steps"] > spec["spec_steps"],
          f"{LLM}: the drive did not put both decode kernels on the path: "
          f"{spec}")
    say(f"{LLM}: {spec['steps']} decode steps, {spec['spec_steps']} of them "
        f"multi-query verify steps ({spec['spec_accepted']}/"
        f"{spec['spec_proposed']} drafts accepted)")

    if rehearsal:
        say("tpu_memory_used_bytes: not checked (the CPU backend reports "
            "no memory accounting)")
    else:
        used = max(metric_samples(server.http, "tpu_memory_used_bytes")
                   .values(), default=0.0)
        weights = llm_weight_bytes()
        check(used >= weights,
              f"tpu_memory_used_bytes {used / 1e9:.2f} GB is less than the "
              f"{weights / 1e9:.2f} GB of decoder weights loaded")
        say(f"tpu_memory_used_bytes: {used / 1e9:.2f} GB in use "
            f"(decoder weights alone are {weights / 1e9:.2f} GB)")
    assert_nothing_absorbed(server)
    server.stop()
    return server


def phase_warm(env, cold: Server) -> Server:
    check(cache_entries() > 0,
          "the compile cache directory is empty after the cold start")
    server = Server("warm start", env, start_timeout=600)
    server.assert_ready((RESNET, LLM))
    check(server.cache_hits > 0
          and server.cache_new < max(1, cold.cache_new),
          f"the warm start compiled as much as the cold one: "
          f"{server.cache_hits} cache hits, {server.cache_new} new entries "
          f"(cold start wrote {cold.cache_new})")
    server.stop()
    return server


def phase_pytest(env, probe) -> None:
    argv = [sys.executable, "-m", "pytest", "tests/", "-m", "tpu", "-q",
            "-p", "no:cacheprovider"]
    if probe["count"] < 4:
        # the one test that needs four chips would skip: say so here and
        # keep "0 skipped" meaning what it says
        argv += ["--deselect", "tests/test_tpu_platform.py::"
                 "test_tp_sharded_pallas_matches_unsharded_fused_xla"]
        say(f"pytest -m tpu: the tp=4 attention test is deselected "
            f"({probe['count']} devices)")
    rc, out, err = run_child(
        "pytest -m tpu", argv, dict(env, CLIENT_TPU_TEST_PLATFORM="tpu"),
        timeout=600,
    )
    summary = (out.strip().splitlines() or [""])[-1]
    passed = re.search(r"(\d+) passed", summary)
    check(rc == 0 and passed is not None
          and not re.search(r"skipped|failed|error", summary),
          f"pytest -m tpu (rc {rc}): {summary}\n{(out + err)[-3000:]}")
    say(f"pytest -m tpu: {summary.strip('= ')}")


def phase_tp4(env, probe, rehearsal: bool) -> str:
    if probe["count"] < 4 and not rehearsal:
        say(f"tp4: not run ({probe['count']} devices)")
        return f"not run ({probe['count']} devices)"
    server = Server("tp4", dict(env, LLAMA_SMOKE_TP="4"), start_timeout=600)
    server.assert_ready((RESNET, LLM))
    mesh = http_json(server.http, "/v2")["devices"]["models"].get(LLM, {})
    check(len(set(mesh.get("devices", []))) == 4,
          f"tp4: the decoder's mesh does not span four devices: {mesh}")
    drive_llm_streams(server, "on")
    memory = metric_samples(server.http, "tpu_device_memory_bytes")
    if not rehearsal:
        check(len(memory) >= 4 and all(memory.values()),
              f"tp4: not every device reports memory in use: {memory}")
    assert_nothing_absorbed(server, (LLM,))
    server.stop()
    in_use = ", ".join(f"{labels} {value / 1e9:.2f} GB"
                       for labels, value in sorted(memory.items()))
    say(f"tp4: mesh {mesh['axes']} over devices {mesh['devices']}; "
        f"tpu_device_memory_bytes: {in_use}")
    return "ran"


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--rehearse-cpu", action="store_true",
        help="debug this script on the CPU at toy sizes; proves nothing "
        "about the chip",
    )
    parser.add_argument(
        "--only", default=",".join(PHASES), metavar="PHASE[,PHASE]",
        help=f"run only these of {', '.join(PHASES)} after the probe and "
        "the build, to repeat one failing phase without paying chip time "
        "for the rest; the result line then lists what ran",
    )
    args = parser.parse_args()
    phases = args.only.split(",")
    if set(phases) - set(PHASES) or ("warm" in phases and "cold" not in phases):
        parser.error(f"--only takes a subset of {PHASES}; warm needs cold")
    for needed in ("client_tpu", "native", MODEL_REPOSITORY):
        if not os.path.isdir(os.path.join(REPO, needed)):
            print(f"chip_smoke: {needed} is not beside this script: run it "
                  "from a checkout of the repository", file=sys.stderr)
            return 2
    sys.path.insert(0, REPO)

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if args.rehearse_cpu:
        env.update(JAX_PLATFORMS="cpu", CLIENT_TPU_SMOKE_TINY="1",
                   CLIENT_TPU_LLM_KERNEL="pallas_interpret",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4")
        # a 256-token vocabulary repeats at once; the interpreter is slow
        global STREAM_TOKENS
        STREAM_TOKENS = 48

    def out_of_time(signum, frame):
        raise SmokeFailure(f"not done after {DEADLINE_S}s")

    signal.signal(signal.SIGALRM, out_of_time)
    signal.alarm(DEADLINE_S)
    began = time.monotonic()
    try:
        rc, out, err = run_child(
            "probe", [sys.executable, "-c", PROBE], env, timeout=300)
        probe = last_json_line(out)
        check(rc == 0 and probe is not None,
              f"JAX did not start (rc {rc}):\n{err[-2000:]}")
        check(probe["platform"] == "tpu" or args.rehearse_cpu,
              f"JAX found no accelerator: platform {probe['platform']} "
              f"({probe['kind']} x{probe['count']})")
        say(f"platform {probe['platform']}, device_kind {probe['kind']}, "
            f"{probe['count']} device(s); jax {probe['jax']}, jaxlib "
            f"{probe['jaxlib']}, libtpu {probe['libtpu']}")
        facts = [
            f"jax {probe['jax']} / jaxlib {probe['jaxlib']} / libtpu "
            f"{probe['libtpu']}",
            f"device_kind {probe['kind']} x{probe['count']}",
            f"warm device_get {probe['device_get_ms']} ms",
        ]

        from tools.build_wheel import build_native

        build_began = time.monotonic()
        try:
            build_native(
                BUILD, ("perf_analyzer", "native_frontend", "cshm_tpu"),
                capture_output=True, text=True, timeout=600,
            )
        except subprocess.CalledProcessError as e:
            raise SmokeFailure(
                f"native build failed:\n{(e.stdout + e.stderr)[-3000:]}"
            ) from None
        say(f"native build: perf_analyzer, _native_frontend.so, "
            f"libcshm_tpu.so in {time.monotonic() - build_began:.0f}s")

        if "cold" in phases:
            cold = phase_cold(env, probe, args.rehearse_cpu)
            facts.append(f"cold start {cold.start_s:.1f}s")
        if "warm" in phases:
            warm = phase_warm(env, cold)
            facts.append(f"warm start {warm.start_s:.1f}s "
                         f"({warm.cache_hits} compile-cache hits)")
        if "pytest" in phases and args.rehearse_cpu:
            say("pytest -m tpu: not run (rehearsal; the tier fails without "
                "an accelerator by design)")
        elif "pytest" in phases:
            phase_pytest(env, probe)
        if "tp4" in phases:
            facts.append(f"tp4 {phase_tp4(env, probe, args.rehearse_cpu)}")
    except SmokeFailure as failure:
        print(f"[smoke] FAIL: {failure}", flush=True)
        return 1
    finally:
        signal.alarm(0)
        stop_all()

    assert "jax" not in sys.modules, "the parent imported JAX"
    facts.append(f"whole smoke {time.monotonic() - began:.0f}s")
    say("set-up facts (not metrics): " + "; ".join(facts))
    result = {"ok": True, "device": {"platform": probe["platform"],
                                     "kind": probe["kind"],
                                     "count": probe["count"]}}
    if args.rehearse_cpu:
        result["rehearsal"] = True
    if phases != list(PHASES):
        result["only"] = phases
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
