#!/usr/bin/env python3
"""One run of one cell of `BENCHMARK.json`.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A JAX-free parent: it builds `native/` if `build/` lacks the front-end,
starts ``python -m client_tpu.server --grpc-frontend native`` on the
cell's own model repository as a child, warms the cell's shapes, ramps the
load to steady occupancy, opens the window ``[T0, T0 + seconds)``, closes
it, drains the server, and only then compares what the timed path
produced with the plain reference (a child of its own). Everything
before T0 is `setup_s`. The last line of stdout is one JSON object.

The load driver is the mix's (`benchmark/drivers/<driver>.py`), the
comparison the configuration's (`benchmark/checks/<check>.py`), each
metric's reader its file's (`benchmark/metrics/<name>.json`): a new cell
brings files and edits nothing here. The server runs as its own defaults
have it; the only settings it is given are the compile cache inside the
checkout, compile logging and a `TMPDIR` inside the checkout.

No chip, or fewer chips than the cell asks for: exit 3 and no result.
``--rehearse-cpu`` walks the same path on the CPU at the ``toy`` sizes of
the configuration and mix, to debug the harness; its line says
``"rehearsal": true`` and none of its numbers is a measurement.
"""

import argparse
import importlib
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
WORK = os.path.join(ROOT, ".bench_work")
BUILD = os.path.join(ROOT, "build")
NO_CHIP = 3

_children = []


class RunFailure(Exception):
    """The run cannot give a result; the message is the reason."""


def say(message: str) -> None:
    print(f"[bench] {message}", file=sys.stderr, flush=True)


def spawn(argv, env, **kwargs) -> subprocess.Popen:
    proc = subprocess.Popen(argv, env=env, cwd=ROOT, start_new_session=True,
                            **kwargs)
    _children.append(proc)
    return proc


def stop_all() -> None:
    """Kill whatever is left of every child's process group, and wait."""
    for proc in _children:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def http(port: int, path: str, body=None, timeout=300, raw=False):
    request = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if body is None else json.dumps(body).encode())
    with urllib.request.urlopen(request, timeout=timeout) as response:
        data = response.read()
    return data if raw else json.loads(data)


# -- the server child ---------------------------------------------------------


class Server:
    """``python -m client_tpu.server`` on one configuration's repository."""

    def __init__(self, repository: str, env: dict, timeout_s: float):
        from client_tpu.testing import parse_server_started

        self.lines, self.compile_times, self.ports = [], [], None
        self._announced = threading.Event()
        self.proc = spawn(
            [sys.executable, "-m", "client_tpu.server", "--host", "127.0.0.1",
             "--http-port", "0", "--grpc-port", "0", "--grpc-frontend",
             "native", "--no-builtin-models", "--model-repository", repository],
            env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)

        def read():
            for line in self.proc.stdout:
                if "Compiling " in line:
                    self.compile_times.append(time.monotonic_ns())
                    continue
                self.lines.append(line)
                del self.lines[:-400]
                event = parse_server_started(line)
                if event is not None:
                    self.ports = event
                    self._announced.set()
            self._announced.set()

        self._reader = threading.Thread(target=read, daemon=True)
        self._reader.start()
        self._announced.wait(timeout_s)
        if self.ports is None:
            raise RunFailure(
                f"the server did not announce server_started in {timeout_s:.0f}s "
                f"(exit code {self.proc.poll()}):\n{self.tail()}")
        self.http_port = self.ports["http_port"]
        self.grpc_url = f"127.0.0.1:{self.ports['grpc_port']}"

    def tail(self, n: int = 30) -> str:
        return "".join(self.lines[-n:])

    def stop(self) -> None:
        """SIGTERM drains and must exit 0."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=90)
        except subprocess.TimeoutExpired:
            raise RunFailure(f"the server ignored SIGTERM:\n{self.tail()}")
        self._reader.join(timeout=10)
        if rc != 0:
            raise RunFailure(f"the server exited {rc} on SIGTERM:\n{self.tail()}")


def parse_prom(text: str) -> dict:
    """{family: sum over labels} of a Prometheus exposition."""
    families = {}
    for line in text.splitlines():
        match = re.match(r"([A-Za-z_:][\w:]*)(\{[^}]*\})? (\S+)$", line)
        if match:
            try:
                families[match.group(1)] = (families.get(match.group(1), 0.0)
                                            + float(match.group(3)))
            except ValueError:
                pass
    return families


def snapshot(server: Server, model: str) -> dict:
    """The program's counters at one instant (``at``, ns)."""
    at = time.monotonic_ns()
    config = http(server.http_port, f"/v2/models/{model}/config")
    parameters = {k: v.get("string_value") for k, v in
                  config.get("parameters", {}).items()}
    stats = http(server.http_port, f"/v2/models/{model}/stats")
    prom = http(server.http_port, "/metrics", raw=True).decode()
    engine = parameters.get("bench_engine")
    return {
        "at": (at + time.monotonic_ns()) // 2,
        "device": json.loads(parameters["bench_device"]),
        "engine": json.loads(engine) if engine else None,
        "stats": stats["model_stats"][0],
        "prom": parse_prom(prom),
    }


# -- one run ------------------------------------------------------------------


class Run:
    """What the readers read."""

    def __init__(self):
        self.requests, self.errors, self.compile_times = [], [], []
        self.t0 = self.t1 = 0
        self.setup_s = 0.0
        self.before = self.after = None
        self.trace = None
        self.config = self.mix = self.peak = None


def read_metric(run: Run, name: str):
    spec = load_json(BENCH, "metrics", f"{name}.json")
    module, function = spec["reader"].split(":")
    reader = getattr(importlib.import_module(f"benchmark.readers.{module}"),
                     function)
    return reader(run, **spec.get("params", {})), spec["unit"]


def cell_metrics(benchmark: dict, workload: str, trace: bool) -> list:
    """Names of the metrics this cell reports in this kind of run."""
    names = []
    for metric in benchmark["per_layer" if trace else "end_to_end"]:
        if workload in metric.get("workloads", [workload]):
            names.append(metric["name"])
    return names


def ensure_native_frontend() -> None:
    if os.path.exists(os.path.join(BUILD, "_native_frontend.so")):
        return
    from tools.build_wheel import build_native

    began = time.monotonic()
    try:
        build_native(BUILD, ("native_frontend",), capture_output=True,
                     text=True, timeout=900)
    except subprocess.CalledProcessError as e:
        raise RunFailure(f"native build failed:\n{(e.stdout + e.stderr)[-3000:]}")
    say(f"built native/ front-end in {time.monotonic() - began:.0f}s")


def child_env(args, seed: int) -> dict:
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["BENCH_SEED"] = str(seed)
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    env["JAX_LOG_COMPILES"] = "1"
    env["TMPDIR"] = os.path.join(WORK, "tmp")
    env["TPU_LOG_DIR"] = "disabled"
    if args.rehearse_cpu:
        env.update(JAX_PLATFORMS="cpu", BENCH_TOY="1",
                   CLIENT_TPU_LLM_KERNEL="pallas_interpret")
    else:
        env["JAX_PLATFORMS"] = "tpu"  # no accelerator: JAX fails at start-up
    return env


def run_child(label, argv, env, timeout_s):
    proc = spawn(argv, env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                 text=True)
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        out, _ = proc.communicate()
        raise RunFailure(f"{label} did not finish in {timeout_s:.0f}s:\n{out[-2000:]}")
    if proc.returncode != 0:
        raise RunFailure(f"{label} exited {proc.returncode}:\n{out[-3000:]}")


def drive(args, config, mix, server, run: Run, began: float):
    """Warm, ramp, window; the load driver is the mix's own
    (`benchmark/drivers/<name>.py`). Returns the trace's directory."""
    driver = importlib.import_module(f"benchmark.drivers.{mix['driver']}")
    target = config["name"]
    seconds = float(args.seconds)
    load = driver.Load(server.grpc_url, config, mix, args.seed)
    load.warm()
    if args.trace:
        http(server.http_port, "/v2/debug/profiling", {"stage_cpu": True})

    edge = {}

    def snap(which):
        edge[which] = snapshot(server, target)

    def trace():
        directory = os.path.join(WORK, "tmp", "trace")
        shutil.rmtree(directory, ignore_errors=True)
        http(server.http_port,
             f"/v2/debug/profile?hz=1&duration_s={mix['trace_seconds']}"
             f"&jax_trace_dir={directory}", raw=True)
        edge["trace_dir"] = directory

    helpers = []

    def at(when_ns, fn, *fn_args):
        def later():
            time.sleep(max(0.0, (when_ns - time.monotonic_ns()) / 1e9))
            fn(*fn_args)
        helpers.append(threading.Thread(target=later, daemon=True))
        helpers[-1].start()

    load.ramp()
    # the counters are read just OUTSIDE the window's edges (a scrape of
    # /metrics holds the server up for a tenth of a second or more), and
    # each reading carries its own instant
    lead_ns = 1_500_000_000
    run.t0 = time.monotonic_ns() + lead_ns
    run.t1 = run.t0 + int(seconds * 1e9)
    run.setup_s = time.monotonic() - began + lead_ns / 1e9
    at(run.t0 - lead_ns, snap, "before")
    at(run.t1 + 50_000_000, snap, "after")
    if args.trace:
        at(run.t0 + int(1e9 * min(1.0, seconds / 4)), trace)
    # the load keeps going past the window's end: drain is outside it
    load.hold(run.t1 + int(float(mix.get("tail_seconds", 1.0)) * 1e9))
    for helper in helpers:
        helper.join(timeout=180)
    load.close()
    missing = {"before", "after"} - set(edge)
    if missing or (args.trace and "trace_dir" not in edge):
        raise RunFailure(f"a helper did not finish: {sorted(missing)} {edge.keys()}")
    run.before, run.after = edge["before"], edge["after"]
    run.requests, run.errors = load.requests, load.errors
    run.compile_times = server.compile_times
    return edge.get("trace_dir")


def compare(args, config, mix, run: Run, env) -> dict:
    """Once the window has closed and the server has gone: the plain
    reference over a seeded sample of what the timed path produced, by
    the configuration's own check (`benchmark/checks/<name>.py`)."""
    import numpy as np

    check = importlib.import_module(f"benchmark.checks.{config['check']}")
    try:
        job = check.sample(run.requests, run.t0, run.t1, mix,
                           np.random.default_rng([args.seed, 6]))
    except ValueError as nothing:
        raise RunFailure(str(nothing))
    job.update(root=ROOT, check=config["check"], seed=args.seed,
               model=config["model"])
    job_path, out_path = (os.path.join(WORK, "check_job.json"),
                          os.path.join(WORK, "check_out.json"))
    with open(job_path, "w") as f:
        json.dump(job, f)
    argv = [sys.executable, "-m", "benchmark.lib.check_child", job_path, out_path]
    if args.control:
        argv.append("--control")
    run_child("the comparison with the reference", argv, env, 900)
    return load_json(out_path)


def judge(numbers: dict, limits: dict, failed: int, compiles: int) -> tuple:
    """({name: {"value", "limit"}} of every number compared, `correct`)."""
    compared = {"failed": {"value": failed, "limit": 0},
                "compiles_in_window": {"value": compiles, "limit": 0}}
    for name, limit in limits.items():
        compared[name] = {"value": numbers[name], "limit": limit}
    return compared, all(c["value"] <= c["limit"] for c in compared.values())


def main() -> int:
    began = time.monotonic()
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument("--rehearse-cpu", action="store_true",
                        help="toy sizes on the CPU; measures nothing")
    parser.add_argument("--control", action="store_true",
                        help="the lower-precision reference stands in the "
                        "program's place in the comparison, which has to "
                        "say `correct: false` (the driver never passes it)")
    args = parser.parse_args()
    for needed in ("client_tpu", "native", "tools"):
        if not os.path.isdir(os.path.join(ROOT, needed)):
            say(f"{needed}/ is not beside benchmark/: run from a checkout")
            return 2
    sys.path.insert(0, ROOT)
    benchmark = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if args.workload not in cells:
        say(f"no workload {args.workload!r} in BENCHMARK.json")
        return 2
    cell = cells[args.workload]
    from benchmark.lib import serving_config, traffic

    config_dir = os.path.join(BENCH, "configs", cell["config"])
    config = serving_config.load_config(config_dir, toy=args.rehearse_cpu)
    mix = traffic.load_mix(traffic.mix_path(ROOT, cell["traffic"]),
                           toy=args.rehearse_cpu)
    peaks = load_json(BENCH, "lib", "peaks.json")
    os.makedirs(os.path.join(WORK, "tmp"), exist_ok=True)
    env = child_env(args, args.seed)
    run = Run()
    run.config, run.mix = config, mix
    server = None
    try:
        ensure_native_frontend()
        server = Server(os.path.join(config_dir, "model_repository"), env, 1100)
        device = snapshot(server, config["name"])["device"]
        if not args.rehearse_cpu and (device["platform"] != "tpu"
                                      or device["count"] < cell["chips"]):
            say(f"the cell needs {cell['chips']} TPU chip(s); JAX found "
                f"{device['count']} x {device['platform']}")
            return NO_CHIP
        if device["kind"] not in peaks and not args.rehearse_cpu:
            raise RunFailure(f"no peaks for device kind {device['kind']!r} "
                             "in benchmark/lib/peaks.json")
        run.peak = peaks.get(device["kind"])
        trace_dir = drive(args, config, mix, server, run, began)
        device = run.after["device"]
        server.stop()
        server = None
        numbers = compare(args, config, mix, run, env)
        if trace_dir:
            out_path = os.path.join(WORK, "trace_summary.json")
            try:
                run_child("the trace reduction",
                          [sys.executable, "-m", "benchmark.lib.trace_child",
                           trace_dir, out_path],
                          dict(env, JAX_PLATFORMS="cpu"), 600)
                run.trace = load_json(out_path)
            except RunFailure as failure:
                if not args.rehearse_cpu:
                    raise
                # the CPU backend's trace has no device plane to reduce
                say(f"rehearsal: no trace metrics ({str(failure)[-200:]})")
            shutil.rmtree(trace_dir, ignore_errors=True)
    except RunFailure as failure:
        say(f"FAIL: {failure}")
        if "Unable to initialize backend" in str(failure):
            return NO_CHIP  # JAX found no accelerator
        return 1
    finally:
        stop_all()

    from benchmark.lib import window

    attempted, failed = window.attempted_failed(run.requests, run.t0, run.t1)
    failed += sum(run.t0 <= t < run.t1 for t, _ in run.errors)
    compiles = sum(run.t0 <= t < run.t1 for t in run.compile_times)
    compared, correct = judge(numbers, config["limits"], failed, compiles)
    metrics = {}
    for name in cell_metrics(benchmark, args.workload, bool(args.trace)):
        value, unit = read_metric(run, name)
        if value is not None:
            metrics[name] = {"value": value, "unit": unit}
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": dict(device)}
    if args.trace and run.trace is not None:
        from benchmark.lib import trace_reduce

        line["device"].update(busy_s=run.trace["busy_s"],
                              window_s=run.trace["window_s"])
        line["breakdown"] = {
            "device_ops": trace_reduce.top(run.trace["ops"]),
            "idle_gaps": trace_reduce.top(run.trace["idle_gaps"])}
    if args.rehearse_cpu:
        line["rehearsal"] = True
    if args.control:
        line["control"] = True
    line["diagnostics"] = {
        "compiles_before_window": sum(t < run.t0 for t in run.compile_times),
        "responses_per_whole_second": [
            window.responses_in_window(run.requests, run.t0 + k * 10 ** 9,
                                       run.t0 + (k + 1) * 10 ** 9)
            for k in range(int(args.seconds))],
        "reference": numbers,
    }
    line["compared"] = compared
    print(json.dumps(line), flush=True)
    for name, c in compared.items():
        say(f"compared {name}: {c['value']} (limit {c['limit']})")
    say(f"correct: {correct}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
