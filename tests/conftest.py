"""Test configuration.

Default tier: force JAX onto a virtual 8-device CPU platform so
sharding/mesh tests run anywhere (multi-chip TPU hardware is exercised
separately by the driver's ``dryrun_multichip`` entry point). Must run
before jax is imported.

TPU tier: tests marked ``@pytest.mark.tpu`` run on the real device and are
selected with ``pytest -m tpu``. Set ``CLIENT_TPU_TEST_PLATFORM=tpu`` (or
``device``) to SKIP the CPU pin entirely so the marked tests see the real
platform:

    CLIENT_TPU_TEST_PLATFORM=tpu python -m pytest tests/ -m tpu -q

Without that env var, ``-m tpu`` tests are skipped (they would run on the
CPU backend and pass vacuously); with it, a missing accelerator FAILS
them. The sandbox has no chip: the tier runs on one through
``python chip_smoke.py``, which owns the chip one child at a time.

Native tier: the session builds ``native/`` into ``build/`` first, once,
in the process that starts the run (the xdist controller; the one
process when there are no workers) and before any test file is
imported, so that ``build/`` is the same for every worker from its first
test: the skip conditions of the files in :data:`NATIVE_TEST_FILES` and
which gRPC front-end ``InProcessServer(grpc=True)`` serves no longer
depend on who got there first. A build that fails FAILS the tests of
those files, each with the compiler's last lines, and every other file
runs. Without ``cmake`` or ``ninja`` nothing is built and those files
skip, which the run's header says once.
"""

import os
import shutil
import subprocess
import sys

TPU_TIER = os.environ.get("CLIENT_TPU_TEST_PLATFORM", "").lower() in (
    "tpu",
    "device",
)

if not TPU_TIER:
    # Force, don't setdefault: the environment pre-sets JAX_PLATFORMS (e.g.
    # to the TPU platform), and the hermetic tier must run on the virtual
    # CPU mesh regardless.
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    # A pytest plugin imports jax before this conftest runs, so jax's config
    # has already captured the original JAX_PLATFORMS value; override it
    # before any backend initializes (backends are still uninitialized here).
    import jax  # noqa: E402

    jax.config.update("jax_platforms", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

if TPU_TIER:
    # the device tier compiles full-width programs: keep them across runs
    from client_tpu.compile_cache import enable_compile_cache  # noqa: E402

    enable_compile_cache()

import pytest  # noqa: E402  (after the platform pinning above)

#: the files whose tests drive the binaries and the extension in build/
NATIVE_TEST_FILES = frozenset({
    "test_native.py",
    "test_native_frontend.py",
    "test_tls_frontend.py",
    "test_integration_cc.py",
})

#: how the session's one native build went, handed from the process that
#: made it to the workers and the re-exec'd children it starts (set in no
#: other way): "" built, "absent" no cmake/ninja, else the build's error
NATIVE_BUILD_ENV = "CLIENT_TPU_TEST_NATIVE_BUILD"


def _build_native_once() -> None:
    """Build ``native/`` unless a parent of this process already did."""
    if NATIVE_BUILD_ENV in os.environ:
        return
    if shutil.which("cmake") is None or shutil.which("ninja") is None:
        os.environ[NATIVE_BUILD_ENV] = "absent"
        return
    from tools.build_wheel import build_native

    try:
        build_native(
            os.path.join(REPO, "build"), capture_output=True, text=True,
            timeout=600,
        )
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired) as e:
        # a timeout hands back bytes whatever `text` says
        said = "".join(
            part.decode(errors="replace") if isinstance(part, bytes) else part
            for part in (e.stdout, e.stderr) if part
        )
        os.environ[NATIVE_BUILD_ENV] = (
            f"native build failed: {e}\n{said[-3000:]}"
        )
    else:
        os.environ[NATIVE_BUILD_ENV] = ""


def pytest_report_header(config):
    state = os.environ.get(NATIVE_BUILD_ENV)
    if state is None:  # the device tier builds nothing
        return None
    files = ", ".join(sorted(NATIVE_TEST_FILES))
    if state == "absent":
        return f"native: cmake/ninja absent, nothing built; {files} skip"
    if state:
        return f"native: BUILD FAILED, the tests of {files} fail with it"
    return "native: build/ is current"


@pytest.hookimpl(tryfirst=True)
def pytest_runtest_setup(item):
    """Ahead of the skip marks: a native build that failed is a failure
    of every test that needs it, never a skip for an absent file."""
    state = os.environ.get(NATIVE_BUILD_ENV, "")
    if state and state != "absent" and item.path.name in NATIVE_TEST_FILES:
        pytest.fail(state, pytrace=False)


def pytest_configure(config):
    if not TPU_TIER and not hasattr(config, "workerinput"):
        _build_native_once()
    config.addinivalue_line(
        "markers",
        "tpu: runs on the real TPU device (select with -m tpu and "
        "CLIENT_TPU_TEST_PLATFORM=tpu); skipped otherwise",
    )
    config.addinivalue_line(
        "markers",
        "sharded: needs a multi-device (CPU-mesh) jax platform; the "
        "sharded_devices fixture re-execs the test in a subprocess with "
        "XLA_FLAGS=--xla_force_host_platform_device_count=8 when this "
        "process's backend initialized single-device",
    )
    config.addinivalue_line(
        "markers",
        "wirefast: PR-11 wire fast path (protobuf-free codec, shm ring, "
        "multiplexed streams) — select with -m wirefast",
    )
    config.addinivalue_line(
        "markers",
        "pod: PR-19 multi-process pod (jax.distributed) tests; the "
        "pod_runtime fixture re-execs the test as a coordinator/worker "
        "subprocess pair, each device-capped so only the assembled pod "
        "holds the full mesh — select with -m pod",
    )
    config.addinivalue_line(
        "markers",
        "fleet: PR-12 multi-replica fleet runtime (routing policies, "
        "hedging, FleetRunner chaos) — select with -m fleet",
    )
    # Clock-injection lint: observability/resilience must never call
    # time.*() clocks directly (their tests run on fake clocks). Failing
    # at session start beats a flaky sleep-based test later.
    import pytest

    from tools.clock_lint import run_clock_lint

    problems = run_clock_lint()
    if problems:
        raise pytest.UsageError(
            "clock lint failed (injectable clocks only in "
            "client_tpu/lifecycle, client_tpu/observability, "
            "client_tpu/resilience and client_tpu/scheduling):\n"
            + "\n".join(problems)
        )
    # Structured-logging lint: the server-side packages must emit through
    # the StructuredLogger (JSON, severity-gated, /v2/logging-controlled)
    # — bare print() and stdlib logging bypass all of that.
    from tools.log_lint import run_log_lint

    problems = run_log_lint()
    if problems:
        raise pytest.UsageError(
            "log lint failed (no bare print()/stdlib logging in "
            "client_tpu/server and client_tpu/observability; use "
            "client_tpu.observability.logging.StructuredLogger):\n"
            + "\n".join(problems)
        )
    # Metric-naming lint: /metrics families follow the Prometheus
    # conventions (tpu_ prefix, _total counters, _seconds/_bytes/_ratio
    # units) — a non-compliant name is a wire-compatibility liability
    # the moment a dashboard keys on it.
    from tools.metric_lint import run_metric_lint

    problems = run_metric_lint()
    if problems:
        raise pytest.UsageError(
            "metric lint failed (tpu_ prefix + unit-suffix conventions "
            "on every family in client_tpu/server/metrics.py; see "
            "tools/metric_lint.py):\n" + "\n".join(problems)
        )


def sharded_reexec_env(device_count: int = 8):
    """The environment a re-exec'd sharded test (or bench row) runs
    under: CPU platform forced to ``device_count`` virtual devices.
    JAX fixes its device count at first backend init, so an
    already-single-device process can only get a mesh by re-executing."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={device_count}"
    )
    env["CLIENT_TPU_SHARDED_REEXEC"] = "1"
    return env


@pytest.fixture
def sharded_devices(request):
    """Devices for sharded (multi-device mesh) tests.

    In the hermetic tier this conftest already pinned an 8-device CPU
    platform, so the fixture just returns ``jax.devices()``. When the
    current process's backend initialized with too few devices (device
    count is frozen at first init — it cannot be raised in-process),
    the test re-execs itself in a subprocess under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``: the
    subprocess runs the real assertions, and this invocation reports
    its verdict (skip-with-evidence carries the pass; a subprocess
    failure fails here with its output). If the platform refuses the
    forced device count even in the subprocess, the test skips with
    the observed device count as evidence.
    """
    import subprocess
    import jax

    # the widest mesh the sharded tests declare is dp=2 x tp=2: a
    # backend with fewer than 4 devices would register those models
    # UNAVAILABLE instead of serving them, so it re-execs too
    required = 4
    devices = jax.devices()
    if len(devices) >= required:
        return devices
    if os.environ.get("CLIENT_TPU_SHARDED_REEXEC"):
        pytest.skip(
            "platform refuses a multi-device CPU mesh: "
            f"{len(devices)} device(s) (need {required}) despite "
            f"XLA_FLAGS={os.environ.get('XLA_FLAGS', '')!r}"
        )
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "pytest",
            "-q",
            "-p",
            "no:cacheprovider",
            request.node.nodeid,
        ],
        cwd=REPO,
        env=sharded_reexec_env(),
        capture_output=True,
        text=True,
        timeout=600,
    )
    if proc.returncode == 0:
        pytest.skip(
            "single-device backend in this process; PASSED in the "
            "re-exec'd 8-device subprocess"
        )
    tail = (proc.stdout + proc.stderr)[-2000:]
    pytest.fail(
        f"re-exec'd sharded subprocess failed (rc {proc.returncode}):\n"
        f"{tail}"
    )


POD_REEXEC_ENV = "CLIENT_TPU_POD_TEST_REEXEC"


@pytest.fixture
def pod_runtime(request):
    """A live 2-process pod for ``@pytest.mark.pod`` tests.

    Mirrors ``sharded_devices``, but where that fixture re-execs ONE
    subprocess with a wider device count, this one re-execs the test as
    a coordinator/worker PAIR: each member gets the pod identity
    environment (:class:`client_tpu.pod.runtime.PodConfig`) plus a
    2-device ``XLA_FLAGS`` cap, joins ``jax.distributed`` inside the
    fixture, and runs the test body against the assembled 4-device
    global mesh — a mesh neither member's capped backend could hold
    alone. Both members run the SAME test body (SPMD: every process must
    enter every collective).

    Verdict plumbing matches ``sharded_devices``: both members passing
    skips here with the evidence; any failure fails here with both log
    tails. When the platform refuses ``jax.distributed`` on CPU the
    member skips with the refusal as evidence and this invocation
    surfaces that skip rather than a pass.
    """
    import subprocess

    if os.environ.get(POD_REEXEC_ENV):
        from client_tpu.pod.runtime import PodConfig, initialize

        config = PodConfig.from_env()
        if config is None:
            pytest.fail(
                "pod re-exec env set but no pod identity handed down"
            )
        try:
            return initialize(config)
        except RuntimeError as e:
            pytest.skip(f"platform refuses jax.distributed on CPU: {e}")
    from client_tpu.pod.launcher import _free_port
    from client_tpu.pod.runtime import PodConfig

    process_count, devices_per_process = 2, 2
    coordinator = f"127.0.0.1:{_free_port()}"
    procs = []
    for index in range(process_count):
        env = dict(os.environ)
        env.update(
            PodConfig(
                coordinator_address=coordinator,
                process_index=index,
                process_count=process_count,
                local_devices=devices_per_process,
            ).env()
        )
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count="
            f"{devices_per_process}"
        )
        env[POD_REEXEC_ENV] = "1"
        procs.append(
            subprocess.Popen(
                [
                    sys.executable,
                    "-m",
                    "pytest",
                    "-q",
                    "-rs",  # print skip reasons: the refusal evidence
                    "-p",
                    "no:cacheprovider",
                    request.node.nodeid,
                ],
                cwd=REPO,
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outputs, rcs = [], []
    for proc in procs:
        try:
            out, _ = proc.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, _ = proc.communicate()
        outputs.append(out or "")
        rcs.append(proc.returncode)
    if all(rc == 0 for rc in rcs):
        refusal = next(
            (
                line.strip()
                for out in outputs
                for line in out.splitlines()
                if "platform refuses jax.distributed" in line
            ),
            None,
        )
        if refusal:
            pytest.skip(f"pod member skipped: {refusal}")
        pytest.skip(
            "single-process backend here; PASSED in the re-exec'd "
            "2-process pod subprocess pair"
        )
    tails = "\n".join(
        f"--- pod member {index} (rc {rc}) ---\n{out[-2000:]}"
        for index, (rc, out) in enumerate(zip(rcs, outputs))
    )
    pytest.fail(f"re-exec'd pod subprocess pair failed:\n{tails}")


def pytest_collection_modifyitems(config, items):
    import pytest

    if TPU_TIER:
        # On the device tier, run ONLY the tpu-marked tests by default —
        # the hermetic suite assumes the 8-device CPU mesh.
        skip_cpu = pytest.mark.skip(
            reason="device tier runs only -m tpu tests"
        )
        for item in items:
            if "tpu" not in item.keywords:
                item.add_marker(skip_cpu)
    else:
        skip_tpu = pytest.mark.skip(
            reason="needs CLIENT_TPU_TEST_PLATFORM=tpu (real device)"
        )
        for item in items:
            if "tpu" in item.keywords:
                item.add_marker(skip_tpu)


@pytest.fixture
def drive_core_path():
    """``drive(core, path, flags)``: one request a flag down ONE of
    ``ServerCore``'s four execution paths, all at once, and what each
    came back as (a response, a list of a stream's responses, or the
    exception). ``path`` is ``single`` (``_infer_single``), ``batcher``
    (``_ModelBatcher``), ``direct`` (``infer_direct``) or ``decoupled``
    (``infer_decoupled``). The model it registers for the path adds one
    to its ``[1, 4]`` input and raises where a row's flag is 999; it
    declares an SLO, so that the telemetry's budget window counts
    failures too. On the two merging paths the flags of one call share
    one execution, so one 999 among them fails them all.
    """
    import asyncio

    import numpy as np

    from client_tpu.server.core import CoreRequest, CoreTensor
    from client_tpu.server.model_repository import Model

    def add_one(x):
        if (np.asarray(x) == 999.0).any():
            raise RuntimeError("flag 999: injected model failure")
        return x + 1.0

    class PathModel(Model):
        inputs = [{"name": "X", "datatype": "FP32", "shape": [4]}]
        outputs = [{"name": "Y", "datatype": "FP32", "shape": [4]}]
        slo = {"availability": 0.9}

        def __init__(self, name, max_batch_size):
            self.name = name
            self.max_batch_size = max_batch_size

        def execute(self, inputs, parameters):
            return {"Y": add_one(inputs["X"])}

    class StreamModel(PathModel):
        decoupled = True

        async def execute_decoupled(self, inputs, parameters):
            y = add_one(inputs["X"])
            yield {"Y": y}
            yield {"Y": y, "__final__": True}

    models = {
        "single": PathModel("path_single", 0),
        "batcher": PathModel("path_batcher", 8),
        "direct": PathModel("path_direct", 8),
        "decoupled": StreamModel("path_decoupled", 0),
    }

    def drive(core, path, flags):
        model = models[path]
        if core.repository.peek(model.name) is None:
            core.repository.add_model(model)
        requests = [
            CoreRequest(
                model_name=model.name,
                id=f"{path}-{i}",
                inputs=[
                    CoreTensor(
                        "X", "FP32", [1, 4],
                        np.full([1, 4], flag, dtype=np.float32),
                    )
                ],
            )
            for i, flag in enumerate(flags)
        ]
        if path == "direct":
            return core.infer_direct(requests)

        async def one(request):
            try:
                if path == "decoupled":
                    return [r async for r in core.infer_decoupled(request)]
                return await core.infer(request)
            except RuntimeError as e:
                return e

        async def all_at_once():
            return await asyncio.gather(*(one(r) for r in requests))

        return asyncio.run(all_at_once())

    return drive


@pytest.fixture
def loopback_echo():
    """``with loopback_echo(core) as echo``: ``core`` serving a pure-numpy
    echo model (``echo``, unbatched: JAX stays out of the request) over
    loopback HTTP; ``echo.send(n)`` makes n OK requests one after
    another on one connection, ``echo.get(path)`` reads a debug
    endpoint's JSON."""
    import http.client
    import json

    from client_tpu.server.model_repository import Model
    from client_tpu.testing import InProcessServer

    class EchoModel(Model):
        inputs = [{"name": "X", "datatype": "FP32", "shape": [-1, 4]}]
        outputs = [{"name": "Y", "datatype": "FP32", "shape": [-1, 4]}]
        name = "echo"
        max_batch_size = 0

        def execute(self, inputs, parameters):
            return {"Y": inputs["X"] + 1.0}

    body = json.dumps({
        "inputs": [{
            "name": "X", "datatype": "FP32", "shape": [1, 4],
            "data": [1.0, 2.0, 3.0, 4.0],
        }]
    }).encode()

    class LoopbackEcho:
        def __init__(self, core):
            core.repository.add_model(EchoModel())
            self._server = InProcessServer(
                core=core, grpc=False, builtin_models=False
            )

        def __enter__(self):
            server = self._server.__enter__()
            self._conn = http.client.HTTPConnection(
                server._host, server.http_port, timeout=30
            )
            return self

        def __exit__(self, *exc):
            self._conn.close()
            return self._server.__exit__(*exc)

        def send(self, n):
            for _ in range(n):
                self._conn.request(
                    "POST", "/v2/models/echo/infer", body=body
                )
                resp = self._conn.getresponse()
                resp.read()
                assert resp.status == 200

        def get(self, path):
            self._conn.request("GET", path)
            resp = self._conn.getresponse()
            assert resp.status == 200
            return json.loads(resp.read())

    return LoopbackEcho
