"""Native C++ layer tests: hermetic unit tests, and live end-to-end runs
of the example client and perf_analyzer against the in-repo server (the
C++ twin of the reference's tier-1 + tier-2 strategy, SURVEY.md §4).
``build/`` is the session's (``tests/conftest.py``)."""

import json
import os
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUILD = os.path.join(REPO, "build")

pytestmark = pytest.mark.skipif(
    not os.path.exists(os.path.join(BUILD, "unit_tests")),
    reason="native build absent",
)


@pytest.fixture(scope="module")
def native_build():
    return BUILD


def test_cpp_unit_tests(native_build):
    out = subprocess.run(
        [os.path.join(native_build, "unit_tests")],
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0 failures" in out.stdout


@pytest.fixture(scope="module")
def live_server():
    from client_tpu.testing import InProcessServer

    with InProcessServer(host="127.0.0.1", grpc=False) as server:
        yield server


def test_cpp_perf_analyzer_live(native_build, live_server, tmp_path):
    export = tmp_path / "export.json"
    csv = tmp_path / "report.csv"
    out = subprocess.run(
        [os.path.join(native_build, "perf_analyzer"),
         "-m", "simple", "-u", live_server.http_url,
         "--concurrency-range", "2",
         "--measurement-interval", "500",
         "--stability-percentage", "60",
         "--max-trials", "4",
         "--json-summary",
         "-f", str(csv),
         "--profile-export-file", str(export)],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    summary = None
    for line in out.stdout.splitlines():
        if line.startswith("{"):
            summary = json.loads(line)
    assert summary is not None
    assert summary["throughput"] > 0
    assert summary["errors"] == 0
    doc = json.loads(export.read_text())
    assert doc["experiments"][0]["requests"]
    assert csv.read_text().startswith("Concurrency,")


def test_cpp_perf_analyzer_shm_live(native_build, live_server):
    out = subprocess.run(
        [os.path.join(native_build, "perf_analyzer"),
         "-m", "simple", "-u", live_server.http_url,
         "--shared-memory", "system",
         "--concurrency-range", "2",
         "--measurement-interval", "400",
         "--stability-percentage", "60",
         "--max-trials", "3",
         "--json-summary"],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    summary = json.loads(
        [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
    )
    assert summary["errors"] == 0
    assert summary["throughput"] > 0


def test_cpp_perf_analyzer_tpushm_live(native_build, live_server):
    """The north-star data plane: perf_analyzer staging inputs AND outputs
    through tpu-shm regions (BASELINE.json gRPC+TPU-shm config; reference
    infer_data_manager_shm.cc CUDA path)."""
    out = subprocess.run(
        [os.path.join(native_build, "perf_analyzer"),
         "-m", "simple", "-u", live_server.http_url,
         "--shared-memory", "tpu",
         "--output-shared-memory-size", "256",
         "--concurrency-range", "2",
         "--measurement-interval", "400",
         "--stability-percentage", "60",
         "--max-trials", "3",
         "--json-summary"],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    summary = json.loads(
        [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
    )
    assert summary["errors"] == 0
    assert summary["throughput"] > 0
    # regions were registered over the tpu extension and cleaned up
    import client_tpu.http as httpclient

    client = httpclient.InferenceServerClient(live_server.http_url)
    try:
        assert client.get_tpu_shared_memory_status() == []
    finally:
        client.close()


@pytest.fixture(scope="module")
def live_grpc_server():
    from client_tpu.testing import InProcessServer

    with InProcessServer(host="127.0.0.1", http=False, grpc=True) as server:
        yield server


def test_cpp_grpc_example_client(native_build, live_grpc_server):
    """End-to-end: native gRPC client (hand-rolled HTTP/2) against the
    grpcio server — sync Infer, AsyncInfer, bidi streaming, statistics."""
    out = subprocess.run(
        [os.path.join(native_build, "simple_grpc_infer_client"),
         "-u", live_grpc_server.grpc_url],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "PASS" in out.stdout


def test_cpp_grpc_client_error_mapping(native_build, live_grpc_server):
    """Unknown model must surface the server's grpc-status as a client
    error (exercises Call()'s trailer handling, not just transport)."""
    out = subprocess.run(
        [os.path.join(native_build, "simple_grpc_infer_client"),
         "-u", live_grpc_server.grpc_url, "-m", "no_such_model"],
        capture_output=True, text=True, timeout=60,
    )
    assert out.returncode != 0
    assert "gRPC status" in (out.stdout + out.stderr)


def test_cpp_perf_analyzer_grpc(native_build, live_grpc_server):
    out = subprocess.run(
        [os.path.join(native_build, "perf_analyzer"),
         "-m", "simple", "-u", live_grpc_server.grpc_url, "-i", "grpc",
         "--concurrency-range", "2",
         "--measurement-interval", "500",
         "--stability-percentage", "60",
         "--max-trials", "3",
         "--json-summary"],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    summary = json.loads(
        [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
    )
    assert summary["errors"] == 0
    assert summary["throughput"] > 0


@pytest.mark.parametrize("algorithm", ["deflate", "gzip"])
def test_cpp_perf_analyzer_grpc_compression(native_build, live_grpc_server,
                                            algorithm):
    """--grpc-compression-algorithm: per-message deflate/gzip request
    bodies, inflated by the server (reference kGrpcCompressionAlgorithm)."""
    out = subprocess.run(
        [os.path.join(native_build, "perf_analyzer"),
         "-m", "simple", "-u", live_grpc_server.grpc_url, "-i", "grpc",
         "--grpc-compression-algorithm", algorithm,
         "--concurrency-range", "2",
         "--measurement-interval", "500",
         "--stability-percentage", "60",
         "--max-trials", "3",
         "--json-summary"],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    summary = json.loads(
        [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
    )
    assert summary["errors"] == 0
    assert summary["throughput"] > 0


def test_cpp_perf_analyzer_binary_search(native_build, live_grpc_server):
    """--binary-search bisects the concurrency range against the latency
    threshold (reference Profile<T> binary mode)."""
    out = subprocess.run(
        [os.path.join(native_build, "perf_analyzer"),
         "-m", "simple", "-u", live_grpc_server.grpc_url, "-i", "grpc",
         "--binary-search", "--concurrency-range", "1:8",
         "--latency-threshold", "10000",
         "--measurement-interval", "400",
         "--stability-percentage", "60",
         "--max-trials", "2",
         "--json-summary"],
        capture_output=True, text=True, timeout=180,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    summary = json.loads(
        [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
    )
    # a 10-second budget is unreachable on loopback: search ends at 8
    assert summary["value"] == 8
    assert summary["errors"] == 0


def test_cpp_perf_analyzer_grpc_streaming_decoupled(native_build,
                                                    live_grpc_server):
    """Decoupled bidi streaming: one request -> N timestamped responses."""
    out = subprocess.run(
        [os.path.join(native_build, "perf_analyzer"),
         "-m", "repeat_int32", "-u", live_grpc_server.grpc_url, "-i", "grpc",
         "--streaming", "--shape", "IN:4",
         "--concurrency-range", "2",
         "--measurement-interval", "500",
         "--stability-percentage", "60",
         "--max-trials", "3",
         "--json-summary"],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    summary = json.loads(
        [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
    )
    assert summary["errors"] == 0
    assert summary["throughput"] > 0


def test_cpp_perf_analyzer_collect_metrics(native_build, live_server):
    """--collect-metrics scrapes the server's Prometheus endpoint."""
    out = subprocess.run(
        [os.path.join(native_build, "perf_analyzer"),
         "-m", "simple", "-u", live_server.http_url,
         "--collect-metrics", "--metrics-interval", "200",
         "--concurrency-range", "2",
         "--measurement-interval", "400",
         "--stability-percentage", "60",
         "--max-trials", "3"],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "Server metrics" in out.stdout
    assert 'tpu_inference_count{model="simple"}' in out.stdout


@pytest.fixture(scope="module")
def live_llm_server():
    from client_tpu.models.serving import register_zoo_models
    from client_tpu.server.core import ServerCore
    from client_tpu.server.model_repository import ModelRepository
    from client_tpu.testing import InProcessServer

    repository = ModelRepository()
    core = ServerCore(repository)
    register_zoo_models(repository)
    with InProcessServer(core=core, host="127.0.0.1", grpc=False,
                         builtin_models=False) as server:
        yield server


def test_cpp_perf_analyzer_openai_sse(native_build, live_llm_server,
                                      tmp_path):
    """OpenAI chat-completions benchmark with SSE streaming against the
    in-repo OpenAI front-end (JAX llama decode behind it)."""
    payload = json.dumps({
        "model": "llm_decode",
        "messages": [{"role": "user", "content": "hello world how are you"}],
        "max_tokens": 4,
    })
    input_file = tmp_path / "openai_input.json"
    input_file.write_text(json.dumps({"data": [{"payload": [payload]}]}))
    out = subprocess.run(
        [os.path.join(native_build, "perf_analyzer"),
         "-m", "llm_decode", "-u", live_llm_server.http_url,
         "--service-kind", "openai", "--streaming",
         "--input-data", str(input_file),
         "--concurrency-range", "2",
         "--measurement-interval", "600",
         "--stability-percentage", "60",
         "--max-trials", "3",
         "--json-summary"],
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    summary = json.loads(
        [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
    )
    assert summary["errors"] == 0
    assert summary["throughput"] > 0


def test_cpp_perf_analyzer_local_inprocess(native_build):
    """--service-kind local embeds CPython and runs the ServerCore
    in-process (triton_c_api analogue): no sockets in the path."""
    from client_tpu.testing import hermetic_child_env

    env = hermetic_child_env(repo_path=REPO)
    out = subprocess.run(
        [os.path.join(native_build, "perf_analyzer"),
         "-m", "simple", "--service-kind", "local",
         "--concurrency-range", "2",
         "--measurement-interval", "500",
         "--stability-percentage", "60",
         "--max-trials", "3",
         "--json-summary"],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    summary = json.loads(
        [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
    )
    assert summary["errors"] == 0
    assert summary["throughput"] > 0


def test_cpp_perf_analyzer_multiprocess(native_build, live_server):
    """Two perf_analyzer ranks rendezvous, measure together, and both
    produce summaries (MPI-driver equivalent, reference mpi_utils)."""
    port = 20000 + os.getpid() % 10000  # avoid cross-run collisions
    base = [os.path.join(native_build, "perf_analyzer"),
            "-m", "simple", "-u", live_server.http_url,
            "--concurrency-range", "2",
            "--measurement-interval", "400",
            "--stability-percentage", "60",
            "--max-trials", "3",
            "--json-summary",
            "--world-size", "2", "--coordinator", f"127.0.0.1:{port}"]
    procs = [
        subprocess.Popen(base + ["--rank", str(rank)],
                         stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                         text=True)
        for rank in (0, 1)
    ]
    outs = [p.communicate(timeout=180) for p in procs]
    for p, (stdout, stderr) in zip(procs, outs):
        assert p.returncode == 0, stdout + stderr
        summary = json.loads(
            [l for l in stdout.splitlines() if l.startswith("{")][-1]
        )
        assert summary["errors"] == 0
        assert summary["throughput"] > 0


def test_python_native_mixed_rendezvous(native_build, live_server):
    """A Python-harness rank and a native rank share one rendezvous
    (same wire protocol on both sides)."""
    import sys

    native = subprocess.Popen(
        [os.path.join(native_build, "perf_analyzer"),
         "-m", "simple", "-u", live_server.http_url,
         "--concurrency-range", "1",
         "--measurement-interval", "400",
         "--stability-percentage", "60",
         "--max-trials", "2",
         "--world-size", "2", "--rank", "0",
         "--coordinator", f"127.0.0.1:{20000 + (os.getpid() + 1) % 10000}"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    from client_tpu.testing import hermetic_child_env

    env = hermetic_child_env(repo_path=REPO)
    pyrank = subprocess.Popen(
        [sys.executable, "-m", "client_tpu.perf.cli",
         "-m", "simple", "-u", live_server.http_url,
         "--concurrency-range", "1",
         "--measurement-interval", "400",
         "--stability-percentage", "60",
         "--max-trials", "2",
         "--world-size", "2", "--rank", "1",
         "--coordinator", f"127.0.0.1:{20000 + (os.getpid() + 1) % 10000}"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
    )
    nout = native.communicate(timeout=180)
    pout = pyrank.communicate(timeout=180)
    assert native.returncode == 0, nout[0] + nout[1]
    assert pyrank.returncode == 0, pout[0] + pout[1]


def test_cpp_perf_analyzer_input_data_dir(native_build, live_server, tmp_path):
    """--input-data <directory>: per-input raw files drive the C++ harness
    (reference ReadDataFromDir, data_loader.h:63)."""
    import numpy as np

    (tmp_path / "INPUT0").write_bytes(
        np.arange(16, dtype=np.int32).tobytes()
    )
    (tmp_path / "INPUT1").write_bytes(
        np.ones(16, dtype=np.int32).tobytes()
    )
    out = subprocess.run(
        [os.path.join(native_build, "perf_analyzer"),
         "-m", "simple", "-u", live_server.http_url,
         "--input-data", str(tmp_path),
         "--concurrency-range", "2",
         "--measurement-interval", "500",
         "--stability-percentage", "60",
         "--max-trials", "2",
         "--json-summary"],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    summary = json.loads(
        [l for l in out.stdout.splitlines() if l.strip().startswith("{")][0]
    )
    assert summary["throughput"] > 0
    assert summary["errors"] == 0


def test_cpp_perf_analyzer_sequence_autodetect(native_build, live_grpc_server):
    """Sequence scheduling auto-detected from model config — no
    --sequence-model flag (reference perf_analyzer.cc:147-148)."""
    out = subprocess.run(
        [os.path.join(native_build, "perf_analyzer"),
         "-m", "sequence_accumulate", "-u", live_grpc_server.grpc_url,
         "-i", "grpc",
         "--concurrency-range", "2",
         "--measurement-interval", "500",
         "--stability-percentage", "80",
         "--max-trials", "2",
         "--json-summary"],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    summary = json.loads(
        [l for l in out.stdout.splitlines() if l.strip().startswith("{")][0]
    )
    assert summary["throughput"] > 0
    assert summary["errors"] == 0


def test_cpp_perf_analyzer_ensemble(native_build, live_grpc_server):
    """Ensembles profile correctly: the parser walks composing models and
    the harness drives the pipeline end to end."""
    out = subprocess.run(
        [os.path.join(native_build, "perf_analyzer"),
         "-m", "add_sub_chain", "-u", live_grpc_server.grpc_url,
         "-i", "grpc",
         "--concurrency-range", "2",
         "--measurement-interval", "500",
         "--stability-percentage", "80",
         "--max-trials", "2",
         "--json-summary"],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    summary = json.loads(
        [l for l in out.stdout.splitlines() if l.strip().startswith("{")][0]
    )
    assert summary["throughput"] > 0
    assert summary["errors"] == 0


@pytest.fixture(scope="module")
def live_zoo_grpc_server():
    """gRPC server with the zoo models (image_classifier for image_client)."""
    from client_tpu.models.serving import register_zoo_models
    from client_tpu.server.core import ServerCore
    from client_tpu.server.model_repository import ModelRepository
    from client_tpu.testing import InProcessServer

    repo = ModelRepository()
    core = ServerCore(repo)
    register_zoo_models(repo, small=True)
    with InProcessServer(core=core, host="127.0.0.1", http=True) as server:
        yield server


@pytest.mark.parametrize(
    "example",
    [
        "simple_http_infer_client",
        "simple_grpc_infer_client",
        "simple_grpc_shm_client",
        "simple_grpc_tpushm_client",
        "simple_grpc_sequence_client",
        "simple_grpc_stream_infer_client",
        "image_client",
        "ensemble_chain_client",
        "simple_grpc_string_infer_client",
        "simple_http_string_infer_client",
        "simple_http_shm_client",
        "simple_grpc_async_infer_client",
        "simple_grpc_health_metadata",
        "simple_grpc_model_control",
        "simple_grpc_infer_multi_client",
        "simple_grpc_custom_repeat_client",
        "simple_grpc_keepalive_client",
        "reuse_infer_objects_client",
    ],
)
def test_cpp_example_suite(native_build, live_zoo_grpc_server, example):
    """Every C++ example binary smoke-runs against a live server
    (reference src/c++/examples/ is its de-facto integration suite)."""
    url = (
        live_zoo_grpc_server.http_url
        if "http" in example
        else live_zoo_grpc_server.grpc_url
    )
    out = subprocess.run(
        [os.path.join(native_build, example), "-u", url],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "PASS" in out.stdout


def test_cpp_perf_analyzer_tfserving(native_build, live_zoo_grpc_server):
    """--service-kind tfserving drives the TFS REST adapter: metadata from
    the signature block, row-format JSON instances (reference
    client_backend/tensorflow_serving/ role)."""
    out = subprocess.run(
        [os.path.join(native_build, "perf_analyzer"),
         "-m", "text_encoder", "-u", live_zoo_grpc_server.http_url,
         "--service-kind", "tfserving",
         "--shape", "INPUT_IDS:8",
         "--warmup-request-period", "1",
         "--concurrency-range", "2",
         "--measurement-interval", "1000",
         "--stability-percentage", "80",
         "--max-trials", "2",
         "--json-summary"],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    summary = json.loads(
        [l for l in out.stdout.splitlines() if l.strip().startswith("{")][0]
    )
    assert summary["throughput"] > 0
    assert summary["errors"] == 0


def test_cpp_perf_analyzer_torchserve(native_build, live_zoo_grpc_server,
                                      tmp_path):
    """--service-kind torchserve posts raw bodies to /predictions/<m>
    (reference client_backend/torchserve/ role; like the reference, input
    bytes come from --input-data)."""
    import numpy as np

    # TorchServe's fabricated contract is a BYTES 'data' input; feed it the
    # raw int32 tensor the text_encoder adapter will decode.
    (tmp_path / "data").write_bytes(
        np.arange(1, 9, dtype=np.int32).tobytes()
    )
    out = subprocess.run(
        [os.path.join(native_build, "perf_analyzer"),
         "-m", "text_encoder", "-u", live_zoo_grpc_server.http_url,
         "--service-kind", "torchserve",
         "--input-data", str(tmp_path),
         "--warmup-request-period", "1",
         "--concurrency-range", "2",
         "--measurement-interval", "1000",
         "--stability-percentage", "80",
         "--max-trials", "2",
         "--json-summary"],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    summary = json.loads(
        [l for l in out.stdout.splitlines() if l.strip().startswith("{")][0]
    )
    assert summary["throughput"] > 0
    assert summary["errors"] == 0


def test_cpp_perf_analyzer_json_tensor_format(native_build, live_server):
    """--input-tensor-format json drives pure-JSON inference bodies."""
    out = subprocess.run(
        [os.path.join(native_build, "perf_analyzer"),
         "-m", "simple", "-u", live_server.http_url,
         "--input-tensor-format", "json",
         "--concurrency-range", "2",
         "--measurement-interval", "500",
         "--stability-percentage", "80",
         "--max-trials", "2",
         "--json-summary"],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    summary = json.loads(
        [l for l in out.stdout.splitlines() if l.strip().startswith("{")][0]
    )
    assert summary["throughput"] > 0
    assert summary["errors"] == 0


def test_cpp_perf_analyzer_trace_forwarding(native_build, live_grpc_server):
    """--trace-level reaches the server's trace API before the run
    (reference client_backend.h:296 trace forwarding)."""
    out = subprocess.run(
        [os.path.join(native_build, "perf_analyzer"),
         "-m", "simple", "-u", live_grpc_server.grpc_url, "-i", "grpc",
         "--trace-level", "TIMESTAMPS",
         "--trace-rate", "500",
         "--concurrency-range", "1",
         "--measurement-interval", "300",
         "--max-trials", "1",
         "--json-summary"],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    # The server must now report the forwarded settings.
    import client_tpu.grpc as grpcclient

    with grpcclient.InferenceServerClient(
        live_grpc_server.grpc_url
    ) as client:
        settings = client.get_trace_settings(as_json=True)["settings"]
    def values(entry):
        # MessageToDict of the TraceSetting map value: {"value": [...]}
        if isinstance(entry, dict):
            return entry.get("value", entry)
        return entry

    assert values(settings["trace_level"]) == ["TIMESTAMPS"]
    assert values(settings["trace_rate"]) == ["500"]


def test_cpp_json_tensor_format_hits_the_wire(native_build):
    """The json format must actually change the wire bytes: a capture
    server asserts Content-Type application/json and a JSON body with
    'data' lists (a silent fallback to the binary extension would pass the
    live test, so pin the encoding here)."""
    import http.server
    import threading

    captured = {}

    class Handler(http.server.BaseHTTPRequestHandler):
        def log_message(self, *args):
            pass

        def _send_json(self, payload):
            body = json.dumps(payload).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            if self.path.endswith("/config"):
                self._send_json({"name": "simple", "max_batch_size": 8})
            else:  # metadata
                self._send_json({
                    "name": "simple",
                    "inputs": [{"name": "IN", "datatype": "INT32",
                                "shape": [-1, 4]}],
                    "outputs": [{"name": "OUT", "datatype": "INT32",
                                 "shape": [-1, 4]}],
                })

        def do_POST(self):
            length = int(self.headers.get("Content-Length", 0))
            body = self.rfile.read(length)
            captured.setdefault("requests", []).append(
                (self.headers.get("Content-Type"), body)
            )
            self._send_json({"outputs": []})

    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        out = subprocess.run(
            [os.path.join(native_build, "perf_analyzer"),
             "-m", "simple", "-u", f"127.0.0.1:{server.server_port}",
             "--input-tensor-format", "json",
             "--request-parameter", "probe:42:int",
             "--concurrency-range", "1",
             "--measurement-interval", "300",
             "--max-trials", "1",
             "--json-summary"],
            capture_output=True, text=True, timeout=60,
        )
        assert out.returncode == 0, out.stdout + out.stderr
    finally:
        server.shutdown()
        thread.join(timeout=10)
    assert captured["requests"], "no inference requests captured"
    content_type, body = captured["requests"][0]
    assert content_type == "application/json"
    doc = json.loads(body)  # pure JSON: no binary section appended
    tensor = doc["inputs"][0]
    assert tensor["name"] == "IN"
    assert isinstance(tensor["data"], list)
    assert len(tensor["data"]) == 4
    assert "binary_data_size" not in tensor.get("parameters", {})
    # request-level parameters ride along
    assert doc["parameters"]["probe"] == 42
