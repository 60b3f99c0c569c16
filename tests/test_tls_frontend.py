"""End-to-end TLS (grpcs) through the REAL native front-end.

The server process terminates TLS in C++ (``--grpc-tls-cert/key``, ALPN
h2 — reference role: tritonserver's --grpc-use-ssl server options), and
the C++ perf harness connects with the reference-named ``--ssl-grpc-*``
client options (reference src/c++/library/grpc_client.h:43-60 SslOptions,
perf_analyzer --ssl-grpc-use-ssl).
"""

import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PA = os.path.join(REPO, "build", "perf_analyzer")

pytestmark = pytest.mark.skipif(
    not os.path.exists(PA), reason="native build absent"
)


@pytest.fixture(scope="module")
def tls_certs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("tls")
    cert = str(tmp / "cert.pem")
    key = str(tmp / "key.pem")
    subprocess.run(
        [
            "openssl", "req", "-x509", "-newkey", "rsa:2048",
            "-keyout", key, "-out", cert, "-days", "2", "-nodes",
            "-subj", "/CN=localhost",
            "-addext", "subjectAltName=DNS:localhost,IP:127.0.0.1",
        ],
        check=True,
        capture_output=True,
    )
    return cert, key


@pytest.fixture(scope="module")
def tls_server(tls_certs):
    from client_tpu.testing import hermetic_child_env, parse_server_started

    cert, key = tls_certs
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "client_tpu.server",
            "--host", "127.0.0.1", "--http-port", "0", "--grpc-port", "0",
            "--grpc-frontend", "native",
            "--grpc-tls-cert", cert, "--grpc-tls-key", key,
        ],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        env=hermetic_child_env(repo_path=REPO),
        cwd=REPO,
    )
    grpc_port = None
    deadline = time.time() + 60
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        event = parse_server_started(line)
        if event is not None:
            grpc_port = event["grpc_port"]
            break
    if grpc_port is None:
        proc.kill()
        pytest.fail("TLS server did not start")
    yield f"127.0.0.1:{grpc_port}", cert
    proc.terminate()
    proc.wait(timeout=10)


def _run_pa(url, cert, extra=None):
    cmd = [
        PA, "-m", "simple", "-u", url, "-i", "grpc",
        "--ssl-grpc-use-ssl",
        "--ssl-grpc-root-certifications-file", cert,
        "--measurement-mode", "count_windows",
        "--measurement-request-count", "50",
        "--concurrency-range", "2", "--max-trials", "2",
        "--json-summary",
    ] + (extra or [])
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    summary = None
    for line in out.stdout.splitlines():
        if line.strip().startswith("{"):
            summary = json.loads(line)
    return out, summary


def test_grpcs_inference_roundtrip(tls_server):
    url, cert = tls_server
    out, summary = _run_pa(url, cert)
    assert summary is not None, out.stdout[-500:] + out.stderr[-300:]
    assert summary["throughput"] > 0
    assert summary["count"] >= 50


def test_grpcs_requires_matching_roots(tls_server, tmp_path):
    url, _cert = tls_server
    # Verification against the WRONG root must fail the handshake.
    wrong = tmp_path / "wrong.pem"
    subprocess.run(
        [
            "openssl", "req", "-x509", "-newkey", "rsa:2048",
            "-keyout", str(tmp_path / "wk.pem"), "-out", str(wrong),
            "-days", "2", "-nodes", "-subj", "/CN=localhost",
        ],
        check=True,
        capture_output=True,
    )
    out, summary = _run_pa(url, str(wrong))
    assert summary is None
    assert "certificate" in (out.stdout + out.stderr).lower() or "TLS" in (
        out.stdout + out.stderr
    )


def test_plaintext_client_rejected_by_tls_port(tls_server):
    url, _cert = tls_server
    cmd = [
        PA, "-m", "simple", "-u", url, "-i", "grpc",
        "--measurement-mode", "count_windows",
        "--measurement-request-count", "10",
        "--concurrency-range", "1", "--max-trials", "1",
    ]
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=60)
    assert out.returncode != 0
