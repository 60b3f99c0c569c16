"""Structured logging + flight recorder (PR 7).

Covers: the StructuredLogger on fake clocks (severity gates, per-model
overrides, rate limiting with suppressed counts, file/sink exporters,
ISO8601), the FlightRecorder sub-buffer semantics, /v2/logging round-trips
that CHANGE emission live on both front-ends, a deliberately failed
request retrievable from /v2/debug/requests with stage timings + error
text + trace id, /v2/debug/state under concurrent load and during drain,
EndpointPool/CircuitBreaker client-side events, the print/stdlib-logging
lint, the perf harness --dump-slow-requests/--log-file flags, and what
the default-on recorder and the quiet logger cost as counts on counting
clocks: clock reads, records and log lines a request, on each of
``ServerCore``'s four execution paths, and none with no capacity.
"""

import asyncio
import io
import json
import threading
import urllib.request

import numpy as np
import pytest

import client_tpu.grpc as grpcclient
import client_tpu.http as httpclient
from client_tpu.observability import FlightRecorder, StructuredLogger
from client_tpu.observability.logging import validate_log_settings
from client_tpu.testing import InProcessServer
from client_tpu.utils import InferenceServerException

pytestmark = pytest.mark.logging


class FakeClock:
    def __init__(self, start=1000.0):
        self.now = start

    def __call__(self):
        return self.now

    def advance(self, s):
        self.now += s


def _logger(events=None, **kwargs):
    kwargs.setdefault("clock", FakeClock())
    sink = events.append if events is not None else None
    return StructuredLogger(name="test", sink=sink, **kwargs)


def _simple_inputs(mod):
    in0 = np.arange(16, dtype=np.int32).reshape(1, 16)
    in1 = np.ones([1, 16], dtype=np.int32)
    a = mod.InferInput("INPUT0", [1, 16], "INT32")
    a.set_data_from_numpy(in0)
    b = mod.InferInput("INPUT1", [1, 16], "INT32")
    b.set_data_from_numpy(in1)
    return [a, b]


# ---------------------------------------------------------------------------
# validation (canonical home moved; back-compat imports must keep working)


def test_validate_log_settings_import_compat():
    from client_tpu.observability import validate_log_settings as from_pkg
    from client_tpu.observability.server import (
        validate_log_settings as from_server,
    )

    assert from_pkg is validate_log_settings
    assert from_server is validate_log_settings
    assert validate_log_settings({"log_verbose_level": 2}) == {
        "log_verbose_level": 2
    }
    with pytest.raises(InferenceServerException, match="unknown log"):
        validate_log_settings({"verbosity": 1})
    with pytest.raises(InferenceServerException, match="boolean"):
        validate_log_settings({"log_info": "yes"})


# ---------------------------------------------------------------------------
# StructuredLogger units


def test_logger_severity_gates_follow_live_settings():
    events = []
    log = _logger(events)
    log.error("e1")
    log.warning("w1")
    log.info("i1")
    assert [e["event"] for e in events] == ["e1", "w1", "i1"]
    log.update({"log_error": False, "log_info": False})
    log.error("e2")
    log.info("i2")
    log.warning("w2")
    assert [e["event"] for e in events] == ["e1", "w1", "i1", "w2"]
    # re-enable live: no restart, no re-construction
    log.update({"log_error": True})
    log.error("e3")
    assert events[-1]["event"] == "e3"


def test_logger_verbose_level_gating_and_hot_flag():
    events = []
    log = _logger(events)
    assert log.verbose_hot is False
    log.verbose("v0")
    assert events == []
    log.update({"log_verbose_level": 1})
    assert log.verbose_hot is True
    log.verbose("v1")
    log.verbose("v2-needs-more", level=2)
    assert [e["event"] for e in events] == ["v1"]
    log.update({"log_verbose_level": 2})
    log.verbose("v2", level=2)
    assert events[-1]["event"] == "v2"
    log.update({"log_verbose_level": 0})
    assert log.verbose_hot is False


def test_logger_per_model_overrides_and_none_clears():
    events = []
    log = _logger(events)
    log.update({"log_verbose_level": 1}, model_name="noisy")
    # the override arms the hot flag and applies only to its model
    assert log.verbose_hot is True
    log.verbose("other", model="quiet")
    log.verbose("mine", model="noisy")
    assert [e["event"] for e in events] == ["mine"]
    assert log.settings("noisy")["log_verbose_level"] == 1
    assert log.settings()["log_verbose_level"] == 0
    # error gate override: model-scoped silence
    log.update({"log_error": False}, model_name="noisy")
    log.error("err-noisy", model="noisy")
    log.error("err-global", model="quiet")
    assert [e["event"] for e in events] == ["mine", "err-global"]
    # None clears the override; global default applies again
    log.update({"log_error": None, "log_verbose_level": None}, "noisy")
    assert log.settings("noisy") == log.settings()
    assert log.verbose_hot is False
    # None on a global setting resets it to the default
    log.update({"log_info": False})
    log.update({"log_info": None})
    assert log.settings()["log_info"] is True
    with pytest.raises(InferenceServerException, match="unknown log"):
        log.update({"bogus": None})


def test_logger_rate_limiting_with_suppressed_count():
    clock = FakeClock()
    events = []
    log = _logger(events, clock=clock, rate_max_per_window=2,
                  rate_window_s=5.0)
    for _ in range(10):
        log.error("hot", rate_key="k")
    assert len(events) == 2
    assert log.suppressed_count == 8
    # a different key has its own budget
    log.error("cold", rate_key="k2")
    assert len(events) == 3
    # next window: emission resumes and carries the suppressed count
    clock.advance(5.1)
    log.error("hot", rate_key="k")
    assert events[-1]["event"] == "hot"
    assert events[-1]["suppressed"] == 8
    # un-keyed emission is never rate limited
    for _ in range(5):
        log.error("unkeyed")
    assert len(events) == 9


def test_logger_file_exporter_and_live_switch(tmp_path):
    path_a = tmp_path / "a.jsonl"
    path_b = tmp_path / "b.jsonl"
    log = StructuredLogger(name="srv", clock=FakeClock())
    log.update({"log_file": str(path_a)})
    log.info("one", model="m", n=1)
    # switching log_file live redirects subsequent records
    log.update({"log_file": str(path_b)})
    log.info("two")
    log.close()
    rec_a = [json.loads(line) for line in path_a.read_text().splitlines()]
    rec_b = [json.loads(line) for line in path_b.read_text().splitlines()]
    assert [r["event"] for r in rec_a] == ["one"]
    assert rec_a[0]["model"] == "m" and rec_a[0]["n"] == 1
    assert rec_a[0]["logger"] == "srv"
    assert [r["event"] for r in rec_b] == ["two"]


def test_logger_stream_and_sink_exporters():
    stream = io.StringIO()
    log = StructuredLogger(stream=stream, clock=FakeClock())
    log.info("to-stream")
    assert json.loads(stream.getvalue())["event"] == "to-stream"
    # an attached sink REPLACES the stream (tests don't spam stderr)
    events = []
    log.sink = events.append
    log.info("to-sink")
    assert [e["event"] for e in events] == ["to-sink"]
    assert "to-sink" not in stream.getvalue()


def test_logger_iso8601_format():
    events = []
    log = _logger(events, clock=FakeClock(start=0.0))
    log.update({"log_format": "ISO8601"})
    log.info("stamped")
    assert events[0]["ts"] == "1970-01-01T00:00:00.000+00:00"
    with pytest.raises(InferenceServerException, match="log_format"):
        log.update({"log_format": "csv"})


def test_logger_exception_carries_traceback():
    events = []
    log = _logger(events)
    try:
        raise ValueError("boom")
    except ValueError as e:
        log.error("failed", model="m", exc=e)
    record = events[0]
    assert record["error"] == "boom"
    assert record["error_type"] == "ValueError"
    assert "ValueError: boom" in record["traceback"]


def test_logger_never_raises():
    # a sink that explodes and a non-JSON-serializable field must both be
    # swallowed — logging can never fail a request
    def bad_sink(record):
        raise RuntimeError("sink down")

    log = StructuredLogger(sink=bad_sink, clock=FakeClock())
    log.info("ok", weird=object())
    events = []
    log.sink = events.append
    log.info("obj", weird=object())
    assert events[0]["event"] == "obj"  # stringified, not dropped


# ---------------------------------------------------------------------------
# FlightRecorder units


def test_recorder_ring_and_reserved_sub_buffers():
    rec = FlightRecorder(
        capacity=4, error_capacity=2, slow_capacity=3, clock=FakeClock()
    )
    rec.record("m", request_id="slowest", total_us=900.0)
    rec.record("m", status="error", error="boom", request_id="bad",
               total_us=10.0)
    # churn: many fast successes roll the main ring
    for i in range(10):
        rec.record("m", request_id=f"fast{i}", total_us=float(i))
    snap = rec.snapshot()
    assert len(snap["recent"]) == 4
    assert snap["recent"][0]["request_id"] == "fast9"  # newest first
    # the error survived the churn in its reserved buffer
    assert [e["request_id"] for e in snap["errors"]] == ["bad"]
    assert snap["errors"][0]["error"] == "boom"
    # slowest kept the high-latency exemplar, descending order
    assert [e["request_id"] for e in snap["slowest"]][0] == "slowest"
    assert [e["total_us"] for e in snap["slowest"]] == sorted(
        [e["total_us"] for e in snap["slowest"]], reverse=True
    )
    assert snap["recorded_total"] == 12
    assert snap["error_total"] == 1


def test_recorder_snapshot_model_filter_and_limit():
    rec = FlightRecorder(clock=FakeClock())
    for i in range(6):
        rec.record("a" if i % 2 else "b", request_id=str(i),
                   total_us=float(i))
    snap = rec.snapshot(model="a", limit=2)
    assert len(snap["recent"]) == 2
    assert all(e["model"] == "a" for e in snap["recent"])
    full = rec.snapshot()
    assert len(full["recent"]) == 6


def test_recorder_rejected_vs_error_counters_and_stats():
    rec = FlightRecorder(clock=FakeClock())
    rec.record("m", status="rejected", error="queue full")
    rec.record("m", status="error", error="boom")
    rec.record("m")
    stats = rec.stats()
    assert stats["rejected_total"] == 1
    assert stats["error_total"] == 1
    assert stats["recorded_total"] == 3
    assert stats["errors"] == 2  # both non-ok exemplars in the buffer
    rec.clear()
    assert rec.stats()["recent"] == 0


def test_recorder_stage_decomposition_fields():
    rec = FlightRecorder(clock=FakeClock())
    rec.record(
        "m",
        queue_us=10.0,
        compute_us=20.0,
        package_us=5.0,
        total_us=35.0,
        rows=4,
        priority=2,
        trace_id="abc",
    )
    e = rec.snapshot()["recent"][0]
    assert e["stages"] == {
        "queue_us": 10.0,
        "compute_us": 20.0,
        "package_us": 5.0,
    }
    assert e["rows"] == 4 and e["priority"] == 2 and e["trace_id"] == "abc"


# ---------------------------------------------------------------------------
# core integration: exemplars + server-side error records


def test_core_records_exemplars_and_logs_swallowed_errors():
    from client_tpu.server.core import CoreRequest, CoreTensor, ServerCore
    from client_tpu.server.model_repository import Model, ModelRepository

    class FlakyModel(Model):
        inputs = [{"name": "X", "datatype": "FP32", "shape": [4]}]
        outputs = [{"name": "Y", "datatype": "FP32", "shape": [4]}]
        name = "flaky"
        max_batch_size = 0

        def execute(self, inputs, parameters):
            if parameters.get("fail"):
                raise RuntimeError("model exploded")
            return {"Y": inputs["X"]}

    events = []
    core = ServerCore(ModelRepository())
    core.logger.sink = events.append
    core.repository.add_model(FlakyModel())

    def request(**params):
        return CoreRequest(
            model_name="flaky",
            id="req-1",
            inputs=[
                CoreTensor(
                    "X", "FP32", [4], np.zeros(4, dtype=np.float32)
                )
            ],
            parameters=params,
        )

    async def drive():
        await core.infer(request())
        with pytest.raises(RuntimeError):
            await core.infer(request(fail=True))

    asyncio.run(drive())
    core.close()
    snap = core.flight_recorder.snapshot()
    ok = [e for e in snap["recent"] if e["status"] == "ok"]
    bad = [e for e in snap["recent"] if e["status"] == "error"]
    assert ok and ok[0]["path"] == "single" and ok[0]["request_id"] == "req-1"
    assert bad and bad[0]["error"] == "model exploded"
    assert snap["errors"] and snap["slowest"]
    # the previously-swallowed exception left a structured server record
    # with a rate-limited traceback
    failures = [e for e in events if e["event"] == "request_failed"]
    assert failures and failures[0]["model"] == "flaky"
    assert "RuntimeError: model exploded" in failures[0]["traceback"]


def test_core_books_rejections_into_recorder():
    from client_tpu.scheduling import QueueFullError
    from client_tpu.server.core import CoreRequest, ServerCore
    from client_tpu.server.model_repository import ModelRepository

    core = ServerCore(ModelRepository())
    request = CoreRequest(model_name="m", id="shed-1")
    core._book_rejection(
        "m", request, QueueFullError("m", 4), record_fail=False
    )
    core.close()
    snap = core.flight_recorder.snapshot()
    assert snap["rejected_total"] == 1
    rejected = snap["errors"][0]
    assert rejected["status"] == "rejected"
    assert "queue" in rejected["error"].lower()


# ---------------------------------------------------------------------------
# /v2/logging round-trips on both front-ends (live emission change)


@pytest.fixture(scope="module")
def server():
    with InProcessServer(grpc="aio") as s:
        yield s


@pytest.fixture()
def log_events(server):
    events = []
    log = server.core.logger
    log.sink = events.append
    yield events
    log.sink = None
    # reset anything a test toggled
    log.update(
        {
            "log_verbose_level": None,
            "log_error": None,
            "log_info": None,
            "log_warning": None,
        }
    )
    for model in list(log.model_overrides()):
        log.update(
            {k: None for k in log.model_overrides().get(model, {})}, model
        )
    server.core.flight_recorder.clear()


def _verbose_requests(events):
    return [e for e in events if e["event"] == "request"]


def test_http_logging_roundtrip_changes_emission_live(server, log_events):
    with httpclient.InferenceServerClient(server.http_url) as client:
        client.infer("simple", _simple_inputs(httpclient))
        assert _verbose_requests(log_events) == []
        settings = client.update_log_settings({"log_verbose_level": 1})
        assert settings["log_verbose_level"] == 1
        client.infer("simple", _simple_inputs(httpclient))
        requests = _verbose_requests(log_events)
        assert requests and requests[-1]["protocol"] == "http"
        assert requests[-1]["model"] == "simple"
        assert requests[-1]["status"] == "ok"
        # toggle back off: emission stops, again with no restart
        client.update_log_settings({"log_verbose_level": 0})
        count = len(_verbose_requests(log_events))
        client.infer("simple", _simple_inputs(httpclient))
        assert len(_verbose_requests(log_events)) == count


def test_http_per_model_logging_override(server, log_events):
    with httpclient.InferenceServerClient(server.http_url) as client:
        # model-scoped route: verbose for one model only
        client.update_log_settings(
            {"log_verbose_level": 1, "model": "simple"}
        )
        assert server.core.logger.settings("simple")["log_verbose_level"] == 1
        assert server.core.log_settings["log_verbose_level"] == 0
        client.infer("simple", _simple_inputs(httpclient))
        assert _verbose_requests(log_events)
        # another model stays quiet
        before = len(_verbose_requests(log_events))
        inp = httpclient.InferInput("INPUT0", [1, 16], "FP32")
        inp.set_data_from_numpy(np.zeros([1, 16], dtype=np.float32))
        client.infer("identity_fp32", [inp])
        assert len(_verbose_requests(log_events)) == before


def test_grpc_logging_roundtrip_changes_emission_live(server, log_events):
    with grpcclient.InferenceServerClient(server.grpc_url) as client:
        client.infer("simple", _simple_inputs(grpcclient))
        assert _verbose_requests(log_events) == []
        out = client.update_log_settings(
            {"log_verbose_level": 1}, as_json=True
        )
        assert out["settings"]["log_verbose_level"]["uint32_param"] == 1
        client.infer("simple", _simple_inputs(grpcclient))
        requests = _verbose_requests(log_events)
        assert requests and requests[-1]["protocol"] == "grpc"
        assert requests[-1]["status"] == "ok"
        # the reserved "model" settings key scopes an override over the
        # wire (the proto has no model field)
        client.update_log_settings({"log_verbose_level": 0})
        client.update_log_settings(
            {"model": "simple", "log_error": False}
        )
        assert (
            server.core.logger.settings("simple")["log_error"] is False
        )
        assert server.core.log_settings["log_error"] is True


def test_http_failed_request_retrievable_with_trace_id(server, log_events):
    # trace every request so the exemplar correlates with a trace id
    server.core.trace_manager.update(
        {"trace_level": ["TIMESTAMPS"], "trace_rate": "1"}
    )
    try:
        with httpclient.InferenceServerClient(server.http_url) as client:
            bad = httpclient.InferInput("BOGUS", [1, 16], "INT32")
            bad.set_data_from_numpy(np.zeros([1, 16], dtype=np.int32))
            with pytest.raises(InferenceServerException):
                client.infer("simple", [bad], request_id="doomed")
    finally:
        server.core.trace_manager.update({"trace_level": ["OFF"]})
    with urllib.request.urlopen(
        f"http://{server.http_url}/v2/debug/requests?model=simple"
    ) as resp:
        snap = json.loads(resp.read())
    failures = [e for e in snap["errors"] if e["request_id"] == "doomed"]
    assert failures, snap["errors"]
    exemplar = failures[0]
    assert "unexpected inference input" in exemplar["error"]
    assert exemplar["trace_id"]  # correlates with the trace record
    assert set(exemplar["stages"]) == {
        "queue_us", "compute_us", "package_us",
    }
    assert exemplar["total_us"] >= 0


def test_debug_requests_query_validation(server):
    request = urllib.request.Request(
        f"http://{server.http_url}/v2/debug/requests?limit=abc"
    )
    with pytest.raises(urllib.error.HTTPError) as err:
        urllib.request.urlopen(request)
    assert err.value.code == 400


def test_debug_state_under_concurrent_load_and_drain(server):
    url = f"http://{server.http_url}/v2/debug/state"

    def fetch_state():
        with urllib.request.urlopen(url) as resp:
            return json.loads(resp.read())

    state = fetch_state()
    assert state["server"]["ready"] is True
    assert state["lifecycle"]["state"] == "serving"
    assert {"queues", "rate_limiter", "models", "log_settings"} <= set(state)
    assert any(m["name"] == "simple" for m in state["models"])

    # concurrent load: infer on several threads while scraping state —
    # every snapshot must be internally sane (no exceptions, counts >= 0)
    snapshots = []
    errors = []

    def hammer():
        try:
            with httpclient.InferenceServerClient(server.http_url) as c:
                for _ in range(10):
                    c.infer("simple", _simple_inputs(httpclient))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=hammer) for _ in range(3)]
    for t in threads:
        t.start()
    while any(t.is_alive() for t in threads):
        snapshots.append(fetch_state())
    for t in threads:
        t.join()
    assert not errors
    for snap in snapshots:
        assert snap["lifecycle"]["inflight_total"] >= 0
        for counts in snap["lifecycle"]["inflight_by_model"].values():
            assert counts >= 0
        assert snap["flight_recorder"]["recorded_total"] >= 0

    # during a drain the endpoint keeps answering and reports the state
    server.core.lifecycle.begin_drain()
    try:
        state = fetch_state()
        assert state["lifecycle"]["state"] == "draining"
        assert state["server"]["ready"] is False
    finally:
        server.core.lifecycle.resume()
    assert fetch_state()["lifecycle"]["state"] == "serving"


# ---------------------------------------------------------------------------
# client-side events (EndpointPool failover, CircuitBreaker transitions)


def test_endpoint_pool_emits_failover_events():
    from client_tpu.lifecycle import EndpointPool

    events = []
    clock = FakeClock()
    pool = EndpointPool(
        ["a:1", "b:2"],
        cooldown_s=2.0,
        clock=clock,
        logger=_logger(events, clock=clock),
    )
    primary = pool.pick()
    pool.observe(primary, token="503", retry_after_s=4.0)
    down = [e for e in events if e["event"] == "endpoint_down"]
    assert down and down[0]["endpoint"] == "a:1"
    assert down[0]["new_primary"] == "b:2"
    assert down[0]["cooldown_s"] == 4.0
    assert down[0]["severity"] == "WARNING"
    clock.advance(5.0)
    pool.observe(primary, ok=True)
    recovered = [e for e in events if e["event"] == "endpoint_recovered"]
    assert recovered and recovered[0]["endpoint"] == "a:1"


def test_circuit_breaker_emits_transition_events():
    from client_tpu.resilience import CircuitBreaker

    events = []
    clock = FakeClock()
    breaker = CircuitBreaker(
        failure_threshold=2,
        cooldown_s=3.0,
        clock=clock,
        logger=_logger(events, clock=clock),
    )
    breaker.record_failure()
    breaker.record_failure()  # trips
    clock.advance(3.5)
    assert breaker.allow()  # open -> half_open probe
    breaker.record_success()  # half_open -> closed
    names = [e["event"] for e in events]
    assert names == ["circuit_open", "circuit_half_open", "circuit_closed"]
    assert events[0]["times_opened"] == 1
    assert events[0]["cooldown_s"] == 3.0


def test_client_surfaces_accept_logger_kwarg(server):
    events = []
    log = _logger(events)
    with httpclient.InferenceServerClient(
        server.http_url, logger=log
    ) as client:
        assert client._aio_client._pool._logger is log
    with grpcclient.InferenceServerClient(
        server.grpc_url, logger=log
    ) as client:
        assert client._pool._logger is log


# ---------------------------------------------------------------------------
# lint: no bare print()/stdlib logging in the server-side packages


def test_log_lint_flags_print_and_stdlib_logging():
    from tools.log_lint import check_source, run_log_lint

    bad = (
        "import logging\n"
        "from logging import getLogger\n"
        "def f():\n"
        "    print('hi')\n"
    )
    findings = check_source(bad, "x.py")
    assert len(findings) == 3
    assert any("print()" in message for _line, message in findings)
    assert any("stdlib logging" in message for _line, message in findings)
    good = (
        "from client_tpu.observability.logging import StructuredLogger\n"
        "def f(log):\n"
        "    log.info('hi')\n"
    )
    assert check_source(good, "y.py") == []
    # the repo itself is clean (conftest enforces this at session start
    # too; asserting here keeps the guarantee visible in the report)
    assert run_log_lint() == []


def test_clock_lint_pins_logging_modules():
    from tools.clock_lint import TARGET_FILES

    pinned = {p.replace("\\", "/") for p in TARGET_FILES}
    assert "client_tpu/observability/logging.py" in pinned
    assert "client_tpu/observability/recorder.py" in pinned


# ---------------------------------------------------------------------------
# perf harness: --dump-slow-requests / --log-file


def test_cli_dump_slow_requests_rejects_non_kserve(capsys):
    from client_tpu.perf.cli import main

    code = main([
        "-m", "gpt", "--service-kind", "openai",
        "--dump-slow-requests", "3",
    ])
    assert code == 2
    assert "--dump-slow-requests" in capsys.readouterr().err


def test_cli_dump_slow_requests_and_log_file(tmp_path, capsys):
    from client_tpu.perf.cli import main

    log_file = tmp_path / "run.jsonl"
    with InProcessServer(grpc=False) as server:
        code = main([
            "-m", "simple",
            "-u", server.http_url,
            "-i", "http",
            "--concurrency-range", "2",
            "--measurement-interval", "300",
            "--stability-percentage", "60",
            "--max-trials", "3",
            "--dump-slow-requests", "3",
            "--log-file", str(log_file),
        ])
    assert code == 0
    out = capsys.readouterr().out
    assert "Slowest requests (server flight recorder):" in out
    # stage-decomposed columns for the worst requests
    assert "queue_us" in out and "compute_us" in out
    records = [
        json.loads(line) for line in log_file.read_text().splitlines()
    ]
    names = [r["event"] for r in records]
    assert names[0] == "run_started"
    assert names[-1] == "run_finished"
    assert "slow_request" in names
    slow = [r for r in records if r["event"] == "slow_request"]
    assert slow[0]["model"] == "simple"
    assert "stages" in slow[0]


# ---------------------------------------------------------------------------
# what the default-on recorder and the quiet logger cost, as counts


class _CountingClock:
    """A wall-seconds clock that advances a millisecond a read and
    counts its reads."""

    def __init__(self):
        self.reads = 0

    def __call__(self) -> float:
        self.reads += 1
        return 1000.0 + self.reads / 1e3


def _counted_core(recorder_capacity=None):
    """A ServerCore whose flight recorder and logger run on counting
    clocks, the logger writing to a list; ``recorder_capacity`` sizes
    the recorder's ring and slowest heap (None: the defaults)."""
    from client_tpu.server.core import ServerCore
    from client_tpu.server.model_repository import ModelRepository

    recorder_clock, logger_clock = _CountingClock(), _CountingClock()
    events = []
    sizes = (
        {}
        if recorder_capacity is None
        else {"capacity": recorder_capacity, "slow_capacity": recorder_capacity}
    )
    core = ServerCore(
        ModelRepository(),
        logger=StructuredLogger(
            name="server", sink=events.append, clock=logger_clock
        ),
        flight_recorder=FlightRecorder(clock=recorder_clock, **sizes),
    )
    return core, recorder_clock, logger_clock, events


def test_recorder_costs_one_clock_read_and_the_quiet_logger_none(loopback_echo):
    """With default settings (recorder ON, verbose logging OFF) a
    loopback request costs ONE read of the recorder's clock and leaves
    ONE record, and the logger neither reads its clock nor writes; at
    ``log_verbose_level`` 1 the logger writes ONE line a request for one
    read of its clock. A later change that adds a read or a line a
    request has to change the numbers here."""
    n = 24
    core, recorder_clock, logger_clock, events = _counted_core()
    with loopback_echo(core) as echo:
        reads, lines = logger_clock.reads, len(events)  # start-up's own
        echo.send(n)
        assert recorder_clock.reads == n
        assert core.flight_recorder.stats() == {
            "recorded_total": n, "error_total": 0, "rejected_total": 0,
            "recent": n, "errors": 0, "slowest": n,
        }
        assert (logger_clock.reads, len(events)) == (reads, lines)
        core.update_log_settings({"log_verbose_level": 1})
        echo.send(n)
        assert logger_clock.reads == reads + n
        written = events[lines:]
        assert [e["event"] for e in written] == ["request"] * n
        assert recorder_clock.reads == 2 * n


def test_recorder_of_no_capacity_stores_nothing(loopback_echo):
    """``FlightRecorder(capacity=0, slow_capacity=0)`` under N requests
    reads no clock, counts, stores and exports nothing, and the default
    log settings write no line for an OK request."""
    n = 24
    core, recorder_clock, logger_clock, events = _counted_core(
        recorder_capacity=0
    )
    with loopback_echo(core) as echo:
        reads, lines = logger_clock.reads, len(events)  # start-up's own
        echo.send(n)
        assert (logger_clock.reads, len(events)) == (reads, lines)
        exported = echo.get("/v2/debug/requests")
        state = echo.get("/v2/debug/state")["flight_recorder"]
    assert recorder_clock.reads == 0
    assert state == {
        "recorded_total": 0, "error_total": 0, "rejected_total": 0,
        "recent": 0, "errors": 0, "slowest": 0,
    }
    assert (
        exported["recent"], exported["errors"], exported["slowest"]
    ) == ([], [], [])


@pytest.mark.parametrize(
    "path,label",
    [
        ("single", "single"),
        ("batcher", "batch"),
        ("direct", "direct"),
        ("decoupled", "decoupled"),
    ],
)
def test_one_record_a_request_on_every_path(drive_core_path, path, label):
    """The accounting a merged spine of ``ServerCore``'s four execution
    paths has to keep (ROADMAP D5): N requests leave exactly N
    flight-recorder records under the path's own label, one read of the
    recorder's clock each, the failures among them in the errors buffer
    too. What differs by path is the log: a failed request writes one
    ``request_failed`` / ``stream_failed`` line on the two unmerged
    paths, a failed execution ONE ``batch_execution_failed`` line for
    all its requests on the two merging ones."""
    ok, failed = 5, 3
    core, recorder_clock, _logger_clock, events = _counted_core()
    try:
        results = drive_core_path(core, path, [1.0] * ok)
        assert not any(isinstance(r, Exception) for r in results)
        results = drive_core_path(core, path, [999.0] * failed)
        assert all(isinstance(r, Exception) for r in results)
    finally:
        core.close()
    assert recorder_clock.reads == ok + failed
    snap = core.flight_recorder.snapshot()
    assert snap["recorded_total"] == ok + failed
    assert snap["error_total"] == failed and snap["rejected_total"] == 0
    assert [e["path"] for e in snap["recent"]] == [label] * (ok + failed)
    assert [e["status"] for e in snap["errors"]] == ["error"] * failed
    if path == "decoupled":
        assert [e["responses"] for e in snap["recent"]] == (
            [0] * failed + [2] * ok  # newest first
        )
    logged = [e["event"] for e in events if e["severity"] == "ERROR"]
    assert logged == {
        "single": ["request_failed"] * failed,
        "batcher": ["batch_execution_failed"],
        "direct": ["batch_execution_failed"],
        "decoupled": ["stream_failed"] * failed,
    }[path]
