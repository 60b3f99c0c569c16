"""PR-16 router tier: the chaos-proven fleet front door.

Unit coverage for the wire splice (forward-request rewrite, id
restoration), the model table, admission shedding, and the autoscaler's
hysteresis; integration coverage for unary/stream/HTTP traffic through
:class:`client_tpu.router.RouterServer` over a live FleetRunner; chaos
coverage for backend death, router-process death (subprocess SIGKILL),
priority shedding under overload, and the SLO-driven scale-out /
drain-in ramp — ISSUE 16's acceptance criteria.
"""

import asyncio
import contextlib
import logging
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from client_tpu.grpc import _wire as wire
from client_tpu.grpc._generated import grpc_service_pb2 as pb
from client_tpu.grpc._utils import set_parameter
from client_tpu.testing import rerun_on_grpc_poller_breakdown
from client_tpu.utils import InferenceServerException

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _proto_request(model="simple", rid="", params=None, payload=b"\1\2\3\4"):
    request = pb.ModelInferRequest(model_name=model, id=rid)
    tensor = request.inputs.add(name="INPUT0", datatype="INT32", shape=[4])
    del tensor  # shape declared; contents ride raw
    request.raw_input_contents.append(payload)
    for key, value in (params or {}).items():
        set_parameter(request.parameters, key, value)
    return request


# ---------------------------------------------------------------------------
# unit: wire splice


def test_splice_forward_request_rewrites_only_the_envelope():
    data = _proto_request(rid="client-id-1", params={"k": 7}).SerializeToString()
    spliced, original = wire.splice_forward_request(data, "r42")
    assert original == "client-id-1"
    assert wire.read_message_id(bytes(spliced)) == "r42"
    parsed = pb.ModelInferRequest.FromString(bytes(spliced))
    assert parsed.id == "r42"
    assert parsed.parameters["multiplex"].bool_param is True
    assert parsed.parameters["k"].int64_param == 7
    assert parsed.model_name == "simple"
    assert list(parsed.raw_input_contents) == [b"\1\2\3\4"]
    assert parsed.inputs[0].name == "INPUT0"


def test_spliced_request_stays_on_scanner_fast_path():
    scanner = wire.RequestScanner()
    data = _proto_request(rid="orig").SerializeToString()
    spliced, _ = wire.splice_forward_request(data, "r1")
    result = scanner.scan(bytes(spliced))
    assert result is not None
    _template, rid, _extra, _raws = result
    assert rid == "r1"


def test_splice_message_id_restores_response_id():
    response = pb.ModelInferResponse(model_name="m", id="r42")
    response.raw_output_contents.append(b"\x09\x09")
    data = response.SerializeToString()
    restored, backend_rid = wire.splice_message_id(data, "client-id-1")
    assert backend_rid == "r42"
    parsed = pb.ModelInferResponse.FromString(bytes(restored))
    assert parsed.id == "client-id-1"
    assert list(parsed.raw_output_contents) == [b"\x09\x09"]


# ---------------------------------------------------------------------------
# unit: model table / admission / classification


def test_model_table_routes_unknown_models_anywhere():
    from client_tpu.router import ModelTable

    table = ModelTable()
    assert table.urls_for("simple") is None  # unknown -> permissive
    table.set_backend_models("a:1", ["simple", "other"])
    table.set_backend_models("b:2", ["simple"])
    assert table.urls_for("simple") == {"a:1", "b:2"}
    assert table.urls_for("other") == {"a:1"}
    assert table.urls_for("never-advertised") is None
    table.drop_backend("a:1")
    # with its one advertiser gone, 'other' degrades to permissive
    # routing (None), not a hard empty set — the backend may still be
    # mid-load; the forward finds out
    assert table.urls_for("other") is None
    assert sorted(table.models()) == ["simple"]


def test_router_admission_sheds_default_priority_only():
    from client_tpu.router import RouterCore, RouterOverloadError

    router = RouterCore({"127.0.0.1:1": None}, max_inflight=2)
    router.admit(0)
    router.admit(0)
    with pytest.raises(RouterOverloadError) as exc_info:
        router.admit(0)
    assert exc_info.value.retry_after_s == 0.25
    assert "queue full" in exc_info.value.message()
    # protected tier is never shed by the backstop (inflight now 3)
    router.admit(1)
    router.release()
    router.release()
    router.admit(0)  # slots freed -> default admits again
    for _ in range(2):
        router.release()


def test_router_classify_reads_priority_and_sequence():
    from client_tpu.router import RouterCore

    router = RouterCore({"127.0.0.1:1": None})
    data = _proto_request(
        params={"priority": 3, "sequence_id": 9}
    ).SerializeToString()
    model, _key, priority, is_sequence = router.classify(data)
    assert (model, priority, is_sequence) == ("simple", 3, True)
    model, _key, priority, is_sequence = router.classify(
        _proto_request().SerializeToString()
    )
    assert (model, priority, is_sequence) == ("simple", 0, False)
    assert router.classify(b"\xff\xff\xff") == ("", None, 0, False)


def test_pool_membership_and_allow_restriction():
    from client_tpu.lifecycle.pool import EndpointPool

    pool = EndpointPool(["a:1", "b:2"])
    assert pool.pick(allow={"b:2"}).url == "b:2"
    pool.add_endpoint("c:3")
    pool.add_endpoint("c:3")  # idempotent
    assert pool.size == 3
    assert pool.remove_endpoint("c:3") is True
    assert pool.remove_endpoint("b:2") is True
    # never empties the pool: removing the last member is refused
    assert pool.remove_endpoint("a:1") is False
    assert pool.size == 1


# ---------------------------------------------------------------------------
# unit: autoscaler hysteresis / flake shim


def test_autoscaler_observe_hysteresis():
    from client_tpu.perf.fleet_runner import Autoscaler

    class _FleetStub:
        size = 2  # mid-range: both directions permitted

    scaler = Autoscaler(
        fleet=_FleetStub(),
        min_replicas=1,
        max_replicas=3,
        burn_high=1.0,
        burn_low=0.1,
        high_ticks=2,
        low_ticks=3,
    )
    assert scaler.observe(5.0) == "hold"  # first high tick arms only
    assert scaler.observe(5.0) == "scale_out"
    assert scaler.observe(5.0) == "hold"  # counter reset after action
    assert scaler.observe(0.5) == "hold"  # mid-band resets both counters
    assert scaler.observe(0.0) == "hold"
    assert scaler.observe(0.0) == "hold"
    assert scaler.observe(0.0) == "scale_in"
    # a mid-band tick between low ticks starts the count over
    assert scaler.observe(0.0) == "hold"
    assert scaler.observe(0.5) == "hold"
    assert scaler.observe(0.0) == "hold"
    assert scaler.observe(0.0) == "hold"
    assert scaler.observe(0.0) == "scale_in"


def test_retry_grpc_poller_flake_retries_empty_runs_only():
    from client_tpu.testing import retry_grpc_poller_flake

    calls = []

    def run():
        calls.append(1)
        return len(calls)

    assert retry_grpc_poller_flake(run, lambda n: n >= 1) == 1
    calls.clear()
    # first attempt "empty", second succeeds
    assert retry_grpc_poller_flake(run, lambda n: n >= 2) == 2
    calls.clear()
    # every attempt failing still returns the last result for assertion
    assert retry_grpc_poller_flake(run, lambda n: False, attempts=3) == 3
    with pytest.raises(ValueError):
        retry_grpc_poller_flake(run, lambda n: True, attempts=0)


@pytest.mark.parametrize("breaks_down,attempts", [(True, 2), (False, 1)])
def test_rerun_on_grpc_poller_breakdown_needs_the_breakdown(
        breaks_down, attempts):
    """A failed test runs again only where asyncio logged the poller's
    breakdown while it ran; without that the first failure is the
    test's, and a test that fails twice fails."""
    calls = []

    @rerun_on_grpc_poller_breakdown
    def flaky(always=False):
        calls.append(1)
        if breaks_down:
            try:
                raise BlockingIOError(11, "Resource temporarily unavailable")
            except BlockingIOError:
                logging.getLogger("asyncio").error(
                    "Exception in callback "
                    "PollerCompletionQueue._handle_events()", exc_info=True)
        assert len(calls) > 1 and not always
        return "served"

    def rerun_said():
        if breaks_down:
            return pytest.warns(UserWarning, match="poller broke down")
        return contextlib.nullcontext()

    with rerun_said():
        if breaks_down:
            assert flaky() == "served"
        else:
            with pytest.raises(AssertionError):
                flaky()
    assert len(calls) == attempts
    calls.clear()
    with rerun_said(), pytest.raises(AssertionError):
        flaky(always=True)
    assert len(calls) == attempts
    assert not logging.getLogger("asyncio").handlers


# ---------------------------------------------------------------------------
# integration: traffic through a live router


def _device_sim_factory(step_s=0.004, max_batch_size=4, slo=None):
    from client_tpu.perf.fleet_runner import DeviceBoundModel

    def factory():
        return DeviceBoundModel(
            step_s=step_s, max_batch_size=max_batch_size, slo=slo
        )

    return factory


@pytest.mark.fleet
def test_router_unary_http_and_control_plane():
    """One router address in front of two replicas: gRPC unary with the
    client's own request id restored, HTTP inference proxied, and the
    control plane (readiness, metadata, /metrics, /v2/router/status)."""
    import json
    import urllib.request

    import client_tpu.grpc as grpcclient
    import client_tpu.http as httpclient
    from client_tpu.perf.fleet_runner import FleetRunner
    from client_tpu.router import RouterServer

    with FleetRunner(2, grpc="aio", http=True) as fleet:
        backends = dict(zip(fleet.grpc_urls, fleet.http_urls))
        with RouterServer(backends, probe_interval_s=0.1) as router:
            with grpcclient.InferenceServerClient(router.grpc_url) as client:
                assert client.is_server_ready()
                assert client.is_model_ready("simple")
                metadata = client.get_model_metadata("simple")
                assert metadata.name == "simple"
                in0 = np.arange(16, dtype=np.int32).reshape(1, 16)
                a = grpcclient.InferInput("INPUT0", [1, 16], "INT32")
                a.set_data_from_numpy(in0)
                b = grpcclient.InferInput("INPUT1", [1, 16], "INT32")
                b.set_data_from_numpy(in0)
                for i in range(6):  # spread over both replicas
                    result = client.infer(
                        "simple", [a, b], request_id=f"my-id-{i}"
                    )
                    assert result.get_response().id == f"my-id-{i}"
                    assert result.as_numpy("OUTPUT0").tolist() == (
                        (in0 + in0).tolist()
                    )
            with httpclient.InferenceServerClient(router.http_url) as hc:
                tensor = httpclient.InferInput("INPUT0", [1, 16], "INT32")
                tensor.set_data_from_numpy(in0)
                tensor2 = httpclient.InferInput("INPUT1", [1, 16], "INT32")
                tensor2.set_data_from_numpy(in0)
                out = hc.infer("simple", [tensor, tensor2])
                assert out.as_numpy("OUTPUT1").tolist() == [[0] * 16]
            base = f"http://{router.http_url}"
            status = json.load(
                urllib.request.urlopen(f"{base}/v2/router/status")
            )
            assert any(
                "simple" in models for models in status["models"].values()
            )
            assert len(status["pool"]["endpoints"]) == 2
            metrics = urllib.request.urlopen(f"{base}/metrics").read()
            assert b"tpu_router_proxy_seconds" in metrics
            assert b"tpu_router_requests_total" in metrics


@pytest.mark.fleet
def test_router_stream_decoupled_roundtrip():
    """Decoupled streaming through the router: one client stream fans
    requests onto a pinned backend stream; every frame comes back with
    the client's own correlation id."""
    import queue

    import client_tpu.grpc as grpcclient
    from client_tpu.perf.fleet_runner import FleetRunner
    from client_tpu.router import RouterServer

    with FleetRunner(2, grpc="aio", http=False) as fleet:
        backends = {url: None for url in fleet.grpc_urls}
        with RouterServer(backends, http=False, probe_interval_s=0.1) as router:
            with grpcclient.InferenceServerClient(router.grpc_url) as client:
                frames = queue.Queue()
                client.start_stream(
                    callback=lambda result, error: frames.put((result, error))
                )
                tensor = grpcclient.InferInput("IN", [3], "INT32")
                tensor.set_data_from_numpy(np.array([7, 8, 9], np.int32))
                client.async_stream_infer(
                    "repeat_int32", [tensor], request_id="stream-1"
                )
                seen = []
                while True:
                    result, error = frames.get(timeout=10)
                    assert error is None
                    response = result.get_response()
                    assert response.id == "stream-1"
                    seen.append(int(result.as_numpy("OUT")[0]))
                    final = response.parameters.get("triton_final_response")
                    if final is not None and final.bool_param:
                        break
                client.stop_stream()
                assert seen == [7, 8, 9]


@pytest.mark.fleet
@pytest.mark.chaos
@rerun_on_grpc_poller_breakdown
def test_router_backend_kill_zero_client_failures():
    """Chaos: a backend replica dies mid-run behind the router; the
    router benches it (readiness probe + UNAVAILABLE retry) and every
    client request still succeeds."""
    import client_tpu.grpc.aio as aio_grpcclient
    from client_tpu.perf.fleet_runner import FleetRunner
    from client_tpu.router import RouterServer

    with FleetRunner(
        2,
        grpc="aio",
        http=False,
        builtin_models=False,
        model_factories=[_device_sim_factory()],
    ) as fleet:
        backends = {url: None for url in fleet.grpc_urls}
        with RouterServer(backends, http=False, probe_interval_s=0.1) as router:

            async def drive():
                stats = {"ok": 0}
                stop = asyncio.Event()
                client = aio_grpcclient.InferenceServerClient(router.grpc_url)
                data = np.ones([4], dtype=np.int32)

                async def worker():
                    while not stop.is_set():
                        tensor = aio_grpcclient.InferInput(
                            "INPUT0", [4], "INT32"
                        )
                        tensor.set_data_from_numpy(data)
                        await client.infer(
                            "device_sim", [tensor], client_timeout=10.0
                        )
                        stats["ok"] += 1

                tasks = [asyncio.create_task(worker()) for _ in range(8)]
                await asyncio.sleep(0.4)
                await asyncio.get_running_loop().run_in_executor(
                    None, fleet.stop_replica, 1
                )
                await asyncio.sleep(0.8)
                stop.set()
                await asyncio.gather(*tasks)
                await client.close()
                return stats

            stats = asyncio.run(drive())
            # zero failures is the assertion: worker raising would have
            # propagated through gather
            assert stats["ok"] > 20
            snapshot = router.router.snapshot()
            states = {
                endpoint["url"]: endpoint["state"]
                for endpoint in snapshot["pool"]["endpoints"]
            }
            assert "down" in states.values() or "ejected" in states.values()


@pytest.mark.fleet
@pytest.mark.chaos
@pytest.mark.scheduling
def test_router_overload_sheds_low_priority_with_retry_after():
    """Overload past the admission limit sheds DEFAULT-priority traffic
    with RESOURCE_EXHAUSTED + Retry-After while the protected tier keeps
    succeeding — the ISSUE 16 backstop semantics."""
    import client_tpu.grpc.aio as aio_grpcclient
    from client_tpu.perf.fleet_runner import FleetRunner
    from client_tpu.router import RouterServer

    with FleetRunner(
        1,
        grpc="aio",
        http=False,
        builtin_models=False,
        model_factories=[_device_sim_factory(step_s=0.05, max_batch_size=1)],
    ) as fleet:
        backends = {url: None for url in fleet.grpc_urls}
        with RouterServer(
            backends,
            http=False,
            probe_interval_s=0.1,
            max_inflight=2,
            shed_retry_after_s=0.25,
        ) as router:

            async def drive():
                client = aio_grpcclient.InferenceServerClient(router.grpc_url)
                data = np.ones([4], dtype=np.int32)

                async def one(priority):
                    tensor = aio_grpcclient.InferInput("INPUT0", [4], "INT32")
                    tensor.set_data_from_numpy(data)
                    try:
                        await client.infer(
                            "device_sim",
                            [tensor],
                            priority=priority,
                            client_timeout=10.0,
                        )
                        return ("ok", None)
                    except InferenceServerException as e:
                        return ("shed", e)

                results = await asyncio.gather(
                    *[one(0) for _ in range(8)], *[one(1) for _ in range(4)]
                )
                await client.close()
                return results[:8], results[8:]

            low, high = asyncio.run(drive())
            assert all(outcome == "ok" for outcome, _ in high), (
                "protected-priority traffic must never be shed"
            )
            shed = [e for outcome, e in low if outcome == "shed"]
            assert shed, "8 defaults against limit 2 must shed some"
            for error in shed:
                assert "RESOURCE_EXHAUSTED" in str(error.status())
                assert error.retry_after_s == 0.25
                assert "queue full" in error.message()
            metrics = router.router.metrics.render()
            assert 'tpu_router_shed_total{priority="default"}' in metrics


@pytest.mark.fleet
@pytest.mark.chaos
def test_router_autoscale_ramp_and_drain():
    """The ISSUE 16 loop closed: a traffic ramp saturates one replica's
    SLO burn, the autoscaler grows the fleet 1 -> 3 (each new replica
    joins the router via readiness), the burn recovers, and the light
    phase drains back down — zero client-visible failures throughout."""
    import client_tpu.grpc.aio as aio_grpcclient
    from client_tpu.perf.fleet_runner import Autoscaler, FleetRunner
    from client_tpu.router import RouterServer

    factory = _device_sim_factory(
        step_s=0.01,
        max_batch_size=1,
        slo={"latency_target_ms": 35, "availability": 0.9, "window_s": 2.0},
    )
    with FleetRunner(
        1, grpc="aio", http=False, builtin_models=False,
        model_factories=[factory],
    ) as fleet:
        backends = {url: None for url in fleet.grpc_urls}
        with RouterServer(backends, http=False, probe_interval_s=0.1) as router:
            scaler = Autoscaler(
                fleet,
                min_replicas=1,
                max_replicas=3,
                burn_high=1.0,
                burn_low=0.1,
                high_ticks=2,
                low_ticks=4,
                interval_s=0.2,
                on_scale_out=lambda server: router.add_backend(
                    server.grpc_url
                ),
                on_scale_in=lambda server: router.remove_backend(
                    server.grpc_url
                ),
            )
            scaler.start()
            latencies = []
            phase = {"drivers": 9}

            async def drive():
                client = aio_grpcclient.InferenceServerClient(router.grpc_url)
                stop = asyncio.Event()
                data = np.ones([4], dtype=np.int32)

                async def worker(index):
                    while not stop.is_set():
                        if index >= phase["drivers"]:
                            await asyncio.sleep(0.05)
                            continue
                        tensor = aio_grpcclient.InferInput(
                            "INPUT0", [4], "INT32"
                        )
                        tensor.set_data_from_numpy(data)
                        started = time.monotonic()
                        await client.infer(
                            "device_sim", [tensor], client_timeout=10.0
                        )
                        latencies.append(time.monotonic() - started)

                tasks = [asyncio.create_task(worker(i)) for i in range(9)]
                for _ in range(60):  # heavy phase: expect 1 -> 3
                    await asyncio.sleep(0.25)
                    if fleet.size >= 3:
                        break
                assert fleet.size >= 2, (
                    f"ramp never scaled out: {scaler.events}"
                )
                phase["drivers"] = 1  # light phase: expect drain
                for _ in range(80):
                    await asyncio.sleep(0.25)
                    if fleet.size <= 1:
                        break
                stop.set()
                await asyncio.gather(*tasks)  # any failure propagates
                await client.close()

            try:
                asyncio.run(drive())
            finally:
                scaler.stop()
            decisions = [event["decision"] for event in scaler.events]
            assert "scale_out" in decisions
            assert max(e["size"] for e in scaler.events) >= 2
            assert "scale_in" in decisions, (
                f"light phase never drained: {scaler.events}"
            )
            assert fleet.size < 3
            latencies.sort()
            p99 = latencies[int(0.99 * len(latencies)) - 1]
            assert p99 < 2.0, f"p99 {p99:.3f}s unbounded during the ramp"


@pytest.mark.fleet
@pytest.mark.chaos
def test_router_process_killed_clients_fail_over():
    """Chaos at the tier above: TWO router subprocesses front one fleet;
    SIGKILL of one mid-run is invisible to a client holding
    urls=[router_a, router_b]. Killing the LAST router surfaces as a
    retryable error, not a hang."""
    from client_tpu.perf.fleet_runner import FleetRunner, read_ports_file
    from client_tpu.testing import hermetic_child_env

    import client_tpu.grpc.aio as aio_grpcclient

    def spawn_router(backends_spec, ports_file):
        return subprocess.Popen(
            [
                sys.executable,
                "-m",
                "client_tpu.router",
                "--serve",
                "--backends",
                backends_spec,
                "--ports-file",
                ports_file,
                "--probe-interval",
                "0.1",
            ],
            env=hermetic_child_env(repo_path=REPO_ROOT),
            stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
        )

    def await_ports(proc, path, wait_s=30.0):
        deadline = time.monotonic() + wait_s
        while time.monotonic() < deadline:
            ports = read_ports_file(path)
            if ports is not None:
                return ports
            assert proc.poll() is None, "router subprocess died on start"
            time.sleep(0.05)
        raise AssertionError(f"no ports file at {path}")

    import tempfile

    with FleetRunner(
        2,
        grpc="aio",
        http=False,
        builtin_models=False,
        model_factories=[_device_sim_factory()],
    ) as fleet:
        spec = ",".join(fleet.grpc_urls)
        with tempfile.TemporaryDirectory(prefix="router_chaos_") as tmp:
            paths = [os.path.join(tmp, f"router{i}.json") for i in (0, 1)]
            routers = [spawn_router(spec, path) for path in paths]
            try:
                urls = [
                    f"127.0.0.1:{await_ports(proc, path)['grpc_port']}"
                    for proc, path in zip(routers, paths)
                ]

                async def drive():
                    stats = {"ok": 0}
                    stop = asyncio.Event()
                    client = aio_grpcclient.InferenceServerClient(
                        ",".join(urls)
                    )
                    data = np.ones([4], dtype=np.int32)

                    async def worker():
                        while not stop.is_set():
                            tensor = aio_grpcclient.InferInput(
                                "INPUT0", [4], "INT32"
                            )
                            tensor.set_data_from_numpy(data)
                            await client.infer(
                                "device_sim", [tensor], client_timeout=10.0
                            )
                            stats["ok"] += 1

                    tasks = [asyncio.create_task(worker()) for _ in range(6)]
                    await asyncio.sleep(0.4)
                    routers[0].send_signal(signal.SIGKILL)  # chaos
                    await asyncio.sleep(0.8)
                    stop.set()
                    await asyncio.gather(*tasks)  # failures propagate
                    await client.close()

                    # the LAST router dying is a retryable error, never
                    # a hang: the single-url client raises promptly
                    routers[1].send_signal(signal.SIGKILL)
                    routers[1].wait(timeout=10)
                    solo = aio_grpcclient.InferenceServerClient(urls[1])
                    tensor = aio_grpcclient.InferInput("INPUT0", [4], "INT32")
                    tensor.set_data_from_numpy(data)
                    with pytest.raises(InferenceServerException):
                        await asyncio.wait_for(
                            solo.infer(
                                "device_sim", [tensor], client_timeout=3.0
                            ),
                            timeout=8.0,
                        )
                    await solo.close()
                    return stats

                stats = asyncio.run(drive())
                assert stats["ok"] > 20, "drive barely ran before the kill"
            finally:
                for proc in routers:
                    if proc.poll() is None:
                        proc.kill()
                        proc.wait(timeout=10)
