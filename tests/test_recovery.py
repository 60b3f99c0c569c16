"""PR-20: self-healing serving — the three supervision tiers.

Tiers, cheapest first:

- request-parameter + error-shape units (no jax): the per-request
  ``recovery`` opt-out parser and ``EngineRecoveringError``'s wire face;
- :class:`EngineRecovery` state machine on fakes (fake clock, fake
  sleep, fake engine): bounded retries, backoff sequence, exhaustion
  failing the parked survivors, metrics booked exactly;
- engine chaos on the real tiny llama: an induced engine-fatal
  quarantines (clean retryable 503s, never a hang), auto-reloads to
  READY with the queue intact, and resumed greedy streams are
  TOKEN-IDENTICAL to an uninterrupted oracle; the ``recovery: fail``
  opt-out fails instead of resuming;
- front-end e2e over the real wire: while quarantined, HTTP answers 503
  WITH ``Retry-After`` (satellite), ``tpu_server_state`` overlays
  ``recovering``, and after recovery ``tpu_recovery_total`` /
  ``tpu_recovery_seconds`` are exact;
- fleet tier: the autoscaler's liveness-replacement branch replaces a
  readiness-dead replica (distinct verb from burn scaling) with zero
  client-visible failures on the surviving replica;
- pod tier (``pod`` marker): SIGKILL a pod member mid-generation — the
  supervisor runs the coordinated restart (respawn + jax.distributed
  re-init + lockstep re-warmup) and the interrupted stream RESUMES
  token-identical to the oracle, with the MTTR booked.
"""

import asyncio
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from client_tpu.llm import recovery as recovery_mod
from client_tpu.llm.engine import EngineRecoveringError, _recovery_param
from client_tpu.llm.recovery import EngineRecovery
from client_tpu.utils import InferenceServerException

pytestmark = pytest.mark.llm


# ---------------------------------------------------------------------------
# units: the request parameter + the error's wire face
# ---------------------------------------------------------------------------


def test_recovery_param_values():
    assert _recovery_param(None) is True
    assert _recovery_param("") is True
    assert _recovery_param("resume") is True
    assert _recovery_param("Resume") is True
    assert _recovery_param("fail") is False
    with pytest.raises(InferenceServerException, match="'resume' or 'fail'"):
        _recovery_param("sometimes")


def test_engine_recovering_error_wire_face():
    from client_tpu.resilience.policy import exception_is_retryable

    e = EngineRecoveringError("llm_x", retry_after_s=2.5)
    assert e.http_status == 503
    assert e.grpc_code == "UNAVAILABLE"
    assert e.retry_after_s == 2.5
    assert e.reason == "recovering"
    assert exception_is_retryable(e) is True
    assert "recovering" in str(e)


# ---------------------------------------------------------------------------
# EngineRecovery state machine on fakes (fake clock, no jax)
# ---------------------------------------------------------------------------


class _Seq:
    def __init__(self):
        self.error = None

    def fail(self, e):
        self.error = e


class _MetricsRecorder:
    def __init__(self):
        self.recoveries = []

    def observe_recovery(self, tier, outcome, seconds):
        self.recoveries.append((tier, outcome, seconds))


class _FakeEngine:
    def __init__(self, survivors, metrics):
        self._survivors = list(survivors)
        self.metrics = metrics
        self.logger = None
        self.recovering = True
        self.on_fatal = None
        self.retry_after_s = None
        self.adopted = None

    def detach_survivors(self):
        out, self._survivors = self._survivors, []
        return out

    def adopt(self, survivors):
        self.adopted = list(survivors)


class _FakeModel:
    name = "fake_llm"

    def __init__(self, engine, fail_attempts):
        self.engine = engine
        self._core = None
        self.reloads = 0
        self._fail_attempts = fail_attempts

    def reload(self):
        self.reloads += 1
        if self.reloads <= self._fail_attempts:
            raise RuntimeError(f"reload attempt {self.reloads} refused")
        self.engine = _FakeEngine([], self.engine.metrics)


def _fake_clock(times):
    state = {"i": 0}

    def clock():
        i = min(state["i"], len(times) - 1)
        state["i"] += 1
        return times[i]

    return clock


def test_engine_recovery_retries_then_succeeds_on_fakes():
    metrics = _MetricsRecorder()
    survivor = _Seq()
    engine = _FakeEngine([survivor], metrics)
    model = _FakeModel(engine, fail_attempts=1)
    sleeps = []
    controller = EngineRecovery(
        model,
        max_attempts=3,
        backoff_s=0.1,
        retry_after_s=2.0,
        clock=_fake_clock([100.0, 107.5]),
        sleep=sleeps.append,
    )
    controller.attach(engine)
    assert engine.on_fatal == controller._on_fatal
    assert engine.retry_after_s == 2.0
    engine.on_fatal(RuntimeError("device lost"))
    controller.join()
    assert controller.state == recovery_mod.READY
    assert controller.recoveries == 1
    assert model.reloads == 2
    assert sleeps == pytest.approx([0.1])  # backoff_s * attempt, once
    # the controller re-attached itself to the replacement engine
    assert model.engine is not engine
    assert model.engine.on_fatal == controller._on_fatal
    # no serving loop existed, so the parked survivor fails retryable
    # rather than silently never streaming again
    assert survivor.error is not None
    assert "serving loop is gone" in str(survivor.error)
    assert metrics.recoveries == [("engine", "success", pytest.approx(7.5))]
    doc = controller.describe()
    assert doc["state"] == "ready" and doc["recoveries"] == 1


def test_engine_recovery_exhaustion_fails_survivors_on_fakes():
    metrics = _MetricsRecorder()
    survivors = [_Seq(), _Seq()]
    engine = _FakeEngine(survivors, metrics)
    model = _FakeModel(engine, fail_attempts=99)
    sleeps = []
    controller = EngineRecovery(
        model,
        max_attempts=3,
        backoff_s=0.1,
        clock=_fake_clock([5.0, 9.0]),
        sleep=sleeps.append,
    )
    controller.attach(engine)
    engine.on_fatal(RuntimeError("device lost"))
    controller.join()
    assert controller.state == recovery_mod.FAILED
    assert controller.failures == 1
    assert model.reloads == 3
    assert sleeps == pytest.approx([0.1, 0.2, 0.3])
    assert engine.recovering is False  # the 503s stop promising recovery
    for seq in survivors:
        assert seq.error is not None
        assert "after 3 attempts" in str(seq.error)
    assert metrics.recoveries == [("engine", "failed", pytest.approx(4.0))]
    assert controller.describe()["state"] == "failed"


# ---------------------------------------------------------------------------
# engine chaos on the real tiny llama
# ---------------------------------------------------------------------------


def _tiny_model(name="llm_heal", **overrides):
    import jax.numpy as jnp

    from client_tpu.llm import EngineConfig
    from client_tpu.llm.serving import LlmEngineModel
    from client_tpu.models import llama

    config = llama.LlamaConfig.tiny(max_seq_len=64, dtype=jnp.float32)
    model = LlmEngineModel(
        name,
        config=config,
        engine_config=EngineConfig(
            block_size=8,
            num_blocks=33,
            max_active=8,
            max_queue=16,
            max_seq_len=64,
        ),
    )
    for key, value in overrides.items():  # auto_recovery / recovery_options
        setattr(model, key, value)
    model.warmup()
    return model


def _dense_reference(model, prompt, max_tokens):
    from client_tpu.models import llama

    return np.asarray(
        llama.generate(
            model._params,
            np.array([prompt], dtype=np.int32),
            model._config,
            max_tokens,
        )
    )[0].tolist()


async def _model_generate(model, prompt, max_tokens, parameters=None,
                          got=None):
    out = [] if got is None else got
    params = {"max_tokens": max_tokens}
    params.update(parameters or {})
    async for response in model.execute_decoupled(
        {"INPUT_IDS": np.array(prompt, dtype=np.int32)}, params
    ):
        out.append(int(response["OUTPUT_IDS"][0]))
        if response["__final__"]:
            break
    return out


def test_engine_fatal_auto_recovers_with_streams_token_identical():
    """Chaos (b): an induced engine-fatal mid-generation quarantines the
    engine, the controller reloads it in the background (fresh KV pool,
    re-warmup), and BOTH in-flight greedy streams resume via seeded
    replay — final tokens EXACTLY the uninterrupted oracle's. Clients
    saw no error at all; the streams just kept going."""
    model = _tiny_model(recovery_options={"backoff_s": 0.01})
    try:
        prompts = [[5, 9, 17, 3], [1, 2, 3]]
        refs = [_dense_reference(model, p, 12) for p in prompts]
        first_engine = model.engine

        async def run():
            streams = [[] for _ in prompts]
            tasks = [
                asyncio.ensure_future(
                    _model_generate(model, p, 12, got=streams[i])
                )
                for i, p in enumerate(prompts)
            ]
            # let both streams emit a few tokens, then pull the device
            # out from under the engine
            while min(len(s) for s in streams) < 3:
                await asyncio.sleep(0.01)
            first_engine.quarantine("induced device failure (chaos)")
            return await asyncio.gather(*tasks)

        results = asyncio.run(run())
        for prompt, tokens, expected in zip(prompts, results, refs):
            assert tokens == expected, f"prompt {prompt} diverged"
        controller = model._recovery
        controller.join()
        assert controller.state == recovery_mod.READY
        assert controller.recoveries == 1
        assert model.engine is not first_engine
        stats = model.engine.stats()
        assert stats["recovering"] is False
        assert stats["kv_blocks_in_use"] == 0
        # the recovered engine serves fresh requests too
        again = asyncio.run(_model_generate(model, prompts[0], 12))
        assert again == refs[0]
    finally:
        model.shutdown()


def test_recovery_fail_optout_gets_error_while_resume_survives():
    """The per-request opt-out: ``recovery: fail`` would rather see a
    retryable error than a transparently resumed stream; its neighbor
    (default ``resume``) rides through the same fatal untouched."""
    model = _tiny_model("llm_optout", recovery_options={"backoff_s": 0.01})
    try:
        prompt = [7, 8, 9]
        ref = _dense_reference(model, prompt, 12)
        first_engine = model.engine

        async def run():
            resumed = []
            failing = []
            resume_task = asyncio.ensure_future(
                _model_generate(model, prompt, 12, got=resumed)
            )
            fail_task = asyncio.ensure_future(
                _model_generate(
                    model, [4, 5], 12, parameters={"recovery": "fail"},
                    got=failing,
                )
            )
            while len(resumed) < 2 or len(failing) < 2:
                await asyncio.sleep(0.01)
            first_engine.quarantine("induced device failure (chaos)")
            tokens = await resume_task
            with pytest.raises(InferenceServerException) as info:
                await fail_task
            return tokens, info.value

        tokens, error = asyncio.run(run())
        assert tokens == ref
        assert getattr(error, "status", lambda: "")() == "UNAVAILABLE"
        model._recovery.join()
        assert model._recovery.state == recovery_mod.READY
    finally:
        model.shutdown()


def test_quarantined_engine_submit_is_recovering_503():
    """While the reload is in flight, submits answer the RECOVERING
    error (503 + Retry-After), not the bare closed UNAVAILABLE — and
    with no recovery wired at all, quarantine still fails everything
    cleanly (the PR-9 posture)."""
    model = _tiny_model("llm_gate", auto_recovery=False)
    try:
        engine = model.engine
        # park the engine in "recovering" by hand: a fatal hook that
        # never reloads (the pod coordinator's shape)
        engine.on_fatal = lambda exc: None
        engine.retry_after_s = 3.0
        engine.quarantine("induced")
        deadline = time.monotonic() + 10
        while not engine.recovering and time.monotonic() < deadline:
            time.sleep(0.01)
        assert engine.recovering is True
        with pytest.raises(EngineRecoveringError) as info:
            engine.submit([1, 2, 3], max_tokens=4)
        assert info.value.retry_after_s == 3.0
        assert info.value.http_status == 503
        stats = engine.stats()
        assert stats["recovering"] is True
        engine.fail_survivors(InferenceServerException("gone"))
        assert engine.recovering is False
        with pytest.raises(InferenceServerException, match="closed"):
            engine.submit([1, 2, 3], max_tokens=4)
    finally:
        model.shutdown()


# ---------------------------------------------------------------------------
# front-end e2e: Retry-After over the real wire + exact metrics
# ---------------------------------------------------------------------------


def _post_json(port, path, payload, timeout=30):
    request = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode("utf-8"),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(request, timeout=timeout) as response:
        return response.status, dict(response.headers), json.loads(
            response.read().decode()
        )


def test_http_503_carries_retry_after_while_quarantined():
    """Satellite e2e: engine-fatal -> the HTTP front-end answers 503
    WITH a Retry-After header while the reload is in flight (the server
    is promising it is healing, not asking for an operator); the state
    gauge overlays ``recovering``; after the reload, the same request
    succeeds and ``tpu_recovery_total`` / ``tpu_recovery_seconds`` are
    EXACT."""
    from client_tpu.server.core import ServerCore
    from client_tpu.server.model_repository import ModelRepository
    from client_tpu.testing import InProcessServer

    model = _tiny_model(
        "llm_wire", recovery_options={"backoff_s": 0.01,
                                      "retry_after_s": 2.0}
    )
    repository = ModelRepository()
    core = ServerCore(repository)
    repository.add_model(model)
    gate = threading.Event()
    original_reload = model.reload

    def gated_reload():
        assert gate.wait(timeout=60), "test never released the reload"
        original_reload()

    model.reload = gated_reload  # type: ignore[method-assign]
    payload = {
        "model": "llm_wire",
        "messages": [{"role": "user", "content": "hi"}],
        "max_tokens": 4,
    }
    with InProcessServer(core=core, builtin_models=False) as server:
        port = server.http_port
        status, _headers, _doc = _post_json(
            port, "/v1/chat/completions", payload
        )
        assert status == 200
        model.engine.quarantine("induced device failure (chaos)")
        deadline = time.monotonic() + 10
        while not core.recovering and time.monotonic() < deadline:
            time.sleep(0.01)
        assert core.recovering is True
        with pytest.raises(urllib.error.HTTPError) as info:
            _post_json(port, "/v1/chat/completions", payload)
        assert info.value.code == 503
        assert info.value.headers["Retry-After"] == "2"
        body = json.loads(info.value.read().decode())
        assert "recovering" in json.dumps(body)
        # the state gauge overlays recovering (3) without dropping
        # readiness — the replica is healing, not draining
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30
        ) as response:
            metrics_text = response.read().decode()
        assert "tpu_server_state 3" in metrics_text
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/v2/health/ready", timeout=30
        ) as response:
            assert response.status == 200
        doc = json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v2/debug/state", timeout=30
            ).read().decode()
        )
        assert doc["server"]["recovering"] is True
        assert doc["llm"]["llm_wire"]["recovery"]["state"] == "recovering"
        # release the reload and watch the replica heal itself
        gate.set()
        model._recovery.join()
        assert model._recovery.state == recovery_mod.READY
        status, _headers, doc = _post_json(
            port, "/v1/chat/completions", payload
        )
        assert status == 200
        assert doc["choices"][0]["message"]["content"]
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30
        ) as response:
            metrics_text = response.read().decode()
        assert "tpu_server_state 0" in metrics_text
        assert (
            'tpu_recovery_total{tier="engine",outcome="success"} 1'
            in metrics_text
        )
        assert (
            'tpu_recovery_seconds_count{tier="engine"} 1' in metrics_text
        )
    model.shutdown()


# ---------------------------------------------------------------------------
# fleet tier: liveness-driven replacement
# ---------------------------------------------------------------------------


def test_autoscaler_liveness_counters_are_hysteretic():
    """check_liveness is pure bookkeeping: a replica must be down
    ``dead_ticks`` CONSECUTIVE ticks (an intentional drain blips shorter
    than that), and counters are keyed by replica identity, not index."""
    from client_tpu.perf.fleet_runner import Autoscaler

    class _FakeFleet:
        def __init__(self):
            self.replicas = ["a", "b"]
            self.size = 2

    fleet = _FakeFleet()
    signal = {"alive": [True, True]}
    scaler = Autoscaler(
        fleet,  # type: ignore[arg-type]
        max_replicas=4,
        dead_ticks=3,
        liveness_signal=lambda: signal["alive"],
    )
    assert scaler.check_liveness() is None
    signal["alive"] = [True, False]
    assert scaler.check_liveness() is None
    assert scaler.check_liveness() is None
    # a recovery blip resets the streak
    signal["alive"] = [True, True]
    assert scaler.check_liveness() is None
    signal["alive"] = [True, False]
    assert scaler.check_liveness() is None
    assert scaler.check_liveness() is None
    assert scaler.check_liveness() == 1
    # replica replaced under the counter: identity key starts fresh
    fleet.replicas[1] = "c"
    assert scaler.check_liveness() is None


def test_fleet_replaces_liveness_dead_replica_zero_client_failures():
    """Chaos (c): a replica whose readiness is down past the threshold
    is REPLACED (router out first, fresh replica in, corpse stopped) —
    while a client hammering the surviving replica sees zero failures —
    and the replacement books tier="fleet" recovery metrics."""
    from client_tpu.perf.fleet_runner import (
        Autoscaler,
        DeviceBoundModel,
        FleetRunner,
    )

    def factory():
        return DeviceBoundModel(step_s=0.001)

    fleet = FleetRunner(2, model_factories=[factory]).start()
    try:
        routed_out, routed_in = [], []
        scaler = Autoscaler(
            fleet,
            max_replicas=4,
            dead_ticks=2,
            on_scale_out=lambda s: routed_in.append(s),
            on_scale_in=lambda s: routed_out.append(s),
        )
        assert scaler.tick() == "hold"
        dead = fleet.replicas[1]
        survivor_port = fleet.replicas[0].http_port
        dead.stop()  # the replica dies (readiness gone, sockets closed)

        failures = []

        def hammer():
            for _ in range(20):
                try:
                    status, _h, _d = _post_json(
                        survivor_port,
                        "/v2/models/device_sim/infer",
                        {
                            "inputs": [
                                {
                                    "name": "INPUT0",
                                    "datatype": "INT32",
                                    "shape": [4],
                                    "data": [1, 2, 3, 4],
                                }
                            ]
                        },
                    )
                    assert status == 200
                except Exception as e:  # noqa: BLE001 - collected below
                    failures.append(e)

        client = threading.Thread(target=hammer, daemon=True)
        client.start()
        decisions = [scaler.tick(), scaler.tick()]
        assert decisions == ["hold", "replace"]
        client.join(timeout=60)
        assert failures == []
        assert fleet.replacements == 1
        assert fleet.size == 2
        replacement = fleet.replicas[1]
        assert replacement is not dead
        assert replacement.core.ready
        assert routed_out == [dead]
        assert routed_in == [replacement]
        event = scaler.events[-1]
        assert event["decision"] == "replace" and event["index"] == 1
        text = replacement.core.metrics.render()
        assert (
            'tpu_recovery_total{tier="fleet",outcome="success"} 1' in text
        )
        assert 'tpu_recovery_seconds_count{tier="fleet"} 1' in text
        # the replacement actually serves
        status, _h, doc = _post_json(
            replacement.http_port,
            "/v2/models/device_sim/infer",
            {
                "inputs": [
                    {
                        "name": "INPUT0",
                        "datatype": "INT32",
                        "shape": [4],
                        "data": [9, 9, 9, 9],
                    }
                ]
            },
        )
        assert status == 200
        assert doc["outputs"][0]["data"] == [9, 9, 9, 9]
        # steady state resumes: no flapping replacements
        assert scaler.tick() == "hold"
        assert fleet.replacements == 1
    finally:
        fleet.stop()


# ---------------------------------------------------------------------------
# pod tier: SIGKILL a member mid-generation, supervisor heals the pod
# ---------------------------------------------------------------------------

POD_PROMPT = [5, 9, 17, 3]
POD_RESUME_TOKENS = 48


def _pod_oracle(max_tokens):
    import jax.numpy as jnp

    from client_tpu.llm.serving import LlmEngineModel
    from client_tpu.models import llama

    config = llama.LlamaConfig.tiny(max_seq_len=256, dtype=jnp.float32)
    model = LlmEngineModel("oracle", config=config)
    model.warmup()
    try:
        return asyncio.run(
            _model_generate(model, POD_PROMPT, max_tokens)
        )
    finally:
        model.shutdown()


async def _stream_pod_into(grpc_port, model_name, max_tokens, sink):
    import client_tpu.grpc.aio as grpcclient

    async with grpcclient.InferenceServerClient(
        f"127.0.0.1:{grpc_port}"
    ) as client:

        async def requests():
            tensor = grpcclient.InferInput(
                "INPUT_IDS", [len(POD_PROMPT)], "INT32"
            )
            tensor.set_data_from_numpy(np.array(POD_PROMPT, dtype=np.int32))
            yield {
                "model_name": model_name,
                "inputs": [tensor],
                "parameters": {"max_tokens": max_tokens},
            }

        async for result, error in client.stream_infer(requests()):
            if error is not None:
                return error
            sink.append(int(result.as_numpy("OUTPUT_IDS")[0]))
        return None


def _http_text(port, path, timeout=30):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout
    ) as response:
        return response.read().decode()


@pytest.mark.pod
def test_pod_member_sigkill_supervised_recovery_token_identical():
    """Chaos (a), the tentpole acceptance test: SIGKILL a pod worker
    MID-GENERATION. The supervisor detects the death, runs the
    coordinated restart (new coordinator address, member respawn,
    jax.distributed re-init across survivor + replacement, lockstep
    re-warmup), and the interrupted stream — whose client connection
    never closed — RESUMES and finishes TOKEN-IDENTICAL to the
    uninterrupted single-process oracle. Zero accepted-then-lost
    requests; MTTR booked in ``tpu_recovery_total{tier="pod"}`` and the
    supervisor's event log."""
    from client_tpu.pod.launcher import PodLauncher
    from client_tpu.pod.supervisor import PodSupervisor
    from client_tpu.perf.fleet_runner import read_ports_file

    oracle = _pod_oracle(POD_RESUME_TOKENS)
    assert len(oracle) == POD_RESUME_TOKENS

    launcher = PodLauncher(process_count=2, devices_per_process=2)
    launcher.launch()
    supervisor = None
    try:
        try:
            ports = launcher.wait_ready(timeout_s=240)
        except (RuntimeError, TimeoutError) as e:
            text = str(e)
            if "distributed" in text.lower() or "coordinator" in text.lower():
                pytest.skip(
                    f"platform refuses jax.distributed on CPU: {text[-800:]}"
                )
            raise
        assert ports.get("epoch") == 0
        supervisor = PodSupervisor(
            launcher, poll_interval_s=0.2, deadline_s=240.0
        ).start()

        tokens = []
        outcome = {}

        def stream():
            outcome["error"] = asyncio.run(
                asyncio.wait_for(
                    _stream_pod_into(
                        ports["grpc_port"], ports["model"],
                        POD_RESUME_TOKENS, tokens,
                    ),
                    timeout=280,
                )
            )

        client = threading.Thread(target=stream, daemon=True)
        client.start()
        deadline = time.monotonic() + 120
        while len(tokens) < 4 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert len(tokens) < POD_RESUME_TOKENS, (
            "stream finished before the chaos kill; raise POD_RESUME_TOKENS"
        )
        launcher.kill(1)  # SIGKILL, mid-generation

        client.join(timeout=280)
        assert not client.is_alive(), "resumed stream never finished"
        assert outcome["error"] is None, (
            f"accepted stream failed across the recovery: "
            f"{outcome['error']}\n{launcher.log_tail()}"
        )
        assert tokens == oracle, (
            f"resumed stream diverged from the oracle\n"
            f"{launcher.log_tail()}"
        )

        # the supervisor recorded exactly one successful recovery with
        # its MTTR, within the chaos deadline
        assert supervisor.epoch == 1
        events = [e for e in supervisor.events if e["outcome"] == "success"]
        assert len(events) == 1
        assert 0.0 < events[0]["duration_s"] <= 240.0
        ports_now = read_ports_file(launcher.ports_file)
        assert ports_now is not None and ports_now["epoch"] == 1

        # the healed pod serves fresh streams, still oracle-identical
        fresh = []
        error = asyncio.run(
            asyncio.wait_for(
                _stream_pod_into(
                    ports["grpc_port"], ports["model"], 8, fresh
                ),
                timeout=120,
            )
        )
        assert error is None, error
        assert fresh == oracle[:8]

        metrics_text = _http_text(ports["http_port"], "/metrics")
        assert (
            'tpu_recovery_total{tier="pod",outcome="success"} 1'
            in metrics_text
        )
        assert 'tpu_recovery_seconds_count{tier="pod"} 1' in metrics_text
        # the replaced member's gauges were pruned and re-seeded, alive
        assert 'tpu_pod_process_up{process="1"} 1' in metrics_text
        assert (
            'tpu_recovery_total{tier="pod",outcome="failed"}'
            not in metrics_text
        )
    finally:
        if supervisor is not None:
            supervisor.stop()
        launcher.stop()
