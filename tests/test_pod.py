"""PR-19: pod-scale serving — multi-process mesh + tp-sharded paged decode.

Tiers, cheapest first:

- step-bus units (no jax backend): codec roundtrip, a real follower
  thread in lockstep, and the no-hang contract — a dead worker surfaces
  as a retryable UNAVAILABLE at the broadcast, BEFORE any collective,
  and the fleet's retry classifier treats it like any dead replica;
- PodConfig identity handoff: env roundtrip + validation;
- topology surfaces: MeshPlan pod fields and the process stamp on the
  server's devices block (single-process values in this tier);
- tp-sharded parity on the in-process 8-device mesh (``sharded``):
  every kernel implementation and its ``*_mq`` twin within 1e-5 of the
  unsharded call; the tp=4 engine's greedy tokens EXACTLY match the
  dense oracle with COW sharing + dry-pool preemption invariants intact;
- the fake pod itself (``pod``): two 2-device-capped processes assemble
  one 4-device global mesh (jax.distributed + gloo) and run a
  cross-process collective; a launcher-spawned pod serves real gRPC
  greedy tokens identical to the single-process unsharded oracle —
  a model NEITHER capped member could hold alone — stamps
  process_index/process_count into /v2 metadata, exports per-member
  ``tpu_pod_process_up``/duty gauges, and turns a SIGKILLed worker into
  a clean retryable UNAVAILABLE, never a hung collective.
"""

import asyncio
import dataclasses
import json
import socket
import struct
import threading
import time
import urllib.request

import numpy as np
import pytest

from client_tpu.pod.bus import (
    STOP_OP,
    PodWorkerLostError,
    StepBus,
    StepFollower,
    decode_step,
    encode_step,
)
from client_tpu.pod.runtime import (
    ENV_COORDINATOR,
    ENV_PROCESS_COUNT,
    ENV_PROCESS_INDEX,
    PodConfig,
    PodConfigError,
)
from client_tpu.testing import retry_grpc_poller_flake

pytestmark = pytest.mark.llm

_LEN = struct.Struct(">I")


# ---------------------------------------------------------------------------
# step bus (no jax)
# ---------------------------------------------------------------------------


class TestStepCodec:
    def test_roundtrip_arrays_and_scalars(self):
        args = (
            np.arange(6, dtype=np.int32).reshape(2, 3),
            np.linspace(0.0, 1.0, 4).astype(np.float32),
            7,
            2.5,
            True,
            None,
            "greedy",
        )
        op, decoded = decode_step(encode_step("decode_multi", args))
        assert op == "decode_multi"
        np.testing.assert_array_equal(decoded[0], args[0])
        assert decoded[0].dtype == np.int32 and decoded[0].shape == (2, 3)
        np.testing.assert_array_equal(decoded[1], args[1])
        assert decoded[1].dtype == np.float32
        assert decoded[2:] == (7, 2.5, True, None, "greedy")

    def test_empty_step(self):
        assert decode_step(encode_step(STOP_OP, ())) == (STOP_OP, ())


class TestStepBus:
    def test_lockstep_follow_ack_and_stop(self):
        bus = StepBus(num_workers=1, ack_timeout_s=10.0)
        seen = []

        def on_decode(tokens, positions):
            seen.append((tokens.copy(), positions.copy()))

        result = {}

        def run():
            follower = StepFollower(bus.address, process_index=1)
            result["reason"] = follower.follow({"decode": on_decode})
            result["steps"] = follower.steps
            follower.close()

        thread = threading.Thread(target=run, daemon=True)
        thread.start()
        bus.accept_workers()
        assert bus.alive_workers() == [1]
        for step in range(3):
            bus.broadcast(
                "decode",
                (np.array([step], np.int32), np.array([step + 7], np.int32)),
            )
        assert bus.steps == 3
        # acks carry cumulative busy time (one step stale by design)
        assert set(bus.worker_busy_ns()) == {1}
        bus.stop()
        thread.join(timeout=10)
        assert not thread.is_alive()
        assert result == {"reason": "stop", "steps": 3}
        assert [int(t[0]) for t, _p in seen] == [0, 1, 2]
        assert [int(p[0]) for _t, p in seen] == [7, 8, 9]

    def test_dead_worker_is_retryable_unavailable_not_a_hang(self):
        """The failure contract end to end: a worker that dies after one
        step makes the NEXT broadcast raise PodWorkerLostError (status
        UNAVAILABLE) — which the fleet's retry machinery classifies as
        retryable, so the pod fails over like any dead replica — and the
        bus forgets the worker immediately (liveness gauges follow)."""
        from client_tpu.resilience.policy import exception_is_retryable

        bus = StepBus(num_workers=1, ack_timeout_s=5.0)

        def read_exact(sock, n):
            data = b""
            while len(data) < n:
                chunk = sock.recv(n - len(data))
                assert chunk, "coordinator closed early"
                data += chunk
            return data

        def run():
            host, _, port = bus.address.rpartition(":")
            sock = socket.create_connection((host, int(port)), timeout=10)
            hello = json.dumps({"process_index": 1}).encode("utf-8")
            sock.sendall(_LEN.pack(len(hello)) + hello)
            # execute exactly one step's protocol, then die mid-pod
            (length,) = _LEN.unpack(read_exact(sock, _LEN.size))
            read_exact(sock, length)
            ack = json.dumps({"busy_ns": 12345}).encode("utf-8")
            sock.sendall(_LEN.pack(len(ack)) + ack)
            sock.close()

        thread = threading.Thread(target=run, daemon=True)
        thread.start()
        bus.accept_workers()
        bus.broadcast("decode", (np.array([1], np.int32),))
        assert bus.worker_busy_ns() == {1: 12345}
        thread.join(timeout=10)
        with pytest.raises(PodWorkerLostError) as excinfo:
            bus.broadcast("decode", (np.array([2], np.int32),))
        assert excinfo.value.status() == "UNAVAILABLE"
        assert exception_is_retryable(excinfo.value) is True
        assert bus.alive_workers() == []
        bus.stop()

    def test_accept_timeout_is_bounded(self):
        bus = StepBus(num_workers=1, accept_timeout_s=0.2)
        with pytest.raises(PodWorkerLostError, match="0/1 workers"):
            bus.accept_workers()
        bus.stop()

    def test_hung_worker_trips_ack_deadline(self):
        """Satellite: the ack deadline as its own unit. A worker whose
        SOCKET stays open but that stops acking (a wedged process, not a
        dead one) trips the per-broadcast deadline with the distinct
        ``reason="ack_timeout"`` — still a retryable UNAVAILABLE, still
        dropped from liveness immediately."""
        from client_tpu.resilience.policy import exception_is_retryable

        bus = StepBus(num_workers=1, ack_timeout_s=0.3)
        release = threading.Event()

        def run():
            host, _, port = bus.address.rpartition(":")
            sock = socket.create_connection((host, int(port)), timeout=10)
            hello = json.dumps({"process_index": 1}).encode("utf-8")
            sock.sendall(_LEN.pack(len(hello)) + hello)
            # receive the step but NEVER ack: the wedge, not the crash
            release.wait(timeout=30)
            sock.close()

        thread = threading.Thread(target=run, daemon=True)
        thread.start()
        bus.accept_workers()
        with pytest.raises(PodWorkerLostError, match="did not ack") as info:
            bus.broadcast("decode", (np.array([1], np.int32),))
        assert info.value.reason == "ack_timeout"
        assert info.value.status() == "UNAVAILABLE"
        assert exception_is_retryable(info.value) is True
        assert bus.alive_workers() == []
        # a cleanly dead socket keeps the original reason
        assert PodWorkerLostError("gone").reason == "worker_lost"
        release.set()
        thread.join(timeout=10)
        bus.stop()

    def test_reinit_broadcast_reaches_survivors_only(self):
        """The recovery handshake: ``broadcast_surviving(__reinit__)``
        delivers the new assembly address to live followers (whose
        ``follow`` returns ``"reinit"`` with the args parked on
        ``reinit_args``) and silently skips dead ones."""
        from client_tpu.pod.bus import REINIT_OP

        bus = StepBus(num_workers=2, ack_timeout_s=10.0)
        result = {}

        def survivor():
            follower = StepFollower(bus.address, process_index=1)
            result["reason"] = follower.follow({})
            result["args"] = follower.reinit_args
            follower.close()

        def casualty():
            host, _, port = bus.address.rpartition(":")
            sock = socket.create_connection((host, int(port)), timeout=10)
            hello = json.dumps({"process_index": 2}).encode("utf-8")
            sock.sendall(_LEN.pack(len(hello)) + hello)
            sock.close()  # dies before the recovery broadcast

        threads = [
            threading.Thread(target=survivor, daemon=True),
            threading.Thread(target=casualty, daemon=True),
        ]
        for thread in threads:
            thread.start()
        bus.accept_workers()
        threads[1].join(timeout=10)
        acked = bus.broadcast_surviving(
            REINIT_OP, ("127.0.0.1:7777", 3)
        )
        assert acked == [1]
        threads[0].join(timeout=10)
        assert result["reason"] == "reinit"
        assert tuple(result["args"]) == ("127.0.0.1:7777", 3)
        bus.stop()


class _RescueEngine:
    """Engine face for the fatal-hook unit: parked survivors, a metrics
    recorder, and the recovering promise the hook must drop."""

    def __init__(self, survivors):
        self._survivors = list(survivors)
        self.recovering = True
        self.observed = []
        self.metrics = self

    def detach_survivors(self):
        survivors, self._survivors = self._survivors, []
        return survivors

    def observe_recovery(self, tier, outcome, seconds):
        self.observed.append((tier, outcome))


class _RescueSeq:
    def __init__(self):
        self.error = None

    def fail(self, exc):
        self.error = exc


def test_pod_rescue_deadline_fails_orphans(monkeypatch):
    """An UNsupervised quarantine must not hold streams open forever:
    when no recovery plan claims the parked survivors within the rescue
    deadline, they fail with a retryable UNAVAILABLE, the engine drops
    its recovering promise, and the abandonment is booked."""
    from client_tpu.pod.worker import RESCUE_DEADLINE_ENV, _wire_pod_fatal_hook
    from client_tpu.resilience.policy import exception_is_retryable

    monkeypatch.setenv(RESCUE_DEADLINE_ENV, "0.2")
    seq = _RescueSeq()
    engine = _RescueEngine([seq])
    holder = {"survivors": []}
    quarantined = threading.Event()
    _wire_pod_fatal_hook(engine, holder, quarantined)
    engine.on_fatal(RuntimeError("member lost"))
    assert quarantined.is_set()
    deadline = time.monotonic() + 10
    while seq.error is None and time.monotonic() < deadline:
        time.sleep(0.02)
    assert seq.error is not None
    assert seq.error.status() == "UNAVAILABLE"
    assert "no recovery plan" in str(seq.error)
    assert exception_is_retryable(seq.error) is True
    assert engine.recovering is False
    assert holder["survivors"] == []
    assert engine.observed == [("pod", "abandoned")]


def test_pod_rescue_deadline_spares_claimed_survivors(monkeypatch):
    """The supervised path: a recovery that claims the survivors (sets
    ``holder["rescued"]``, as ``_recover_pod`` does at its start) keeps
    the deadline timer's hands off them."""
    from client_tpu.pod.worker import RESCUE_DEADLINE_ENV, _wire_pod_fatal_hook

    monkeypatch.setenv(RESCUE_DEADLINE_ENV, "0.2")
    seq = _RescueSeq()
    engine = _RescueEngine([seq])
    holder = {"survivors": []}
    _wire_pod_fatal_hook(engine, holder, threading.Event())
    engine.on_fatal(RuntimeError("member lost"))
    with holder["lock"]:
        holder["rescued"].set()
        survivors = list(holder["survivors"])
        holder["survivors"][:] = []
    assert survivors == [seq]
    time.sleep(0.5)
    assert seq.error is None
    assert engine.recovering is True
    assert engine.observed == []


# ---------------------------------------------------------------------------
# pod identity handoff
# ---------------------------------------------------------------------------


class TestPodConfig:
    def test_env_roundtrip(self):
        config = PodConfig(
            coordinator_address="127.0.0.1:5000",
            process_index=1,
            process_count=2,
            local_devices=2,
            bus_address="127.0.0.1:5001",
        )
        assert not config.is_coordinator
        parsed = PodConfig.from_env(config.env())
        assert parsed == config
        # without a bus the variable is absent, not empty
        solo = dataclasses.replace(config, bus_address=None)
        assert "CLIENT_TPU_POD_BUS" not in solo.env()
        assert PodConfig.from_env(solo.env()) == solo

    def test_non_member_environment_is_none(self):
        assert PodConfig.from_env({}) is None

    def test_rejects_malformed_identity(self):
        with pytest.raises(PodConfigError, match="host:port"):
            PodConfig("nohostport", 0, 1)
        with pytest.raises(PodConfigError, match="process_count"):
            PodConfig("127.0.0.1:1", 0, 0)
        with pytest.raises(PodConfigError, match="out of range"):
            PodConfig("127.0.0.1:1", 2, 2)
        with pytest.raises(PodConfigError, match="integers"):
            PodConfig.from_env(
                {
                    ENV_COORDINATOR: "127.0.0.1:1",
                    ENV_PROCESS_INDEX: "zero",
                    ENV_PROCESS_COUNT: "2",
                }
            )


# ---------------------------------------------------------------------------
# topology surfaces (single-process values in this tier)
# ---------------------------------------------------------------------------


def test_mesh_plan_reports_single_process_topology():
    from client_tpu.parallel import sharding as mesh_sharding

    plan = mesh_sharding.resolve(
        mesh_sharding.MeshSpec.parse({"axes": {"tp": 4}})
    )
    doc = plan.describe()
    assert doc["process_count"] == 1
    assert doc["spans_processes"] is False
    assert doc["local_device_count"] == 4


def test_server_topology_stamps_process_identity():
    from client_tpu.pod.runtime import pod_info
    from client_tpu.server.core import ServerCore
    from client_tpu.server.model_repository import ModelRepository

    assert pod_info() == {"process_index": 0, "process_count": 1}
    topology = ServerCore(ModelRepository()).device_topology()
    assert topology["process_index"] == 0
    assert topology["process_count"] == 1
    assert topology["devices"], "expected a device inventory"
    assert all("process" in entry for entry in topology["devices"])


def test_pod_process_gauges_prune_on_replacement():
    """Satellite: ``prune_pod_process`` drops a member's gauge children
    (member replaced / pod shut down) so a scrape never reports a stale
    liveness twin; pruning an absent member is a no-op."""
    from client_tpu.server.core import ServerCore
    from client_tpu.server.model_repository import ModelRepository

    metrics = ServerCore(ModelRepository()).metrics
    metrics.set_pod_process(0, True, 0.25)
    metrics.set_pod_process(1, True, 0.5)
    text = metrics.render()
    assert 'tpu_pod_process_up{process="1"} 1' in text
    assert 'tpu_pod_process_duty_ratio{process="1"} 0.5' in text
    metrics.prune_pod_process(1)
    text = metrics.render()
    assert 'process="1"' not in text
    assert 'tpu_pod_process_up{process="0"} 1' in text
    metrics.prune_pod_process(7)  # never set: no-op, no raise
    metrics.prune_pod_process(0)
    assert "process=" not in metrics.render()


# ---------------------------------------------------------------------------
# tp-sharded parity on the in-process mesh
# ---------------------------------------------------------------------------

KERNELS = ("fused_xla", "pallas_interpret")

#: two full blocks at block_size=8 — the shared prefix of the COW tests
PREFIX = [9, 3, 7, 1, 5, 2, 8, 4, 6, 1, 2, 3, 4, 5, 6, 7]


def _tiny_float32(max_seq_len=64):
    import jax
    import jax.numpy as jnp

    from client_tpu.models import llama

    config = llama.LlamaConfig.tiny(max_seq_len=max_seq_len, dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), config)
    return config, params


async def _model_generate(model, prompt, max_tokens):
    out = []
    async for response in model.execute_decoupled(
        {"INPUT_IDS": np.array(prompt, dtype=np.int32)},
        {"max_tokens": max_tokens},
    ):
        out.append(int(response["OUTPUT_IDS"][0]))
        if response["__final__"]:
            break
    return out


@pytest.mark.sharded
@pytest.mark.parametrize("kernel", KERNELS)
def test_tp_paged_decode_parity_per_kernel(sharded_devices, monkeypatch, kernel):
    """Acceptance: the tp=4 engine's device fns (prefill + paged decode)
    stay within 1e-5 of the single-device oracle, with identical argmax,
    for every kernel implementation."""
    from client_tpu.llm.serving import LlmEngineModel

    monkeypatch.setenv("CLIENT_TPU_LLM_KERNEL", kernel)
    config, params = _tiny_float32()
    oracle = LlmEngineModel(f"oracle_{kernel}", config=config, params=params)
    tp = LlmEngineModel(f"tp4_{kernel}", config=config, params=params, tp=4)
    oracle.warmup()
    tp.warmup()
    try:
        assert oracle.decode_kernel == kernel
        assert tp.decode_kernel == kernel
        assert tp.mesh_plan is not None and not tp.mesh_plan.spans_processes
        assert tp.config()["parameters"]["tp"]["string_value"] == "4"
        p1, d1, _ = oracle._device_fns
        p4, d4, _ = tp._device_fns
        pages1, pages4 = oracle.engine._pages, tp.engine._pages
        bucket = oracle.engine_config.prefill_bucket_min
        table = np.zeros(
            [oracle.engine_config.max_blocks_per_seq], np.int32
        )
        table[:4] = [1, 2, 3, 4]
        rng = np.random.default_rng(0)
        tokens = rng.integers(
            1, config.vocab_size - 1, size=(1, bucket)
        ).astype(np.int32)
        l1, pages1 = p1(tokens, table, pages1, bucket - 1, 0)
        l4, pages4 = p4(tokens, table, pages4, bucket - 1, 0)
        a1, a4 = np.asarray(l1), np.asarray(l4)
        assert np.abs(a1 - a4).max() <= 1e-5
        assert a1[0].argmax() == a4[0].argmax()
        position = bucket
        # the first step takes prefill's token from the host; every
        # later one takes the ids the step before left on the device
        width = oracle.engine_config.ids_width
        i1 = i4 = np.zeros([width], np.int32)
        lane_map = np.array([-1], np.int32)
        tok = np.array([int(a1[0].argmax())], np.int32)
        for _step in range(4):
            i1, o1, pages1 = d1(
                i1, lane_map, tok, np.array([position], np.int32),
                table[None, :4], pages1,
            )
            i4, o4, pages4 = d4(
                i4, lane_map, tok, np.array([position], np.int32),
                table[None, :4], pages4,
            )
            a1, a4 = np.asarray(o1), np.asarray(o4)
            assert np.abs(a1 - a4).max() <= 1e-5, f"decode step {_step}"
            assert a1[0].argmax() == a4[0].argmax()
            assert np.asarray(i1).tolist() == np.asarray(i4).tolist() == (
                [int(a1[0].argmax())] + [0] * (width - 1)
            )
            lane_map = np.array([0], np.int32)
            position += 1
    finally:
        oracle.shutdown()
        tp.shutdown()


@pytest.mark.sharded
def test_tp_attention_twins_match_unsharded(sharded_devices):
    """``make_tp_attention`` — the shard_map wrap the engine applies
    under tp — equals the unsharded kernel call within 1e-5 for every
    served implementation, at one query row a sequence (decode) and at
    several (speculative verify), on ragged page layouts."""
    from client_tpu.models import paged_attention as pa
    from client_tpu.parallel import sharding as mesh_sharding

    plan = mesh_sharding.resolve(
        mesh_sharding.MeshSpec.parse({"axes": {"tp": 4}})
    )
    rng = np.random.default_rng(7)
    b, h, kv, d, bs, num_blocks, width = 3, 8, 4, 16, 8, 17, 4
    k_pages = rng.normal(size=(num_blocks, bs, kv, d)).astype(np.float32)
    v_pages = rng.normal(size=(num_blocks, bs, kv, d)).astype(np.float32)
    tables = np.zeros((b, width), np.int32)
    tables[0, :1] = [1]
    tables[1, :2] = [2, 3]
    tables[2, :4] = [4, 5, 6, 7]
    first = np.array([5, 11, 25], np.int32)
    for name in KERNELS:
        _, attn = pa.resolve_decode_attention(name, "cpu")
        wrapped = pa.make_tp_attention(attn, plan.mesh)
        for t in (1, 3):
            q = rng.normal(size=(b, t, h, d)).astype(np.float32)
            positions = (first[:, None] + np.arange(t)[None, :]).astype(
                np.int32
            )
            args = (q, k_pages, v_pages, tables, positions)
            reference = np.asarray(attn(*args))
            got = np.asarray(wrapped(*args))
            assert np.abs(got - reference).max() <= 1e-5, (name, t)


@pytest.mark.sharded
def test_tp_engine_cow_preemption_and_tokens_match_oracle(sharded_devices):
    """COW/refcount and preemption invariants don't know the pool is
    sharded: a tp=4 engine under a dry pool (8 allocatable blocks <<
    the gross working set) reproduces the dense single-device oracle
    EXACTLY, hits the shared prefix, preempts, and reclaims every
    block."""
    from client_tpu.llm import EngineConfig
    from client_tpu.llm.serving import LlmEngineModel
    from client_tpu.models import llama

    config, params = _tiny_float32()
    model = LlmEngineModel(
        "llm_tp_dry_pool",
        config=config,
        params=params,
        engine_config=EngineConfig(
            block_size=8,
            num_blocks=9,
            max_active=8,
            max_queue=16,
            max_seq_len=64,
        ),
        tp=4,
    )
    model.warmup()
    try:
        prompts = [PREFIX + [30 + i] for i in range(4)]
        # the dense oracle runs the UNSHARDED reference forward pass
        references = [
            np.asarray(
                llama.generate(
                    params, np.array([p], dtype=np.int32), config, 14
                )
            )[0].tolist()
            for p in prompts
        ]

        async def run():
            results = await asyncio.gather(
                *[_model_generate(model, p, 14) for p in prompts]
            )
            for prompt, got, expected in zip(prompts, results, references):
                assert got == expected, f"prompt {prompt} diverged"
            stats = model.engine.stats()
            assert stats["preemptions"] > 0
            assert stats["prefix_cache_hits"] > 0
            assert stats["kv_blocks_in_use"] == 0

        asyncio.run(run())
    finally:
        model.shutdown()


# ---------------------------------------------------------------------------
# the fake pod: coordinator/worker pair
# ---------------------------------------------------------------------------


@pytest.mark.pod
def test_pod_assembles_global_mesh_and_collectives(pod_runtime):
    """Two 2-device-capped processes assemble ONE 4-device global mesh:
    jax sees the pod, a process-spanning placement really is
    non-addressable, a cross-process collective produces the global
    answer, the mesh plan reports pod topology, and the canonical
    capacity error carries the pod context."""
    import jax
    import jax.numpy as jnp

    from client_tpu.parallel import sharding as mesh_sharding
    from client_tpu.parallel.executor import gather_global, place_global

    assert pod_runtime.process_count == 2
    assert pod_runtime.local_device_count == 2
    assert pod_runtime.global_device_count == 4
    assert len(jax.devices()) == 4
    assert len(jax.local_devices()) == 2

    plan = mesh_sharding.resolve(
        mesh_sharding.MeshSpec.parse({"axes": {"tp": 4}})
    )
    doc = plan.describe()
    assert doc["process_count"] == 2
    assert doc["spans_processes"] is True
    assert doc["local_device_count"] == 2

    x = np.arange(8.0, dtype=np.float32)
    global_x = place_global(x, plan.sharding("tp"))
    assert not global_x.sharding.is_fully_addressable
    total = jax.jit(jnp.sum, out_shardings=plan.replicated())(global_x)
    assert float(np.asarray(gather_global(total))) == pytest.approx(28.0)

    with pytest.raises(
        mesh_sharding.MeshUnavailableError,
        match=r"pod of 2 processes, 2 devices local",
    ):
        mesh_sharding.resolve(
            mesh_sharding.MeshSpec.parse({"axes": {"tp": 8}})
        )


# ---------------------------------------------------------------------------
# the fake pod: launcher-spawned serving + chaos
# ---------------------------------------------------------------------------

POD_PROMPT = [5, 9, 17, 3]
POD_TOKENS = 8


def _oracle_tokens():
    """The single-process unsharded oracle for the pod worker's default
    model (same config family, same PRNGKey(0) params)."""
    import jax.numpy as jnp

    from client_tpu.llm.serving import LlmEngineModel
    from client_tpu.models import llama

    config = llama.LlamaConfig.tiny(max_seq_len=256, dtype=jnp.float32)
    model = LlmEngineModel("oracle", config=config)
    model.warmup()
    try:
        return asyncio.run(_model_generate(model, POD_PROMPT, POD_TOKENS))
    finally:
        model.shutdown()


async def _stream_pod(grpc_port, model_name):
    """One greedy stream against the pod; returns (tokens, error)."""
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
                "parameters": {"max_tokens": POD_TOKENS},
            }

        tokens = []
        async for result, error in client.stream_infer(requests()):
            if error is not None:
                return tokens, error
            tokens.append(int(result.as_numpy("OUTPUT_IDS")[0]))
        return tokens, None


def _http_get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=30
    ) as response:
        return response.read().decode()


def _pod_up(metrics_text, process):
    """The exported ``tpu_pod_process_up{process="N"}`` sample value."""
    needle = f'tpu_pod_process_up{{process="{process}"}} '
    for line in metrics_text.splitlines():
        if line.startswith(needle):
            return float(line.split()[-1])
    return None


@pytest.mark.pod
def test_pod_launcher_serves_model_no_member_could_hold_alone():
    """The tentpole acceptance test, end to end on the fake pod: the
    launcher spawns a coordinator/worker pair, each capped to 2 virtual
    devices, that together serve the tp=4 model (mesh demand 4 > either
    member's budget) over real gRPC with greedy tokens IDENTICAL to the
    single-process unsharded oracle; /v2 metadata stamps the process
    topology and /metrics exports per-member liveness. Then the chaos
    half: SIGKILLing the worker mid-service turns the next stream into
    a clean retryable UNAVAILABLE — never a hung collective — and the
    coordinator's liveness gauge drops to 0."""
    from client_tpu.pod.launcher import PodLauncher

    oracle = _oracle_tokens()
    assert len(oracle) == POD_TOKENS

    launcher = PodLauncher(process_count=2, devices_per_process=2)
    launcher.launch()
    try:
        try:
            ports = launcher.wait_ready(timeout_s=240)
        except (RuntimeError, TimeoutError) as e:
            text = str(e)
            if "distributed" in text.lower() or "coordinator" in text.lower():
                pytest.skip(
                    "platform refuses jax.distributed on CPU: "
                    f"{text[-800:]}"
                )
            raise
        # neither member could hold this mesh alone: demand 4, budget 2
        assert ports["process_count"] == 2
        assert ports["global_device_count"] == 4
        assert ports["local_device_count"] == 2

        # a stream that comes back empty with no error is the grpcio
        # poller flake, not a pod regression — the shared shim retries
        tokens, error = retry_grpc_poller_flake(
            lambda: asyncio.run(
                asyncio.wait_for(
                    _stream_pod(ports["grpc_port"], ports["model"]),
                    timeout=120,
                )
            ),
            lambda result: result[1] is not None or len(result[0]) > 0,
        )
        assert error is None, error
        assert tokens == oracle

        metadata = json.loads(_http_get(ports["http_port"], "/v2"))
        assert metadata["devices"]["process_index"] == 0
        assert metadata["devices"]["process_count"] == 2
        metrics = _http_get(ports["http_port"], "/metrics")
        assert _pod_up(metrics, 0) == 1.0
        assert _pod_up(metrics, 1) == 1.0
        assert "tpu_pod_process_duty_ratio" in metrics

        # chaos: kill the worker, then ask the pod to decode again
        launcher.kill(1)
        tokens, error = retry_grpc_poller_flake(
            lambda: asyncio.run(
                asyncio.wait_for(
                    _stream_pod(ports["grpc_port"], ports["model"]),
                    timeout=120,
                )
            ),
            lambda result: result[1] is not None or len(result[0]) > 0,
        )
        assert error is not None, (
            f"stream succeeded ({tokens}) after the worker died"
        )
        status = str(getattr(error, "status", lambda: "")() or "")
        assert "UNAVAILABLE" in (status + str(error))
        # the reporter notices the dropped worker within its 1s cadence
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            metrics = _http_get(ports["http_port"], "/metrics")
            if _pod_up(metrics, 1) == 0.0:
                break
            time.sleep(0.5)
        assert _pod_up(metrics, 1) == 0.0
        assert _pod_up(metrics, 0) == 1.0
    finally:
        launcher.stop()
