"""Scheduling & admission control (client_tpu.scheduling).

Covers the QoS layer end to end: queue-policy resolution, the priority
queue's ordering/expiry semantics (fake clocks — explicit "now" values),
the rate limiter's grant order, batcher integration (priority ordering
under contention, queue-full shedding at max_queue_size, queue timeouts
firing before execution), the 429/RESOURCE_EXHAUSTED wire mapping on both
front-ends, Retry-After honoring in the resilience layer, and the
64-request overload burst the subsystem exists for.
"""

import asyncio
import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from client_tpu.scheduling import (
    TIMEOUT_ACTION_CONTINUE,
    AdmissionGate,
    PriorityQueue,
    QueueFullError,
    QueuePolicy,
    QueueTimeoutError,
    RateLimiter,
)
from client_tpu.server.core import (
    CoreRequest,
    CoreResponse,
    CoreTensor,
    ServerCore,
    _BatchMeta,
)
from client_tpu.server.model_repository import Model, ModelRepository
from client_tpu.testing.inprocess import InProcessServer
from client_tpu.utils import InferenceServerException

pytestmark = pytest.mark.scheduling


class SchedModel(Model):
    """Batchable model with a blockable execute() that records batches."""

    inputs = [{"name": "X", "datatype": "FP32", "shape": [2]}]
    outputs = [{"name": "Y", "datatype": "FP32", "shape": [2]}]

    def __init__(self, name="sched", delay_s=0.0, **overrides):
        self.name = name
        self.delay_s = delay_s
        for key, value in overrides.items():
            setattr(self, key, value)
        self.gate = threading.Event()
        self.gate.set()
        self.executed = []  # per execution: sorted first-column values
        self.seen_parameters = []

    def execute(self, inputs, parameters):
        self.gate.wait(timeout=10)
        if self.delay_s:
            import time

            time.sleep(self.delay_s)
        self.seen_parameters.append(dict(parameters))
        x = inputs["X"]
        rows = np.atleast_2d(x)
        self.executed.append(sorted(float(v) for v in rows[:, 0]))
        return {"Y": x + 1.0}


def make_core(model):
    repository = ModelRepository()
    repository.add_model(model)
    return ServerCore(repository)


def request_for(
    model_name, value, rows=2, priority=None, timeout_us=None, extra=None
):
    data = np.full([rows, 2], value, dtype=np.float32)
    parameters = dict(extra or {})
    if priority is not None:
        parameters["priority"] = priority
    if timeout_us is not None:
        parameters["timeout"] = timeout_us
    return CoreRequest(
        model_name=model_name,
        inputs=[CoreTensor("X", "FP32", list(data.shape), data)],
        parameters=parameters,
    )


def metric_value(text, name, **labels):
    for line in text.splitlines():
        if line.startswith(name) and all(
            f'{k}="{v}"' in line for k, v in labels.items()
        ):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


# ---------------------------------------------------------------------------
# QueuePolicy


def test_queue_policy_priority_resolution():
    policy = QueuePolicy(priority_levels=3, default_priority_level=0)
    # unprioritized traffic lands on the LOWEST level
    assert policy.priority_of({}) == 3
    assert policy.priority_of({"priority": 1}) == 1
    # clamping
    assert policy.priority_of({"priority": 99}) == 3
    assert policy.priority_of({"priority": -2}) == 3
    assert policy.priority_of({"priority": "bogus"}) == 3
    explicit_default = QueuePolicy(
        priority_levels=3, default_priority_level=2
    )
    assert explicit_default.priority_of({}) == 2
    # no levels declared: everything is level 1
    assert QueuePolicy().priority_of({"priority": 7}) == 1


def test_queue_policy_timeout_resolution():
    policy = QueuePolicy(default_timeout_us=1000)
    assert policy.timeout_us_of({}) == 1000
    assert policy.timeout_us_of({"timeout": 250}) == 250
    assert policy.timeout_us_of({"timeout_us": 300}) == 300
    assert policy.deadline_ns({}, arrival_ns=5_000) == 5_000 + 1000 * 1000
    # override disabled: the request's own timeout is ignored
    pinned = QueuePolicy(default_timeout_us=1000, allow_timeout_override=False)
    assert pinned.timeout_us_of({"timeout": 1}) == 1000
    # no timeout anywhere -> no deadline
    assert QueuePolicy().deadline_ns({}, arrival_ns=5_000) is None


def test_queue_policy_from_model():
    model = SchedModel(
        priority_levels=2,
        default_priority_level=1,
        queue_policy={
            "max_queue_size": 8,
            "default_timeout_us": 500,
            "timeout_action": "continue",
            "allow_timeout_override": False,
        },
        rate_limiter={
            "resources": [{"name": "slot", "count": 2}],
            "priority": 1,
        },
    )
    policy = QueuePolicy.from_model(model)
    assert policy.max_queue_size == 8
    assert policy.default_timeout_us == 500
    assert policy.timeout_action == TIMEOUT_ACTION_CONTINUE
    assert not policy.allow_timeout_override
    assert policy.levels == 2
    assert policy.rate_resources == {"slot": 2}
    assert policy.rate_priority == 1
    assert policy.enabled
    assert not QueuePolicy.from_model(SchedModel()).enabled


def test_model_config_declares_scheduling():
    model = SchedModel(
        max_batch_size=4,
        priority_levels=2,
        queue_policy={"max_queue_size": 8, "timeout_action": "continue"},
        rate_limiter={"resources": [{"name": "slot", "count": 1}]},
    )
    config = model.config()
    db = config["dynamic_batching"]
    assert db["priority_levels"] == 2
    assert db["default_queue_policy"]["max_queue_size"] == 8
    assert db["default_queue_policy"]["timeout_action"] == "DELAY"
    assert config["rate_limiter"]["resources"] == [
        {"name": "slot", "count": 1}
    ]


# ---------------------------------------------------------------------------
# PriorityQueue (fake clock: explicit now_ns values)


def test_priority_queue_orders_levels_fifo():
    q = PriorityQueue(levels=2)
    q.push("low-a", level=2)
    q.push("high-a", level=1)
    q.push("low-b", level=2)
    q.push("high-b", level=1)
    assert [i.value for i in q.scan()] == [
        "high-a", "high-b", "low-a", "low-b",
    ]
    assert len(q) == 4
    assert q.depths() == {1: 2, 2: 2}
    items = q.scan()
    q.remove([items[0], items[2]])
    assert [i.value for i in q.scan()] == ["high-b", "low-b"]
    assert len(q) == 2


def test_priority_queue_expire_reject_and_demote():
    q = PriorityQueue(levels=2)
    q.push("keeps", level=1, deadline_ns=1_000)
    q.push("rejects", level=1, deadline_ns=100, timeout_action="reject")
    q.push("demotes", level=1, deadline_ns=100, timeout_action="continue")
    rejected = q.expire(now_ns=500)
    assert [i.value for i in rejected] == ["rejects"]
    # demoted entry survives, behind every live entry, and expires once
    assert [i.value for i in q.scan()] == ["keeps", "demotes"]
    assert len(q) == 2
    assert q.expire(now_ns=2_000_000) != []  # "keeps" now expires
    assert [i.value for i in q.scan()] == ["demotes"]
    assert q.depths() == {1: 1, 2: 0}


def test_priority_queue_clamps_levels():
    q = PriorityQueue(levels=2)
    q.push("a", level=99)
    q.push("b", level=0)
    assert [i.level for i in q.scan()] == [1, 2]


# ---------------------------------------------------------------------------
# RateLimiter


def test_rate_limiter_acquire_and_release():
    limiter = RateLimiter()
    limiter.register({"slot": 1})
    assert limiter.acquire_blocking({"slot": 1}, timeout_s=0.5)
    assert limiter.available("slot") == 0
    limiter.release({"slot": 1})
    assert limiter.available("slot") == 1
    # register grows capacity to the max demand
    limiter.register({"slot": 3})
    assert limiter.available("slot") == 3


def test_rate_limiter_grants_by_priority():
    limiter = RateLimiter()
    limiter.register({"slot": 1})
    order = []

    async def run():
        await limiter.acquire({"slot": 1})

        async def waiter(tag, prio):
            await limiter.acquire({"slot": 1}, priority=prio)
            order.append(tag)
            limiter.release({"slot": 1})

        low = asyncio.ensure_future(waiter("low", 2))
        await asyncio.sleep(0)
        high = asyncio.ensure_future(waiter("high", 1))
        await asyncio.sleep(0)
        limiter.release({"slot": 1})
        await asyncio.gather(low, high)

    asyncio.run(run())
    assert order == ["high", "low"]


def test_rate_limiter_blocking_timeout():
    limiter = RateLimiter()
    limiter.register({"slot": 1})
    assert limiter.acquire_blocking({"slot": 1})
    assert not limiter.acquire_blocking({"slot": 1}, timeout_s=0.01)
    limiter.release({"slot": 1})
    assert limiter.acquire_blocking({"slot": 1}, timeout_s=0.01)


def test_rate_limiter_serializes_models_sharing_a_pool():
    """Two models declaring the same resource may not execute
    concurrently (resource exhaustion blocks the second)."""
    shared = {"resources": [{"name": "device", "count": 1}]}
    a = SchedModel(name="ratelim_a", rate_limiter=shared)
    b = SchedModel(name="ratelim_b", rate_limiter=shared)
    repository = ModelRepository()
    repository.add_model(a)
    repository.add_model(b)
    core = ServerCore(repository)
    a.gate.clear()

    async def run():
        fut_a = asyncio.ensure_future(core.infer(request_for("ratelim_a", 1.0)))
        await asyncio.sleep(0.1)  # a holds the device resource
        fut_b = asyncio.ensure_future(core.infer(request_for("ratelim_b", 2.0)))
        await asyncio.sleep(0.1)
        assert b.executed == []  # blocked on the pool, not executing
        a.gate.set()
        await asyncio.gather(fut_a, fut_b)

    try:
        asyncio.run(run())
    finally:
        core.close()
    assert a.executed and b.executed


# ---------------------------------------------------------------------------
# AdmissionGate + deadline helpers


def test_admission_gate_bounds_waiting_room():
    gate = AdmissionGate(QueuePolicy(max_queue_size=1))
    ticket = gate.enter("m")
    with pytest.raises(QueueFullError):
        gate.enter("m")
    ticket.started()
    ticket.started()  # idempotent
    second = gate.enter("m")
    second.close()
    assert gate.waiting == 0


def test_batch_signature_ignores_scheduling_params():
    model = SchedModel(max_batch_size=4)
    meta = _BatchMeta(model)
    base = request_for("sched", 1.0, rows=1)
    prioritized = request_for("sched", 1.0, rows=1, priority=1, timeout_us=500)
    other = request_for("sched", 1.0, rows=1, priority=2)
    custom = request_for("sched", 1.0, rows=1, extra={"temperature": 0.5})
    assert meta.signature(base) == meta.signature(prioritized)
    assert meta.signature(base) == meta.signature(other)
    # non-scheduling params still fragment batches (execution inputs)
    assert meta.signature(base) != meta.signature(custom)


def test_requests_differing_only_in_scheduling_params_share_a_batch():
    model = SchedModel(max_batch_size=4)
    core = make_core(model)
    model.gate.clear()

    async def run():
        blocker = asyncio.ensure_future(core.infer(request_for("sched", 0.0)))
        await asyncio.sleep(0.05)
        a = asyncio.ensure_future(
            core.infer(request_for("sched", 1.0, rows=1, priority=1))
        )
        b = asyncio.ensure_future(
            core.infer(request_for("sched", 2.0, rows=1, timeout_us=10**9))
        )
        await asyncio.sleep(0.02)
        model.gate.set()
        await asyncio.gather(blocker, a, b)

    try:
        asyncio.run(run())
    finally:
        core.close()
    # blocker alone, then ONE merged execution for both stragglers
    assert model.executed == [[0.0, 0.0], [1.0, 2.0]]


# ---------------------------------------------------------------------------
# Batcher integration


def test_priority_ordering_under_contention():
    model = SchedModel(max_batch_size=2, priority_levels=2)
    core = make_core(model)
    model.gate.clear()

    async def run():
        blocker = asyncio.ensure_future(core.infer(request_for("sched", 0.0)))
        await asyncio.sleep(0.05)
        lows = [
            asyncio.ensure_future(
                core.infer(request_for("sched", 10.0 + i, priority=2))
            )
            for i in range(2)
        ]
        await asyncio.sleep(0.01)
        high = asyncio.ensure_future(
            core.infer(request_for("sched", 20.0, priority=1))
        )
        await asyncio.sleep(0.01)
        model.gate.set()
        await asyncio.gather(blocker, high, *lows)

    try:
        asyncio.run(run())
    finally:
        core.close()
    # the high-priority request arrived LAST but executes first after the
    # in-flight batch; FIFO within the low-priority level is preserved
    assert model.executed == [
        [0.0, 0.0], [20.0, 20.0], [10.0, 10.0], [11.0, 11.0],
    ]


def test_queue_full_rejection_at_max_queue_size():
    model = SchedModel(max_batch_size=2, queue_policy={"max_queue_size": 2})
    core = make_core(model)
    model.gate.clear()

    async def run():
        blocker = asyncio.ensure_future(core.infer(request_for("sched", 0.0)))
        await asyncio.sleep(0.05)
        queued = [
            asyncio.ensure_future(core.infer(request_for("sched", 1.0 + i)))
            for i in range(2)
        ]
        await asyncio.sleep(0.02)
        with pytest.raises(QueueFullError) as excinfo:
            await core.infer(request_for("sched", 9.0))
        assert excinfo.value.status() == "RESOURCE_EXHAUSTED"
        assert excinfo.value.http_status == 429
        model.gate.set()
        await asyncio.gather(blocker, *queued)

    try:
        asyncio.run(run())
    finally:
        core.close()
    text = core.metrics.render()
    assert metric_value(
        text, "tpu_queue_rejected_total", model="sched", reason="queue_full"
    ) == 1
    # rejected requests count as failures in the statistics extension too
    stats = core.statistics("sched")["model_stats"][0]
    assert stats["inference_stats"]["fail"]["count"] == 1


def test_queue_timeout_fires_before_execution():
    model = SchedModel(max_batch_size=2)
    core = make_core(model)
    model.gate.clear()

    async def run():
        blocker = asyncio.ensure_future(core.infer(request_for("sched", 0.0)))
        await asyncio.sleep(0.05)
        doomed = asyncio.ensure_future(
            core.infer(request_for("sched", 1.0, timeout_us=1000))
        )
        await asyncio.sleep(0.05)  # far past the 1 ms queue deadline
        model.gate.set()
        await blocker
        with pytest.raises(QueueTimeoutError) as excinfo:
            await doomed
        assert excinfo.value.status() == "DEADLINE_EXCEEDED"

    try:
        asyncio.run(run())
    finally:
        core.close()
    # the timed-out request never reached the device
    assert model.executed == [[0.0, 0.0]]
    assert metric_value(
        core.metrics.render(),
        "tpu_queue_rejected_total",
        model="sched",
        reason="timeout",
    ) == 1


def test_queue_timeout_continue_demotes_instead_of_rejecting():
    model = SchedModel(
        max_batch_size=2,
        queue_policy={"timeout_action": "continue"},
    )
    core = make_core(model)
    model.gate.clear()

    async def run():
        blocker = asyncio.ensure_future(core.infer(request_for("sched", 0.0)))
        await asyncio.sleep(0.05)
        late = asyncio.ensure_future(
            core.infer(request_for("sched", 1.0, timeout_us=1000))
        )
        await asyncio.sleep(0.05)  # past its deadline -> demoted, not shed
        fresh = asyncio.ensure_future(core.infer(request_for("sched", 2.0)))
        await asyncio.sleep(0.01)
        model.gate.set()
        responses = await asyncio.gather(blocker, late, fresh)
        assert all(isinstance(r, CoreResponse) for r in responses)

    try:
        asyncio.run(run())
    finally:
        core.close()
    # the demoted (timed-out) request executed AFTER the fresh one
    assert model.executed == [[0.0, 0.0], [2.0, 2.0], [1.0, 1.0]]


def test_batcher_rechecks_deadlines_after_rate_limit_wait():
    """A batch popped from the queue can outlive its deadline while
    waiting for a rate-limiter grant; reject-action entries must still
    fail BEFORE execution."""
    model = SchedModel(
        max_batch_size=2,
        rate_limiter={"resources": [{"name": "pool", "count": 1}]},
    )
    core = make_core(model)
    core.rate_limiter.register({"pool": 1})

    async def run():
        await core.rate_limiter.acquire({"pool": 1})  # starve the pool
        doomed = asyncio.ensure_future(
            core.infer(request_for("sched", 1.0, timeout_us=1000))
        )
        await asyncio.sleep(0.05)  # grant wait outlives the 1 ms deadline
        core.rate_limiter.release({"pool": 1})
        with pytest.raises(QueueTimeoutError):
            await doomed

    try:
        asyncio.run(run())
    finally:
        core.close()
    assert model.executed == []  # never reached the device


def test_decoupled_streams_shed_while_parked_on_the_pool():
    """Decoupled streams waiting for a rate-limiter grant keep counting
    against max_queue_size (the waiting room empties only after the
    grant), so excess streams shed with 429 instead of hanging."""

    class StreamModel(Model):
        name = "streamer"
        decoupled = True
        max_batch_size = 0
        queue_policy = {"max_queue_size": 1}
        rate_limiter = {"resources": [{"name": "pool", "count": 1}]}
        inputs = [{"name": "X", "datatype": "FP32", "shape": [2]}]
        outputs = [{"name": "Y", "datatype": "FP32", "shape": [2]}]

        async def execute_decoupled(self, inputs, parameters):
            yield {"Y": inputs["X"] + 1.0}

    model = StreamModel()
    core = make_core(model)
    core.rate_limiter.register({"pool": 1})

    async def consume(value):
        results = []
        async for response in core.infer_decoupled(
            request_for("streamer", value, rows=2)
        ):
            results.append(response)
        return results

    async def run():
        await core.rate_limiter.acquire({"pool": 1})  # starve the pool
        waiting = asyncio.ensure_future(consume(1.0))
        await asyncio.sleep(0.05)  # parked in acquire, still "waiting"
        with pytest.raises(QueueFullError):
            await asyncio.wait_for(consume(2.0), timeout=5)
        core.rate_limiter.release({"pool": 1})
        responses = await asyncio.wait_for(waiting, timeout=5)
        assert len(responses) == 1

    try:
        asyncio.run(run())
    finally:
        core.close()


def test_infer_direct_enforces_queue_deadlines():
    """The synchronous direct path (native front-end pump) honors the
    same per-request queue deadline: an expired entry fails with a
    deadline error instead of executing, aligned with its slot."""
    model = SchedModel(max_batch_size=2)
    core = make_core(model)
    good = request_for("sched", 1.0, rows=1)
    doomed = request_for("sched", 2.0, rows=1, timeout_us=1)
    try:
        results = core.infer_direct([good, doomed])
    finally:
        core.close()
    assert isinstance(results[0], CoreResponse)
    assert isinstance(results[1], QueueTimeoutError)
    assert model.executed == [[1.0]]
    assert metric_value(
        core.metrics.render(),
        "tpu_queue_rejected_total",
        model="sched",
        reason="timeout",
    ) == 1


# ---------------------------------------------------------------------------
# The acceptance burst: 64 concurrent, max_queue_size=8, priority_levels=2


def test_burst_64_resolves_everything_and_counts_match():
    model = SchedModel(
        max_batch_size=4,
        priority_levels=2,
        queue_policy={"max_queue_size": 8},
        delay_s=0.002,
    )
    core = make_core(model)

    async def run():
        tasks = [
            asyncio.ensure_future(
                core.infer(
                    request_for(
                        "sched",
                        float(i),
                        rows=1,
                        priority=1 if i % 2 else 2,
                        timeout_us=2_000_000,
                    )
                )
            )
            for i in range(64)
        ]
        return await asyncio.gather(*tasks, return_exceptions=True)

    try:
        results = asyncio.run(run())
    finally:
        core.close()
    successes = [r for r in results if isinstance(r, CoreResponse)]
    rejects = [r for r in results if isinstance(r, QueueFullError)]
    timeouts = [r for r in results if isinstance(r, QueueTimeoutError)]
    # (a) zero hangs: every request resolved as one of the three outcomes
    assert len(successes) + len(rejects) + len(timeouts) == 64
    assert successes and rejects  # overload actually shed
    # (c) the Prometheus counter equals the client-observed reject count
    text = core.metrics.render()
    booked = metric_value(
        text, "tpu_queue_rejected_total", model="sched", reason="queue_full"
    ) + metric_value(
        text, "tpu_queue_rejected_total", model="sched", reason="timeout"
    )
    assert booked == len(rejects) + len(timeouts)


# ---------------------------------------------------------------------------
# Front-end mapping


def _http_infer_payload(value=1.0):
    return json.dumps(
        {
            "inputs": [
                {
                    "name": "X",
                    "datatype": "FP32",
                    "shape": [2, 2],
                    "data": [value] * 4,
                }
            ]
        }
    ).encode()


def test_http_frontend_maps_queue_full_to_429_with_retry_after():
    from client_tpu.http import aio as httpclient

    model = SchedModel(max_batch_size=2, queue_policy={"max_queue_size": 1})
    core = make_core(model)
    model.gate.clear()
    with InProcessServer(core=core, grpc=False, builtin_models=False) as server:
        url = server.http_url

        async def run():
            async with httpclient.InferenceServerClient(url) as client:
                def build():
                    x = httpclient.InferInput("X", [2, 2], "FP32")
                    x.set_data_from_numpy(
                        np.ones([2, 2], dtype=np.float32)
                    )
                    return [x]

                # stagger so the first is executing (blocked) before the
                # second queues — only then is the queue exactly full
                inflight = [
                    asyncio.ensure_future(client.infer("sched", build()))
                ]
                await asyncio.sleep(0.2)
                inflight.append(
                    asyncio.ensure_future(client.infer("sched", build()))
                )
                await asyncio.sleep(0.2)

                def raw_post():
                    request = urllib.request.Request(
                        f"http://{url}/v2/models/sched/infer",
                        data=_http_infer_payload(),
                        headers={"Content-Type": "application/json"},
                    )
                    try:
                        urllib.request.urlopen(request, timeout=10)
                        return None, None
                    except urllib.error.HTTPError as e:
                        return e.code, e.headers.get("Retry-After")

                status, retry_after = await asyncio.to_thread(raw_post)
                model.gate.set()
                await asyncio.gather(*inflight)
                return status, retry_after

        status, retry_after = asyncio.run(run())
    assert status == 429
    assert retry_after is not None and int(retry_after) >= 1


def test_http_client_surfaces_queue_timeout_as_504():
    from client_tpu.http import aio as httpclient

    model = SchedModel(max_batch_size=2)
    core = make_core(model)
    model.gate.clear()
    with InProcessServer(core=core, grpc=False, builtin_models=False) as server:

        async def run():
            async with httpclient.InferenceServerClient(
                server.http_url
            ) as client:
                def build():
                    x = httpclient.InferInput("X", [2, 2], "FP32")
                    x.set_data_from_numpy(np.ones([2, 2], dtype=np.float32))
                    return [x]

                blocker = asyncio.ensure_future(
                    client.infer("sched", build())
                )
                await asyncio.sleep(0.2)
                # µs queue timeout, matching the gRPC surface semantics
                doomed = asyncio.ensure_future(
                    client.infer("sched", build(), timeout=1000)
                )
                await asyncio.sleep(0.1)
                model.gate.set()
                await blocker
                with pytest.raises(InferenceServerException) as excinfo:
                    await doomed
                return excinfo.value

        error = asyncio.run(run())
    assert error.status() == "504"
    assert "timed out in queue" in error.message()


def test_grpc_frontend_maps_queue_full_to_resource_exhausted():
    from client_tpu.grpc import aio as grpcclient

    model = SchedModel(max_batch_size=2, queue_policy={"max_queue_size": 1})
    core = make_core(model)
    model.gate.clear()
    with InProcessServer(
        core=core, http=False, grpc="aio", builtin_models=False
    ) as server:

        async def run():
            client = grpcclient.InferenceServerClient(server.grpc_url)
            try:
                def build():
                    x = grpcclient.InferInput("X", [2, 2], "FP32")
                    x.set_data_from_numpy(np.ones([2, 2], dtype=np.float32))
                    return [x]

                # stagger so the first is executing (blocked) before the
                # second queues — only then is the queue exactly full
                inflight = [
                    asyncio.ensure_future(client.infer("sched", build()))
                ]
                await asyncio.sleep(0.2)
                inflight.append(
                    asyncio.ensure_future(client.infer("sched", build()))
                )
                await asyncio.sleep(0.2)
                with pytest.raises(InferenceServerException) as excinfo:
                    await client.infer("sched", build())
                model.gate.set()
                await asyncio.gather(*inflight)
                return excinfo.value
            finally:
                await client.close()

        error = asyncio.run(run())
    assert "RESOURCE_EXHAUSTED" in (error.status() or "")
    assert "queue" in error.message()


def test_http_client_sends_priority_and_timeout_parameters():
    """Satellite parity fix: the HTTP surface can express priority and
    the µs queue timeout exactly like the gRPC client."""
    from client_tpu.http import aio as httpclient

    model = SchedModel(max_batch_size=0)
    core = make_core(model)
    with InProcessServer(core=core, grpc=False, builtin_models=False) as server:

        async def run():
            async with httpclient.InferenceServerClient(
                server.http_url
            ) as client:
                x = httpclient.InferInput("X", [2], "FP32")
                x.set_data_from_numpy(np.ones([2], dtype=np.float32))
                await client.infer(
                    "sched", [x], priority=2, timeout=5_000_000
                )
                # legacy seconds-float timeouts fail LOUDLY instead of
                # silently becoming a microsecond queue deadline
                with pytest.raises(InferenceServerException) as excinfo:
                    await client.infer("sched", [x], timeout=2.0)
                assert "MICROSECONDS" in excinfo.value.message()

        asyncio.run(run())
    assert model.seen_parameters[0]["priority"] == 2
    assert model.seen_parameters[0]["timeout"] == 5_000_000


# ---------------------------------------------------------------------------
# Resilience interplay


def test_retry_after_hint_floors_backoff():
    from client_tpu.http._utils import retry_after_seconds
    from client_tpu.resilience import RetryPolicy, run_with_resilience

    sleeps = []
    fake_now = [0.0]
    policy = RetryPolicy(
        max_attempts=3,
        initial_backoff_s=0.001,
        max_backoff_s=0.001,
        jitter=False,
        clock=lambda: fake_now[0],
        sleep=sleeps.append,
    )
    responses = iter(
        [
            (429, b"", {"Retry-After": "0.5"}),
            (200, b"ok", {}),
        ]
    )
    status, _body, _headers = run_with_resilience(
        lambda _timeout: next(responses),
        retry_policy=policy,
        result_status=lambda value: str(value[0]),
        result_backoff_hint=lambda value: retry_after_seconds(value[2]),
    )
    assert status == 200
    # the server's Retry-After floor replaced the 1 ms backoff
    assert sleeps == [0.5]


def test_retry_after_header_parsing():
    from client_tpu.http._utils import retry_after_seconds

    assert retry_after_seconds({"Retry-After": "2"}) == 2.0
    assert retry_after_seconds({"retry-after": "1.5"}) == 1.5
    assert retry_after_seconds({"Retry-After": "soon"}) is None
    assert retry_after_seconds({}) is None
    assert retry_after_seconds(None) is None


@pytest.mark.chaos
def test_retry_with_backoff_drains_a_shed_burst():
    """Overload end-to-end: a burst larger than the queue sheds with 429s;
    clients with a retry policy back off (honoring Retry-After) and every
    request eventually succeeds."""
    from client_tpu.http import aio as httpclient
    from client_tpu.resilience import RetryPolicy

    model = SchedModel(
        max_batch_size=2,
        queue_policy={"max_queue_size": 2},
        delay_s=0.002,
    )
    core = make_core(model)
    with InProcessServer(core=core, grpc=False, builtin_models=False) as server:

        async def run():
            policy = RetryPolicy(
                max_attempts=10,
                initial_backoff_s=0.02,
                max_backoff_s=0.2,
            )
            async with httpclient.InferenceServerClient(
                server.http_url, retry_policy=policy
            ) as client:
                def build(i):
                    x = httpclient.InferInput("X", [1, 2], "FP32")
                    x.set_data_from_numpy(
                        np.full([1, 2], float(i), dtype=np.float32)
                    )
                    return [x]

                results = await asyncio.gather(
                    *[client.infer("sched", build(i)) for i in range(12)],
                    return_exceptions=True,
                )
                return results

        results = asyncio.run(run())
    failures = [r for r in results if isinstance(r, BaseException)]
    assert not failures  # the retry layer drained the burst
    shed = metric_value(
        core.metrics.render(),
        "tpu_queue_rejected_total",
        model="sched",
        reason="queue_full",
    )
    assert shed > 0  # ...and sheds really happened along the way


# ---------------------------------------------------------------------------
# Perf harness overload mode (CLI end-to-end)


def test_cli_overload_mode_reports_scheduling(capsys):
    from client_tpu.perf.cli import main

    model = SchedModel(
        name="shed_demo",
        max_batch_size=2,
        priority_levels=2,
        queue_policy={"max_queue_size": 8},
        delay_s=0.004,
    )
    core = make_core(model)
    with InProcessServer(core=core, grpc=False, builtin_models=False) as server:
        code = main(
            [
                "-m", "shed_demo",
                "-u", server.http_url,
                "-i", "http",
                "--concurrency-range", "16",
                "--measurement-mode", "count_windows",
                "--measurement-request-count", "80",
                "--measurement-interval", "4000",
                "--stability-percentage", "999",
                "--max-trials", "1",
                "--request-priority", "1,2",
                "--queue-timeout-us", "2000000",
                "--json-summary",
            ]
        )
    assert code == 0
    out = capsys.readouterr().out
    assert "Scheduling: shed rate" in out
    assert "priority 1:" in out and "priority 2:" in out
    summary_line = [
        line for line in out.splitlines() if line.startswith("{")
    ][-1]
    doc = json.loads(summary_line)
    assert "shed_rate" in doc and "goodput" in doc
    assert doc["rejected"] > 0
    assert doc["goodput"] == pytest.approx(doc["throughput"])
    split = doc["per_priority_p99_us"]
    # (b) high-priority p99 strictly below low-priority p99 under overload
    assert split["1"] < split["2"]
