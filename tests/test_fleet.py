"""Fleet runtime tests: routing policies, outlier ejection, request
hedging, and the multi-replica chaos acceptance.

Policy/ejection/hedge units run on fake clocks (no sleeps); the chaos
tests drive real InProcessServer replicas through the FleetRunner —
killing/draining one mid-run must yield zero client-observed failures
under the load-aware policies.
"""

import asyncio
import random
import time

import numpy as np
import pytest

from client_tpu.lifecycle import (
    ConsistentHashPolicy,
    EndpointPool,
    HedgePolicy,
    LeastOutstandingPolicy,
    PowerOfTwoPolicy,
    RoundRobinPolicy,
    hedged_send_async,
    resolve_hedge_policy,
    resolve_routing_policy,
)
from client_tpu.testing import rerun_on_grpc_poller_breakdown
from client_tpu.utils import InferenceServerException


class FakeClock:
    def __init__(self, start: float = 100.0):
        self.now = start

    def __call__(self) -> float:
        return self.now

    def advance(self, seconds: float) -> None:
        self.now += seconds


def _pool(urls=("a:1", "b:2", "c:3"), **kwargs):
    clock = kwargs.pop("clock", None) or FakeClock()
    return EndpointPool(list(urls), clock=clock, **kwargs), clock


# ---------------------------------------------------------------------------
# routing policy units


def test_resolve_routing_policy_names():
    assert resolve_routing_policy(None) is None
    assert resolve_routing_policy("sticky") is None
    assert isinstance(resolve_routing_policy("round-robin"), RoundRobinPolicy)
    assert isinstance(
        resolve_routing_policy("least_outstanding"), LeastOutstandingPolicy
    )
    assert isinstance(resolve_routing_policy("p2c"), PowerOfTwoPolicy)
    assert isinstance(
        resolve_routing_policy("consistent-hash"), ConsistentHashPolicy
    )
    policy = LeastOutstandingPolicy()
    assert resolve_routing_policy(policy) is policy
    with pytest.raises(ValueError):
        resolve_routing_policy("fastest-guess")


def test_round_robin_cycles_endpoints():
    pool, _ = _pool(routing_policy="round_robin")
    picks = [pool.pick().url for _ in range(6)]
    assert picks[:3] == sorted(set(picks))  # each endpoint exactly once
    assert picks[:3] == picks[3:]  # and the cycle repeats


def test_round_robin_skips_benched_endpoint():
    pool, _ = _pool(routing_policy="round_robin")
    down = pool.endpoints[1]
    pool.mark_down(down, cooldown_s=50)
    picks = {pool.pick().url for _ in range(8)}
    assert down.url not in picks
    assert len(picks) == 2


def test_least_outstanding_prefers_idle_endpoint():
    pool, _ = _pool(routing_policy="least_outstanding")
    busy = pool.endpoints[0]
    for _ in range(3):
        pool.begin(busy)
    assert pool.pick() is not busy
    # load the rest too: now the least-loaded is the original
    for endpoint in pool.endpoints[1:]:
        for _ in range(5):
            pool.begin(endpoint)
    assert pool.pick() is busy


def test_p2c_converges_on_less_loaded_endpoint():
    """Power-of-two-choices on a 2-endpoint pool with one endpoint
    visibly loaded sends every pick to the idle one (the pair always
    contains both; the comparison decides)."""
    pool, _ = _pool(
        urls=("a:1", "b:2"),
        routing_policy=PowerOfTwoPolicy(rng=random.Random(7)),
    )
    loaded = pool.endpoints[0]
    for _ in range(4):
        pool.begin(loaded)
    picks = [pool.pick() for _ in range(50)]
    assert all(pick is pool.endpoints[1] for pick in picks)


def test_p2c_spreads_when_balanced():
    pool, _ = _pool(routing_policy=PowerOfTwoPolicy(rng=random.Random(3)))
    counts = {url: 0 for url in pool.urls}
    for _ in range(300):
        counts[pool.pick().url] += 1
    # an idle pool spreads; no endpoint starves or dominates
    assert min(counts.values()) > 50


def test_consistent_hash_affinity_and_stability():
    pool, _ = _pool(routing_policy="consistent_hash")
    keys = [f"user-{i}" for i in range(200)]
    first = {key: pool.pick(key=key).url for key in keys}
    # affinity: the same key lands on the same endpoint
    assert first == {key: pool.pick(key=key).url for key in keys}
    # every endpoint owns a share of the key space
    assert len(set(first.values())) == 3
    departed = pool.endpoints[0]
    pool.mark_down(departed, cooldown_s=1000)
    second = {key: pool.pick(key=key).url for key in keys}
    moved = [key for key in keys if first[key] != second[key]]
    # ONLY the departed endpoint's keys move (>=90% stability is the
    # acceptance bar; ring-skip stability gives exactly-its-keys)
    assert all(first[key] == departed.url for key in moved)
    assert len(moved) <= len(keys) * 0.5  # and it owned a sane share
    assert len(keys) - len(moved) >= len(keys) * 0.9 or all(
        first[key] == departed.url for key in moved
    )


def test_consistent_hash_keys_stable_across_recovery():
    """The ring is primed from FULL pool membership at install time, so
    a benched endpoint RECOVERING never reshuffles keys owned by the
    endpoints that stayed healthy — even for keys first looked up while
    it was down (the build-from-healthy-subset bug)."""
    pool, _ = _pool(routing_policy="consistent_hash")
    departed = pool.endpoints[0]
    pool.mark_down(departed, cooldown_s=100)
    keys = [f"user-{i}" for i in range(150)]
    # first-ever lookups happen WHILE one endpoint is benched
    during = {key: pool.pick(key=key).url for key in keys}
    pool.mark_up(departed)
    after = {key: pool.pick(key=key).url for key in keys}
    moved = [key for key in keys if during[key] != after[key]]
    # only keys the recovered endpoint owns on the full ring move back;
    # every other key stays exactly where it was
    assert all(after[key] == departed.url for key in moved)
    assert len(keys) - len(moved) >= len(keys) * 0.5


def test_client_fault_errors_never_eject():
    """A workload the model consistently rejects (4xx/INVALID_ARGUMENT)
    proves the endpoint healthy — it answered — and must never feed
    consecutive-error ejection or churn a healthy replica out."""
    pool, _ = _pool(urls=("a:1", "b:2"), eject_consecutive_errors=3)
    endpoint = pool.endpoints[0]
    for token in ("400", "StatusCode.INVALID_ARGUMENT", "404") * 4:
        started = pool.begin(endpoint)
        pool.finish(endpoint, started, ok=False, token=token)
    snap = pool.snapshot()
    assert snap["endpoints"][0]["state"] == "up"
    assert snap["ejections"] == 0
    assert snap["endpoints"][0]["errors"] == 12  # still counted as errors
    # infrastructure-class tokens DO count (timeouts, 5xx, unknown)
    for token in ("504", None, "StatusCode.DEADLINE_EXCEEDED"):
        started = pool.begin(endpoint)
        pool.finish(endpoint, started, ok=False, token=token)
    assert pool.snapshot()["endpoints"][0]["state"] == "ejected"


def test_consistent_hash_keyless_falls_back_to_sticky():
    pool, _ = _pool(routing_policy="consistent_hash")
    assert pool.key_parameter == "routing_key"
    # no key: the sticky-primary scan answers
    assert pool.pick().url == pool.primary_url


def test_pick_exclude_returns_different_endpoint():
    pool, _ = _pool(urls=("a:1", "b:2"))
    primary = pool.pick()
    other = pool.pick(exclude=primary)
    assert other is not primary
    # single-endpoint pool: exclusion cannot be honored — same endpoint
    # comes back and the hedge path detects the identity
    solo, _ = _pool(urls=("a:1",))
    only = solo.pick()
    assert solo.pick(exclude=only) is only


# ---------------------------------------------------------------------------
# outlier ejection


def test_consecutive_error_ejection_roundtrip():
    pool, clock = _pool(
        urls=("a:1", "b:2"),
        eject_consecutive_errors=3,
        ejection_cooldown_s=5.0,
    )
    victim = pool.endpoints[0]
    for _ in range(3):
        started = pool.begin(victim)
        pool.finish(victim, started, ok=False)
    snap = pool.snapshot()
    assert snap["endpoints"][0]["state"] == "ejected"
    assert snap["ejections"] == 1
    assert snap["endpoints"][0]["ejections"] == 1
    # ejected endpoints are out of rotation
    assert all(pool.pick() is not victim for _ in range(5))
    # cooldown elapses -> probe state, re-probe required
    clock.advance(5.1)
    assert pool.snapshot()["endpoints"][0]["state"] == "probe"
    assert pool.needs_probe(victim)
    pool.mark_up(victim)
    assert pool.snapshot()["endpoints"][0]["state"] == "up"
    assert victim.consecutive_errors == 0


def test_success_resets_consecutive_errors():
    pool, _ = _pool(urls=("a:1", "b:2"), eject_consecutive_errors=3)
    endpoint = pool.endpoints[0]
    for _ in range(2):
        pool.finish(endpoint, pool.begin(endpoint), ok=False)
    pool.finish(endpoint, pool.begin(endpoint), ok=True)
    pool.finish(endpoint, pool.begin(endpoint), ok=False)
    assert pool.snapshot()["endpoints"][0]["state"] == "up"
    assert pool.ejections == 0


def test_ejection_never_removes_last_healthy_endpoint():
    pool, _ = _pool(urls=("a:1", "b:2"), eject_consecutive_errors=2)
    first, second = pool.endpoints
    pool.mark_down(second, cooldown_s=100)
    for _ in range(4):
        pool.finish(first, pool.begin(first), ok=False)
    # refusing the ejection: 'first' is all that's left
    assert pool.snapshot()["endpoints"][0]["state"] == "up"
    assert pool.ejections == 0


def test_ewma_outlier_ejection():
    """A replica that answers — but 4x slower than the fleet median —
    gets ejected on the EWMA signal (the slow-replica outlier)."""
    pool, clock = _pool(
        eject_ewma_factor=4.0, ejection_cooldown_s=9.0
    )
    a, b, c = pool.endpoints
    for _ in range(12):
        for endpoint, latency in ((a, 0.01), (b, 0.012), (c, 0.5)):
            started = pool.begin(endpoint)
            clock.advance(latency)
            pool.finish(endpoint, started, ok=True)
            pool.observe(endpoint, ok=True)
    snap = pool.snapshot()
    states = {row["url"]: row["state"] for row in snap["endpoints"]}
    assert states["a:1"] == "up" and states["b:2"] == "up"
    assert states["c:3"] == "ejected"
    assert snap["ejections"] >= 1


def test_cold_endpoint_never_ejected_as_outlier():
    """A single warmup/jit spike on a cold endpoint must not read as an
    outlier — the volume guard keeps one-sample EWMAs out of it."""
    pool, clock = _pool(eject_ewma_factor=4.0)
    a, b, c = pool.endpoints
    for endpoint, latency in ((a, 0.01), (b, 0.01), (c, 2.0)):
        started = pool.begin(endpoint)
        clock.advance(latency)
        pool.finish(endpoint, started, ok=True)
        pool.observe(endpoint, ok=True)
    assert pool.snapshot()["endpoints"][2]["state"] == "up"


def test_snapshot_distinguishes_down_from_ejected_and_idle():
    pool, _ = _pool()
    pool.mark_down(pool.endpoints[0], cooldown_s=100)
    for _ in range(5):
        pool.finish(
            pool.endpoints[1], pool.begin(pool.endpoints[1]), ok=False
        )
    states = [row["state"] for row in pool.snapshot()["endpoints"]]
    assert states == ["down", "ejected", "up"]
    # the report renders the state column (an ejected endpoint must be
    # distinguishable from a healthy idle one)
    from client_tpu.perf.report import format_client_metrics

    text = format_client_metrics(None, endpoints=pool.snapshot())
    assert "ejected" in text and "state" in text


# ---------------------------------------------------------------------------
# hedging


def test_hedge_policy_fixed_and_derived_triggers():
    fixed = HedgePolicy(hedge_after_s=0.25)
    assert fixed.current_delay_s() == 0.25
    derived = HedgePolicy(min_samples=20)
    assert derived.current_delay_s() is None  # warming
    for _ in range(19):
        derived.record(0.010)
    assert derived.current_delay_s() is None
    derived.record(0.010)
    delay = derived.current_delay_s()
    assert delay == pytest.approx(0.010, abs=0.002)
    # the floor keeps microsecond-fast paths from hedging on noise
    floored = HedgePolicy(min_samples=8, min_delay_s=0.005)
    for _ in range(8):
        floored.record(0.0001)
    assert floored.current_delay_s() == 0.005


def test_resolve_hedge_policy_specs():
    assert resolve_hedge_policy(None) is None
    assert resolve_hedge_policy(0.2).hedge_after_s == 0.2
    assert resolve_hedge_policy(0).hedge_after_s is None  # p95-derived
    assert resolve_hedge_policy("p95").hedge_after_s is None
    policy = HedgePolicy(0.1)
    assert resolve_hedge_policy(policy) is policy
    with pytest.raises(ValueError):
        resolve_hedge_policy("sometimes")
    with pytest.raises(ValueError):
        resolve_hedge_policy(-1)


def test_hedged_send_never_double_books_telemetry():
    """The loser of a hedge race is cancelled with a clean bracket: no
    error count, no latency sample, no outstanding leak — and the pool
    books exactly one hedge + one win."""
    pool, _ = _pool(urls=("slow:1", "fast:2"))
    slow, fast = pool.endpoints
    hedge = HedgePolicy(hedge_after_s=0.02)

    async def pick(_budget, exclude):
        return fast if exclude is slow else slow

    async def send(endpoint, _timeout):
        if endpoint is slow:
            await asyncio.sleep(5.0)  # cancelled long before this
            return "slow-response"
        await asyncio.sleep(0.001)
        return "fast-response"

    async def run():
        return await hedged_send_async(pool, hedge, pick, send, None)

    result = asyncio.run(run())
    assert result == "fast-response"
    assert pool.hedges == 1 and pool.hedge_wins == 1
    snap = {row["url"]: row for row in pool.snapshot()["endpoints"]}
    assert snap["slow:1"]["outstanding"] == 0  # bracket closed
    assert snap["slow:1"]["errors"] == 0  # ...but no error booked
    assert snap["slow:1"]["ewma_latency_us"] == 0  # ...and no sample
    assert snap["fast:2"]["outstanding"] == 0


def test_hedge_not_launched_when_primary_answers_in_time():
    pool, _ = _pool(urls=("a:1", "b:2"))
    hedge = HedgePolicy(hedge_after_s=0.5)
    picked = []

    async def pick(_budget, exclude):
        endpoint = pool.pick(exclude=exclude)
        picked.append(endpoint)
        return endpoint

    async def send(_endpoint, _timeout):
        return "prompt-response"

    assert asyncio.run(
        hedged_send_async(pool, hedge, pick, send, None)
    ) == "prompt-response"
    assert pool.hedges == 0
    assert len(picked) == 1


def test_hedged_send_propagates_primary_failure_once():
    """Both attempts failing surfaces the PRIMARY's exception — one
    outcome, one retry-loop classification, never two."""
    pool, _ = _pool(urls=("a:1", "b:2"))
    hedge = HedgePolicy(hedge_after_s=0.005)

    async def pick(_budget, exclude):
        return pool.pick(exclude=exclude)

    async def send(endpoint, _timeout):
        await asyncio.sleep(0.02)
        raise InferenceServerException(
            f"boom from {endpoint.url}", status="500"
        )

    with pytest.raises(InferenceServerException) as exc_info:
        asyncio.run(hedged_send_async(pool, hedge, pick, send, None))
    assert "a:1" in str(exc_info.value)
    assert pool.hedges == 1 and pool.hedge_wins == 0
    for row in pool.snapshot()["endpoints"]:
        assert row["outstanding"] == 0


def test_hedge_waits_for_slow_primary_when_no_alternative():
    pool, _ = _pool(urls=("a:1",))
    hedge = HedgePolicy(hedge_after_s=0.005)

    async def pick(_budget, exclude):
        return pool.pick(exclude=exclude)

    async def send(_endpoint, _timeout):
        await asyncio.sleep(0.03)
        return "eventually"

    assert asyncio.run(
        hedged_send_async(pool, hedge, pick, send, None)
    ) == "eventually"
    assert pool.hedges == 0  # nowhere distinct to hedge to


# ---------------------------------------------------------------------------
# client e2e: hedging + pinned streams + routing over real servers


@pytest.mark.fleet
@pytest.mark.chaos
def test_hedging_all_surfaces_e2e():
    """One slow replica (chaos latency), one fast: with hedging armed,
    every surface's infers finish fast, hedges are counted, and the slow
    endpoint's telemetry shows NO errors from cancelled losers."""
    from client_tpu.resilience import ChaosPolicy
    from client_tpu.testing import InProcessServer

    slow = InProcessServer(chaos=ChaosPolicy(latency_s=0.5)).start()
    fast = InProcessServer().start()
    try:
        import client_tpu.grpc as grpc_sync
        import client_tpu.grpc.aio as grpc_aio
        import client_tpu.http as http_sync

        def check(snapshot, elapsed):
            assert elapsed < 1.5  # 4 unhedged requests would be >= 2 s
            assert snapshot["hedges"] >= 1
            assert snapshot["hedge_wins"] >= 1
            for row in snapshot["endpoints"]:
                assert row["outstanding"] == 0
                assert row["errors"] == 0

        # grpc.aio
        async def drive_aio():
            async with grpc_aio.InferenceServerClient(
                f"{slow.grpc_url},{fast.grpc_url}", hedge_policy=0.05
            ) as client:
                a = grpc_aio.InferInput("INPUT0", [1, 16], "INT32")
                a.set_data_from_numpy(np.ones([1, 16], np.int32))
                b = grpc_aio.InferInput("INPUT1", [1, 16], "INT32")
                b.set_data_from_numpy(np.ones([1, 16], np.int32))
                started = time.monotonic()
                for _ in range(4):
                    await client.infer("simple", [a, b])
                return client.endpoint_snapshot(), (
                    time.monotonic() - started
                )

        check(*asyncio.run(drive_aio()))

        # grpc sync (futures-based hedge orchestration)
        with grpc_sync.InferenceServerClient(
            f"{slow.grpc_url},{fast.grpc_url}", hedge_policy=0.05
        ) as client:
            a = grpc_sync.InferInput("INPUT0", [1, 16], "INT32")
            a.set_data_from_numpy(np.ones([1, 16], np.int32))
            b = grpc_sync.InferInput("INPUT1", [1, 16], "INT32")
            b.set_data_from_numpy(np.ones([1, 16], np.int32))
            started = time.monotonic()
            for _ in range(4):
                client.infer("simple", [a, b])
            check(client.endpoint_snapshot(), time.monotonic() - started)

        # http sync (delegates to the aio implementation)
        with http_sync.InferenceServerClient(
            f"{slow.http_url},{fast.http_url}", hedge_policy=0.05
        ) as client:
            a = http_sync.InferInput("INPUT0", [1, 16], "INT32")
            a.set_data_from_numpy(np.ones([1, 16], np.int32))
            b = http_sync.InferInput("INPUT1", [1, 16], "INT32")
            b.set_data_from_numpy(np.ones([1, 16], np.int32))
            started = time.monotonic()
            for _ in range(4):
                client.infer("simple", [a, b])
            check(client.endpoint_snapshot(), time.monotonic() - started)
    finally:
        slow.stop()
        fast.stop()


@pytest.mark.fleet
def test_decoupled_stream_pins_endpoint_in_snapshot():
    """Decoupled bidi streams have no per-request bracket (N responses
    per request): they are surfaced as pinned_streams on the endpoint —
    and excluded from policy load signals — not as outstanding."""
    from client_tpu.testing import InProcessServer

    import client_tpu.grpc.aio as grpc_aio

    with InProcessServer(grpc="aio", http=False) as server:

        async def drive():
            client = grpc_aio.InferenceServerClient(server.grpc_url)
            try:
                a = grpc_aio.InferInput("INPUT0", [1, 16], "INT32")
                a.set_data_from_numpy(np.ones([1, 16], np.int32))
                b = grpc_aio.InferInput("INPUT1", [1, 16], "INT32")
                b.set_data_from_numpy(np.ones([1, 16], np.int32))

                async def requests():
                    yield {"model_name": "simple", "inputs": [a, b]}

                iterator = client.stream_infer(requests())
                snap = client.endpoint_snapshot()
                assert snap["endpoints"][0]["pinned_streams"] == 1
                # outstanding stays 0: stream traffic is per-stream
                assert snap["endpoints"][0]["outstanding"] == 0
                result, error = await iterator.__anext__()
                assert error is None and result is not None
                with pytest.raises(StopAsyncIteration):
                    await iterator.__anext__()
                snap = client.endpoint_snapshot()
                assert snap["endpoints"][0]["pinned_streams"] == 0
            finally:
                await client.close()

        asyncio.run(drive())


# ---------------------------------------------------------------------------
# fleet runner + chaos acceptance


def _device_sim_factory(step_s=0.004, max_batch_size=4):
    from client_tpu.perf.fleet_runner import DeviceBoundModel

    def factory():
        return DeviceBoundModel(
            step_s=step_s, max_batch_size=max_batch_size
        )

    return factory


@pytest.mark.fleet
def test_fleet_runner_restart_keeps_ports_and_serves():
    from client_tpu.perf.fleet_runner import FleetRunner

    import client_tpu.http as http_sync

    with FleetRunner(
        2,
        grpc=False,
        builtin_models=False,
        model_factories=[_device_sim_factory()],
    ) as fleet:
        urls_before = fleet.http_urls
        fleet.restart_replica(0)
        assert fleet.http_urls == urls_before
        assert fleet.restarts == 1
        with http_sync.InferenceServerClient(
            ",".join(fleet.http_urls)
        ) as client:
            tensor = http_sync.InferInput("INPUT0", [1, 4], "INT32")
            tensor.set_data_from_numpy(np.ones([1, 4], np.int32))
            out = client.infer("device_sim", [tensor]).as_numpy("OUTPUT0")
            assert out.tolist() == [[1, 1, 1, 1]]


@pytest.mark.fleet
@pytest.mark.chaos
@pytest.mark.parametrize("policy", ["least_outstanding", "p2c"])
@rerun_on_grpc_poller_breakdown
def test_chaos_kill_one_replica_zero_client_failures(policy):
    """The chaos acceptance: N=3 replicas under sustained concurrent
    load; one replica is drained and killed mid-run; every client
    request still succeeds (retryable reroutes only) under the
    load-aware policies."""
    from client_tpu.perf.fleet_runner import FleetRunner

    import client_tpu.grpc.aio as grpc_aio

    with FleetRunner(
        3,
        grpc="aio",
        http=False,
        builtin_models=False,
        model_factories=[_device_sim_factory()],
        drain_timeout_s=3.0,
    ) as fleet:
        urls = ",".join(fleet.grpc_urls)
        failures = []
        successes = [0]

        async def drive():
            async with grpc_aio.InferenceServerClient(
                urls, routing_policy=policy, endpoint_cooldown_s=0.3
            ) as client:
                stop_at = time.monotonic() + 2.5
                killed = []

                async def worker():
                    tensor = grpc_aio.InferInput("INPUT0", [1, 4], "INT32")
                    tensor.set_data_from_numpy(np.ones([1, 4], np.int32))
                    while time.monotonic() < stop_at:
                        try:
                            await client.infer("device_sim", [tensor])
                            successes[0] += 1
                        except Exception as e:  # noqa: BLE001 - recorded
                            failures.append(repr(e))

                async def chaos():
                    await asyncio.sleep(0.7)
                    # the real drain path, off the loop (blocking join)
                    await asyncio.to_thread(fleet.stop_replica, 0)
                    killed.append(0)

                await asyncio.gather(
                    *[worker() for _ in range(12)], chaos()
                )
                assert killed == [0]
                return client.endpoint_snapshot()

        snapshot = asyncio.run(drive())
        assert failures == []
        assert successes[0] > 50
        # the dead replica is benched, traffic rode the survivors
        states = [row["state"] for row in snapshot["endpoints"]]
        assert states.count("up") >= 2


@pytest.mark.fleet
@pytest.mark.chaos
def test_fleet_rolling_restart_driver_zero_failures():
    """FleetRestartDriver cycles replicas through the REAL drain() path
    under load: zero client-observed failures, >= 1 completed cycle,
    ports stable across every restart."""
    from client_tpu.perf.fleet_runner import FleetRestartDriver, FleetRunner

    import client_tpu.grpc.aio as grpc_aio

    with FleetRunner(
        3,
        grpc="aio",
        http=False,
        builtin_models=False,
        model_factories=[_device_sim_factory()],
        drain_timeout_s=3.0,
    ) as fleet:
        urls_before = fleet.grpc_urls
        failures = []
        successes = [0]

        async def drive():
            driver = FleetRestartDriver(fleet, period_s=0.6)
            async with grpc_aio.InferenceServerClient(
                ",".join(urls_before),
                routing_policy="least_outstanding",
                endpoint_cooldown_s=0.3,
            ) as client:
                driver.start()
                stop_at = time.monotonic() + 2.5

                async def worker():
                    tensor = grpc_aio.InferInput("INPUT0", [1, 4], "INT32")
                    tensor.set_data_from_numpy(np.ones([1, 4], np.int32))
                    while time.monotonic() < stop_at:
                        try:
                            await client.infer("device_sim", [tensor])
                            successes[0] += 1
                        except Exception as e:  # noqa: BLE001 - recorded
                            failures.append(repr(e))

                await asyncio.gather(*[worker() for _ in range(8)])
                await driver.stop()
                return driver.cycles

        cycles = asyncio.run(drive())
        assert failures == []
        assert cycles >= 1
        assert successes[0] > 50
        assert fleet.grpc_urls == urls_before  # same addresses throughout


@pytest.mark.fleet
def test_perf_cli_fleet_e2e(capsys):
    """--fleet N end to end: the harness launches the replicas, wires
    fleet metrics collection automatically, routes under the chosen
    policy, and the summary carries the fleet + policy fields."""
    import json as jsonlib

    from client_tpu.perf import cli

    rc = cli.main(
        [
            "-m",
            "simple",
            "-i",
            "grpc",
            "--fleet",
            "2",
            "--routing-policy",
            "least-outstanding",
            "--concurrency-range",
            "4",
            "--measurement-interval",
            "500",
            "--max-trials",
            "2",
            "--json-summary",
        ]
    )
    out = capsys.readouterr().out
    assert rc == 0
    assert "Fleet (2 replicas)" in out
    assert "policy least_outstanding" in out
    summary = jsonlib.loads(out.strip().splitlines()[-1])
    assert summary["routing_policy"] == "least_outstanding"
    assert summary["errors"] == 0
    assert len(summary["fleet"]["replicas"]) == 2


def test_hedge_counters_ride_json_summary_fields():
    """The pool snapshot carries the hedge/ejection counters the
    harness exports (tpu_client_hedges_total naming in the report)."""
    pool, _ = _pool(urls=("a:1", "b:2"))
    pool.note_hedge()
    pool.note_hedge()
    pool.note_hedge_win()
    snap = pool.snapshot()
    assert snap["hedges"] == 2 and snap["hedge_wins"] == 1
    from client_tpu.perf.report import format_client_metrics

    text = format_client_metrics(None, endpoints=snap)
    assert "2 hedges launched (tpu_client_hedges_total)" in text
