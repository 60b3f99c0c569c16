"""Live-telemetry tests: sliding-window quantile sketches (rotation,
merge associativity, concurrent record-while-scrape), SLO objectives and
error-budget burn rates (agreement with histogram-derived values on both
front-ends), the ``/v2/debug/slo`` document tracking a fake-clock load
shift while the cumulative histogram lags, per-endpoint pool telemetry,
OpenMetrics exemplars linking ``/metrics`` to the flight recorder,
3-replica fleet aggregation with skew detection, the metric-lint tool,
and what the window sketch costs as counts on a counting clock: clock
reads and observations a request, on each of ``ServerCore``'s four
execution paths, and none of either when disabled.
"""

import asyncio
import http.client
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import client_tpu.grpc as grpcclient
import client_tpu.http as httpclient
from client_tpu.lifecycle import EndpointPool
from client_tpu.observability.fleet import (
    fleet_skew,
    merge_families,
    replica_stats,
    summarize_fleet,
)
from client_tpu.observability.metrics import (
    Histogram,
    MetricsRegistry,
    counter_total,
    gauge_values,
    histogram_totals,
    parse_exposition,
)
from client_tpu.observability.slo import LiveTelemetry, SloObjective
from client_tpu.observability.window import (
    WindowedCounter,
    WindowedHistogram,
    WindowSnapshot,
)
from client_tpu.perf.metrics_collector import FleetCollector
from client_tpu.server.core import ServerCore
from client_tpu.server.metrics import DURATION_BUCKETS_S
from client_tpu.server.model_repository import Model, ModelRepository
from client_tpu.testing import InProcessServer

pytestmark = pytest.mark.telemetry


class FakeClock:
    """Nanosecond fake clock shared by every window in a test."""

    def __init__(self, start_s: float = 0.0):
        self.now_ns = int(start_s * 1e9)

    def ns(self) -> int:
        return self.now_ns

    def advance(self, seconds: float) -> None:
        self.now_ns += int(seconds * 1e9)


def _simple_inputs(mod):
    in0 = np.arange(16, dtype=np.int32).reshape(1, 16)
    in1 = np.ones([1, 16], dtype=np.int32)
    a = mod.InferInput("INPUT0", [1, 16], "INT32")
    a.set_data_from_numpy(in0)
    b = mod.InferInput("INPUT1", [1, 16], "INT32")
    b.set_data_from_numpy(in1)
    return [a, b]


# ---------------------------------------------------------------------------
# window.py: the sliding-window sketch


def test_window_rejects_bad_config():
    with pytest.raises(ValueError):
        WindowedHistogram((0.1,), horizon_s=0)
    with pytest.raises(ValueError):
        WindowedHistogram((0.1,), subwindows=0)
    with pytest.raises(ValueError):
        WindowedHistogram(())  # empty grid
    with pytest.raises(ValueError):
        WindowedHistogram((0.2, 0.1))  # not increasing
    with pytest.raises(ValueError):
        WindowedHistogram((0.1, 0.1))  # duplicate bound


def test_window_quantiles_and_totals():
    clock = FakeClock()
    window = WindowedHistogram(
        (0.001, 0.01, 0.1, 1.0), horizon_s=30, subwindows=6,
        clock_ns=clock.ns,
    )
    for _ in range(90):
        window.observe(0.0005)  # first bucket
    for _ in range(10):
        window.observe(0.5)  # (0.1, 1.0] bucket
    snap = window.snapshot()
    assert snap.count == 100
    assert snap.sum == pytest.approx(90 * 0.0005 + 10 * 0.5)
    assert snap.quantile(0.5) <= 0.001
    # p95 rank 95 falls inside the (0.1, 1.0] bucket
    assert 0.1 < snap.quantile(0.95) <= 1.0
    # observations beyond the last bound report the grid edge
    window.observe(50.0, count=1000)
    assert window.snapshot().quantile(0.99) == 1.0


def test_window_rotation_expires_old_subwindows():
    clock = FakeClock()
    window = WindowedHistogram(
        (0.001, 0.1, 1.0), horizon_s=30, subwindows=6, clock_ns=clock.ns
    )
    window.observe(0.5, count=100)  # slow load in sub-window 0
    clock.advance(15)
    window.observe(0.0005, count=100)  # fast load mid-horizon
    snap = window.snapshot()
    assert snap.count == 200
    assert snap.quantile(0.99) > 0.1  # slow half still in the window
    clock.advance(16)  # slow sub-window (t=0) rotates out at t=31
    snap = window.snapshot()
    assert snap.count == 100
    assert snap.quantile(0.99) <= 0.001  # only the fast load remains
    clock.advance(31)  # everything expires
    assert window.snapshot().count == 0
    # a gap far longer than the horizon clears the whole ring at once
    window.observe(0.5, count=7)
    clock.advance(3600)
    assert window.snapshot().count == 0


def test_window_snapshot_merge_is_associative():
    def _snap(counts, total, sum_):
        return WindowSnapshot(
            bounds=(0.001, 0.1), counts=list(counts), sum=sum_, count=total,
            horizon_s=30.0,
        )

    a = _snap([5, 2, 1], 8, 0.3)
    b = _snap([0, 7, 2], 9, 1.1)
    c = _snap([3, 0, 4], 7, 2.2)
    left = a.merge(b).merge(c)
    right = a.merge(b.merge(c))
    assert left.counts == right.counts == [8, 9, 7]
    assert left.count == right.count == 24
    assert left.sum == pytest.approx(right.sum) == pytest.approx(3.6)
    with pytest.raises(ValueError):
        a.merge(WindowSnapshot(bounds=(0.5,), counts=[0, 0]))


def test_windowed_counter_rolls_off():
    clock = FakeClock()
    counter = WindowedCounter(horizon_s=300, subwindows=10, clock_ns=clock.ns)
    counter.add(good=90, bad=10)
    assert counter.totals() == (90, 10)
    clock.advance(150)
    counter.add(good=40)
    assert counter.totals() == (130, 10)
    clock.advance(180)  # the first sub-window (t=0) is now past 300 s
    assert counter.totals() == (40, 0)


def test_window_concurrent_record_while_snapshot():
    clock = FakeClock()
    window = WindowedHistogram(
        DURATION_BUCKETS_S, horizon_s=30, subwindows=6, clock_ns=clock.ns
    )
    per_thread, threads = 2000, 4
    inconsistent = []
    stop = threading.Event()

    def record():
        for i in range(per_thread):
            window.observe(0.0001 * (1 + i % 7))

    def scrape():
        while not stop.is_set():
            snap = window.snapshot()
            if sum(snap.counts) != snap.count:
                inconsistent.append(snap)

    workers = [threading.Thread(target=record) for _ in range(threads)]
    scraper = threading.Thread(target=scrape)
    scraper.start()
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    stop.set()
    scraper.join()
    assert not inconsistent  # every snapshot internally consistent
    assert window.snapshot().count == per_thread * threads  # nothing lost


# ---------------------------------------------------------------------------
# slo.py: objectives + burn-rate accounting


def test_slo_objective_declaration_validation():
    class NoSlo:
        pass

    assert SloObjective.from_model(NoSlo()) is None

    def model_with(slo):
        return type("M", (), {"slo": slo})()

    obj = SloObjective.from_model(
        model_with({"latency_target_ms": 50, "availability": 0.99})
    )
    assert obj.latency_target_s == pytest.approx(0.05)
    assert obj.availability == 0.99
    with pytest.raises(ValueError):
        SloObjective.from_model(model_with("fast please"))
    with pytest.raises(ValueError):
        SloObjective.from_model(model_with({"latency_budget": 1}))
    with pytest.raises(ValueError):
        SloObjective.from_model(model_with({"availability": 1.5}))
    with pytest.raises(ValueError):
        SloObjective.from_model(model_with({"window_s": 0}))


def test_live_telemetry_burn_rate_math():
    clock = FakeClock()
    objective = SloObjective(
        latency_target_s=0.05, availability=0.9, window_s=300
    )
    telemetry = LiveTelemetry(
        buckets=DURATION_BUCKETS_S,
        clock_ns=clock.ns,
        objective_resolver=lambda name: objective,
    )
    for _ in range(6):
        telemetry.record("m", 0.001)  # under target: good
    for _ in range(2):
        telemetry.record("m", 0.2)  # over target: bad
    telemetry.record("m", 0.0, ok=False, count=2)  # failures: bad
    status = telemetry.slo_status("m")
    assert status["window_good"] == 6
    assert status["window_bad"] == 4
    # bad fraction 0.4 over an allowed fraction of 0.1
    assert status["burn_rate"] == pytest.approx(4.0)
    assert status["error_budget_remaining"] == 0.0
    # failures count toward the budget but never the latency windows
    assert telemetry.rolling("m")["30s"]["count"] == 8
    # disabled telemetry records nothing (the A/B switch)
    telemetry.enabled = False
    telemetry.record("m", 0.2, count=100)
    assert telemetry.slo_status("m")["window_bad"] == 4


def test_live_telemetry_reset_re_resolves_objective():
    """Hot model reload: reset() drops the cached objective so the next
    record tracks the repository's CURRENT slo declaration."""
    clock = FakeClock()
    objectives = {
        "m": SloObjective(latency_target_s=0.05, availability=0.9)
    }
    telemetry = LiveTelemetry(
        buckets=DURATION_BUCKETS_S,
        clock_ns=clock.ns,
        objective_resolver=lambda name: objectives.get(name),
    )
    telemetry.record("m", 0.02)  # under the 50 ms target: good
    assert telemetry.slo_status("m")["window_bad"] == 0
    # reload tightens the target to 10 ms
    objectives["m"] = SloObjective(latency_target_s=0.01, availability=0.9)
    telemetry.reset("m")
    telemetry.record("m", 0.02)  # over the NEW target: bad
    status = telemetry.slo_status("m")
    assert status["objective"]["latency_target_s"] == 0.01
    assert status["window_bad"] == 1
    assert telemetry.rolling("m")["30s"]["count"] == 1  # windows restarted


def test_reset_racing_first_record_installs_current_objective():
    """TOCTOU guard: an objective resolved BEFORE a concurrent reset()
    must not be installed after it. The resolver here triggers the race
    deterministically — mid-resolution, a reload swaps the declaration
    and calls reset() (legal: resolution runs outside the lock); the
    first record must re-resolve and track the post-reload objective."""
    clock = FakeClock()
    objectives = {
        "m": SloObjective(latency_target_s=0.05, availability=0.9)
    }
    resolutions = []

    def resolver(name):
        stale = objectives[name]
        if not resolutions:
            # simulate the reload landing between resolve and install
            objectives[name] = SloObjective(
                latency_target_s=0.01, availability=0.9
            )
            telemetry.reset(name)
        resolutions.append(name)
        return stale

    telemetry = LiveTelemetry(
        buckets=DURATION_BUCKETS_S,
        clock_ns=clock.ns,
        objective_resolver=resolver,
    )
    telemetry.record("m", 0.02)  # good vs 50 ms, bad vs the new 10 ms
    assert len(resolutions) == 2  # first resolution was discarded
    status = telemetry.slo_status("m")
    assert status["objective"]["latency_target_s"] == 0.01
    assert status["window_bad"] == 1


def test_malformed_slo_declaration_warns_and_disables():
    """A typo'd slo dict must not fail requests, but it must leave a
    server-side signal instead of silently tracking nothing."""

    class BadSlo(_EchoModel):
        name = "bad_slo"
        slo = {"latency_budget": 1}  # unknown key

    core = ServerCore(ModelRepository())
    core.repository.add_model(BadSlo())
    events = []
    core.logger.sink = events.append
    core.metrics.observe_success("bad_slo", 0, 1000, 1000)
    assert core.metrics.telemetry.slo_status("bad_slo") is None
    warnings = [e for e in events if e["event"] == "slo_declaration_invalid"]
    assert warnings and "latency_budget" in warnings[0]["error"]
    # rolling windows still track the model; requests never failed
    assert core.metrics.telemetry.rolling("bad_slo")["30s"]["count"] == 1


def test_reload_resets_model_telemetry_over_http():
    core = ServerCore(ModelRepository())
    core.repository.add_model(_SloModel())
    with InProcessServer(core=core, grpc=False, builtin_models=False) as srv:
        with httpclient.InferenceServerClient(srv.http_url) as client:
            _infer_fp32(httpclient, client, "slo_echo", 0.0)
            assert core.metrics.telemetry.rolling("slo_echo")["30s"][
                "count"
            ] == 1
            client.load_model("slo_echo")  # reload clears the windows
            assert core.metrics.telemetry.rolling("slo_echo") == {}


def test_collect_prunes_gauges_for_reset_models():
    """After reset() (model unload/reload), the next scrape must DROP
    the model's rolling/SLO gauge children — not freeze their last
    pre-unload values, which would keep a burn-rate alert firing for a
    model that no longer serves and contradict /v2/debug/slo."""
    from client_tpu.observability.metrics import Gauge

    clock = FakeClock()
    objectives = {
        "m": SloObjective(latency_target_s=0.001, availability=0.9)
    }
    telemetry = LiveTelemetry(
        buckets=DURATION_BUCKETS_S,
        clock_ns=clock.ns,
        objective_resolver=lambda name: objectives.get(name),
    )
    rolling = Gauge("t_roll", "d", ("model", "window", "quantile"))
    burn = Gauge("t_burn", "d", ("model",))
    budget = Gauge("t_budget", "d", ("model",))
    telemetry.record("m", 0.05)  # over target: burns budget
    telemetry.record("other", 0.002)
    telemetry.collect(rolling, burn, budget)
    assert {k[0] for k in rolling.label_sets()} == {"m", "other"}
    assert {k[0] for k in burn.label_sets()} == {"m"}
    telemetry.reset("m")  # unload: "m" stops being tracked
    telemetry.collect(rolling, burn, budget)
    assert {k[0] for k in rolling.label_sets()} == {"other"}
    assert burn.label_sets() == [] and budget.label_sets() == []
    # a reload that DROPS the slo declaration prunes the SLO gauges too
    del objectives["m"]
    telemetry.record("m", 0.05)
    telemetry.collect(rolling, burn, budget)
    assert {k[0] for k in rolling.label_sets()} == {"m", "other"}
    assert burn.label_sets() == [] and budget.label_sets() == []


def test_live_telemetry_snapshot_document():
    clock = FakeClock()
    telemetry = LiveTelemetry(
        buckets=DURATION_BUCKETS_S, clock_ns=clock.ns
    )
    telemetry.record("m", 0.002, count=10)
    doc = telemetry.snapshot()
    assert [w["label"] for w in doc["windows"]] == ["30s", "5m"]
    rolling = doc["models"]["m"]["rolling"]
    assert rolling["30s"]["count"] == 10
    assert rolling["30s"]["p99_us"] > 0
    assert "slo" not in doc["models"]["m"]  # no objective declared
    summary = telemetry.summary()
    assert summary["m"]["rolling_30s_count"] == 10


# ---------------------------------------------------------------------------
# server integration: /v2/debug/slo + gauges


class _EchoModel(Model):
    inputs = [{"name": "X", "datatype": "FP32", "shape": [-1, 4]}]
    outputs = [{"name": "Y", "datatype": "FP32", "shape": [-1, 4]}]
    name = "echo"
    max_batch_size = 0

    def execute(self, inputs, parameters):
        return {"Y": inputs["X"] + 1.0}


class _SloModel(Model):
    """Echo with a declared SLO; input value 1 sleeps past the latency
    target, value 999 raises (an availability violation)."""

    inputs = [{"name": "X", "datatype": "FP32", "shape": [-1, 4]}]
    outputs = [{"name": "Y", "datatype": "FP32", "shape": [-1, 4]}]
    name = "slo_echo"
    max_batch_size = 0
    slo = {"latency_target_ms": 50.0, "availability": 0.9, "window_s": 600}

    def execute(self, inputs, parameters):
        flag = float(np.asarray(inputs["X"]).ravel()[0])
        if flag == 999.0:
            raise RuntimeError("chaos: injected model failure")
        if flag == 1.0:
            time.sleep(0.12)  # deliberate latency-SLO violation
        return {"Y": inputs["X"] + 1.0}


def _fetch_json(url: str):
    with urllib.request.urlopen(url) as resp:
        return json.loads(resp.read())


def _fetch_text(url: str) -> str:
    with urllib.request.urlopen(url) as resp:
        return resp.read().decode()


def _infer_fp32(client_mod, client, model: str, flag: float):
    data = np.full([1, 4], flag, dtype=np.float32)
    x = client_mod.InferInput("X", [1, 4], "FP32")
    x.set_data_from_numpy(data)
    return client.infer(model, [x])


def test_debug_slo_tracks_load_shift_while_cumulative_lags():
    """The acceptance scenario: after a fast->slow->fast load shift the
    rolling p99 from ``/v2/debug/slo`` reflects the new regime within
    one sub-window rotation, while the cumulative histogram is still
    dominated by the old one."""
    with InProcessServer(grpc=False) as server:
        clock = FakeClock()
        metrics = server.core.metrics
        metrics.telemetry = LiveTelemetry(
            buckets=DURATION_BUCKETS_S,
            clock_ns=clock.ns,
            objective_resolver=metrics._resolve_objective,
        )
        slow_ns = int(0.05e9)
        fast_ns = int(0.001e9)
        # slow regime: 400 requests at 50 ms land in sub-window 0
        metrics.observe_success("shifty", 0, slow_ns, slow_ns, count=400)
        clock.advance(29)
        # regime shift: 200 fast requests just before the rotation
        metrics.observe_success("shifty", 0, fast_ns, fast_ns, count=200)
        base = f"http://{server.http_url}"
        doc = _fetch_json(f"{base}/v2/debug/slo")
        rolling = doc["models"]["shifty"]["rolling"]["30s"]
        assert rolling["count"] == 600
        assert rolling["p99_us"] > 20_000  # slow regime still in window

        # one sub-window rotation later (30 s horizon / 6 sub-windows =
        # 5 s each; t=29 -> t=31 crosses exactly one boundary) the slow
        # sub-window has expired:
        clock.advance(2)
        doc = _fetch_json(f"{base}/v2/debug/slo")
        rolling = doc["models"]["shifty"]["rolling"]["30s"]
        assert rolling["count"] == 200
        assert rolling["p99_us"] <= 1_000  # tracks the fast regime

        # ... while the cumulative histogram still reports the lifetime
        # tail (99th-percentile rank sits in the slow buckets):
        families = parse_exposition(_fetch_text(f"{base}/metrics"))
        totals = histogram_totals(
            families["tpu_inference_request_duration"], {"model": "shifty"}
        )
        assert totals["count"] == 600
        rank = 0.99 * totals["count"]
        cumulative_p99_le = next(
            le for le, cum in totals["buckets"] if cum >= rank
        )
        assert cumulative_p99_le >= 0.025  # lifetime p99 still ~50 ms

        # the /v2/debug/state summary block carries the same live view
        state = _fetch_json(f"{base}/v2/debug/state")
        assert state["slo"]["shifty"]["rolling_30s_count"] == 200


def _burn_gauge_agreement(base_url: str, model: str):
    """Parse one scrape; return (burn_gauge, burn_from_histogram,
    budget_gauge, budget_from_histogram) for ``model``."""
    families = parse_exposition(_fetch_text(f"{base_url}/metrics"))
    match = {"model": model}
    success = counter_total(
        families["tpu_inference_request_success"], match
    )
    failures = counter_total(
        families["tpu_inference_request_failure"], match
    )
    totals = histogram_totals(
        families["tpu_inference_request_duration"], match
    )
    target_s = _SloModel.slo["latency_target_ms"] / 1e3
    under_target = max(
        (cum for le, cum in totals["buckets"] if le <= target_s),
        default=0,
    )
    bad = (totals["count"] - under_target) + failures
    total = success + failures
    allowed = 1.0 - _SloModel.slo["availability"]
    expected_burn = (bad / total) / allowed if total else 0.0
    expected_budget = (
        max(0.0, min(1.0, 1.0 - bad / (allowed * total))) if total else 1.0
    )
    burn = gauge_values(families["tpu_slo_latency_burn_rate"], match)
    budget = gauge_values(
        families["tpu_slo_error_budget_remaining"], match
    )
    assert burn and budget
    return burn[0], expected_burn, budget[0], expected_budget


def test_slo_burn_rate_agrees_with_histogram_on_both_frontends():
    """The SLO gauges are fed from the same stage events as the
    cumulative histograms, so a burn rate recomputed from the scraped
    histogram + failure counter must agree exactly — whichever front-end
    carried the traffic."""
    core = ServerCore(ModelRepository())
    core.repository.add_model(_SloModel())
    with InProcessServer(core=core, grpc="aio", builtin_models=False) as srv:
        base = f"http://{srv.http_url}"
        with httpclient.InferenceServerClient(srv.http_url) as client:
            for _ in range(6):
                _infer_fp32(httpclient, client, "slo_echo", 0.0)
            _infer_fp32(httpclient, client, "slo_echo", 1.0)  # slow
            with pytest.raises(Exception):
                _infer_fp32(httpclient, client, "slo_echo", 999.0)
        burn, want_burn, budget, want_budget = _burn_gauge_agreement(
            base, "slo_echo"
        )
        assert burn == pytest.approx(want_burn, rel=1e-6)
        assert budget == pytest.approx(want_budget, rel=1e-6)
        assert burn > 1.0  # 2/8 bad against a 0.1 allowance: alerting

        with grpcclient.InferenceServerClient(srv.grpc_url) as client:
            for _ in range(6):
                _infer_fp32(grpcclient, client, "slo_echo", 0.0)
            _infer_fp32(grpcclient, client, "slo_echo", 1.0)  # slow
            with pytest.raises(Exception):
                _infer_fp32(grpcclient, client, "slo_echo", 999.0)
        burn, want_burn, budget, want_budget = _burn_gauge_agreement(
            base, "slo_echo"
        )
        assert burn == pytest.approx(want_burn, rel=1e-6)
        assert budget == pytest.approx(want_budget, rel=1e-6)


def test_live_telemetry_extension_advertised_on_both_frontends():
    with InProcessServer(grpc="aio") as server:
        with httpclient.InferenceServerClient(server.http_url) as client:
            assert "live_telemetry" in client.get_server_metadata()[
                "extensions"
            ]
        with grpcclient.InferenceServerClient(server.grpc_url) as client:
            meta = client.get_server_metadata(as_json=True)
            assert "live_telemetry" in meta["extensions"]


# ---------------------------------------------------------------------------
# OpenMetrics exemplars


def test_exemplar_render_and_parse_round_trip():
    registry = MetricsRegistry()
    hist = Histogram(
        "t_req_seconds", "Latency.", ("model",), buckets=(0.1, 1.0),
        registry=registry,
    )
    hist.labels("m").observe(0.05)
    baseline = registry.render()
    hist.labels("m").observe(
        0.5, exemplar=({"trace_id": 'ab"12'}, 0.5)
    )
    # default rendering is byte-identical modulo the new observation
    plain = registry.render()
    assert "# {" not in plain.replace("# HELP", "").replace("# TYPE", "")
    assert plain.count("\n") == baseline.count("\n")
    decorated = registry.render(exemplars=True)
    assert 'trace_id="ab\\"12"' in decorated
    families = parse_exposition(decorated)
    buckets = [
        s
        for s in families["t_req_seconds"].samples
        if s.name.endswith("_bucket")
    ]
    carried = [s for s in buckets if s.exemplar is not None]
    assert len(carried) == 1
    labels, value = carried[0].exemplar
    assert labels == {"trace_id": 'ab"12'}
    assert value == 0.5
    assert carried[0].labels["le"] == "1"
    # the parser's totals are unaffected by the exemplar tail
    assert histogram_totals(families["t_req_seconds"])["count"] == 2


def test_exemplars_served_on_metrics_endpoint():
    """A traced request's id rides the duration histogram as an
    OpenMetrics exemplar under ?exemplars=true, linking the `/metrics`
    bucket to the same id in /v2/debug/requests; the default scrape
    stays plain Prometheus text."""
    trace_id = "cd" * 16
    traceparent = f"00-{trace_id}-{'ab' * 8}-01"
    with InProcessServer(grpc=False) as server:
        # tracing defaults to all-OFF; the sampled traceparent then picks
        # the trace id the exemplar must carry
        server.core.trace_manager.update(
            {"trace_level": ["TIMESTAMPS"], "trace_rate": "1"}
        )
        with httpclient.InferenceServerClient(server.http_url) as client:
            client.infer(
                "simple",
                _simple_inputs(httpclient),
                headers={"traceparent": traceparent},
            )
        base = f"http://{server.http_url}"
        plain = _fetch_text(f"{base}/metrics")
        assert trace_id not in plain
        decorated = _fetch_text(f"{base}/metrics?exemplars=true")
        assert f'trace_id="{trace_id}"' in decorated
        # the same id is retrievable evidence in the flight recorder
        requests_doc = _fetch_json(f"{base}/v2/debug/requests?model=simple")
        assert any(
            e["trace_id"] == trace_id for e in requests_doc["recent"]
        )


# ---------------------------------------------------------------------------
# per-endpoint pool telemetry


def test_endpoint_pool_telemetry_units():
    now = [100.0]
    pool = EndpointPool(["a:1", "b:2"], cooldown_s=5.0, clock=lambda: now[0])
    a, b = pool.endpoints
    t0 = pool.begin(a)
    t1 = pool.begin(a)
    assert a.outstanding == 2
    now[0] += 0.2
    pool.finish(a, t0, ok=True)
    assert a.outstanding == 1
    assert a.ewma_latency_s == pytest.approx(0.2)  # first sample seeds
    now[0] += 0.2
    pool.finish(a, t1, ok=True)  # 0.4 s sample folds in at alpha=0.1
    assert a.ewma_latency_s == pytest.approx(0.2 + 0.1 * (0.4 - 0.2))
    t2 = pool.begin(b)
    pool.finish(b, t2, ok=False)
    assert b.errors == 1 and b.ewma_latency_s == 0.0
    pool.mark_down(a)  # primary moves: the reroute charges to a
    snap = pool.snapshot()
    assert snap["primary"] == "b:2"
    assert snap["failovers"] == 1
    rows = {r["url"]: r for r in snap["endpoints"]}
    assert rows["a:1"]["reroutes"] == 1
    assert rows["a:1"]["down"] is True
    assert rows["a:1"]["outstanding"] == 0
    assert rows["a:1"]["ewma_latency_us"] == pytest.approx(220_000.0)
    assert rows["b:2"]["errors"] == 1
    assert rows["b:2"]["down"] is False


def test_client_surfaces_expose_endpoint_snapshot():
    with InProcessServer(grpc="aio") as server:
        with httpclient.InferenceServerClient(server.http_url) as client:
            client.infer("simple", _simple_inputs(httpclient))
            snap = client.endpoint_snapshot()
        assert snap["primary"]
        (endpoint,) = snap["endpoints"]
        assert endpoint["outstanding"] == 0  # brackets closed
        assert endpoint["ewma_latency_us"] > 0
        assert endpoint["errors"] == 0
        with grpcclient.InferenceServerClient(server.grpc_url) as client:
            client.infer("simple", _simple_inputs(grpcclient))
            snap = client.endpoint_snapshot()
        (endpoint,) = snap["endpoints"]
        assert endpoint["outstanding"] == 0
        assert endpoint["ewma_latency_us"] > 0


def test_client_metrics_section_formats_pool_snapshot():
    """The PR 3 leftover: the section renders with a pool snapshot
    alone (no tracer), with a tracer alone, and says so when neither
    telemetry source is live."""
    from client_tpu.perf.report import format_client_metrics

    pool = {
        "primary": "a:1",
        "failovers": 2,
        "endpoints": [
            {
                "url": "a:1", "outstanding": 3, "ewma_latency_us": 120.5,
                "successes": 9, "errors": 1, "marked_down": 1,
                "reroutes": 2, "down": False,
            }
        ],
    }
    text = format_client_metrics(None, endpoints=pool)
    assert (
        "Endpoint pool (1 endpoint, policy sticky, primary a:1, "
        "2 failovers, 0 ejections)" in text
    )
    assert "120.5" in text
    tracer_snapshot = {
        "request_count": 4, "error_count": 1, "retry_count": 2,
        "avg_latency_us": 10.0, "latency_histogram_us": [],
    }
    text = format_client_metrics(tracer_snapshot)
    assert "Requests: 4 (errors 1, retries 2)" in text
    assert "(no client telemetry recorded)" in format_client_metrics(None)


# ---------------------------------------------------------------------------
# fleet aggregation


def _render_doc(families_text: str):
    return parse_exposition(families_text)


def test_merge_families_sums_counters_and_maxes_gauges():
    doc_a = _render_doc(
        "# TYPE tpu_x_total counter\n"
        'tpu_x_total{model="m"} 3\n'
        "# TYPE tpu_g gauge\n"
        "tpu_g 5\n"
    )
    doc_b = _render_doc(
        "# TYPE tpu_x_total counter\n"
        'tpu_x_total{model="m"} 4\n'
        'tpu_x_total{model="n"} 7\n'
        "# TYPE tpu_g gauge\n"
        "tpu_g 2\n"
    )
    merged = merge_families([doc_a, doc_b])
    assert counter_total(merged["tpu_x_total"], {"model": "m"}) == 7
    assert counter_total(merged["tpu_x_total"], {"model": "n"}) == 7
    assert gauge_values(merged["tpu_g"]) == [5]  # max across replicas


def test_replica_stats_prefers_rolling_p99_with_histogram_fallback():
    first = _render_doc(
        "# TYPE tpu_inference_request_success counter\n"
        'tpu_inference_request_success{model="m"} 0\n'
        "# TYPE tpu_inference_request_duration histogram\n"
        'tpu_inference_request_duration_bucket{model="m",le="0.001"} 0\n'
        'tpu_inference_request_duration_bucket{model="m",le="0.1"} 0\n'
        'tpu_inference_request_duration_bucket{model="m",le="+Inf"} 0\n'
        'tpu_inference_request_duration_sum{model="m"} 0\n'
        'tpu_inference_request_duration_count{model="m"} 0\n'
    )
    last = _render_doc(
        "# TYPE tpu_inference_request_success counter\n"
        'tpu_inference_request_success{model="m"} 100\n'
        "# TYPE tpu_inference_request_duration histogram\n"
        'tpu_inference_request_duration_bucket{model="m",le="0.001"} 95\n'
        'tpu_inference_request_duration_bucket{model="m",le="0.1"} 100\n'
        'tpu_inference_request_duration_bucket{model="m",le="+Inf"} 100\n'
        'tpu_inference_request_duration_sum{model="m"} 1.0\n'
        'tpu_inference_request_duration_count{model="m"} 100\n'
    )
    stats = replica_stats("r1:8000", first, last, window_s=10.0, model="m")
    assert stats.requests == 100
    assert stats.p99_source == "histogram"
    assert stats.p99_s == pytest.approx(0.1)  # bucket upper bound
    # a live rolling gauge wins over the histogram estimate
    last_rolling = _render_doc(
        "# TYPE tpu_rolling_latency_seconds gauge\n"
        'tpu_rolling_latency_seconds{model="m",window="30s",'
        'quantile="0.99"} 0.007\n'
    )
    for name, family in last_rolling.items():
        last[name] = family
    stats = replica_stats("r1:8000", first, last, model="m")
    assert stats.p99_source == "rolling"
    assert stats.p99_s == pytest.approx(0.007)


def test_fleet_skew_flags_slow_replica():
    from client_tpu.observability.fleet import ReplicaStats

    fast = ReplicaStats(url="a", p99_s=0.002)
    slow = ReplicaStats(url="b", p99_s=0.005)
    verdict = fleet_skew([fast, slow])
    assert verdict["flagged"] and verdict["slowest"] == "b"
    assert verdict["ratio"] == pytest.approx(2.5)
    assert fleet_skew([fast]) is None  # one replica: nothing to compare
    calm = ReplicaStats(url="c", p99_s=0.0025)
    assert fleet_skew([fast, calm])["flagged"] is False


def test_fleet_skew_never_compares_across_p99_sources():
    """The rolling gauge interpolates inside its bucket; the histogram
    fallback reports the bucket's upper bound. A mixed pair could flag a
    healthy replica on pure quantization, so skew only compares within
    one source (preferring the live rolling one)."""
    from client_tpu.observability.fleet import ReplicaStats

    live = ReplicaStats(url="a", p99_s=0.0024, p99_source="rolling")
    coarse = ReplicaStats(url="b", p99_s=0.005, p99_source="histogram")
    assert fleet_skew([live, coarse]) is None  # not comparable
    live2 = ReplicaStats(url="c", p99_s=0.0011, p99_source="rolling")
    verdict = fleet_skew([live, live2, coarse])
    # the histogram replica sits out; the rolling pair is compared
    assert verdict["source"] == "rolling"
    assert verdict["compared"] == 2
    assert verdict["slowest"] == "a" and verdict["flagged"] is True


def test_three_replica_fleet_aggregation_with_skew(tmp_path):
    """The fleet e2e: three in-process replicas, one deliberately
    slowed; the aggregator's per-replica rows split the traffic, the
    totals sum, and skew detection calls out the slow replica from its
    own rolling p99."""

    def make_server(slow_s: float) -> InProcessServer:
        class Echo(_EchoModel):
            def execute(self, inputs, parameters):
                if slow_s:
                    time.sleep(slow_s)
                return {"Y": inputs["X"] + 1.0}

        core = ServerCore(ModelRepository())
        core.repository.add_model(Echo())
        return InProcessServer(core=core, grpc=False, builtin_models=False)

    # The slowed replica must land in a histogram bucket above any
    # plausible scheduling hiccup on the fast replicas: with only 15
    # requests each, p99 ~= max, so a single >slow_s outlier on a fast
    # replica would steal "slowest". 0.11s sits in the (0.1, 0.25]
    # bucket — noise spikes of >100ms don't happen here.
    servers = [make_server(0.0), make_server(0.0), make_server(0.11)]
    try:
        for server in servers:
            server.start()
        urls = [server.http_url for server in servers]

        def drive():
            for server in servers:
                with httpclient.InferenceServerClient(
                    server.http_url
                ) as client:
                    for _ in range(15):
                        _infer_fp32(httpclient, client, "echo", 0.0)

        async def run():
            fleet = FleetCollector(urls, interval_s=30.0, model_name="echo")
            await fleet.start()  # baseline scrape per replica
            await asyncio.to_thread(drive)
            await fleet.stop()  # closing scrape per replica
            return fleet.fleet_summary()

        summary = asyncio.run(run())
    finally:
        for server in servers:
            server.stop()

    assert [r.url.split("//")[-1].split("/")[0] for r in summary.replicas]
    assert summary.total_requests == 45
    assert summary.total_failures == 0
    by_url = {r.url: r for r in summary.replicas}
    for url in urls:
        row = by_url[next(u for u in by_url if url in u)]
        assert row.requests == 15
        assert row.p99_source == "rolling"  # live gauge, not the fallback
    assert summary.skew is not None
    assert summary.skew["flagged"] is True
    assert urls[2] in summary.skew["slowest"]
    assert summary.skew["ratio"] >= 2.0
    # merged families: fleet-wide success counter sums the replicas
    assert (
        counter_total(
            summary.merged["tpu_inference_request_success"],
            {"model": "echo"},
        )
        == 45
    )


def test_summarize_fleet_per_replica_windows():
    """A replica whose endpoint died mid-run covers a shorter span; its
    duty must divide by ITS window, not the fleet-wide max."""
    first = _render_doc(
        "# TYPE tpu_device_compute_ns_total counter\n"
        "tpu_device_compute_ns_total 0\n"
    )

    def last_busy(busy_ns):
        return _render_doc(
            "# TYPE tpu_device_compute_ns_total counter\n"
            f"tpu_device_compute_ns_total {busy_ns}\n"
        )

    summary = summarize_fleet(
        [
            ("a", first, last_busy(9_000_000_000), 30.0),
            ("b", first, last_busy(9_000_000_000), 10.0),  # died at 10 s
        ],
        window_s=30.0,
    )
    by_url = {r.url: r for r in summary.replicas}
    assert by_url["a"].duty == pytest.approx(0.3)
    assert by_url["b"].duty == pytest.approx(0.9)  # its own span
    assert by_url["b"].window_s == 10.0
    assert summary.window_s == 30.0


def test_cli_fleet_section_and_client_metrics_fix(capsys):
    """--metrics-url with a comma list adds the Fleet section; the
    "Client metrics" section prints under --collect-metrics alone (the
    PR 3 leftover tied it to --stage-breakdown) and includes the
    endpoint-pool table."""
    from client_tpu.perf.cli import main

    with InProcessServer(grpc=False) as primary:
        with InProcessServer(grpc=False) as secondary:
            code = main([
                "-m", "simple",
                "-u", primary.http_url,
                "-i", "http",
                "--concurrency-range", "2",
                "--measurement-interval", "250",
                "--stability-percentage", "60",
                "--max-trials", "3",
                "--collect-metrics",
                "--metrics-interval", "0.1",
                "--metrics-url",
                f"{primary.http_url},{secondary.http_url}",
            ])
    assert code == 0
    out = capsys.readouterr().out
    assert "Server metrics" in out  # primary replica keeps the old block
    assert "Fleet (2 replicas)" in out
    assert "Skew:" in out
    # the satellite fix: no --stage-breakdown, yet client telemetry prints
    assert "Client metrics:" in out
    assert "Endpoint pool (1 endpoint" in out


# ---------------------------------------------------------------------------
# tools: metric lint


def test_metric_lint_repo_is_clean_and_rules_fire():
    from tools.metric_lint import check_family, check_source, run_metric_lint

    assert run_metric_lint() == []
    assert check_family("nv_gpu_utilization", "Gauge")  # wrong namespace
    assert check_family("tpu_things", "Counter")  # counter sans _total
    assert check_family("tpu_infer_latency", "Histogram")  # unitless time
    assert check_family("tpu_wait_ms", "Gauge")  # non-base unit
    assert check_family("tpu_cache_utilization", "Gauge")  # not _ratio
    assert check_family("tpu_rolling_latency_seconds", "Gauge") == []
    assert check_family("tpu_slo_latency_burn_rate", "Gauge") == []
    assert check_family("tpu_inference_request_duration", "Histogram") == []
    findings = check_source(
        'Counter("tpu_oops", "h", registry=r)\n'
        'Gauge("tpu_fine_ratio", "h", registry=r)\n',
        "<test>",
    )
    assert len(findings) == 1 and findings[0][0] == 1


# ---------------------------------------------------------------------------
# what the sketch costs, as counts: clock reads and observations


class _CountingClock:
    """A nanosecond clock that advances a microsecond a read and counts
    its reads."""

    def __init__(self):
        self.reads = 0

    def __call__(self) -> int:
        self.reads += 1
        return self.reads * 1000


def _counted_telemetry(core, objective_resolver=None):
    """Give ``core`` a LiveTelemetry on a counting clock; returns both."""
    clock = _CountingClock()
    telemetry = core.metrics.telemetry = LiveTelemetry(
        buckets=DURATION_BUCKETS_S,
        clock_ns=clock,
        objective_resolver=(
            objective_resolver or core.metrics._resolve_objective
        ),
    )
    return telemetry, clock


def _window_counts(telemetry, model):
    """{window label: observations in it}; reads the clock (rotation), so
    take the clock's count first."""
    return {
        label: entry["count"]
        for label, entry in telemetry.rolling(model).items()
    }


CORE_PATHS = ("single", "batcher", "direct", "decoupled")


def test_window_sketch_costs_one_clock_read_a_request(loopback_echo):
    """With live telemetry recording (the default) a loopback request
    costs ONE read of the telemetry's clock and leaves ONE observation
    in each rolling window; switched off mid-run, further requests cost
    no read and leave the windows as they were. A later change that adds
    a read a request has to change the numbers here."""
    n = 24
    core = ServerCore(ModelRepository())
    telemetry, clock = _counted_telemetry(core)
    with loopback_echo(core) as echo:
        echo.send(n)
        assert clock.reads == n
        assert _window_counts(telemetry, "echo") == {"30s": n, "5m": n}
        telemetry.enabled = False
        reads = clock.reads  # the windows' own, for the counts above
        echo.send(n)
        assert clock.reads == reads
        assert _window_counts(telemetry, "echo") == {"30s": n, "5m": n}


def test_telemetry_disabled_is_inert(drive_core_path):
    """Disabled from the start, telemetry keeps no state at all: no
    clock read, no SLO declaration resolved, no model tracked, and the
    ``/v2/debug/slo`` document names no model, on every execution path,
    failures included. Enabled again, the same requests are booked."""
    core = ServerCore(ModelRepository())
    resolved = []
    telemetry, clock = _counted_telemetry(core, resolved.append)
    telemetry.enabled = False
    try:
        for path in CORE_PATHS:
            drive_core_path(core, path, [1.0, 2.0, 3.0])
            drive_core_path(core, path, [999.0])
        assert clock.reads == 0
        assert resolved == []
        assert telemetry.models() == []
        assert core.debug_slo()["models"] == {}
        telemetry.enabled = True
        drive_core_path(core, "single", [1.0, 2.0, 3.0])
        assert clock.reads == 3
        assert resolved == ["path_single"]
        assert _window_counts(telemetry, "path_single") == {"30s": 3, "5m": 3}
    finally:
        core.close()


@pytest.mark.parametrize("path", CORE_PATHS)
def test_one_observation_a_request_on_every_path(drive_core_path, path):
    """The accounting a merged spine of the four paths has to keep
    (ROADMAP D5): N requests leave exactly N observations, the
    successes in every latency window and successes and failures in
    the SLO budget window. What differs by path is the number of clock
    reads: ``infer_direct`` books a merged chunk's successes in ONE
    record of ``count=n``, the others one record a request."""
    ok, failed = 5, 3
    core = ServerCore(ModelRepository())
    telemetry, clock = _counted_telemetry(core)
    try:
        results = drive_core_path(core, path, [1.0] * ok)
        assert not any(isinstance(r, Exception) for r in results)
        results = drive_core_path(core, path, [999.0] * failed)
        assert all(isinstance(r, Exception) for r in results)
        model = f"path_{path}"
        assert clock.reads == (1 if path == "direct" else ok) + failed
        assert _window_counts(telemetry, model) == {"30s": ok, "5m": ok}
        slo = telemetry.slo_status(model)
        assert (slo["window_good"], slo["window_bad"]) == (ok, failed)
    finally:
        core.close()
