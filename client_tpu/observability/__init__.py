"""End-to-end request tracing and client-side telemetry.

The observability layer answers "where did this request's time go?"
across the whole stack:

Client side (:mod:`client_tpu.observability.trace`)
    A lightweight :class:`Tracer` every client surface accepts
    (``tracer=`` kwarg, off by default): per-call spans for serialize ->
    send -> wait -> deserialize, one transport span per retry attempt,
    annotated with the resilience layer's retry and circuit-breaker
    events. Exporters: in-memory (tests), JSONL file, and a
    :class:`ClientMetrics` snapshot (counts, errors, latency histogram).

Context propagation
    W3C ``traceparent`` — generated by the clients, carried as an HTTP
    header / gRPC metadata entry, extracted by both server front-ends,
    so one trace id spans the client span and the server record.

Metrics (:mod:`client_tpu.observability.metrics`)
    A dependency-free Prometheus registry (Counter/Gauge/Histogram with
    labels, exact exposition-format rendering) plus a parser for the
    same format. The server's ``/metrics`` families live in
    :mod:`client_tpu.server.metrics`; the perf harness's
    ``--collect-metrics`` scraper parses them back.

Profiling (:mod:`client_tpu.observability.profiling`)
    The hot-path instrument: per-stage thread-CPU accounting
    (``tpu_request_cpu_seconds{stage}``, default-off, one branch per
    stage event while disabled) and an on-demand wall-stack sampler with
    collapsed-stack / speedscope exporters (``GET /v2/debug/profile``).
    The perf harness's ``--profile-server`` turns both into the
    "Wire-gap attribution" report. :class:`LapSpans` is the always-on
    wall-clock counterpart for one loop (the LLM engine's step phases),
    with a record of its turns: steady or a stall, a stall with a cause
    (:class:`ProcessEvents`, :class:`StallWatch`).

Logging (:mod:`client_tpu.observability.logging`)
    The real backend of the ``/v2/logging`` extension: a dependency-free
    structured JSON logger whose severity gates re-read the live
    settings (global + per-model) on every emission, with rate-limited
    hot-path error records and stderr/file/injected-sink exporters.

Flight recorder (:mod:`client_tpu.observability.recorder`)
    Fixed-size ring of completed-request exemplars (model, ids, status,
    per-stage timings, error text) with reserved error/slowest
    sub-buffers — ``GET /v2/debug/requests``.

Live telemetry (:mod:`client_tpu.observability.window` /
:mod:`client_tpu.observability.slo` / :mod:`client_tpu.observability.fleet`)
    The rolling signals the cumulative aggregates cannot give: sliding-
    window quantile sketches over the histogram bucket grid (30s/5m
    p50/p95/p99 per model, ``tpu_rolling_latency_seconds``), per-model
    SLO objectives tracked as error-budget burn rates
    (``tpu_slo_latency_burn_rate``, ``GET /v2/debug/slo``), and fleet
    aggregation merging N replicas' ``/metrics`` with per-replica skew
    detection (the perf harness's ``--metrics-url a,b,c``).

Server side (:mod:`client_tpu.observability.server`)
    :class:`TraceManager` makes the trace-settings extension real:
    ``trace_rate`` sampling, ``trace_count`` budgets, per-model
    overrides, Triton-style timestamped records (request/queue/compute
    stages from the core's statistics hooks) written via the JSONL
    exporter named by ``trace_file``.

Everything is clock-injectable; ``tools/clock_lint.py`` bans direct
``time.*()`` calls in this package so tests never need to sleep.
"""

from client_tpu.observability.logging import (
    DEFAULT_LOG_SETTINGS,
    StructuredLogger,
    validate_log_settings,
)
from client_tpu.observability.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    parse_exposition,
)
from client_tpu.observability.fleet import (
    FleetSummary,
    ReplicaStats,
    merge_families,
    summarize_fleet,
)
from client_tpu.observability.recorder import FlightRecorder
from client_tpu.observability.slo import LiveTelemetry, SloObjective
from client_tpu.observability.window import (
    WindowedCounter,
    WindowedHistogram,
    WindowSnapshot,
)
from client_tpu.observability.profiling import (
    STAGES,
    STALL_NS,
    LapSpans,
    ProcessEvents,
    ProfileResult,
    StageCpuAccounting,
    StallWatch,
    WallProfiler,
    maybe_jax_trace,
    stage_scope,
)
from client_tpu.observability.server import (
    ServerTrace,
    TraceManager,
)
from client_tpu.observability.trace import (
    NOOP_TRACE,
    TRACEPARENT_HEADER,
    ClientMetrics,
    ClientTrace,
    InMemoryExporter,
    JsonlExporter,
    Span,
    TraceContext,
    Tracer,
    last_stages,
    reset_last_stages,
    start_trace,
)

__all__ = [
    "DEFAULT_LOG_SETTINGS",
    "NOOP_TRACE",
    "STAGES",
    "STALL_NS",
    "TRACEPARENT_HEADER",
    "ClientMetrics",
    "ClientTrace",
    "Counter",
    "FleetSummary",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "InMemoryExporter",
    "JsonlExporter",
    "LapSpans",
    "LiveTelemetry",
    "MetricsRegistry",
    "ProcessEvents",
    "ProfileResult",
    "ReplicaStats",
    "ServerTrace",
    "SloObjective",
    "Span",
    "StageCpuAccounting",
    "StallWatch",
    "StructuredLogger",
    "TraceContext",
    "TraceManager",
    "Tracer",
    "WallProfiler",
    "WindowSnapshot",
    "WindowedCounter",
    "WindowedHistogram",
    "last_stages",
    "maybe_jax_trace",
    "merge_families",
    "parse_exposition",
    "reset_last_stages",
    "stage_scope",
    "start_trace",
    "summarize_fleet",
    "validate_log_settings",
]
