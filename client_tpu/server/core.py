"""Protocol-independent server engine.

Both the HTTP and gRPC front-ends reduce a request to :class:`CoreRequest`
(name->ndarray inputs plus requested-output descriptors), hand it to
:meth:`ServerCore.infer` / :meth:`ServerCore.infer_decoupled`, and serialize
the returned :class:`CoreResponse` objects back onto their wire. Statistics
are accounted the way Triton's statistics extension reports them
(success/fail/queue/compute_input/compute_infer/compute_output cumulative
count+ns; reference SURVEY.md §5 observability).
"""

import asyncio
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, AsyncIterator, Dict, List, Optional

import numpy as np

from client_tpu.scheduling import (
    SCHEDULING_PARAM_KEYS,
    TIMEOUT_ACTION_REJECT,
    AdmissionGate,
    PriorityQueue,
    QueueFullError,
    QueuePolicy,
    QueueTimeoutError,
    RateLimiter,
    SchedulingError,
)
from client_tpu.lifecycle import DrainController, ServerDrainingError
from client_tpu.server.model_repository import Model, ModelRepository
from client_tpu.server.shm import SharedMemoryManager
from client_tpu.utils import (
    InferenceServerException,
    deserialize_bytes_tensor,
    np_to_triton_dtype,
    num_elements,
    serialize_byte_tensor,
    triton_to_np_dtype,
)

SERVER_NAME = "client_tpu_server"
SERVER_VERSION = "0.1.0"
SERVER_EXTENSIONS = [
    "classification",
    "sequence",
    "model_repository",
    "model_repository(unload_dependents)",
    "schedule_policy",
    "model_configuration",
    "system_shared_memory",
    "cuda_shared_memory",
    "tpu_shared_memory",
    "binary_tensor_data",
    "parameters",
    "statistics",
    "trace",
    "logging",
    # rolling-window quantiles + SLO burn rates (GET /v2/debug/slo, the
    # tpu_rolling_latency_seconds / tpu_slo_* gauge families); advertised
    # by both front-ends' server-metadata responses
    "live_telemetry",
    # mesh-sharded multi-device execution (client_tpu.parallel): models
    # declare a mesh + per-tensor shardings, the server resolves and
    # executes them, topology rides server metadata (HTTP), the model
    # config parameters map (both protocols), and /v2/debug/state; per-
    # device busy-ns exports as tpu_device_compute_ns_total{device}
    "sharding",
]


@dataclass(slots=True)
class CoreTensor:
    name: str
    datatype: str
    shape: List[int]
    data: np.ndarray  # host ndarray (object dtype for BYTES)


@dataclass(slots=True)
class CoreRequestedOutput:
    name: str
    binary_data: bool = False
    classification: int = 0
    shm_region: Optional[str] = None
    shm_byte_size: int = 0
    shm_offset: int = 0


@dataclass(slots=True)
class CoreRequest:
    model_name: str
    model_version: str = ""
    id: str = ""
    inputs: List[CoreTensor] = field(default_factory=list)
    outputs: List[CoreRequestedOutput] = field(default_factory=list)
    parameters: Dict[str, Any] = field(default_factory=dict)
    # server trace attached by the front-end (observability.ServerTrace);
    # the execution paths add queue/compute stage events to it
    trace: Optional[Any] = None
    # scheduling fields stamped at admission (QueuePolicy.stamp): the
    # effective queue level (1 = highest) and the absolute queue deadline
    # in monotonic ns (None = no deadline)
    priority_level: int = 0
    deadline_ns: Optional[int] = None
    # shm-ring ticket (server.shm_ring.RingTicket) attached by the
    # front-end when the request sourced its inputs from a ring slot;
    # the front-end routes the response back through ticket.complete()
    shm_ring: Optional[Any] = None


def _trace_id_of(request) -> str:
    """The request's trace id ("" when untraced) — rides the success
    booking into the metrics layer as the duration histogram's
    OpenMetrics exemplar, linking a ``/metrics`` bucket to the same
    request's ``/v2/debug/requests`` evidence."""
    trace = request.trace
    return trace.trace_id if trace is not None else ""


def _trace_stages(
    trace, queue_start_ns: int, compute_start_ns: int,
    compute_end_ns: int, request_end_ns: int,
) -> None:
    """Stamp the Triton-style stage timestamps onto a server trace
    (no-op for untraced requests). REQUEST_START was recorded by the
    front-end when it accepted the request."""
    if trace is None:
        return
    trace.event("QUEUE_START", queue_start_ns)
    trace.event("COMPUTE_START", compute_start_ns)
    trace.event("COMPUTE_END", compute_end_ns)
    trace.event("REQUEST_END", request_end_ns)


@dataclass(slots=True)
class CoreResponse:
    model_name: str
    model_version: str
    id: str
    outputs: List[CoreTensor]
    parameters: Dict[str, Any] = field(default_factory=dict)
    # outputs redirected to shared memory: name -> (region, byte_size, offset)
    shm_outputs: Dict[str, Any] = field(default_factory=dict)


class _Stats:
    """Cumulative per-model statistics (counts + ns).

    ``metrics`` (a :class:`client_tpu.server.metrics.ServerMetrics`) gets
    the same events as the counters — every booking path feeds both, so
    the statistics extension and the Prometheus families can never
    disagree. Metrics calls happen outside ``self.lock``.
    """

    FIELDS = ("success", "fail", "queue", "compute_input", "compute_infer", "compute_output")

    def __init__(self, metrics=None, model_name: str = ""):
        self._metrics = metrics
        self._model = model_name
        self.lock = threading.Lock()
        self.counts = {f: 0 for f in self.FIELDS}
        self.ns = {f: 0 for f in self.FIELDS}
        self.inference_count = 0
        self.execution_count = 0
        self.last_inference = 0
        # Decoupled response statistics, keyed by response index (Triton's
        # response_stats map: key "0" aggregates first responses, so its
        # success ns/count is the average time-to-first-response).
        self.response_stats: Dict[str, Dict[str, List[int]]] = {}

    def record(self, field_name: str, duration_ns: int) -> None:
        with self.lock:
            self.counts[field_name] += 1
            self.ns[field_name] += duration_ns
        if field_name == "fail" and self._metrics is not None:
            self._metrics.observe_failure(self._model)

    def record_success(
        self, batch: int, queue_ns, in_ns, infer_ns, out_ns,
        executions: int = 1, trace_id: str = "",
    ):
        """Account one successful request. ``executions`` is 0 for requests
        that shared a dynamically-batched model execution with an earlier
        request in the same batch (Triton semantics: inference_count counts
        requests/rows, execution_count counts device executions).
        ``trace_id`` (traced requests only) rides to the metrics hook as
        the duration histogram's OpenMetrics exemplar."""
        now_ms = int(time.time() * 1000)
        total = queue_ns + in_ns + infer_ns + out_ns
        with self.lock:
            self.inference_count += batch
            self.execution_count += executions
            self.last_inference = now_ms
            for f, ns in (
                ("success", total),
                ("queue", queue_ns),
                ("compute_input", in_ns),
                ("compute_infer", infer_ns),
                ("compute_output", out_ns),
            ):
                self.counts[f] += 1
                self.ns[f] += ns
        if self._metrics is not None:
            self._metrics.observe_success(
                self._model, queue_ns, in_ns + infer_ns + out_ns, total,
                trace_id=trace_id,
            )

    def record_success_batch(
        self,
        n_requests: int,
        rows: int,
        queue_ns_total: int,
        infer_ns_total: int,
        out_ns_total: int,
        executions: int = 1,
    ) -> None:
        """Account ``n_requests`` successful requests of one merged
        execution with a single lock acquisition (the direct path runs
        this per chunk instead of record_success per request)."""
        now_ms = int(time.time() * 1000)
        total = queue_ns_total + infer_ns_total + out_ns_total
        with self.lock:
            self.inference_count += rows
            self.execution_count += executions
            self.last_inference = now_ms
            for f, ns in (
                ("success", total),
                ("queue", queue_ns_total),
                ("compute_input", 0),
                ("compute_infer", infer_ns_total),
                ("compute_output", out_ns_total),
            ):
                self.counts[f] += n_requests
                self.ns[f] += ns
        if self._metrics is not None and n_requests:
            # per-request averages of the chunk totals, booked n at once
            self._metrics.observe_success(
                self._model,
                queue_ns_total // n_requests,
                (infer_ns_total + out_ns_total) // n_requests,
                total // n_requests,
                count=n_requests,
            )

    def record_execution(self) -> None:
        """Count a device execution whose every request failed packaging."""
        with self.lock:
            self.execution_count += 1

    RESPONSE_FIELDS = (
        "success",
        "fail",
        "cancel",
        "compute_infer",
        "compute_output",
        "empty_response",
    )

    def record_response(
        self,
        index: int,
        infer_ns: int,
        out_ns: int,
        latency_ns: int,
        empty: bool,
    ) -> None:
        """Account one decoupled response (Triton response_stats shape):
        ``infer_ns`` = model time since the previous response, ``out_ns`` =
        packaging, ``latency_ns`` = cumulative since request start."""
        with self.lock:
            entry = self.response_stats.setdefault(
                str(index), {f: [0, 0] for f in self.RESPONSE_FIELDS}
            )
            if empty:
                # Disjoint categories (Triton semantics): an empty response
                # is not a success and carries no compute samples.
                entry["empty_response"][0] += 1
                entry["empty_response"][1] += latency_ns
                return
            entry["success"][0] += 1
            entry["success"][1] += latency_ns
            entry["compute_infer"][0] += 1
            entry["compute_infer"][1] += infer_ns
            entry["compute_output"][0] += 1
            entry["compute_output"][1] += out_ns

    def record_response_failure(
        self, index: int, latency_ns: int, cancelled: bool = False
    ) -> None:
        """Account a response slot that errored (or was cancelled) mid-stream
        — the per-response twin of the aggregate 'fail' field, mirroring the
        fail/cancel entries of Triton's InferResponseStatistics."""
        with self.lock:
            entry = self.response_stats.setdefault(
                str(index), {f: [0, 0] for f in self.RESPONSE_FIELDS}
            )
            key = "cancel" if cancelled else "fail"
            entry[key][0] += 1
            entry[key][1] += latency_ns

    def snapshot(self) -> Dict[str, Any]:
        with self.lock:
            snap = {
                "inference_count": self.inference_count,
                "execution_count": self.execution_count,
                "last_inference": self.last_inference,
                "inference_stats": {
                    f: {"count": self.counts[f], "ns": self.ns[f]}
                    for f in self.FIELDS
                },
            }
            if self.response_stats:
                # Decoupled per-response statistics (Triton response_stats
                # wire shape). The reference's client-side stats treat a
                # stream as one opaque request — its own known blind spot
                # (grpc_client.cc:1650-1653); don't inherit that.
                snap["response_stats"] = {
                    key: {
                        f: {"count": v[0], "ns": v[1]}
                        for f, v in fields.items()
                    }
                    for key, fields in self.response_stats.items()
                }
            return snap


def _to_host(raw: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """Materialize model outputs on host with ONE batched transfer.

    ``jax.device_get`` of the whole dict issues a single batched transfer
    instead of one blocking readback per array. Models that already
    return numpy pass through untouched. Runs inside the executor thread
    so the event loop never blocks on a device round-trip. A failed
    transfer is the model's failure and propagates.
    """
    if all(isinstance(v, np.ndarray) for v in raw.values()):
        return raw
    try:
        import jax
    except ImportError:  # numpy-only install: nothing lives on a device
        pass
    else:
        raw = jax.device_get(raw)
    return {k: np.asarray(v) for k, v in raw.items()}


class _BatchMeta:
    """Per-model caches + pure helpers shared by the two dynamic-batching
    paths (the event-loop :class:`_ModelBatcher` and the synchronous
    :meth:`ServerCore.infer_direct` used by the native front-end's pump
    thread). Read-only after construction, so cross-thread use is safe."""

    def __init__(self, model: Model):
        self.model = model
        self.declared = {i["name"] for i in model.inputs}
        self.declared_shapes = {
            i["name"]: list(i["shape"]) for i in model.inputs
        }
        self.ragged = bool(getattr(model, "allow_ragged_batch", False))

    def validate(self, request: CoreRequest) -> int:
        """Batch-path request validation; returns the request's row count.

        Happens per request so a malformed request fails alone instead of
        poisoning the batch it would have joined.
        """
        model = self.model
        declared = self.declared
        rows = 1
        if request.inputs:
            rows = int(request.inputs[0].shape[0]) if request.inputs[0].shape else 1
            for t in request.inputs:
                if declared and t.name not in declared:
                    raise InferenceServerException(
                        f"unexpected inference input '{t.name}' for model "
                        f"'{model.name}'"
                    )
                if not t.shape or int(t.shape[0]) != rows:
                    raise InferenceServerException(
                        f"all inputs must share the batch dimension: input "
                        f"'{t.name}' shape {list(t.shape)} does not match "
                        f"batch size {rows}"
                    )
            if rows > model.max_batch_size:
                raise InferenceServerException(
                    f"inference request batch-size must be <= "
                    f"{model.max_batch_size} for '{model.name}', got {rows}"
                )
        return rows

    @staticmethod
    def _signature_params(parameters: Dict[str, Any]) -> str:
        """Parameter part of the batch-compat signature. Scheduling
        params (priority/timeout) are admission inputs, not execution
        inputs — two same-shape requests that differ only in them must
        still share a batch, so they are excluded here."""
        if not parameters:
            return ""
        filtered = [
            (k, v)
            for k, v in sorted(parameters.items())
            if k not in SCHEDULING_PARAM_KEYS
        ]
        return repr(filtered) if filtered else ""

    def signature(self, request: CoreRequest):
        if not self.ragged:
            return (
                tuple(
                    (t.name, t.datatype, tuple(t.shape[1:]))
                    for t in request.inputs
                ),
                self._signature_params(request.parameters),
            )
        sig = []
        for t in request.inputs:
            declared = self.declared_shapes.get(t.name)
            dims = tuple(t.shape[1:])
            if declared is not None and len(declared) == len(dims):
                # Drop ragged (-1) dims: they merge via padding. The rank
                # stays in the signature so a wrong-rank request can never
                # share (and poison) a well-formed batch.
                dims = tuple(
                    d for d, dd in zip(dims, declared) if dd != -1
                )
            sig.append((t.name, t.datatype, len(t.shape), dims))
        return (
            tuple(sig),
            self._signature_params(request.parameters),
        )

    def pad_ragged(self, name: str, arrays: List[np.ndarray]) -> List[np.ndarray]:
        """Zero-pad the -1-declared dims of `arrays` to a shared
        power-of-two bucket so they concatenate along axis 0."""
        from client_tpu.server.models import pad_batch_bucket

        declared = self.declared_shapes.get(name)
        rank = arrays[0].ndim
        if declared is None or len(declared) != rank - 1:
            return arrays
        cap = getattr(self.model, "ragged_dim_cap", None)
        targets = []
        for ax in range(1, rank):
            if declared[ax - 1] == -1:
                bucket = pad_batch_bucket(max(a.shape[ax] for a in arrays))
                if cap is not None:
                    # The bucket must not exceed the model's hard limit: a
                    # batch of individually-valid requests would otherwise
                    # be rejected wholesale (cap >= every member, so the
                    # clamped bucket still covers the batch).
                    bucket = min(bucket, cap)
                targets.append(bucket)
            else:
                targets.append(arrays[0].shape[ax])
        out = []
        pad_value = getattr(self.model, "ragged_pad_value", 0)
        for a in arrays:
            pads = [(0, 0)] + [
                (0, targets[ax - 1] - a.shape[ax]) for ax in range(1, rank)
            ]
            if any(p[1] for p in pads):
                a = np.pad(a, pads, constant_values=pad_value)
            out.append(a)
        return out

    def merge_inputs(self, requests: List[CoreRequest]) -> Dict[str, np.ndarray]:
        """Concatenate the batch's inputs along axis 0 (ragged dims padded)."""
        if len(requests) == 1:
            return {t.name: t.data for t in requests[0].inputs}
        merged: Dict[str, np.ndarray] = {}
        for pos, t in enumerate(requests[0].inputs):
            name = t.name
            arrays = []
            for r in requests:
                # Same-position fast path: clients nearly always order
                # inputs identically (the signature guarantees the same
                # input SET, not order).
                cand = r.inputs[pos]
                if cand.name != name:
                    cand = next(i for i in r.inputs if i.name == name)
                arrays.append(cand.data)
            if self.ragged:
                arrays = self.pad_ragged(name, arrays)
            merged[name] = np.concatenate(arrays, axis=0)
        return merged


class _ModelBatcher:
    """Serial dynamic batcher (the server-side analogue of Triton's
    ``dynamic_batching`` scheduler).

    While one batch executes on device, newly arriving requests queue; the
    next batch takes everything compatible that is pending, up to
    ``max_batch_size`` rows. The execution time itself is the accumulation
    window — no artificial delay — so a lone request sees no added latency
    while concurrent load amortizes the device round-trip.

    Requests are compatible when their input signature matches: same input
    names, datatypes, non-batch dims, and parameters. Incompatible requests
    wait for a batch of their own, preserving arrival order per signature.

    Models with ``allow_ragged_batch`` relax the shape part of the
    signature: dims declared -1 are excluded, and at merge time those dims
    are zero-padded to a shared power-of-two bucket (Triton's ragged
    batching, server-side) — so concurrent BERT/LLM requests of different
    sequence lengths share one device execution.

    Admission control (client_tpu.scheduling): the pending list is a
    bounded multi-level :class:`PriorityQueue` — ``submit()`` rejects
    with 429/RESOURCE_EXHAUSTED once ``max_queue_size`` requests wait,
    ``_take_batch`` consumes in (priority, arrival) order, and entries
    whose queue deadline passes fail with a deadline error before
    execution (or are demoted behind in-deadline work when the model's
    ``timeout_action`` is "continue").
    """

    def __init__(self, core: "ServerCore", model: Model):
        self.core = core
        self.model = model
        self.meta = core._batch_meta(model)
        self.policy = core._queue_policy(model)
        # queued entries: (request, future, signature, rows, arrival_ns)
        self.pending = PriorityQueue(levels=self.policy.levels)
        self.running = False

    def submit(self, request: CoreRequest) -> "asyncio.Future[CoreResponse]":
        """Validate + enqueue a request; returns a future for its response.

        Raises :class:`QueueFullError` (already booked on metrics/stats)
        when the queue is at ``max_queue_size``."""
        rows = self.meta.validate(request)
        policy = self.policy
        if (
            policy.max_queue_size
            and len(self.pending) >= policy.max_queue_size
        ):
            error = QueueFullError(self.model.name, policy.max_queue_size)
            self.core._book_rejection(
                self.model.name, request, error, record_fail=True
            )
            raise error
        arrival_ns = time.monotonic_ns()
        policy.stamp(request, arrival_ns)
        loop = asyncio.get_running_loop()
        future: asyncio.Future = loop.create_future()
        self.pending.push(
            (request, future, self.meta.signature(request), rows, arrival_ns),
            level=request.priority_level,
            deadline_ns=request.deadline_ns,
            timeout_action=policy.timeout_action,
        )
        self._publish_depths()
        if not self.running:
            self.running = True
            loop.create_task(self._drain())
        return future

    async def _drain(self) -> None:
        try:
            while len(self.pending):
                self._expire_pending()
                if not len(self.pending):
                    break
                batch = self._take_batch()
                resources = self.policy.rate_resources
                if resources:
                    await self.core.rate_limiter.acquire(
                        resources, self.policy.rate_priority
                    )
                    try:
                        # the grant wait may have outlived queue
                        # deadlines: reject-action entries still fail
                        # BEFORE execution, as the policy promises
                        batch = self._expire_taken(batch)
                        if batch:
                            await self._execute_batch(batch)
                    finally:
                        self.core.rate_limiter.release(resources)
                else:
                    await self._execute_batch(batch)
        finally:
            self.running = False
            if len(self.pending):  # raced with a submit after the check
                self.running = True
                asyncio.get_running_loop().create_task(self._drain())

    def _reject_expired(self, entry, now_ns: int) -> None:
        """Fail one (request, future, ...) entry with a deadline error."""
        request, future, _sig, _rows, arrival_ns = entry
        error = QueueTimeoutError(
            self.model.name, self.policy.timeout_us_of(request.parameters)
        )
        self.core._book_rejection(
            self.model.name,
            request,
            error,
            record_fail=True,
            latency_ns=now_ns - arrival_ns,
        )
        if not future.done():
            future.set_exception(error)

    def _expire_pending(self) -> None:
        """Fail queued entries whose deadline passed (reject action);
        "continue" entries were demoted inside the queue instead."""
        now_ns = time.monotonic_ns()
        expired = self.pending.expire(now_ns)
        for item in expired:
            self._reject_expired(item.value, now_ns)
        if expired:
            self._publish_depths()

    def _expire_taken(self, entries: List[Any]) -> List[Any]:
        """Deadline re-check for a batch already popped from the queue
        (the rate-limiter grant wait sits between take and execute);
        returns the still-live entries."""
        if self.policy.timeout_action != TIMEOUT_ACTION_REJECT:
            return entries
        now_ns = time.monotonic_ns()
        live = []
        for entry in entries:
            deadline_ns = entry[0].deadline_ns
            if deadline_ns is not None and now_ns > deadline_ns:
                self._reject_expired(entry, now_ns)
            else:
                live.append(entry)
        return live

    def _take_batch(self) -> List[Any]:
        """Pop the highest-priority oldest request plus every compatible
        queued request, bounded by max_batch_size rows (submit() already
        rejected any single request exceeding the max). The scan walks
        the queue in (priority, arrival) order and stops taking a
        signature at its first entry that does not fit the row budget, so
        arrival order within a (priority, signature) lane is preserved."""
        items = self.pending.scan()
        signature = items[0].value[2]
        budget = self.model.max_batch_size
        taken_items, taken, rows = [], [], 0
        signature_full = False
        for item in items:
            entry = item.value
            if (
                entry[2] == signature
                and not signature_full
                and rows + entry[3] <= budget
            ):
                taken_items.append(item)
                taken.append(entry)
                rows += entry[3]
            elif entry[2] == signature:
                signature_full = True
        self.pending.remove(taken_items)
        self._publish_depths()
        return taken

    def _publish_depths(self) -> None:
        self.core.metrics.set_queue_depth(
            self.model.name, self.pending.depths()
        )

    async def _execute_batch(self, entries: List[Any]) -> None:
        loop = asyncio.get_running_loop()
        model, core = self.model, self.core
        stats = core._stats_for(model.name)
        prof = core.profiling
        exec_start = time.monotonic_ns()
        requests = [e[0] for e in entries]
        n = len(entries)
        # one take() decision covers the whole batch's stage brackets
        measured = prof.take()
        try:
            if measured:
                # queue_wait is a wall phenomenon (no thread attached):
                # CPU books 0, the wall total is the batch's queued ns
                prof.account(
                    "queue_wait",
                    0,
                    wall_ns=sum(exec_start - e[4] for e in entries),
                    count=n,
                )
                a0 = prof.cpu_now()
                merged = self.meta.merge_inputs(requests)
                prof.account("batch_assembly", prof.cpu_now() - a0, count=n)
            else:
                merged = self.meta.merge_inputs(requests)

            def _run():
                # compute vs readback split on the executor thread (its
                # own thread-CPU clock — exactly the CPU this stage burnt)
                with model.placement():
                    if not measured:
                        return _to_host(
                            model.execute(merged, requests[0].parameters)
                        )
                    c0 = prof.cpu_now()
                    raw = model.execute(merged, requests[0].parameters)
                    c1 = prof.cpu_now()
                    host = _to_host(raw)
                    c2 = prof.cpu_now()
                    prof.account("compute", c1 - c0, count=n)
                    prof.account("readback", c2 - c1, count=n)
                    return host

            raw = await loop.run_in_executor(core._executor, _run)
            infer_end = time.monotonic_ns()
            core.add_busy_ns(model, infer_end - exec_start)
            core.metrics.observe_execution(
                model.name, sum(e[3] for e in entries)
            )
        except Exception as e:  # noqa: BLE001 - fail every request in batch
            # the only trace this previously left was N client error
            # responses — record the server-side evidence too
            core._log_request_error(
                "batch_execution_failed", model.name, e, path="batch"
            )
            now = time.monotonic_ns()
            for req, future, _sig, _rows, arrival in entries:
                stats.record("fail", now - arrival)
                core._record_exemplar(
                    model.name,
                    req,
                    path="batch",
                    status="error",
                    error=str(e),
                    arrival_ns=arrival,
                    exec_start_ns=exec_start,
                    end_ns=now,
                )
                if not future.done():
                    future.set_exception(e)
            return
        offset = 0
        # The ONE device execution is credited to the first request whose
        # packaging succeeds; if every request fails packaging it is still
        # counted (the execution happened regardless).
        execution_pending = 1
        for request, future, _sig, rows, arrival in entries:
            try:
                if len(entries) == 1:
                    sliced = raw
                else:
                    sliced = {k: v[offset : offset + rows] for k, v in raw.items()}
                response = core._package_profiled(model, request, sliced)
                out_end = time.monotonic_ns()
                stats.record_success(
                    rows,
                    queue_ns=exec_start - arrival,
                    in_ns=0,
                    infer_ns=infer_end - exec_start,
                    out_ns=out_end - infer_end,
                    executions=execution_pending,
                    trace_id=_trace_id_of(request),
                )
                _trace_stages(
                    request.trace, arrival, exec_start, infer_end, out_end
                )
                core._record_exemplar(
                    model.name,
                    request,
                    path="batch",
                    arrival_ns=arrival,
                    exec_start_ns=exec_start,
                    infer_end_ns=infer_end,
                    end_ns=out_end,
                    rows=rows,
                )
                execution_pending = 0
                if not future.done():
                    future.set_result(response)
            except Exception as e:  # noqa: BLE001 - per-request packaging error
                core._log_request_error(
                    "packaging_failed", model.name, e, path="batch"
                )
                now = time.monotonic_ns()
                stats.record("fail", now - arrival)
                core._record_exemplar(
                    model.name,
                    request,
                    path="batch",
                    status="error",
                    error=str(e),
                    arrival_ns=arrival,
                    exec_start_ns=exec_start,
                    infer_end_ns=infer_end,
                    end_ns=now,
                    rows=rows,
                )
                if not future.done():
                    future.set_exception(e)
            offset += rows
        if execution_pending:
            stats.record_execution()


class ServerCore:
    """The protocol-independent inference engine."""

    def __init__(
        self,
        repository: Optional[ModelRepository] = None,
        max_workers: int = 32,
        logger=None,
        flight_recorder=None,
    ):
        self.repository = repository or ModelRepository()
        self.shm = SharedMemoryManager()
        self.stats: Dict[str, _Stats] = {}
        self._stats_lock = threading.Lock()
        self._batchers: Dict[str, _ModelBatcher] = {}
        self._executor = ThreadPoolExecutor(
            max_workers=max_workers, thread_name_prefix="client-tpu-exec"
        )
        self.live = True
        # The trace extension, made real: sampling, per-model settings,
        # timestamped records (observability.TraceManager). The old inert
        # trace_settings dict survives as a read-only property below.
        from client_tpu.observability.server import TraceManager

        self.trace_manager = TraceManager()
        # Execution grants against named resource pools (ModelRateLimiter
        # semantics); models that declare rate_limiter resources acquire
        # them around every device execution.
        self.rate_limiter = RateLimiter()
        # Cumulative device-busy nanoseconds (device-placed executions
        # only) — the monotone counter scrapers derive duty cycle from.
        # Owned here, not by an HTTP handler, so every front-end and any
        # number of concurrent scrapers see one consistent time base.
        self._busy_lock = threading.Lock()
        self._device_busy_ns = 0
        # per-device split of the same counter: sharded models credit
        # every device of their mesh, plain models their default device —
        # the source of tpu_device_compute_ns_total{device} and the
        # per-chip duty/skew view
        self._device_busy: Dict[str, int] = {}
        self._default_device_label: Optional[str] = None
        from client_tpu.server.metrics import ServerMetrics

        self.metrics = ServerMetrics(self)
        # Fixed-layout shm rings over registered regions (server.shm_ring):
        # validated lazily per registration, cached per region object.
        from client_tpu.server.shm_ring import RingRegistry

        self.shm_rings = RingRegistry(self.shm, metrics=self.metrics)
        # Per-stage thread-CPU accounting (observability.profiling):
        # default-off; while disabled every stage event is one attribute
        # check. Enabled via POST /v2/debug/profiling (the perf
        # harness's --profile-server does this for the run's duration).
        from client_tpu.observability.profiling import StageCpuAccounting

        self.profiling = StageCpuAccounting(
            metrics_hook=self.metrics.observe_stage_cpu
        )
        # Graceful lifecycle: SERVING -> DRAINING -> STOPPED state plus
        # the in-flight census every execution path reports into, so a
        # drain can WAIT for work instead of cancelling it.
        self.lifecycle = DrainController()
        # The logging extension, made real (observability.logging): the
        # /v2/logging settings live inside the logger and gate what it
        # emits — toggling them changes server output with no restart.
        from client_tpu.observability.logging import StructuredLogger
        from client_tpu.observability.recorder import FlightRecorder

        self.logger = logger if logger is not None else StructuredLogger(
            name="server"
        )
        # Per-request exemplars of recent/failed/slowest requests
        # (GET /v2/debug/requests). On by default — recording is one clock
        # read, a dict build, a lock and a deque append a request.
        self.flight_recorder = (
            flight_recorder if flight_recorder is not None else FlightRecorder()
        )

    @property
    def trace_settings(self) -> Dict[str, Any]:
        """The effective global trace settings (compat view over the
        trace manager; update through ``trace_manager.update``)."""
        return self.trace_manager.settings()

    @property
    def log_settings(self) -> Dict[str, Any]:
        """The effective global log settings (compat view over the
        structured logger; update through :meth:`update_log_settings`)."""
        return self.logger.settings()

    def update_log_settings(
        self, updates: Dict[str, Any], model_name: str = ""
    ) -> Dict[str, Any]:
        """Validated /v2/logging update (per-model override when
        ``model_name`` is set); returns the effective settings."""
        return self.logger.update(updates, model_name)

    def _shutdown_model_hooks(self) -> None:
        """Stop model-owned background machinery (the LLM engine's step
        loop): invoked on the serving loop at the end of a drain, and
        again (idempotently) from close() for cores that never drain."""
        for entry in self.repository.index():
            model = self.repository.peek(entry["name"])
            shutdown = getattr(model, "shutdown", None)
            if shutdown is not None:
                try:
                    shutdown()
                except Exception:  # noqa: BLE001 - best-effort teardown
                    pass

    def close(self) -> None:
        self.lifecycle.mark_stopped()
        self._shutdown_model_hooks()
        self._executor.shutdown(wait=False, cancel_futures=True)
        self.trace_manager.close()
        self.logger.close()

    # -- graceful lifecycle --------------------------------------------------

    @property
    def ready(self) -> bool:
        """Readiness as load balancers should see it: live, accepting
        (not draining), and the repository's ready set non-degraded.
        Liveness (:attr:`live`) deliberately stays true through a drain."""
        return (
            self.live
            and self.lifecycle.accepting
            and not self.repository.degraded()
        )

    @property
    def recovering(self) -> bool:
        """True while any loaded model's engine reload is in flight
        (surfaced in ``debug_state()`` and overlaid on the
        ``tpu_server_state`` gauge; readiness is NOT dropped — the
        replica keeps serving its healthy models and answers the
        quarantined one with retryable 503s)."""
        for entry in self.repository.index():
            try:
                model = self.repository.peek(entry["name"])
            except Exception:  # noqa: BLE001 - introspection best-effort
                continue
            if getattr(model, "recovering", False):
                return True
        return False

    def _lifecycle_admit(self, model_name: str, trace=None) -> None:
        """Drain gate + in-flight tracking for one request; books the
        rejection counter and the trace event when draining."""
        try:
            self.lifecycle.admit(model_name)
        except ServerDrainingError:
            self.metrics.observe_drain_rejection(model_name)
            if trace is not None:
                trace.event("DRAIN_REJECTED")
            raise

    def reject_if_draining(self, model_name: str = "") -> None:
        """Front-end fast path: raise the drain rejection before paying
        request decode cost. Books exactly like an admission rejection
        (check() never touches the in-flight census)."""
        try:
            self.lifecycle.check()
        except ServerDrainingError:
            self.metrics.observe_drain_rejection(model_name)
            raise

    async def drain(self, timeout_s: Optional[float] = None) -> bool:
        """Graceful shutdown sequence (runs on the serving loop):
        stop admitting, wait for in-flight + queued work up to
        ``timeout_s``, then fail anything still queued with a clean
        503/UNAVAILABLE (never a cancelled future). Returns True when
        everything drained inside the deadline."""
        self.lifecycle.begin_drain()
        self.logger.info(
            "drain_started",
            timeout_s=timeout_s,
            inflight=self.lifecycle.inflight(),
        )
        drained = await self.lifecycle.wait_idle(timeout_s)
        if not drained:
            failed = self.fail_pending()
            self.logger.warning(
                "drain_deadline_expired", failed_pending=failed
            )
            # the failed futures' awaiters need a tick to observe before
            # the front-ends close under them (deliberately NOT folded
            # into the return value: the deadline DID expire)
            await self.lifecycle.wait_idle(min(1.0, timeout_s or 1.0))
        self.lifecycle.mark_stopped()
        # runs ON the serving loop: model background tasks (engine step
        # loops) cancel cleanly here, before the loop itself closes
        self._shutdown_model_hooks()
        self.logger.info("drain_completed", drained=drained)
        return drained

    def fail_pending(self, model_name: Optional[str] = None) -> int:
        """Fail every queued (not yet executing) batcher entry with a
        drain rejection — the past-deadline counterpart of waiting.
        Loop-thread only (the futures belong to the serving loop)."""
        failed = 0
        for name, batcher in list(self._batchers.items()):
            if model_name is not None and name != model_name:
                continue
            items = batcher.pending.scan()
            if not items:
                continue
            batcher.pending.remove(items)
            batcher._publish_depths()
            for item in items:
                _request, future, _sig, _rows, _arrival = item.value
                self.metrics.observe_drain_rejection(name)
                if not future.done():
                    future.set_exception(
                        ServerDrainingError(
                            self.lifecycle.state,
                            retry_after_s=self.lifecycle.retry_after_s,
                        )
                    )
                failed += 1
        return failed

    def load_model(
        self, name: str, config_override: Optional[str] = None
    ) -> None:
        """Repository load plus the telemetry bookkeeping every load
        path needs: the model's live-telemetry state is reset so the
        next record re-resolves the freshly-loaded slo declaration.
        Front-ends and the in-process backend all load through here."""
        self.repository.load(name, config_override=config_override)
        self.metrics.telemetry.reset(name)
        self.logger.info("model_loaded", model=name)

    def unload_model(self, name: str, drain_timeout_s: float = 5.0):
        """Repository unload with real per-model lifecycle: the model
        stops admitting immediately (503/UNAVAILABLE), queued and
        in-flight work drains in the background, then the batcher state
        is evicted and the index entry flips to UNAVAILABLE/"unloaded".

        Returns the finalization task when a loop is running (callers on
        the serving loop — both front-ends — never block on the drain),
        else finalizes synchronously.
        """
        old_model = self.repository.peek(name)
        epoch = self.repository.unload(name)
        # drop the model's live-telemetry state: the rolling windows
        # describe the outgoing instance, and a later load must
        # re-resolve the repository's (possibly changed) slo declaration
        self.metrics.telemetry.reset(name)
        self.logger.info(
            "model_unloading",
            model=name,
            inflight=self.lifecycle.inflight(name),
        )
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            loop = None
        if loop is None:
            self._evict_batcher(name, old_model)
            self.repository.finish_unload(name, epoch)
            # in-flight completions between the reset above and here
            # re-create telemetry state for the dead model; this final
            # reset is the one collect() prunes gauges against
            self.metrics.telemetry.reset(name)
            return None
        return loop.create_task(
            self._finalize_unload(name, old_model, epoch, drain_timeout_s)
        )

    async def _finalize_unload(
        self, name: str, old_model, epoch: int, drain_timeout_s: float
    ) -> None:
        drained = await self.lifecycle.wait_idle(
            drain_timeout_s, model_name=name
        )
        if self.repository.epoch_of(name) != epoch:
            # a load() superseded this unload mid-drain (the rolling
            # restart pattern): the census now counts the NEW model's
            # traffic — failing its queued work here would drop the very
            # requests the reload exists to keep serving
            return
        if not drained:
            # past the drain deadline: queued entries fail cleanly
            self.fail_pending(name)
        self._evict_batcher(name, old_model)
        self.repository.finish_unload(name, epoch)
        # requests that completed during the drain re-created telemetry
        # state for the outgoing model (observe_success -> record); this
        # final reset — epoch-guarded above, so a superseding load's
        # traffic is never dropped — leaves nothing for collect() to
        # keep exporting
        self.metrics.telemetry.reset(name)
        self.logger.info("model_unloaded", model=name, drained=drained)

    def _evict_batcher(self, name: str, model=None) -> None:
        """Drop a model's batcher state if it still belongs to the
        unloaded model object and holds no queued work (a reload may
        already have installed a new batcher — leave that one alone)."""
        batcher = self._batchers.get(name)
        if batcher is None:
            return
        if model is not None and batcher.model is not model:
            return
        if len(batcher.pending):
            return
        self._batchers.pop(name, None)

    def _stats_for(self, model_name: str) -> _Stats:
        with self._stats_lock:
            if model_name not in self.stats:
                self.stats[model_name] = _Stats(
                    metrics=self.metrics, model_name=model_name
                )
            return self.stats[model_name]

    # -- flight recorder / structured logging --------------------------------

    def _record_exemplar(
        self,
        model_name: str,
        request: CoreRequest,
        path: str,
        status: str = "ok",
        error: str = "",
        arrival_ns: int = 0,
        exec_start_ns: Optional[int] = None,
        infer_end_ns: Optional[int] = None,
        end_ns: Optional[int] = None,
        rows: int = 1,
        responses: Optional[int] = None,
    ) -> None:
        """Book one completed request into the flight recorder. Stage
        boundaries are the same monotonic reads the statistics extension
        books (queue = arrival->exec, compute = exec->infer_end, package
        = infer_end->end), so exemplars and aggregates always agree."""
        if end_ns is None:
            end_ns = time.monotonic_ns()
        exec_start = exec_start_ns if exec_start_ns is not None else end_ns
        infer_end = infer_end_ns if infer_end_ns is not None else exec_start
        trace = request.trace
        self.flight_recorder.record(
            model_name,
            request_id=request.id,
            trace_id=trace.trace_id if trace is not None else "",
            status=status,
            error=error,
            path=path,
            queue_us=(exec_start - arrival_ns) / 1e3 if arrival_ns else 0.0,
            compute_us=(infer_end - exec_start) / 1e3,
            package_us=(end_ns - infer_end) / 1e3,
            total_us=(
                (end_ns - arrival_ns) if arrival_ns else (end_ns - exec_start)
            )
            / 1e3,
            rows=rows,
            priority=request.priority_level,
            responses=responses,
        )

    def _log_request_error(
        self, event: str, model_name: str, exc: BaseException, path: str
    ) -> None:
        """Server-side record for an execution/packaging failure that is
        otherwise only converted into a client response. Rate-limited per
        (event, model): a model bug failing every request leaves a
        traceback trail without melting the log sink."""
        self.logger.error(
            event,
            model=model_name,
            exc=exc,
            rate_key=(event, model_name),
            path=path,
        )

    # -- device busy accounting (duty cycle) --------------------------------

    def add_busy_ns(self, model: Model, duration_ns: int) -> None:
        """Credit one device execution's nanoseconds to the busy counter.
        Host-placed models (device == "cpu") never count — they execute on
        the host and must not report the TPU as busy.

        The same duration also books per device: a sharded model's SPMD
        program runs on every device of its mesh in lockstep, so each
        mesh device is credited the execution's wall time; unsharded
        models credit their (single) default device. This is the one
        seam all four execution paths already pass through, so per-device
        accounting needs no per-path wiring."""
        if getattr(model, "device", "") == "cpu":
            return
        labels = self._device_labels_for(model)
        with self._busy_lock:
            self._device_busy_ns += duration_ns
            busy = self._device_busy
            for label in labels:
                busy[label] = busy.get(label, 0) + duration_ns

    def _device_labels_for(self, model: Model) -> tuple:
        """The metric labels of the devices this model executes on
        (cached on the model object; a reload rebuilds it)."""
        labels = getattr(model, "_ctpu_device_labels", None)
        if labels is None:
            plan = getattr(model, "mesh_plan", None)
            if plan is not None:
                labels = plan.device_labels
            else:
                labels = (self._default_device_label_value(),)
            model._ctpu_device_labels = labels
        return labels

    def _default_device_label_value(self) -> str:
        if self._default_device_label is None:
            try:
                import jax

                self._default_device_label = str(jax.devices()[0].id)
            except Exception:  # noqa: BLE001 - no backend available
                self._default_device_label = "0"
        return self._default_device_label

    @property
    def device_busy_ns_total(self) -> int:
        with self._busy_lock:
            return self._device_busy_ns

    def device_busy_by_device(self) -> Dict[str, int]:
        """Cumulative busy nanoseconds per device label (monotone; empty
        until the first device execution)."""
        with self._busy_lock:
            return dict(self._device_busy)

    def _batch_meta(self, model: Model) -> _BatchMeta:
        """Per-model batching caches, shared by both batching paths.
        Cached on the model object so a repository reload invalidates it."""
        meta = getattr(model, "_ctpu_batch_meta", None)
        if meta is None or meta.model is not model:
            meta = _BatchMeta(model)
            model._ctpu_batch_meta = meta
        return meta

    # -- scheduling / admission control --------------------------------------

    def _queue_policy(self, model: Model) -> QueuePolicy:
        """The model's resolved admission policy (cached on the model so
        a repository reload rebuilds it). First resolution registers the
        model's rate-limiter demands with the shared pool."""
        policy = getattr(model, "_ctpu_queue_policy", None)
        if policy is None or policy.model is not model:
            policy = QueuePolicy.from_model(model)
            model._ctpu_queue_policy = policy
            if policy.rate_resources:
                self.rate_limiter.register(policy.rate_resources)
        return policy

    def _admission_for(self, model: Model) -> AdmissionGate:
        """Waiting-room gate for the non-batcher execution paths."""
        gate = getattr(model, "_ctpu_admission_gate", None)
        if gate is None or gate.policy.model is not model:
            gate = AdmissionGate(self._queue_policy(model))
            model._ctpu_admission_gate = gate
        return gate

    def _book_rejection(
        self,
        model_name: str,
        request: CoreRequest,
        error: SchedulingError,
        record_fail: bool = False,
        latency_ns: int = 0,
    ) -> None:
        """Account one admission rejection everywhere it is observable:
        the dedicated reject counter (by reason), the trace record, and —
        when no other error path will — the statistics 'fail' field."""
        self.metrics.observe_rejection(model_name, error.reason)
        if request.trace is not None:
            request.trace.event("QUEUE_REJECTED")
        if record_fail:
            self._stats_for(model_name).record("fail", latency_ns)
        now_ns = time.monotonic_ns()
        self._record_exemplar(
            model_name,
            request,
            path="admission",
            status="rejected",
            error=error.message(),
            arrival_ns=now_ns - latency_ns,
            exec_start_ns=now_ns,
            end_ns=now_ns,
        )
        self.logger.verbose(
            "request_rejected",
            model=model_name,
            reason=error.reason,
            request_id=request.id,
        )

    def _admit_single(self, model: Model, request: CoreRequest):
        """Admission for the non-batcher paths: stamps the scheduling
        fields and claims a waiting-room slot. Returns the gate ticket
        (``started()`` releases the slot when execution begins), or None
        on the fast path — an unconfigured model and a request with no
        parameters have nothing to schedule, so the stamp and the gate
        lock are skipped entirely. Raises :class:`QueueFullError` —
        already booked — when the room is full."""
        policy = self._queue_policy(model)
        if not policy.enabled and not request.parameters:
            return None
        policy.stamp(request, time.monotonic_ns())
        gate = self._admission_for(model)
        try:
            return gate.enter(model.name)
        except SchedulingError as e:
            self._book_rejection(model.name, request, e, record_fail=True)
            raise

    def _check_deadline(self, model: Model, request: CoreRequest) -> None:
        """Fail a request whose queue deadline passed before execution
        (reject action only; "continue" executes late)."""
        if (
            request.deadline_ns is not None
            and time.monotonic_ns() > request.deadline_ns
        ):
            policy = self._queue_policy(model)
            if policy.timeout_action == TIMEOUT_ACTION_REJECT:
                error = QueueTimeoutError(
                    model.name, policy.timeout_us_of(request.parameters)
                )
                # Fully booked here; generic error paths skip stats
                # accounting for SchedulingError to avoid double counts.
                self._book_rejection(
                    model.name, request, error, record_fail=True
                )
                raise error

    def _run_single(self, model: Model, request: CoreRequest, ticket=None):
        """Executor-side entry for the single path: leave the waiting
        room, enforce the queue deadline, then run the model. NEVER
        blocks on the rate limiter — a parked executor thread could
        starve the very execution whose release it waits for; limiter
        waits happen on the event loop (async path) or the caller's own
        pump thread (direct path) instead."""
        if ticket is not None:
            ticket.started()
        self._check_deadline(model, request)
        return self._run_model(model, request)

    # -- statistics API ------------------------------------------------------

    def statistics(self, model_name: str = "", model_version: str = ""):
        models = (
            [model_name]
            if model_name
            else [m["name"] for m in self.repository.index()]
        )
        result = []
        for name in models:
            try:
                model = self.repository.get(name)
            except InferenceServerException:
                if model_name:
                    raise
                continue
            snap = self._stats_for(name).snapshot()
            snap.update({"name": name, "version": model.version})
            result.append(snap)
        return {"model_stats": result}

    # -- device / mesh topology ----------------------------------------------

    def device_topology(self) -> Dict[str, Any]:
        """The ``devices`` block server metadata and ``debug_state()``
        serve: host platform + device inventory, and for every loaded
        model that resolved a mesh, which devices it occupies and how
        its tensors shard (plus the executor's cumulative
        device_put/compute/gather accounting when the model exposes
        one)."""
        try:
            import jax

            devices = jax.devices()
            from client_tpu.pod.runtime import pod_info

            # under jax.distributed the device list is GLOBAL — stamp
            # which process this report comes from so a pod member's
            # topology is distinguishable from a single-process replica
            # (and per-device, which member owns it)
            info: Dict[str, Any] = {
                "platform": devices[0].platform if devices else "unknown",
                "device_count": len(devices),
                **pod_info(),
                "devices": [
                    {
                        "id": d.id,
                        "kind": getattr(d, "device_kind", "") or d.platform,
                        "process": getattr(d, "process_index", 0),
                    }
                    for d in devices
                ],
            }
        except Exception as e:  # noqa: BLE001 - no backend available
            info = {
                "platform": "unavailable",
                "device_count": 0,
                "devices": [],
                "error": str(e),
            }
        models: Dict[str, Any] = {}
        for entry in self.repository.index():
            model = self.repository.peek(entry["name"])
            if model is None:
                continue
            plan = getattr(model, "mesh_plan", None)
            if plan is not None:
                doc = plan.describe()
                executor = getattr(model, "_executor", None)
                snapshot = getattr(executor, "snapshot", None)
                if snapshot is not None:
                    doc["executor"] = snapshot()
                models[entry["name"]] = doc
            elif isinstance(getattr(model, "mesh", None), dict):
                # declared but unresolved (e.g. load failed: mesh
                # requires N devices) — show what was asked for
                models[entry["name"]] = {
                    "axes": dict(model.mesh.get("axes", {})),
                    "resolved": False,
                    "reason": entry.get("reason", ""),
                }
        info["models"] = models
        return info

    # -- live-state introspection (GET /v2/debug/state) ----------------------

    def debug_state(self) -> Dict[str, Any]:
        """One snapshot of the server's live internals: what an operator
        asks a misbehaving replica before anything else. Each subsystem
        is captured under its own lock (a single consistent view per
        subsystem; cross-subsystem counts may be one request apart —
        taking one global lock across the hot path would cost more than
        the skew is worth)."""
        from client_tpu.observability.profiling import PROCESS

        queues: Dict[str, Any] = {}
        for name, batcher in list(self._batchers.items()):
            queues[name] = {
                "depths": {
                    str(level): depth
                    for level, depth in batcher.pending.depths().items()
                },
                "max_queue_size": batcher.policy.max_queue_size,
            }
        # LLM engines: live continuous-batching/speculation counters per
        # engine-backed model (kv blocks, tokens-per-step, acceptance
        # rate) — the same document engine.stats() returns, so the debug
        # surface and the tests read one source of truth
        llm: Dict[str, Any] = {}
        for entry in self.repository.index():
            try:
                model = self.repository.peek(entry["name"])
            except Exception:  # noqa: BLE001 - introspection best-effort
                continue
            engine = getattr(model, "engine", None)
            stats = getattr(engine, "stats", None)
            if callable(stats):
                try:
                    doc = stats()
                    stall_log = getattr(engine, "stall_log", None)
                    if callable(stall_log):
                        doc["stall_log"] = stall_log()
                    controller = getattr(model, "_recovery", None)
                    if controller is not None:
                        doc["recovery"] = controller.describe()
                    llm[entry["name"]] = doc
                except Exception:  # noqa: BLE001 - a broken engine must
                    continue  # not take down the debug surface
        return {
            "server": {
                "name": SERVER_NAME,
                "version": SERVER_VERSION,
                "live": self.live,
                "ready": self.ready,
                "recovering": self.recovering,
            },
            "llm": llm,
            "lifecycle": self.lifecycle.snapshot(),
            # device inventory + per-model mesh occupancy (which devices
            # a loaded sharded model runs on, and its executor's
            # cumulative device_put/compute/gather split)
            "devices": self.device_topology(),
            "queues": queues,
            "rate_limiter": self.rate_limiter.snapshot(),
            "models": self.repository.index(),
            "log_settings": self.logger.settings(),
            "log_model_overrides": self.logger.model_overrides(),
            "trace": {
                "settings": self.trace_manager.settings(),
                "started": self.trace_manager.started_count,
                "completed": self.trace_manager.completed_count,
            },
            "profiling": self.profiling.config(),
            # the last jax.profiler sessions: when each start and stop
            # began and ended, on the clock of the laps and the stall log
            "profiler_sessions": list(PROCESS.sessions),
            "flight_recorder": self.flight_recorder.stats(),
            # compact live-telemetry block: shortest-window rolling p99 +
            # SLO burn per model (the full document is GET /v2/debug/slo)
            "slo": self.metrics.telemetry.summary(),
        }

    def debug_slo(self) -> Dict[str, Any]:
        """The ``GET /v2/debug/slo`` document: every tracked model's
        rolling latency windows (30s/5m p50/p95/p99 over the same bucket
        grid as ``/metrics``) plus error-budget status for models that
        declare an ``slo`` config."""
        return self.metrics.telemetry.snapshot()

    # -- inference -----------------------------------------------------------

    @staticmethod
    def _declared_ranks(model: Model) -> Dict[str, int]:
        """name -> declared rank, cached on the model (hot path)."""
        ranks = getattr(model, "_ctpu_declared_ranks", None)
        if ranks is None:
            ranks = {i["name"]: len(i["shape"]) for i in model.inputs}
            model._ctpu_declared_ranks = ranks
        return ranks

    @staticmethod
    def _has_batch_dim(model: Model, request: CoreRequest) -> bool:
        """True when the request's input shapes include the batch dim.

        Clients may send a batchable model its unbatched form (e.g. an
        [H, W, 3] image to a [-1, H, W, 3] model); those requests bypass
        the dynamic batcher — concatenating along axis 0 would corrupt
        them — and execute singly, as before batching existed. Only a
        request where EVERY declared input matches its unbatched rank
        counts; mixed-rank requests stay on the batcher path so its
        batch-dim validation rejects them. A model that declares no input
        metadata (or a request whose inputs match none of the declared
        names) gives nothing to compare ranks against — those requests
        execute singly rather than risking a concatenation along a dim 0
        that may not be a batch dim. For the same reason no max_batch_size
        check applies to them (dim 0 cannot be assumed to be a batch count),
        and they book inference_count 1 per request.
        """
        declared = ServerCore._declared_ranks(model)
        matches = [
            len(t.shape) == declared[t.name]
            for t in request.inputs
            if t.name in declared
        ]
        if not matches:
            return False
        return not all(matches)

    def _resolve_batch(self, model: Model, request: CoreRequest) -> int:
        if not request.inputs:
            return 1
        shape = request.inputs[0].shape
        if (
            model.max_batch_size > 0
            and shape
            and self._has_batch_dim(model, request)
        ):
            return int(shape[0])
        return 1

    def _run_model(
        self, model: Model, request: CoreRequest
    ) -> Dict[str, np.ndarray]:
        inputs = {t.name: t.data for t in request.inputs}
        declared = {i["name"] for i in model.inputs}
        for t in request.inputs:
            if declared and t.name not in declared:
                raise InferenceServerException(
                    f"unexpected inference input '{t.name}' for model "
                    f"'{model.name}'"
                )
        prof = self.profiling
        with model.placement():
            if not prof.take():
                return _to_host(model.execute(inputs, request.parameters))
            c0 = prof.cpu_now()
            raw = model.execute(inputs, request.parameters)
            c1 = prof.cpu_now()
            host = _to_host(raw)
            c2 = prof.cpu_now()  # before accounting, like the batch paths
            prof.account("compute", c1 - c0)
            prof.account("readback", c2 - c1)
            return host

    def _package_profiled(
        self, model: Model, request: CoreRequest, raw: Dict[str, np.ndarray]
    ) -> CoreResponse:
        """_package_outputs with its thread-CPU booked under "package" —
        deliberately distinct from the front-ends' "encode" (wire
        serialization): packaging is paid by the in-process path too, so
        folding them together would overstate the wire-only CPU."""
        prof = self.profiling
        if not prof.take():
            return self._package_outputs(model, request, raw)
        c0 = prof.cpu_now()
        try:
            return self._package_outputs(model, request, raw)
        finally:
            prof.account("package", prof.cpu_now() - c0)

    def _package_outputs(
        self, model: Model, request: CoreRequest, raw: Dict[str, np.ndarray]
    ) -> CoreResponse:
        requested = request.outputs
        if not requested:
            # Hot path: the default "all declared outputs" list is
            # per-model-constant; cache it on the model object.
            requested = getattr(model, "_ctpu_default_outputs", None)
            if requested is None:
                requested = [
                    CoreRequestedOutput(name=o["name"])
                    for o in model.outputs
                ]
                model._ctpu_default_outputs = requested
        out_tensors: List[CoreTensor] = []
        shm_outputs: Dict[str, Any] = {}
        for req_out in requested:
            if req_out.name not in raw:
                raise InferenceServerException(
                    f"unexpected inference output '{req_out.name}' for model "
                    f"'{model.name}'"
                )
            arr = raw[req_out.name]
            if type(arr) is not np.ndarray:
                arr = np.asarray(arr)
            if req_out.classification > 0:
                arr = self._classify(model, req_out, arr)
            datatype = np_to_triton_dtype(arr.dtype)
            tensor = CoreTensor(
                name=req_out.name,
                datatype=datatype,
                shape=list(arr.shape),
                data=arr,
            )
            if req_out.shm_region is not None:
                if datatype == "BYTES":
                    payload = serialize_byte_tensor(arr).tobytes()
                else:
                    payload = np.ascontiguousarray(arr).tobytes()
                if len(payload) > req_out.shm_byte_size:
                    raise InferenceServerException(
                        f"shared memory region for output '{req_out.name}' is "
                        f"too small: need {len(payload)} bytes, have "
                        f"{req_out.shm_byte_size}"
                    )
                self.shm.write(req_out.shm_region, req_out.shm_offset, payload)
                shm_outputs[req_out.name] = (
                    req_out.shm_region,
                    len(payload),
                    req_out.shm_offset,
                )
            out_tensors.append(tensor)
        return CoreResponse(
            model_name=model.name,
            model_version=model.version,
            id=request.id,
            outputs=out_tensors,
            shm_outputs=shm_outputs,
        )

    def _classify(
        self, model: Model, req_out: CoreRequestedOutput, arr: np.ndarray
    ) -> np.ndarray:
        """Convert a score tensor to Triton classification strings
        ``"value:index[:label]"`` over the last axis."""
        k = min(req_out.classification, arr.shape[-1])
        labels = model.labels(req_out.name)
        flat = arr.reshape(-1, arr.shape[-1])
        rows = []
        for row in flat:
            top = np.argsort(row)[::-1][:k]
            entries = []
            for idx in top:
                s = f"{row[idx]:f}:{idx}"
                if labels and idx < len(labels):
                    s += f":{labels[idx]}"
                entries.append(s.encode("utf-8"))
            rows.append(entries)
        out = np.array(rows, dtype=np.object_)
        return out.reshape(list(arr.shape[:-1]) + [k])

    def infer_nowait(self, request: CoreRequest) -> "asyncio.Future":
        """Submit a request->response inference; returns its future.

        The allocation-free twin of :meth:`infer` for callback-style
        front-ends (the native gRPC bridge): batchable requests go straight
        to the batcher's future — no coroutine, no task. Other requests
        fall back to a task wrapping the slow path. Raises synchronously on
        validation errors.
        """
        self._lifecycle_admit(request.model_name, request.trace)
        try:
            model = self.repository.get(
                request.model_name, request.model_version
            )
            if model.decoupled:
                raise InferenceServerException(
                    f"model '{model.name}' is decoupled; use streaming "
                    "inference"
                )
            if model.max_batch_size > 1 and self._has_batch_dim(model, request):
                future = self._submit_batched(model, request)
            else:
                ticket = self._admit_single(model, request)
                future = asyncio.ensure_future(
                    self._infer_single(model, request, ticket)
                )
        except BaseException:
            self.lifecycle.finish(request.model_name)
            raise
        self.metrics.pending_inc(model.name)

        def _settled(_f, name=model.name, census=request.model_name):
            self.metrics.pending_dec(name)
            self.lifecycle.finish(census)

        future.add_done_callback(_settled)
        return future

    def _submit_batched(
        self, model: Model, request: CoreRequest
    ) -> "asyncio.Future[CoreResponse]":
        """Route a batchable request to its model's dynamic batcher."""
        batcher = self._batchers.get(model.name)
        if batcher is None or batcher.model is not model:
            batcher = _ModelBatcher(self, model)
            self._batchers[model.name] = batcher
        try:
            return batcher.submit(request)
        except SchedulingError:
            # Admission rejections are fully booked inside submit()
            # (reject counter + stats fail + trace event).
            raise
        except InferenceServerException:
            # Validation failures surface synchronously; execution
            # failures are accounted inside the batcher already.
            self._stats_for(model.name).record("fail", 0)
            raise

    def infer_direct(self, requests: List[CoreRequest]) -> List[Any]:
        """Synchronously execute a batch of unary requests on the CALLING
        thread — no event loop, no futures, no executor hop.

        This is the native gRPC front-end's hot path: its pump thread
        drains parsed requests from C++ and runs them here, so the
        per-request asyncio machinery (future + task + done-callback +
        thread-pool hop) disappears entirely. Dynamic batching still
        applies — compatible requests in ``requests`` merge into one
        device execution exactly as the event-loop batcher would merge
        them, and the C++ ready-queue that accumulates while a batch
        executes is the batching window.

        Returns a list aligned with ``requests``: CoreResponse on
        success, Exception on failure (never raises per-request errors).
        """
        results: List[Any] = [None] * len(requests)
        arrival_ns = time.monotonic_ns()
        # key -> (model, meta, [(index, rows), ...]); ordered by first
        # arrival so same-signature requests execute in request order.
        groups: Dict[Any, Any] = {}
        # repository.get takes the repo lock; under load nearly every
        # request in a batch targets the same model, so resolve once.
        model_cache: Dict[Any, Model] = {}
        # every request admitted into the lifecycle census; this whole
        # call is synchronous, so they all finish before it returns
        admitted: List[str] = []
        for idx, request in enumerate(requests):
            model = None
            grouped = False
            try:
                self._lifecycle_admit(request.model_name, request.trace)
                admitted.append(request.model_name)
                model_key = (request.model_name, request.model_version)
                model = model_cache.get(model_key)
                if model is None:
                    model = self.repository.get(
                        request.model_name, request.model_version
                    )
                    model_cache[model_key] = model
                self.metrics.pending_inc(model.name)
                if model.decoupled:
                    raise InferenceServerException(
                        f"model '{model.name}' is decoupled; use streaming "
                        "inference"
                    )
                if model.max_batch_size > 1 and self._has_batch_dim(
                    model, request
                ):
                    meta = self._batch_meta(model)
                    rows = meta.validate(request)
                    ticket = self._admit_single(model, request)
                    key = (model.name, meta.signature(request))
                    group = groups.get(key)
                    if group is None:
                        groups[key] = (model, meta, [(idx, rows, ticket)])
                    else:
                        group[2].append((idx, rows, ticket))
                    # grouped requests stay pending until their chunk
                    # executes (_execute_direct_chunk decrements)
                    grouped = True
                else:
                    ticket = self._admit_single(model, request)
                    results[idx] = self._infer_single_sync(
                        model, request, ticket
                    )
            except Exception as e:  # noqa: BLE001 - aligned error result
                # Only account stats for models that exist: booking by a
                # client-supplied unknown name would grow self.stats
                # without bound under hostile clients. Admission
                # rejections were fully booked at the rejection site.
                if model is not None and not isinstance(e, SchedulingError):
                    now = time.monotonic_ns()
                    self._stats_for(model.name).record(
                        "fail", now - arrival_ns
                    )
                    self._log_request_error(
                        "request_failed", model.name, e, path="direct"
                    )
                    self._record_exemplar(
                        model.name,
                        request,
                        path="direct",
                        status="error",
                        error=str(e),
                        arrival_ns=arrival_ns,
                        end_ns=now,
                    )
                results[idx] = e
            finally:
                if model is not None and not grouped:
                    self.metrics.pending_dec(model.name)
        try:
            for model, meta, entries in groups.values():
                budget = model.max_batch_size
                chunk: List[Any] = []
                chunk_rows = 0
                for entry in entries:
                    if chunk and chunk_rows + entry[1] > budget:
                        self._execute_direct_chunk(
                            model, meta, chunk, requests, results, arrival_ns
                        )
                        chunk, chunk_rows = [], 0
                    chunk.append(entry)
                    chunk_rows += entry[1]
                if chunk:
                    self._execute_direct_chunk(
                        model, meta, chunk, requests, results, arrival_ns
                    )
        finally:
            for name in admitted:
                self.lifecycle.finish(name)
        return results

    def _execute_direct_chunk(
        self,
        model: Model,
        meta: _BatchMeta,
        chunk: List[Any],
        requests: List[CoreRequest],
        results: List[Any],
        arrival_ns: int,
    ) -> None:
        """One merged device execution for the direct path (the synchronous
        twin of _ModelBatcher._execute_batch). Chunk entries are
        ``(index, rows, admission_ticket)``; entries whose queue deadline
        passed while the chunk formed fail with a deadline error before
        the merge."""
        stats = self._stats_for(model.name)
        policy = self._queue_policy(model)
        check_ns = time.monotonic_ns()
        live = []
        for idx, rows, ticket in chunk:
            if ticket is not None:
                ticket.started()
            request = requests[idx]
            if (
                request.deadline_ns is not None
                and check_ns > request.deadline_ns
                and policy.timeout_action == TIMEOUT_ACTION_REJECT
            ):
                error = QueueTimeoutError(
                    model.name, policy.timeout_us_of(request.parameters)
                )
                self._book_rejection(
                    model.name,
                    request,
                    error,
                    record_fail=True,
                    latency_ns=check_ns - arrival_ns,
                )
                results[idx] = error
                self.metrics.pending_dec(model.name)
            else:
                live.append((idx, rows))
        chunk = live
        if not chunk:
            return
        resources = policy.rate_resources
        if resources:
            self.rate_limiter.acquire_blocking(
                resources, policy.rate_priority
            )
        exec_start = time.monotonic_ns()
        reqs = [requests[idx] for idx, _rows in chunk]
        prof = self.profiling
        n = len(chunk)
        try:
            try:
                if prof.take():
                    prof.account(
                        "queue_wait",
                        0,
                        wall_ns=(exec_start - arrival_ns) * n,
                        count=n,
                    )
                    a0 = prof.cpu_now()
                    merged = meta.merge_inputs(reqs)
                    a1 = prof.cpu_now()
                    with model.placement():
                        raw = model.execute(merged, reqs[0].parameters)
                        a2 = prof.cpu_now()
                        raw = _to_host(raw)
                    a3 = prof.cpu_now()
                    prof.account("batch_assembly", a1 - a0, count=n)
                    prof.account("compute", a2 - a1, count=n)
                    prof.account("readback", a3 - a2, count=n)
                else:
                    merged = meta.merge_inputs(reqs)
                    with model.placement():
                        raw = _to_host(
                            model.execute(merged, reqs[0].parameters)
                        )
            finally:
                if resources:
                    self.rate_limiter.release(resources)
            infer_end = time.monotonic_ns()
            self.add_busy_ns(model, infer_end - exec_start)
            self.metrics.observe_execution(
                model.name, sum(rows for _idx, rows in chunk)
            )
        except Exception as e:  # noqa: BLE001 - fail every request in chunk
            self._log_request_error(
                "batch_execution_failed", model.name, e, path="direct"
            )
            now = time.monotonic_ns()
            for idx, _rows in chunk:
                stats.record("fail", now - arrival_ns)
                self._record_exemplar(
                    model.name,
                    requests[idx],
                    path="direct",
                    status="error",
                    error=str(e),
                    arrival_ns=arrival_ns,
                    exec_start_ns=exec_start,
                    end_ns=now,
                )
                results[idx] = e
            self.metrics.pending_dec(model.name, len(chunk))
            return
        offset = 0
        ok_requests = 0
        ok_rows = 0
        for (idx, rows), request in zip(chunk, reqs):
            try:
                if len(chunk) == 1:
                    sliced = raw
                else:
                    sliced = {
                        k: v[offset : offset + rows] for k, v in raw.items()
                    }
                results[idx] = self._package_profiled(model, request, sliced)
                request_end = time.monotonic_ns()
                _trace_stages(
                    request.trace,
                    arrival_ns,
                    exec_start,
                    infer_end,
                    request_end,
                )
                self._record_exemplar(
                    model.name,
                    request,
                    path="direct",
                    arrival_ns=arrival_ns,
                    exec_start_ns=exec_start,
                    infer_end_ns=infer_end,
                    end_ns=request_end,
                    rows=rows,
                )
                ok_requests += 1
                ok_rows += rows
            except Exception as e:  # noqa: BLE001 - per-request packaging
                self._log_request_error(
                    "packaging_failed", model.name, e, path="direct"
                )
                now = time.monotonic_ns()
                stats.record("fail", now - arrival_ns)
                self._record_exemplar(
                    model.name,
                    request,
                    path="direct",
                    status="error",
                    error=str(e),
                    arrival_ns=arrival_ns,
                    exec_start_ns=exec_start,
                    infer_end_ns=infer_end,
                    end_ns=now,
                    rows=rows,
                )
                results[idx] = e
            offset += rows
        out_end = time.monotonic_ns()
        self.metrics.pending_dec(model.name, len(chunk))
        if ok_requests:
            # One lock + one booking for the whole chunk; packaging time
            # is split evenly across its requests. The ONE device
            # execution is credited once (Triton execution_count
            # semantics).
            stats.record_success_batch(
                ok_requests,
                ok_rows,
                queue_ns_total=(exec_start - arrival_ns) * ok_requests,
                infer_ns_total=(infer_end - exec_start) * ok_requests,
                out_ns_total=out_end - infer_end,
                executions=1,
            )
        else:
            stats.record_execution()

    def _infer_single_sync(
        self, model: Model, request: CoreRequest, ticket=None
    ) -> CoreResponse:
        """Unbatched synchronous execution (the direct-path twin of
        _infer_single); raises on failure, caller accounts the 'fail'
        (admission rejections book themselves). Runs on the native
        front-end's pump thread — its own thread, not the shared
        executor — so a blocking limiter wait here cannot starve the
        execution that would release the grant."""
        stats = self._stats_for(model.name)
        policy = self._queue_policy(model)
        if policy.rate_resources:
            # before t0: the grant wait must not book as device-busy time
            self.rate_limiter.acquire_blocking(
                policy.rate_resources, policy.rate_priority
            )
            try:
                t0 = time.monotonic_ns()
                raw = self._run_single(model, request, ticket)
            finally:
                self.rate_limiter.release(policy.rate_resources)
        else:
            t0 = time.monotonic_ns()
            raw = self._run_single(model, request, ticket)
        t1 = time.monotonic_ns()
        self.add_busy_ns(model, t1 - t0)
        response = self._package_profiled(model, request, raw)
        t2 = time.monotonic_ns()
        rows = self._resolve_batch(model, request)
        self.metrics.observe_execution(model.name, rows)
        stats.record_success(
            rows,
            queue_ns=0,
            in_ns=0,
            infer_ns=t1 - t0,
            out_ns=t2 - t1,
            trace_id=_trace_id_of(request),
        )
        _trace_stages(request.trace, t0, t0, t1, t2)
        self._record_exemplar(
            model.name,
            request,
            path="single",
            arrival_ns=t0,
            exec_start_ns=t0,
            infer_end_ns=t1,
            end_ns=t2,
            rows=rows,
        )
        return response

    async def infer(self, request: CoreRequest) -> CoreResponse:
        """Execute a request->response inference (decoupled models rejected)."""
        self._lifecycle_admit(request.model_name, request.trace)
        try:
            model = self.repository.get(
                request.model_name, request.model_version
            )
            if model.decoupled:
                raise InferenceServerException(
                    f"model '{model.name}' is decoupled; use streaming "
                    "inference"
                )
            self.metrics.pending_inc(model.name)
            try:
                if model.max_batch_size > 1 and self._has_batch_dim(
                    model, request
                ):
                    return await self._submit_batched(model, request)
                # Awaited single path: run the coroutine inline — no Task.
                ticket = self._admit_single(model, request)
                return await self._infer_single(model, request, ticket)
            finally:
                self.metrics.pending_dec(model.name)
        finally:
            # the census covers queued batcher time too: the future above
            # resolves only when the request left the queue and executed
            self.lifecycle.finish(request.model_name)

    async def _infer_single(
        self, model: Model, request: CoreRequest, ticket=None
    ) -> CoreResponse:
        """Unbatched execution path (max_batch_size <= 1 or no batch dim).

        ``ticket`` is the admission-gate slot claimed by the caller; the
        executor closure releases it when execution begins (and the
        finally below is the safety net for requests cancelled before
        their executor slot ran)."""
        stats = self._stats_for(model.name)
        policy = self._queue_policy(model)
        t0 = time.monotonic_ns()
        loop = asyncio.get_running_loop()
        rate_resources = None
        try:
            if policy.rate_resources:
                # waited on the LOOP, never on an executor thread (a
                # parked worker could starve the releasing execution)
                await self.rate_limiter.acquire(
                    policy.rate_resources, policy.rate_priority
                )
                rate_resources = policy.rate_resources
            t1 = time.monotonic_ns()
            raw = await loop.run_in_executor(
                self._executor, self._run_single, model, request, ticket
            )
            t2 = time.monotonic_ns()
            response = self._package_profiled(model, request, raw)
            t3 = time.monotonic_ns()
        except Exception as e:
            # admission rejections (queue timeout) were booked already
            if not isinstance(e, SchedulingError):
                now = time.monotonic_ns()
                stats.record("fail", now - t0)
                self._log_request_error(
                    "request_failed", model.name, e, path="single"
                )
                self._record_exemplar(
                    model.name,
                    request,
                    path="single",
                    status="error",
                    error=str(e),
                    arrival_ns=t0,
                    end_ns=now,
                )
            raise
        finally:
            if rate_resources is not None:
                self.rate_limiter.release(rate_resources)
            if ticket is not None:
                ticket.close()
        self.add_busy_ns(model, t2 - t1)
        rows = self._resolve_batch(model, request)
        self.metrics.observe_execution(model.name, rows)
        stats.record_success(
            rows,
            queue_ns=t1 - t0,
            in_ns=0,
            infer_ns=t2 - t1,
            out_ns=t3 - t2,
            trace_id=_trace_id_of(request),
        )
        if self.profiling.take():
            self.profiling.account("queue_wait", 0, wall_ns=t1 - t0)
        _trace_stages(request.trace, t0, t1, t2, t3)
        self._record_exemplar(
            model.name,
            request,
            path="single",
            arrival_ns=t0,
            exec_start_ns=t1,
            infer_end_ns=t2,
            end_ns=t3,
            rows=rows,
        )
        return response

    async def infer_decoupled(
        self, request: CoreRequest
    ) -> AsyncIterator[CoreResponse]:
        """Execute a streaming inference; yields 0..N responses.

        Non-decoupled models yield exactly one response, so the streaming
        front-end can serve both kinds (Triton semantics).
        """
        model = self.repository.get(request.model_name, request.model_version)
        # Engine-backed models (client_tpu.llm) hook into the server they
        # serve under — metrics registry, executor, structured logger —
        # on first use; one getattr per stream start, idempotent per core.
        bind = getattr(model, "bind_core", None)
        if bind is not None:
            bind(self)
        stats = self._stats_for(model.name)
        ticket = None
        rate_resources = None
        if model.decoupled:
            # Drain gate + census first (non-decoupled delegates to
            # infer(), which runs its own), then admission: the
            # waiting-room bound sheds streams that would only pile up
            # behind a saturated device (raises a booked QueueFullError).
            self._lifecycle_admit(request.model_name, request.trace)
            try:
                ticket = self._admit_single(model, request)
            except BaseException:
                self.lifecycle.finish(request.model_name)
                raise
        t0 = time.monotonic_ns()
        # Split the stream's lifetime into model-compute vs output-packaging
        # time, and record time-to-first-response — the reference's stats
        # treat a stream as one opaque request (its own known blind spot,
        # grpc_client.cc:1650-1653); don't inherit that.
        packaging_ns = 0
        # Device-busy attribution for the stream: only time spent awaiting
        # the model's next item counts (model_wait_ns). The stream's wall
        # time also contains suspension at `yield` while the front-end
        # writes to the consumer — booking that would read a slow client
        # as a busy TPU (duty cycle ~1.0 on an idle device).
        model_wait_ns = 0
        prev_ns = t0
        index = 0
        final_delivered = False

        def _book_success() -> None:
            t1 = time.monotonic_ns()
            self.add_busy_ns(model, model_wait_ns)
            stats.record_success(
                self._resolve_batch(model, request),
                queue_ns=0,
                in_ns=0,
                infer_ns=(t1 - t0) - packaging_ns,
                out_ns=packaging_ns,
                trace_id=_trace_id_of(request),
            )
            _trace_stages(request.trace, t0, t0, t1, t1)
            self._record_exemplar(
                model.name,
                request,
                path="decoupled",
                arrival_ns=t0,
                exec_start_ns=t0,
                infer_end_ns=t1 - packaging_ns,
                end_ns=t1,
                responses=index,
            )

        if model.decoupled:
            # non-decoupled requests delegate to infer(), which tracks its
            # own pending gauge — tracking both would double-count
            self.metrics.pending_inc(model.name)
        try:
            if not model.decoupled:
                yield await self.infer(request)
                return
            policy = self._queue_policy(model)
            if policy.rate_resources:
                # the stream holds its resource grant for its lifetime
                await self.rate_limiter.acquire(
                    policy.rate_resources, policy.rate_priority
                )
                rate_resources = policy.rate_resources
            # Leave the waiting room and re-check the queue deadline only
            # AFTER the grant wait (mirroring _run_single's ordering):
            # streams parked on the pool must keep counting against
            # max_queue_size, and a deadline that passes during the wait
            # must still fail the stream before it touches the model.
            if ticket is not None:
                ticket.started()
            self._check_deadline(model, request)
            inputs = {t.name: t.data for t in request.inputs}
            prof = self.profiling
            resume_ns = time.monotonic_ns()
            # Decoupled models run as async generators on the loop
            # thread; the loop thread's CPU between resuming the model
            # and its next item is the step's compute (an approximation:
            # other tasks interleaved on the loop contaminate it).
            measure_step = prof.take()
            cpu_resume = prof.cpu_now() if measure_step else 0
            async for raw in model.execute_decoupled(inputs, request.parameters):
                final = raw.pop("__final__", False) if isinstance(raw, dict) else False
                p0 = time.monotonic_ns()
                model_wait_ns += p0 - resume_ns
                if measure_step:
                    prof.account("compute", prof.cpu_now() - cpu_resume)
                if raw:
                    response = self._package_profiled(model, request, raw)
                else:
                    response = CoreResponse(
                        model_name=model.name,
                        model_version=model.version,
                        id=request.id,
                        outputs=[],
                    )
                if final:
                    response.parameters["triton_final_response"] = True
                p1 = time.monotonic_ns()
                packaging_ns += p1 - p0
                stats.record_response(
                    index,
                    infer_ns=p0 - prev_ns,
                    out_ns=p1 - p0,
                    latency_ns=p1 - t0,
                    empty=not raw,
                )
                if request.trace is not None:
                    request.trace.event(f"RESPONSE_{index}", p1)
                prev_ns = p1
                index += 1
                # A close/cancel that arrives while suspended at this yield
                # means the yielded value WAS delivered — so a final-marked
                # response makes the stream complete, not cancelled (clients
                # routinely stop iterating at triton_final_response).
                final_delivered = final
                yield response
                # back from the consumer; the next await is model time
                resume_ns = time.monotonic_ns()
                measure_step = prof.take()
                if measure_step:
                    cpu_resume = prof.cpu_now()
        except (asyncio.CancelledError, GeneratorExit):
            # Task cancellation (gRPC stream teardown) and generator close
            # (HTTP/OpenAI front-end client disconnect): if the final
            # response was already delivered this is normal completion;
            # otherwise book a cancel entry at the in-flight response index.
            if model.decoupled:
                if final_delivered:
                    _book_success()
                else:
                    stats.record_response_failure(
                        index, time.monotonic_ns() - t0, cancelled=True
                    )
            raise
        except Exception as e:
            # Only the decoupled path accounts here: non-decoupled requests
            # were delegated to infer(), which already recorded the failure
            # (recording again would double-count it).
            if model.decoupled:
                now = time.monotonic_ns()
                # Book the in-flight response slot too, not just the
                # aggregate: response_stats mirrors Triton's
                # InferResponseStatistics, which carries fail entries.
                stats.record_response_failure(index, now - t0)
                # admission rejections booked their aggregate fail already
                if not isinstance(e, SchedulingError):
                    stats.record("fail", now - t0)
                    self._log_request_error(
                        "stream_failed", model.name, e, path="decoupled"
                    )
                    self._record_exemplar(
                        model.name,
                        request,
                        path="decoupled",
                        status="error",
                        error=str(e),
                        arrival_ns=t0,
                        end_ns=now,
                        responses=index,
                    )
            raise
        else:
            _book_success()
        finally:
            if rate_resources is not None:
                self.rate_limiter.release(rate_resources)
            if ticket is not None:
                ticket.close()
            if model.decoupled:
                self.metrics.pending_dec(model.name)
                self.lifecycle.finish(request.model_name)

    # -- wire-side input decoding -------------------------------------------

    def decode_input(
        self,
        name: str,
        datatype: str,
        shape: List[int],
        raw: Optional[bytes] = None,
        json_data: Optional[list] = None,
        shm_region: Optional[str] = None,
        shm_byte_size: int = 0,
        shm_offset: int = 0,
    ) -> CoreTensor:
        """Materialize an input tensor from any of the three data sources
        (inline binary, JSON, shared memory)."""
        count = num_elements(shape)
        if shm_region is not None:
            # Zero-copy view into the registered region (np.frombuffer
            # below wraps it without copying). Read-only so a model that
            # mutates its input in place raises instead of silently
            # corrupting the client's region. The region must stay
            # registered while requests that reference it are in flight —
            # same contract as the reference server's direct shm reads.
            raw = self.shm.read(
                shm_region, shm_offset, shm_byte_size
            ).toreadonly()
        if raw is not None:
            if datatype == "BYTES":
                arr = deserialize_bytes_tensor(raw).reshape(shape)
            else:
                np_dtype = triton_to_np_dtype(datatype)
                if np_dtype is None:
                    raise InferenceServerException(
                        f"unsupported datatype '{datatype}' for input '{name}'"
                    )
                expected = count * np_dtype.itemsize
                if len(raw) != expected:
                    raise InferenceServerException(
                        f"input '{name}' expected {expected} bytes for shape "
                        f"{shape} and datatype {datatype}, got {len(raw)}"
                    )
                arr = np.frombuffer(raw, dtype=np_dtype).reshape(shape)
        elif json_data is not None:
            if datatype == "BYTES":
                arr = np.array(
                    [
                        d.encode("utf-8") if isinstance(d, str) else d
                        for d in json_data
                    ],
                    dtype=np.object_,
                ).reshape(shape)
            else:
                np_dtype = triton_to_np_dtype(datatype)
                if np_dtype is None:
                    raise InferenceServerException(
                        f"unsupported datatype '{datatype}' for input '{name}'"
                    )
                arr = np.array(json_data, dtype=np_dtype).reshape(shape)
        else:
            raise InferenceServerException(
                f"input '{name}' has no data (inline, JSON, or shared memory)"
            )
        return CoreTensor(name=name, datatype=datatype, shape=list(shape), data=arr)
