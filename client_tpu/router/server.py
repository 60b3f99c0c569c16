"""Router front-ends: gRPC + HTTP over one :class:`RouterCore`.

Both protocol fronts are THIN — the gRPC servicer registers with
``raw_infer_bytes = True`` so inference requests arrive and leave as
serialized bytes (the router never builds a proto on the hot path), and
the HTTP front is a byte-level reverse proxy. Health endpoints are
answered locally (the router's readiness is "≥1 healthy backend", so a
client pool of routers benches a router whose whole fleet is gone);
control-plane metadata RPCs proxy to a healthy backend with the same
UNAVAILABLE failover the data path gets.

:class:`RouterServer` runs both fronts on a background event loop in a
daemon thread — the same harness shape as
:class:`client_tpu.testing.InProcessServer`, so tests and the ``python
-m client_tpu.router`` CLI share one lifecycle.
"""

import asyncio
import json
import threading
from typing import Dict, Optional, Tuple

import grpc

from client_tpu.grpc import _wire as wire
from client_tpu.grpc._generated import grpc_service_pb2 as pb
from client_tpu.grpc._service_stubs import (
    _METHODS,
    GRPCInferenceServiceServicer,
    add_GRPCInferenceServiceServicer_to_server,
)
from client_tpu.grpc._utils import rpc_error_to_exception
from client_tpu.lifecycle.pool import status_is_unavailable
from client_tpu.router.core import RouterCore, RouterOverloadError
from client_tpu.utils import InferenceServerException

MAX_GRPC_MESSAGE_SIZE = 2**31 - 1  # INT32_MAX, both directions

_STATUS_BY_TOKEN = {f"StatusCode.{code.name}": code for code in grpc.StatusCode}

# hop-by-hop headers never cross a proxy (RFC 9110 §7.6.1)
_HOP_HEADERS = frozenset(
    (
        "connection",
        "keep-alive",
        "proxy-authenticate",
        "proxy-authorization",
        "te",
        "trailers",
        "transfer-encoding",
        "upgrade",
        "host",
        "content-length",
    )
)


def _grpc_code_for(token: Optional[str]) -> grpc.StatusCode:
    if token in _STATUS_BY_TOKEN:
        return _STATUS_BY_TOKEN[token]
    if status_is_unavailable(token):
        return grpc.StatusCode.UNAVAILABLE
    return grpc.StatusCode.INTERNAL


def _stream_error_frame(message: str, request_id: str) -> bytes:
    """An in-band ModelStreamInferResponse error whose inner response
    carries the CLIENT's request id — error frames stay correlatable on
    multiplexed client streams (server parity)."""
    inner, _ = wire.splice_message_id(b"", request_id)
    out = bytearray()
    wire.encode_stream_response(out, inner, message)
    return bytes(out)


# control-plane RPCs forwarded verbatim to a healthy backend
_PROXIED_METHODS = (
    "ServerMetadata",
    "ModelMetadata",
    "ModelConfig",
    "ModelStatistics",
    "RepositoryIndex",
)


class _RouterServicer(GRPCInferenceServiceServicer):
    """gRPC front: raw-bytes inference forwarding + local health."""

    raw_infer_bytes = True

    def __init__(self, router: RouterCore, proxy_timeout_s: float = 5.0):
        self.router = router
        self.proxy_timeout_s = proxy_timeout_s
        self.draining = False

    # -- inference (raw serialized bytes in/out) -----------------------------

    async def ModelInfer(self, request_bytes, context):
        router = self.router
        try:
            return await router.forward_unary(request_bytes, protocol="grpc")
        except RouterOverloadError as e:
            await context.abort(
                grpc.StatusCode.RESOURCE_EXHAUSTED,
                e.message(),
                trailing_metadata=(("retry-after", f"{e.retry_after_s:g}"),),
            )
        except InferenceServerException as e:
            await context.abort(_grpc_code_for(e.status()), e.message())

    async def ModelStreamInfer(self, request_iterator, context):
        """Client stream front. The whole client stream pins to ONE
        backend at its first request (strict ordering and sequence
        affinity live on a single replica — the client mux's own
        pinned-stream semantics); frames are forwarded with spliced
        correlation ids and restored per response frame, N frames per
        request supported (decoupled models). Admission is bracketed
        from forward to FIRST response frame. A backend stream death
        surfaces as per-request in-band UNAVAILABLE errors — retryable
        under the client's derived-status mapping, never a hung stream.
        """
        router = self.router
        out_q: "asyncio.Queue" = asyncio.Queue()
        DONE = ("done",)
        rids: Dict[str, str] = {}  # router rid -> client's original id
        admitted = set()  # rids still holding an admission slot
        state = {"ep": None, "link": None}

        def sink_for(rid):
            def sink(error_message, response, failure):
                out_q.put_nowait(("frame", rid, error_message, response, failure))

            return sink

        async def reader() -> None:
            try:
                async for data in request_iterator:
                    try:
                        original = wire.read_message_id(data)
                    except wire.WireError as e:
                        await out_q.put(
                            ("error", "", InferenceServerException(str(e)))
                        )
                        continue
                    model_name, key, priority, _seq = router.classify(data)
                    try:
                        router.admit(priority)
                    except RouterOverloadError as e:
                        router.m_requests.labels("grpc_stream", "shed").inc()
                        await out_q.put(("error", original, e))
                        continue
                    if state["ep"] is None:
                        ep = router.pool.pick(
                            key=key, allow=router.table.urls_for(model_name)
                        )
                        router.pool.pin_stream(ep)
                        state["ep"] = ep
                        state["link"] = router.link_for(ep.url)
                    rid = router.next_rid()
                    payload, _orig = wire.splice_forward_request(data, rid)
                    link = state["link"]
                    rids[rid] = original
                    admitted.add(rid)
                    link.register(rid, sink_for(rid), long_lived=True)
                    try:
                        await link.write(payload)
                    except InferenceServerException as e:
                        link.unregister(rid)
                        rids.pop(rid, None)
                        if rid in admitted:
                            admitted.discard(rid)
                            router.release()
                        router.m_requests.labels(
                            "grpc_stream", "error"
                        ).inc()
                        await out_q.put(("error", original, e))
                        continue
                    router.m_requests.labels("grpc_stream", "ok").inc()
                await out_q.put(DONE)
            except asyncio.CancelledError:
                raise
            except BaseException as e:  # noqa: BLE001 - surfaced to writer
                await out_q.put(("abort", e))

        reader_task = asyncio.ensure_future(reader())
        try:
            while True:
                item = await out_q.get()
                kind = item[0]
                if item is DONE:
                    break
                if kind == "abort":
                    raise item[1]
                if kind == "error":
                    _kind, original, exc = item
                    yield _stream_error_frame(exc.message(), original)
                    continue
                _kind, rid, error_message, response, failure = item
                original = rids.get(rid, "")
                if rid in admitted:
                    admitted.discard(rid)
                    router.release()
                if failure is not None:
                    rids.pop(rid, None)
                    yield _stream_error_frame(failure.message(), original)
                    continue
                spliced, _rid = wire.splice_message_id(response, original)
                out = bytearray()
                wire.encode_stream_response(out, spliced, error_message)
                yield bytes(out)
        finally:
            reader_task.cancel()
            link = state["link"]
            if link is not None:
                for rid in rids:
                    link.unregister(rid)
            for _rid in admitted:
                router.release()
            if state["ep"] is not None:
                router.pool.unpin_stream(state["ep"])

    # -- local health --------------------------------------------------------

    def _fleet_ready(self) -> bool:
        if self.draining:
            return False
        router = self.router
        now = router.now()
        return any(ep.state(now) == "up" for ep in router.pool.endpoints)

    async def ServerLive(self, request, context):
        return pb.ServerLiveResponse(live=True)

    async def ServerReady(self, request, context):
        return pb.ServerReadyResponse(ready=self._fleet_ready())

    async def ModelReady(self, request, context):
        if self.router.table.urls_for(request.name):
            return pb.ModelReadyResponse(ready=True)
        # table does not know the model (cold start): ask a backend
        return await self._proxy("ModelReady", request, context)

    # -- proxied control plane -----------------------------------------------

    async def _proxy(self, method_name, request, context):
        router = self.router
        exclude = None
        max_attempts = max(2, router.pool.size)
        for attempt in range(max_attempts):
            ep = router.pool.pick(exclude=exclude)
            link = router.link_for(ep.url)
            try:
                return await getattr(link.stub, method_name)(
                    request, timeout=self.proxy_timeout_s
                )
            except grpc.RpcError as e:
                exc = rpc_error_to_exception(e)
                token = exc.status()
                if status_is_unavailable(token):
                    router.pool.observe(ep, ok=False, token=token)
                    if (
                        attempt + 1 < max_attempts
                        and router.pool.has_alternative(ep)
                    ):
                        exclude = ep
                        continue
                await context.abort(_grpc_code_for(token), exc.message())


def _make_unimplemented(name):
    async def handler(self, request, context):
        await context.abort(
            grpc.StatusCode.UNIMPLEMENTED,
            f"{name} is not supported by the router tier",
        )

    handler.__name__ = name
    return handler


def _make_proxied(name):
    async def handler(self, request, context):
        return await self._proxy(name, request, context)

    handler.__name__ = name
    return handler


for _name in _PROXIED_METHODS:
    setattr(_RouterServicer, _name, _make_proxied(_name))
for _name in _METHODS:
    if _name not in _RouterServicer.__dict__:
        # shared-memory RPCs and the like: host-local concepts that are
        # meaningless across a proxy hop
        setattr(_RouterServicer, _name, _make_unimplemented(_name))


async def serve_router_grpc(
    router: RouterCore, host: str, port: int
) -> Tuple[object, int, _RouterServicer]:
    server = grpc.aio.server(
        options=[
            ("grpc.max_send_message_length", MAX_GRPC_MESSAGE_SIZE),
            ("grpc.max_receive_message_length", MAX_GRPC_MESSAGE_SIZE),
        ]
    )
    servicer = _RouterServicer(router)
    add_GRPCInferenceServiceServicer_to_server(servicer, server)
    bound = server.add_insecure_port(f"{host}:{port}")
    await server.start()
    return server, bound, servicer


# -- HTTP front ---------------------------------------------------------------


class _HttpFront:
    """aiohttp reverse proxy: local health/metrics/status, everything
    else forwarded byte-for-byte to a healthy backend's HTTP address.

    The HTTP infer path cannot see the gRPC priority parameter without
    parsing the JSON body, so HTTP admission uses the DEFAULT priority
    class — latency-protected traffic belongs on gRPC.
    """

    def __init__(self, servicer: _RouterServicer):
        from aiohttp import web

        self.web = web
        self.servicer = servicer
        self.router = servicer.router
        self._session = None
        self.app = web.Application(client_max_size=1 << 30)
        self.app.router.add_get("/v2/health/live", self.handle_live)
        self.app.router.add_get("/v2/health/ready", self.handle_ready)
        self.app.router.add_get("/metrics", self.handle_metrics)
        self.app.router.add_get("/v2/router/status", self.handle_status)
        self.app.router.add_route("*", "/{tail:.*}", self.handle_proxy)

    async def handle_live(self, request):
        return self.web.Response(status=200)

    async def handle_ready(self, request):
        if self.servicer._fleet_ready():
            return self.web.Response(status=200)
        return self.web.Response(
            status=503,
            headers={"Retry-After": "1"},
            text="no healthy backend",
        )

    async def handle_metrics(self, request):
        return self.web.Response(
            text=self.router.metrics.render(),
            content_type="text/plain",
        )

    async def handle_status(self, request):
        return self.web.json_response(self.router.snapshot())

    async def handle_proxy(self, request):
        router = self.router
        is_infer = request.method == "POST" and request.path.endswith(
            "/infer"
        )
        if is_infer:
            try:
                router.admit(0)
            except RouterOverloadError as e:
                router.m_requests.labels("http", "shed").inc()
                return self.web.Response(
                    status=429,
                    headers={"Retry-After": f"{e.retry_after_s:g}"},
                    text=json.dumps({"error": e.message()}),
                    content_type="application/json",
                )
        started = router.now()
        outcome = "error"
        try:
            response = await self._forward_http(request)
            outcome = "ok" if response.status < 500 else "error"
            return response
        finally:
            if is_infer:
                router.release()
                router.m_proxy.observe(router.now() - started)
                router.m_requests.labels("http", outcome).inc()

    async def _forward_http(self, request):
        import aiohttp

        router = self.router
        if self._session is None:
            self._session = aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=None)
            )
        body = await request.read()
        headers = {
            k: v
            for k, v in request.headers.items()
            if k.lower() not in _HOP_HEADERS
        }
        allow = {
            url for url, http_url in router.http_urls.items() if http_url
        }
        if not allow:
            return self.web.Response(
                status=503, text="no HTTP-capable backend"
            )
        exclude = None
        max_attempts = max(2, len(allow))
        for attempt in range(max_attempts):
            ep = router.pool.pick(exclude=exclude, allow=allow)
            target = router.http_urls.get(ep.url)
            if target is None:
                break
            url = f"http://{target}{request.path_qs}"
            started = router.pool.begin(ep)
            try:
                async with self._session.request(
                    request.method, url, data=body, headers=headers
                ) as upstream:
                    payload = await upstream.read()
                    ok = upstream.status < 500
                    router.pool.finish(
                        ep,
                        started,
                        ok=ok,
                        token=None if ok else str(upstream.status),
                    )
                    router.pool.observe(
                        ep,
                        ok=ok,
                        token=None if ok else str(upstream.status),
                    )
                    if (
                        upstream.status == 503
                        and attempt + 1 < max_attempts
                        and router.pool.has_alternative(ep)
                    ):
                        exclude = ep
                        continue
                    out_headers = {
                        k: v
                        for k, v in upstream.headers.items()
                        if k.lower() not in _HOP_HEADERS
                    }
                    return self.web.Response(
                        status=upstream.status,
                        headers=out_headers,
                        body=payload,
                    )
            except aiohttp.ClientError:
                router.pool.finish(ep, started, ok=False, token="503")
                router.pool.observe(ep, ok=False, token="503")
                if (
                    attempt + 1 < max_attempts
                    and router.pool.has_alternative(ep)
                ):
                    exclude = ep
                    continue
                return self.web.Response(
                    status=503,
                    headers={"Retry-After": "1"},
                    text="backend unavailable",
                )
        return self.web.Response(status=503, text="backend unavailable")

    async def close(self) -> None:
        if self._session is not None:
            await self._session.close()
            self._session = None


async def serve_router_http(servicer: _RouterServicer, host: str, port: int):
    from aiohttp import web

    front = _HttpFront(servicer)
    runner = web.AppRunner(front.app, access_log=None)
    await runner.setup()
    site = web.TCPSite(runner, host, port)
    await site.start()
    return runner, front


# -- lifecycle ----------------------------------------------------------------


class RouterServer:
    """Both router fronts on a background event loop in a daemon thread
    (the InProcessServer harness shape). ``backends`` maps each
    backend's gRPC address to its HTTP address (or None)."""

    def __init__(
        self,
        backends: Dict[str, Optional[str]],
        host: str = "127.0.0.1",
        http: bool = True,
        http_port: int = 0,
        grpc_port: int = 0,
        routing_policy="least_outstanding",
        hedge=None,
        max_inflight: int = 0,
        shed_retry_after_s: float = 0.25,
        probe_interval_s: float = 0.25,
        logger=None,
    ):
        self._backends = dict(backends)
        self._host = host
        self._want_http = http
        self._http_bind_port = http_port
        self._grpc_bind_port = grpc_port
        self._routing_policy = routing_policy
        self._hedge = hedge
        self._max_inflight = max_inflight
        self._shed_retry_after_s = shed_retry_after_s
        self._probe_interval_s = probe_interval_s
        self._logger = logger
        self.router: Optional[RouterCore] = None
        self.http_port: Optional[int] = None
        self.grpc_port: Optional[int] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._thread: Optional[threading.Thread] = None
        self._ready = threading.Event()
        self._stop = None  # asyncio.Event created on the loop
        self._error: Optional[BaseException] = None
        self._servicer: Optional[_RouterServicer] = None

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "RouterServer":
        self._thread = threading.Thread(
            target=self._run, name="client-tpu-router", daemon=True
        )
        self._thread.start()
        self._ready.wait(timeout=60)
        if self._error is not None:
            raise self._error
        if not self._ready.is_set():
            raise RuntimeError("router failed to start in 60s")
        return self

    def _run(self) -> None:
        self._loop = asyncio.new_event_loop()
        asyncio.set_event_loop(self._loop)
        try:
            self._loop.run_until_complete(self._serve())
        except BaseException as e:  # noqa: BLE001 - propagate to starter
            self._error = e
            self._ready.set()
        finally:
            self._loop.close()

    async def _serve(self) -> None:
        from client_tpu.router.backends import ReadinessProber

        self._stop = asyncio.Event()
        self.router = RouterCore(
            self._backends,
            routing_policy=self._routing_policy,
            hedge=self._hedge,
            max_inflight=self._max_inflight,
            shed_retry_after_s=self._shed_retry_after_s,
            logger=self._logger,
        )
        prober = ReadinessProber(
            self.router, self.router.links, interval_s=self._probe_interval_s
        )
        # resolve the model table before taking traffic; link creation
        # is lazy, so touch every backend's link first
        for url in list(self.router.pool.urls):
            self.router.link_for(url)
        try:
            await prober.probe_once()
        except Exception:  # noqa: BLE001 - backends may still be booting
            pass
        prober.start()
        grpc_server, self.grpc_port, self._servicer = await serve_router_grpc(
            self.router, self._host, self._grpc_bind_port
        )
        http_runner = None
        http_front = None
        if self._want_http:
            http_runner, http_front = await serve_router_http(
                self._servicer, self._host, self._http_bind_port
            )
            self.http_port = http_runner.addresses[0][1]
        self._ready.set()
        await self._stop.wait()
        # flip readiness first so router-pool clients fail over cleanly
        self._servicer.draining = True
        await prober.stop()
        await grpc_server.stop(grace=1)
        if http_runner is not None:
            await http_front.close()
            await http_runner.cleanup()
        await self.router.close()

    def stop(self) -> None:
        if self._loop is not None and self._stop is not None:
            self._loop.call_soon_threadsafe(self._stop.set)
        if self._thread is not None:
            self._thread.join(timeout=30)

    def __enter__(self) -> "RouterServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- membership (called from any thread) ---------------------------------

    def add_backend(self, grpc_url: str, http_url: Optional[str] = None):
        """Thread-safe: schedule the join on the router loop (the
        autoscaler calls this from the fleet thread)."""

        def _add():
            self.router.add_backend(grpc_url, http_url)

        asyncio.run_coroutine_threadsafe(
            _call_async(_add), self._loop
        ).result(timeout=10)

    def remove_backend(self, grpc_url: str) -> None:
        """Thread-safe: pull the backend from routing NOW, close its
        link once its in-flights have drained out."""

        async def _remove():
            link = self.router.remove_backend(grpc_url)
            if link is not None:
                # in-flights already forwarded keep their sinks; give
                # them a moment to drain before the channel closes
                for _ in range(50):
                    if link.pending == 0:
                        break
                    await asyncio.sleep(0.1)
                await link.close()

        asyncio.run_coroutine_threadsafe(_remove(), self._loop).result(
            timeout=30
        )

    # -- convenience ---------------------------------------------------------

    @property
    def grpc_url(self) -> str:
        return f"{self._host}:{self.grpc_port}"

    @property
    def http_url(self) -> str:
        return f"{self._host}:{self.http_port}"


async def _call_async(fn):
    return fn()
