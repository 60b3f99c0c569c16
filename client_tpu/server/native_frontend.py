"""Python bridge for the native C++ gRPC front-end.

The extension module (native/frontend/grpc_frontend.cc, built as
``_native_frontend.so``) owns the sockets, HTTP/2 framing, HPACK, flow
control, and protobuf parsing on C++ threads; this bridge is the narrow
GIL-bound slice per request:

* a single pump thread drains batches of parsed requests from the C++
  queue (``wait_requests``, GIL released while blocked) and schedules the
  whole batch onto the core's event loop with ONE wakeup — reader threads
  never touch the GIL, and per-request bridge cost amortizes under load;
* request tensors arrive as numpy views (zero-copy into the C++ request
  buffers, which live until the final ``complete`` for the handle);
* ``complete`` (event loop -> C++): hand back output ndarrays; C++ copies
  them while serializing the response and frees the request.
* ``rpc`` (C++ reader thread -> here): non-inference methods, answered by
  :mod:`client_tpu.server._grpc_codec` on the event loop.

This replaces the grpc.aio front-end on the hot path — measured ~2 ms of
per-request Python/grpc-machinery overhead (PERF.md) — while remaining
wire-compatible with every gRPC client, including grpc/grpcio and this
repo's own h2 C++ client.
"""

import asyncio
import os
import sys
import threading
import traceback
from typing import Any, Dict, Optional

import numpy as np

from client_tpu.observability.profiling import WATCH
from client_tpu.server import _grpc_codec as codec
from client_tpu.server import shm_ring as ring_codec
from client_tpu.server.core import (
    CoreRequest,
    CoreRequestedOutput,
    CoreResponse,
    CoreTensor,
    ServerCore,
)
from client_tpu.utils import (
    InferenceServerException,
    deserialize_bytes_tensor,
    serialize_byte_tensor,
    triton_to_np_dtype,
)

_native = None
_native_error: Optional[str] = None


def _load_native():
    """Import the _native_frontend extension, searching the package dir
    (wheel layout) then the repo build tree."""
    global _native, _native_error
    if _native is not None or _native_error is not None:
        return _native
    import importlib.machinery
    import importlib.util

    here = os.path.dirname(os.path.abspath(__file__))
    package_root = os.path.dirname(here)
    repo_root = os.path.dirname(package_root)
    candidates = [
        os.path.join(package_root, "_native_frontend.so"),
        os.path.join(repo_root, "build", "_native_frontend.so"),
    ]
    for path in candidates:
        if not os.path.exists(path):
            continue
        loader = importlib.machinery.ExtensionFileLoader(
            "client_tpu._native_frontend", path
        )
        spec = importlib.util.spec_from_file_location(
            "client_tpu._native_frontend", path, loader=loader
        )
        module = importlib.util.module_from_spec(spec)
        try:
            loader.exec_module(module)
        except ImportError as e:
            _native_error = str(e)
            return None
        sys.modules["client_tpu._native_frontend"] = module
        _native = module
        return _native
    _native_error = "no _native_frontend.so found (build native/ first)"
    return None


def native_available() -> bool:
    return _load_native() is not None


class NativeGrpcFrontend:
    """The native gRPC server bound to one ServerCore + event loop."""

    def __init__(self, core: ServerCore, loop: asyncio.AbstractEventLoop):
        lib = _load_native()
        if lib is None:
            raise RuntimeError(
                f"native frontend unavailable: {_native_error}"
            )
        self._lib = lib
        self._core = core
        self._loop = loop
        self._id: Optional[int] = None
        self.port: Optional[int] = None
        # handle -> asyncio.Task; loop-thread only (cancel hops onto the
        # loop), so no lock is needed.
        self._tasks: Dict[int, Any] = {}
        self._pump: Optional[threading.Thread] = None
        # Pump batch size: bounds the per-wakeup GIL slice. 128 keeps the
        # loop responsive while amortizing the wakeup under load.
        self._batch = 128

    # -- lifecycle -----------------------------------------------------------

    def start(
        self,
        host: str = "0.0.0.0",
        port: int = 0,
        tls_cert: Optional[str] = None,
        tls_key: Optional[str] = None,
    ) -> None:
        """Bind + serve. With ``tls_cert``/``tls_key`` (PEM paths) the
        C++ listener terminates TLS itself (ALPN h2) — grpcs clients
        connect directly, no fronting proxy needed."""
        self._id = self._lib.start(
            host, port, self._rpc, self._cancel, tls_cert, tls_key
        )
        self.port = self._lib.port(self._id)
        self._pump = threading.Thread(
            target=self._pump_loop, name="ctpu-grpc-pump", daemon=True
        )
        # it waits for requests inside the native call: no stall's stack
        WATCH.idle_in(self._pump_loop)
        self._pump.start()

    def stop(self) -> None:
        if self._id is not None:
            fid, self._id = self._id, None
            self._lib.stop(fid)
            if self._pump is not None:
                self._pump.join(timeout=10)
                self._pump = None

    # -- request path --------------------------------------------------------

    def _pump_loop(self) -> None:
        """Drain parsed requests from C++ in batches. Unary requests run
        RIGHT HERE on the pump thread through ServerCore.infer_direct —
        no event-loop crossing, no per-request future/task/executor hop
        (PERF.md: that asyncio machinery was the dominant per-request
        server cost). Streaming requests hop to the event loop; while a
        direct batch executes, new arrivals queue in C++ and become the
        next batch — the dynamic-batching window.

        wait_requests blocks with the GIL released."""
        try:
            import ctypes

            libc = ctypes.CDLL(None)
            libc.pthread_self.restype = ctypes.c_void_p
            libc.pthread_setname_np.argtypes = [
                ctypes.c_void_p,
                ctypes.c_char_p,
            ]
            libc.pthread_setname_np(libc.pthread_self(), b"ctpu-grpc-pump")
        except Exception:  # noqa: BLE001 - naming is best-effort
            pass
        fid = self._id
        while True:
            batch = self._lib.wait_requests(fid, self._batch, 200)
            if batch is None:
                return  # frontend stopped
            if not batch:
                continue
            streaming_items = [item for item in batch if item[7]]
            if streaming_items:
                try:
                    self._loop.call_soon_threadsafe(
                        self._submit_batch, streaming_items
                    )
                except RuntimeError:  # loop closed under us
                    for item in streaming_items:
                        self._complete_error(
                            item[0],
                            "server shutting down",
                            codec.GRPC_UNAVAILABLE,
                        )
            if len(streaming_items) != len(batch):
                direct_items = [item for item in batch if not item[7]]
                try:
                    self._run_direct(direct_items)
                except Exception:  # noqa: BLE001 - pump must survive
                    # A failure here is a bridge bug, not a request
                    # error; contain it so the front-end keeps serving,
                    # and fail the affected handles (no-op for any that
                    # already completed).
                    traceback.print_exc()
                    for item in direct_items:
                        try:
                            self._complete_error(
                                item[0],
                                "internal error completing request batch",
                                codec.GRPC_INTERNAL,
                            )
                        except Exception:  # noqa: BLE001
                            pass

    def _build_request(self, item) -> CoreRequest:
        """One wire-request tuple -> CoreRequest (raises on bad input)."""
        (
            _handle,
            model_name,
            model_version,
            request_id,
            inputs,
            outputs,
            params,
            _streaming,
        ) = item
        decode_input = self._core.decode_input
        request = CoreRequest(
            model_name=model_name,
            model_version=model_version,
            id=request_id,
            parameters=params,
        )
        for name, datatype, shape, data, shm in inputs:
            if type(data) is np.ndarray:
                # Fastest path: the C++ side already built the
                # zero-copy view (shape/dtype validated there).
                request.inputs.append(
                    CoreTensor(name, datatype, list(shape), data)
                )
                continue
            if shm is None and data is not None:
                # Hot path: raw bytes -> numpy view. frombuffer /
                # reshape validate the byte count against the shape.
                if datatype == "BYTES":
                    arr = deserialize_bytes_tensor(data).reshape(shape)
                else:
                    np_dtype = triton_to_np_dtype(datatype)
                    if np_dtype is None:
                        raise InferenceServerException(
                            f"unsupported datatype '{datatype}' "
                            f"for input '{name}'"
                        )
                    arr = np.frombuffer(data, dtype=np_dtype).reshape(shape)
                tensor = CoreTensor(name, datatype, list(shape), arr)
            elif shm is not None:
                region, byte_size, offset = shm
                tensor = decode_input(
                    name,
                    datatype,
                    list(shape),
                    shm_region=region,
                    shm_byte_size=int(byte_size),
                    shm_offset=int(offset),
                )
            else:
                raise InferenceServerException(
                    f"input '{name}' has no data (inline, typed "
                    "contents, or shared memory)"
                )
            request.inputs.append(tensor)
        for name, classification, shm in outputs:
            if shm is not None:
                region, byte_size, offset = shm
                request.outputs.append(
                    CoreRequestedOutput(
                        name=name,
                        classification=int(classification),
                        shm_region=region,
                        shm_byte_size=int(byte_size),
                        shm_offset=int(offset),
                    )
                )
            else:
                request.outputs.append(
                    CoreRequestedOutput(
                        name=name, classification=int(classification)
                    )
                )
        # shm-ring requests: inputs view the ring slot, the response
        # goes back into it (ticket on request.shm_ring)
        ring_codec.attach(self._core, request)
        return request


    def _run_direct(self, items) -> None:
        """Pump thread: decode + execute + complete a batch of unary
        requests synchronously (ServerCore.infer_direct). All completions
        for the batch ride ONE complete_many call — the C++ side then
        serializes and writes the whole batch in a single GIL release."""
        handles = []
        requests = []
        completions = []
        prof = self._core.profiling
        # one take() covers this pump batch's decode AND encode brackets
        measured = prof.take()
        decode_cpu0 = prof.cpu_now() if measured else 0
        for item in items:
            try:
                request = self._build_request(item)
            except Exception as e:  # noqa: BLE001 - wire-level badness
                # Decode errors (including numpy size/shape ValueErrors)
                # are the client's fault: INVALID_ARGUMENT.
                completions.append(
                    self._error_completion(
                        item[0], e, default=codec.GRPC_INVALID_ARGUMENT
                    )
                )
                continue
            handles.append(item[0])
            requests.append(request)
        if measured and requests:
            prof.account(
                "frontend_decode",
                prof.cpu_now() - decode_cpu0,
                count=len(requests),
            )
        if requests:
            results = self._core.infer_direct(requests)
            encode_cpu0 = prof.cpu_now() if measured else 0
            log = self._core.logger
            for handle, request, result in zip(handles, requests, results):
                if isinstance(result, Exception):
                    # Execution errors are the server/model's fault:
                    # INTERNAL (matching the event-loop unary path).
                    if request.shm_ring is not None:
                        request.shm_ring.fail()
                    completions.append(
                        self._error_completion(handle, result)
                    )
                else:
                    if request.shm_ring is not None:
                        try:
                            result = request.shm_ring.complete(result)
                        except Exception as e:  # noqa: BLE001 - per-request
                            # a response that doesn't fit its slot fails
                            # THIS request cleanly; co-batched requests
                            # still complete
                            completions.append(
                                self._error_completion(
                                    handle,
                                    e,
                                    default=codec.GRPC_INVALID_ARGUMENT,
                                )
                            )
                            continue
                    if log.verbose_hot:
                        log.verbose(
                            "request",
                            model=result.model_name,
                            protocol="grpc-native",
                            status="ok",
                            request_id=result.id,
                        )
                    completions.append(
                        self._response_completion(handle, result, 1)
                    )
            if measured:
                prof.account(
                    "encode",
                    prof.cpu_now() - encode_cpu0,
                    count=len(requests),
                )
        if completions:
            self._lib.complete_many(completions)

    def _error_completion(
        self, handle: int, e: Exception, default: Optional[int] = None
    ):
        """complete() argument tuple for a failed request. ``default`` is
        the status for non-InferenceServerException errors (INTERNAL when
        unset — execution context)."""
        if isinstance(e, InferenceServerException):
            message = e.message()
            status = codec.status_code_for(message, exc=e)
        else:
            message = str(e)
            status = codec.GRPC_INTERNAL if default is None else default
        log = self._core.logger
        if log.verbose_hot:
            log.verbose(
                "request",
                protocol="grpc-native",
                status="error",
                error=message,
                grpc_status=status,
            )
        return (handle, "", "", "", None, None, 1, message, status)

    def _submit_batch(self, batch) -> None:
        """Event loop: build CoreRequests and start streaming tasks."""
        prof = self._core.profiling
        for item in batch:
            handle = item[0]
            try:
                if prof.take():
                    decode_cpu0 = prof.cpu_now()
                    request = self._build_request(item)
                    prof.account(
                        "frontend_decode", prof.cpu_now() - decode_cpu0
                    )
                else:
                    request = self._build_request(item)
                task = self._loop.create_task(
                    self._run_stream(handle, request)
                )
                self._tasks[handle] = task
                task.add_done_callback(
                    lambda _t, h=handle: self._tasks.pop(h, None)
                )
            except Exception as e:  # noqa: BLE001 - wire-level badness
                self._lib.complete(
                    *self._error_completion(
                        handle, e, default=codec.GRPC_INVALID_ARGUMENT
                    )
                )

    def _cancel(self, handle: int) -> None:
        """C++ thread: peer reset the stream / dropped the connection."""
        try:
            self._loop.call_soon_threadsafe(self._cancel_on_loop, handle)
        except RuntimeError:
            pass
        # Guarantee the native side frees the request even if the task never
        # ran. complete() on an already-finalized handle is a no-op, so a
        # race with normal completion is safe.
        self._complete_error(handle, "request cancelled", 1)

    def _cancel_on_loop(self, handle: int) -> None:
        task = self._tasks.pop(handle, None)
        if task is not None:
            task.cancel()

    # -- completion helpers --------------------------------------------------

    def _complete_error(self, handle: int, message: str, status: int) -> None:
        self._lib.complete(handle, "", "", "", None, None, 1, message, status)

    @staticmethod
    def _payload(tensor) -> np.ndarray:
        if tensor.datatype == "BYTES":
            return serialize_byte_tensor(tensor.data)
        data = tensor.data
        if data.flags.c_contiguous:
            return data  # row slices of a C-contiguous batch land here
        return np.ascontiguousarray(data)

    def _response_completion(
        self, handle: int, response: CoreResponse, final: int
    ):
        """complete() argument tuple for a successful response."""
        outs = []
        for t in response.outputs:
            shm = response.shm_outputs.get(t.name)
            if shm is not None:
                outs.append((t.name, t.datatype, tuple(t.shape), None, shm))
            else:
                outs.append(
                    (
                        t.name,
                        t.datatype,
                        tuple(t.shape),
                        self._payload(t),
                        None,
                    )
                )
        return (
            handle,
            response.model_name,
            response.model_version,
            response.id,
            outs,
            response.parameters or None,
            final,
            None,
            0,
        )

    def _complete_response(
        self, handle: int, response: CoreResponse, final: bool
    ) -> None:
        self._lib.complete(
            *self._response_completion(handle, response, 1 if final else 0)
        )

    # -- per-request coroutines ----------------------------------------------

    async def _run_stream(self, handle: int, request: CoreRequest) -> None:
        """One request on a ModelStreamInfer stream: 0..N responses.

        The native side needs `final` on the LAST response (it frees the
        request buffers there), so responses are sent with one-item
        lookahead.
        """
        held: Optional[CoreResponse] = None
        try:
            if request.shm_ring is not None:
                # ring slots hold exactly one response: unary execution,
                # tensors diverted into the slot, slim ack on the wire
                response = await self._core.infer(request)
                self._complete_response(
                    handle, request.shm_ring.complete(response), final=True
                )
                return
            async for response in self._core.infer_decoupled(request):
                if held is not None:
                    self._complete_response(handle, held, final=False)
                held = response
        except asyncio.CancelledError:
            if request.shm_ring is not None:
                request.shm_ring.fail()
            if not self._core.lifecycle.accepting:
                # torn down by a drain deadline, not by the peer: the
                # client gets a clean retryable UNAVAILABLE, never a
                # bare CANCELLED from a cancelled future
                self._complete_error(
                    handle,
                    "server is draining and not accepting new inference "
                    "requests",
                    codec.GRPC_UNAVAILABLE,
                )
            else:
                self._complete_error(handle, "request cancelled", 1)
            raise
        except InferenceServerException as e:
            if request.shm_ring is not None:
                request.shm_ring.fail()
            self._complete_error(
                handle, e.message(), codec.status_code_for(e.message(), exc=e)
            )
            return
        except Exception as e:  # noqa: BLE001
            if request.shm_ring is not None:
                request.shm_ring.fail()
            self._complete_error(handle, str(e), codec.GRPC_INTERNAL)
            return
        if held is not None:
            self._complete_response(handle, held, final=True)
        else:
            # Zero-response stream: emit Triton's final empty response so
            # the client's request completes.
            empty = CoreResponse(
                model_name=request.model_name,
                model_version=request.model_version,
                id=request.id,
                outputs=[],
                parameters={"triton_final_response": True},
            )
            self._complete_response(handle, empty, final=True)

    # -- non-inference methods ----------------------------------------------

    def _rpc(self, method: str, payload: bytes):
        """C++ reader thread: run a non-inference method on the loop (same
        single-threaded core access as the other front-ends) and block —
        GIL released inside result() — for the answer."""
        try:
            future = asyncio.run_coroutine_threadsafe(
                self._rpc_on_loop(method, payload), self._loop
            )
            return future.result(timeout=120)
        except Exception as e:  # noqa: BLE001 - includes loop shutdown
            return (codec.GRPC_INTERNAL, b"", f"internal error: {e}")

    async def _rpc_on_loop(self, method: str, payload: bytes):
        try:
            return (
                0,
                codec.handle_method_bytes(self._core, method, payload),
                "",
            )
        except codec.RpcError as e:
            return (e.status, b"", e.message)
        except Exception as e:  # noqa: BLE001
            return (codec.GRPC_INTERNAL, b"", str(e))


async def serve_grpc_native(
    core: ServerCore,
    host: str = "0.0.0.0",
    port: int = 8001,
    tls_cert: Optional[str] = None,
    tls_key: Optional[str] = None,
):
    """Start the native gRPC front-end; returns (frontend, bound_port).

    Signature mirrors grpc_server.serve_grpc so callers can switch
    implementations; `frontend.stop()` is synchronous. TLS termination
    (grpcs) is enabled by passing PEM cert/key paths.
    """
    frontend = NativeGrpcFrontend(core, asyncio.get_running_loop())
    frontend.start(host, port, tls_cert=tls_cert, tls_key=tls_key)
    return frontend, frontend.port
