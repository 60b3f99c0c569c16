"""Model abstraction + repository for the in-repo server.

A model exposes KServe v2 metadata/config and an execute function over
name->ndarray dicts. Decoupled (streaming) models yield multiple responses
per request via an async generator, mirroring Triton's decoupled transaction
policy (reference model_config.proto ModelTransactionPolicy).
"""

import importlib.util
import json
import os
import threading
from typing import Any, AsyncIterator, Dict, List, Optional

import numpy as np

from client_tpu.utils import InferenceServerException

# index() states (Triton RepositoryIndex wire values)
STATE_READY = "READY"
STATE_UNAVAILABLE = "UNAVAILABLE"
STATE_LOADING = "LOADING"
STATE_UNLOADING = "UNLOADING"


class ModelUnavailableError(InferenceServerException):
    """A request targeted a model that exists but is not serving
    (unloaded, unloading, or load-failed).

    Carries both wire faces directly — HTTP 503 (a retryable status, so
    clients with a retry policy ride through an unload->load window) and
    gRPC UNAVAILABLE — instead of the generic 400/INVALID_ARGUMENT a
    missing model gets: "temporarily gone" and "never existed" are
    different contracts."""

    http_status = 503
    grpc_code = "UNAVAILABLE"

    def __init__(self, msg: str):
        super().__init__(msg, status="UNAVAILABLE")


def _mesh_capacity_failure(exc: Optional[BaseException]) -> bool:
    """True when a load failure is a mesh-capacity problem ("mesh
    requires N devices, host has M") anywhere in the cause chain — a
    property of the host, not a broken model, so it must not degrade
    whole-server readiness the way corrupt weights do."""
    try:
        from client_tpu.parallel.sharding import MeshUnavailableError
    except Exception:  # noqa: BLE001 - parallel layer optional at import
        return False
    seen = set()
    while exc is not None and id(exc) not in seen:
        if isinstance(exc, MeshUnavailableError):
            return True
        seen.add(id(exc))
        exc = exc.__cause__
    return False


class Model:
    """Base class for served models.

    Subclasses define ``inputs``/``outputs`` metadata and implement
    :meth:`execute` (one response) or :meth:`execute_decoupled` (stream of
    responses; set ``decoupled = True``).
    """

    name: str = "model"
    version: str = "1"
    platform: str = "jax"
    backend: str = "jax"
    max_batch_size: int = 0
    decoupled: bool = False
    # Placement hint: "" = framework default (the accelerator), "cpu" = the
    # host JAX backend. Tiny elementwise models should be host-placed:
    # only models with real FLOPs (conv/matmul) earn a device round-trip.
    device: str = ""
    # [{"name", "datatype", "shape"}] — shape without batch dim if
    # max_batch_size > 0, matching Triton config conventions.
    inputs: List[Dict[str, Any]] = []
    outputs: List[Dict[str, Any]] = []
    # Mixed-shape dynamic batching (the server-side half of Triton's ragged
    # batching, reference docs ragged_batching.md): when True, concurrent
    # requests whose shapes differ ONLY in dims the model declares as -1
    # share one execution — the batcher zero-pads those dims to a shared
    # power-of-two bucket (bounding XLA retraces) before concatenating.
    # The model must tolerate padding (e.g. mask pad_token positions).
    allow_ragged_batch: bool = False
    ragged_pad_value: int = 0
    # Hard upper bound for padded ragged dims (e.g. max sequence length);
    # the batcher clamps its power-of-two bucket here so merging can never
    # push a batch past a limit its members individually respect.
    ragged_dim_cap: Optional[int] = None
    # Scheduler declarations, surfaced through the model-configuration
    # extension so clients (perf_analyzer's ModelParser, reference
    # model_parser.cc scheduler-kind detection) can auto-detect how to
    # drive the model. dynamic_batching is emitted automatically for
    # batchable models (the core batcher is always on for them).
    sequence_batching: Optional[Dict[str, Any]] = None
    ensemble_scheduling: Optional[Dict[str, Any]] = None
    # Admission control (client_tpu.scheduling; the ModelDynamicBatching
    # priority / ModelQueuePolicy / ModelRateLimiter surface):
    # priority_levels N declares queue levels 1..N (1 = highest);
    # requests without a priority parameter land on default_priority_level
    # (or the lowest level when 0). queue_policy keys: max_queue_size,
    # default_timeout_us, timeout_action ("reject"|"continue"),
    # allow_timeout_override. rate_limiter: {"resources": [{"name",
    # "count"}], "priority"} — executions acquire those pool resources.
    priority_levels: int = 0
    default_priority_level: int = 0
    queue_policy: Optional[Dict[str, Any]] = None
    rate_limiter: Optional[Dict[str, Any]] = None
    # Sharded execution (client_tpu.parallel.sharding): a mesh
    # declaration {"axes": {"dp": 2, "tp": 2}, "inputs": {name: spec},
    # "outputs": {name: spec}} resolved against jax.devices() at
    # load/warmup time into a Mesh + per-tensor NamedShardings. Models
    # that resolve one publish the live plan as ``mesh_plan`` (used by
    # debug_state()'s devices block and per-device busy accounting). A
    # host with too few devices surfaces the model as UNAVAILABLE with
    # reason "load failed: mesh requires N devices, host has M".
    mesh: Optional[Dict[str, Any]] = None
    mesh_plan: Optional[Any] = None

    def metadata(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "versions": [self.version],
            "platform": self.platform,
            "inputs": [
                {
                    "name": i["name"],
                    "datatype": i["datatype"],
                    "shape": ([-1] if self.max_batch_size > 0 else [])
                    + list(i["shape"]),
                }
                for i in self.inputs
            ],
            "outputs": [
                {
                    "name": o["name"],
                    "datatype": o["datatype"],
                    "shape": ([-1] if self.max_batch_size > 0 else [])
                    + list(o["shape"]),
                }
                for o in self.outputs
            ],
        }

    def config(self) -> Dict[str, Any]:
        config = {
            "name": self.name,
            "platform": self.platform,
            "backend": self.backend,
            "max_batch_size": self.max_batch_size,
            "input": [
                {
                    "name": i["name"],
                    "data_type": "TYPE_" + i["datatype"].replace("BYTES", "STRING"),
                    "dims": list(i["shape"]),
                }
                for i in self.inputs
            ],
            "output": [
                {
                    "name": o["name"],
                    "data_type": "TYPE_" + o["datatype"].replace("BYTES", "STRING"),
                    "dims": list(o["shape"]),
                }
                for o in self.outputs
            ],
            "model_transaction_policy": {"decoupled": self.decoupled},
        }
        if self.sequence_batching is not None:
            config["sequence_batching"] = dict(self.sequence_batching)
        elif self.max_batch_size > 1 and self.ensemble_scheduling is None:
            # Batchable models ride the core's dynamic batcher; declare it
            # the way Triton configs do so clients can see the scheduler.
            # Ensembles never declare it (the proto's scheduling_choice is
            # a oneof — both protocols must report the same scheduler).
            dynamic_batching: Dict[str, Any] = {}
            if self.priority_levels:
                dynamic_batching["priority_levels"] = self.priority_levels
                dynamic_batching["default_priority_level"] = (
                    self.default_priority_level
                )
            if self.queue_policy:
                qp = self.queue_policy
                # Triton wire names (ModelQueuePolicy)
                dynamic_batching["default_queue_policy"] = {
                    "timeout_action": (
                        "DELAY"
                        if qp.get("timeout_action") == "continue"
                        else "REJECT"
                    ),
                    "default_timeout_microseconds": int(
                        qp.get("default_timeout_us", 0)
                    ),
                    "allow_timeout_override": bool(
                        qp.get("allow_timeout_override", True)
                    ),
                    "max_queue_size": int(qp.get("max_queue_size", 0)),
                }
            config["dynamic_batching"] = dynamic_batching
        if self.rate_limiter:
            config["rate_limiter"] = {
                "resources": [
                    dict(r) for r in self.rate_limiter.get("resources", [])
                ],
                "priority": int(self.rate_limiter.get("priority", 0)),
            }
        if self.ensemble_scheduling is not None:
            config["ensemble_scheduling"] = {
                "step": [dict(s) for s in
                         self.ensemble_scheduling.get("step", [])]
            }
        if isinstance(self.mesh, dict):
            # Mesh topology rides the config's parameters map (Triton
            # ModelParameter wire shape: {"string_value": ...}) so BOTH
            # protocols expose it — the gRPC ServerMetadataResponse has
            # no free-form field, the ModelConfig parameters map does.
            # A resolved plan reports the live topology (device ids
            # included); an unresolved declaration reports what was
            # asked for.
            plan = self.mesh_plan
            payload = (
                plan.describe()
                if plan is not None
                else {"axes": dict(self.mesh.get("axes", {})), "resolved": False}
            )
            config["parameters"] = {
                "mesh": {"string_value": json.dumps(payload)}
            }
        return config

    def labels(self, output_name: str) -> Optional[List[str]]:
        """Classification labels for an output (None if unlabeled)."""
        return None

    def placement(self):
        """Context manager placing this model's JAX work per ``device``.

        Honored by the server core around execute() and usable from
        warmup(). Falls back to the default device when the requested
        backend is unavailable (e.g. jax_platforms pinned away from cpu).
        """
        import contextlib

        if self.device == "cpu":
            try:
                import jax

                return jax.default_device(jax.devices("cpu")[0])
            except Exception:  # noqa: BLE001 - backend unavailable
                pass
        return contextlib.nullcontext()

    def execute(
        self, inputs: Dict[str, np.ndarray], parameters: Dict[str, Any]
    ) -> Dict[str, np.ndarray]:
        raise InferenceServerException(
            f"model '{self.name}' does not implement execute"
        )

    async def execute_decoupled(
        self, inputs: Dict[str, np.ndarray], parameters: Dict[str, Any]
    ) -> AsyncIterator[Dict[str, np.ndarray]]:
        raise InferenceServerException(
            f"model '{self.name}' is not decoupled"
        )
        yield {}  # pragma: no cover - makes this an async generator

    def warmup(self) -> None:
        """Called at load; jit-compile here so first request is fast."""


class ModelRepository:
    """Name -> model registry with Triton-style load/unload semantics.

    Models can be registered programmatically (``add_model``) or loaded from
    a repository directory where each subdirectory holds a ``model.py``
    defining ``create_model()`` (the python_backend analogue).
    """

    def __init__(self, repository_path: Optional[str] = None):
        self._models: Dict[str, Model] = {}
        self._state: Dict[str, str] = {}
        self._reason: Dict[str, str] = {}
        # per-name load/unload generation: async unload finalization and
        # batcher eviction only apply when no load() happened in between
        self._epoch: Dict[str, int] = {}
        # names whose "load failed" is a host-capacity (mesh) problem:
        # excluded from degraded() so one oversized mesh never pulls the
        # whole replica out of its load balancer
        self._capacity_failed: set = set()
        self._lock = threading.Lock()
        self._repository_path = repository_path

    def _set_state(self, name: str, state: str, reason: str = "") -> None:
        # lock held by caller
        self._state[name] = state
        self._reason[name] = reason
        if state == STATE_READY:
            self._capacity_failed.discard(name)

    def _classify_failure(self, name: str, capacity: bool) -> None:
        # lock held by caller. Membership must track the LATEST failure:
        # a capacity miss followed by a real load bug (corrupt weights)
        # must degrade, and vice versa.
        if capacity:
            self._capacity_failed.add(name)
        else:
            self._capacity_failed.discard(name)

    def add_model(self, model: Model, ready: bool = True) -> None:
        """Register a programmatic model. A warmup failure does NOT
        raise: the model registers as UNAVAILABLE with reason
        ``load failed: <why>`` — the same index semantics a failed
        directory load gets — so one unloadable model (e.g. a sharded
        model whose mesh needs more devices than the host has) degrades
        to a clean per-model 503 instead of blocking server startup.
        A later programmatic ``load()`` re-runs warmup and recovers it."""
        failure: Optional[str] = None
        capacity = False
        try:
            model.warmup()
        except Exception as e:  # noqa: BLE001 - surfaced via the index
            failure = f"load failed: {e}"
            capacity = _mesh_capacity_failure(e)
        with self._lock:
            self._models[model.name] = model
            if failure is not None:
                self._set_state(model.name, STATE_UNAVAILABLE, failure)
                self._classify_failure(model.name, capacity)
            else:
                self._set_state(
                    model.name, STATE_READY if ready else STATE_UNAVAILABLE
                )
            self._epoch[model.name] = self._epoch.get(model.name, 0) + 1

    def peek(self, name: str) -> Optional[Model]:
        """The registered model object regardless of readiness (the server
        core uses it to pin per-model state across an unload)."""
        with self._lock:
            return self._models.get(name)

    def get(self, name: str, version: str = "") -> Model:
        with self._lock:
            model = self._models.get(name)
            ready = self._state.get(name) == STATE_READY
        if model is None:
            raise InferenceServerException(
                f"Request for unknown model: '{name}' is not found"
            )
        if not ready:
            raise ModelUnavailableError(
                f"Request for unavailable model: '{name}' is not ready"
            )
        if version and version != model.version:
            raise InferenceServerException(
                f"Request for unknown model version: '{name}' version "
                f"{version} is not found"
            )
        return model

    def is_ready(self, name: str, version: str = "") -> bool:
        with self._lock:
            if name not in self._models:
                return False
            if version and self._models[name].version != version:
                return False
            return self._state.get(name) == STATE_READY

    def degraded(self) -> bool:
        """True when the ready set is degraded: a model is mid-load or
        stuck in a failed load. Intentional removals (unloading/unloaded)
        do NOT degrade readiness — draining one model out of a serving
        process is normal operations, not an unhealthy server."""
        with self._lock:
            for name in self._models:
                if self._state.get(name) == STATE_LOADING:
                    return True
                if (
                    self._reason.get(name, "").startswith("load failed")
                    and name not in self._capacity_failed
                ):
                    return True
        return False

    def index(self) -> List[Dict[str, str]]:
        with self._lock:
            return [
                {
                    "name": m.name,
                    "version": m.version,
                    "state": self._state.get(m.name, STATE_UNAVAILABLE),
                    "reason": self._reason.get(m.name, ""),
                }
                for m in self._models.values()
            ]

    def load(self, name: str, config_override: Optional[str] = None) -> None:
        """Load (or reload) a model by name — atomically.

        Directory models are (re-)imported from ``<repo>/<name>/model.py``;
        an already-serving model keeps serving the OLD object until the
        new one passes ``warmup()``, then requests cut over in one swap.
        A failed load leaves the old model serving (the error still
        propagates to the caller). Programmatic models are re-warmed on
        reload — a bare re-mark-ready would resurrect a model that was
        unloaded precisely because its state went bad.
        """
        model_py = (
            os.path.join(self._repository_path, name, "model.py")
            if self._repository_path
            else None
        )
        with self._lock:
            known = name in self._models
            was_ready = self._state.get(name) == STATE_READY
        if model_py is None or not os.path.exists(model_py):
            if not known:
                if self._repository_path is None:
                    raise InferenceServerException(
                        f"failed to load '{name}': no model repository "
                        "configured"
                    )
                raise InferenceServerException(
                    f"failed to load '{name}': {model_py} not found"
                )
            # Programmatic reload: same object, fresh warmup.
            model = self._models[name]
            try:
                model.warmup()
            except Exception as e:  # noqa: BLE001 - surfaced to caller
                with self._lock:
                    if not was_ready:
                        self._set_state(
                            name, STATE_UNAVAILABLE, f"load failed: {e}"
                        )
                        self._classify_failure(
                            name, _mesh_capacity_failure(e)
                        )
                raise InferenceServerException(
                    f"failed to load '{name}': {e}"
                ) from e
            with self._lock:
                self._set_state(name, STATE_READY)
                self._epoch[name] = self._epoch.get(name, 0) + 1
            return
        with self._lock:
            # Old model (if ready) keeps serving through the load; a brand
            # new name is LOADING (not ready) until warmup passes.
            if known and was_ready:
                self._reason[name] = "loading"
            else:
                self._set_state(name, STATE_LOADING, "loading")
        try:
            spec = importlib.util.spec_from_file_location(
                f"client_tpu_model_{name}", model_py
            )
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            if not hasattr(module, "create_model"):
                raise InferenceServerException(
                    f"failed to load '{name}': model.py must define "
                    "create_model()"
                )
            model = module.create_model()
            if config_override:
                try:
                    overrides = json.loads(config_override)
                except json.JSONDecodeError as e:
                    raise InferenceServerException(
                        f"failed to load '{name}': bad config override: {e}"
                    ) from None
                if "max_batch_size" in overrides:
                    model.max_batch_size = int(overrides["max_batch_size"])
            model.name = name
            model.warmup()
        except Exception as e:  # noqa: BLE001 - load failure bookkeeping
            with self._lock:
                if known and was_ready:
                    # old model still serving: load failure is an event,
                    # not a state — readiness is untouched
                    self._reason[name] = ""
                elif known:
                    self._set_state(
                        name, STATE_UNAVAILABLE, f"load failed: {e}"
                    )
                    self._classify_failure(name, _mesh_capacity_failure(e))
                else:
                    # never-loaded name: no registry entry to degrade
                    self._state.pop(name, None)
                    self._reason.pop(name, None)
            if isinstance(e, InferenceServerException):
                raise
            raise InferenceServerException(
                f"failed to load '{name}': {e}"
            ) from e
        # Atomic cutover: one assignment under the lock; requests admitted
        # before this instant run to completion against the old object.
        with self._lock:
            self._models[name] = model
            self._set_state(name, STATE_READY)
            self._epoch[name] = self._epoch.get(name, 0) + 1

    def unload(self, name: str) -> int:
        """Begin unloading: the model stops admitting immediately (new
        requests get a 503/UNAVAILABLE :class:`ModelUnavailableError`)
        while queued and in-flight work drains. Returns the unload epoch;
        the caller (ServerCore) drains and then calls
        :meth:`finish_unload` with it."""
        with self._lock:
            if name not in self._models:
                raise InferenceServerException(
                    f"failed to unload '{name}': model is not loaded"
                )
            self._set_state(name, STATE_UNLOADING, "unloading")
            self._epoch[name] = self._epoch.get(name, 0) + 1
            return self._epoch[name]

    def epoch_of(self, name: str) -> Optional[int]:
        """The model's current load/unload generation (None if unknown).
        Callers finalizing an async unload compare against the epoch
        :meth:`unload` returned — a mismatch means a load() superseded
        the unload and its cleanup must not touch the new model."""
        with self._lock:
            return self._epoch.get(name)

    def finish_unload(self, name: str, epoch: Optional[int] = None) -> None:
        """Mark an unload complete (state UNAVAILABLE, reason "unloaded").
        With ``epoch``, a no-op when a load() superseded the unload."""
        with self._lock:
            if epoch is not None and self._epoch.get(name) != epoch:
                return
            if self._state.get(name) == STATE_UNLOADING:
                self._set_state(name, STATE_UNAVAILABLE, "unloaded")

    def scan(self) -> None:
        """Load every model directory found in the repository path."""
        if not self._repository_path:
            return
        for entry in sorted(os.listdir(self._repository_path)):
            if os.path.exists(
                os.path.join(self._repository_path, entry, "model.py")
            ):
                self.load(entry)


def build_repository(
    repository_path=None, builtin: bool = True, zoo: bool = False
) -> "ModelRepository":
    """Standard repository bootstrap shared by the CLI server, the
    in-process test server, and the embedded (perf local-backend) runner:
    fixture models, optional model-zoo adapters, then a directory scan."""
    repository = ModelRepository(repository_path)
    if builtin:
        from client_tpu.server.models import register_builtin_models

        register_builtin_models(repository)
    if zoo:
        from client_tpu.models.serving import register_zoo_models

        register_zoo_models(repository)
    repository.scan()
    return repository
