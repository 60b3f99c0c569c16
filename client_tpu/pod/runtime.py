"""``jax.distributed`` bootstrap for a multi-process pod.

A pod is N processes sharing one global device mesh: process 0 runs the
coordinator service, every process calls :func:`initialize` with the
same coordinator address and its own ``process_index``, and after that
``jax.devices()`` returns the GLOBAL device list (local + every other
process's devices) so process-spanning meshes resolve exactly like
single-process ones.

The identity triple (coordinator address, process index, process count)
travels as environment variables — :class:`PodConfig` parses and emits
them — because the launcher hands them to subprocesses and the pytest
``pod`` fixture re-execs tests under them. On CPU the fake pod uses the
gloo collectives backend (``jax_cpu_collectives_implementation``); real
TPU pods get their collectives from the platform and ignore that knob.

``initialize`` must run BEFORE the first jax backend touch: jax freezes
its device count (and its distributed-ness) at first backend init.

Self-healing (PR 20): the distributed runtime is constructed MANUALLY
(service + client via ``jax._src.lib._jax``) rather than through
``jax.distributed.initialize``, for one reason — survivability.  The
stock client installs a missed-heartbeat callback that LOG(FATAL)s the
whole process the moment a peer dies, and its destructor runs a
shutdown barrier that can never complete against a dead peer (also
fatal).  Building the pieces ourselves lets us (a) swap in a benign
heartbeat callback so a dead peer is an *event*, not a process abort,
and (b) :func:`abandon` a broken runtime by stashing the old
service/client (their destructors must never run) and wiping the
backend caches, after which :func:`reinitialize` assembles a fresh pod
at a NEW coordinator address across survivors + replacement.  This is
validated for the CPU/gloo fake pod this repo's CI runs; real TPU
re-slicing has platform steps this module does not attempt.
"""

import dataclasses
import os
import sys
from typing import Any, Dict, List, Mapping, Optional, Tuple

#: environment handoff keys (launcher -> worker / fixture -> re-exec)
ENV_COORDINATOR = "CLIENT_TPU_POD_COORDINATOR"
ENV_PROCESS_INDEX = "CLIENT_TPU_POD_PROCESS_INDEX"
ENV_PROCESS_COUNT = "CLIENT_TPU_POD_PROCESS_COUNT"
ENV_LOCAL_DEVICES = "CLIENT_TPU_POD_LOCAL_DEVICES"
ENV_BUS = "CLIENT_TPU_POD_BUS"


class PodConfigError(ValueError):
    """The pod environment/identity handoff is malformed (a launcher
    bug — every field is launcher-emitted, never operator-typed)."""


@dataclasses.dataclass(frozen=True)
class PodConfig:
    """One process's pod identity: who coordinates, which process this
    is, how many there are, and (for the CPU fake pod) how many virtual
    devices each process is capped to."""

    coordinator_address: str
    process_index: int
    process_count: int
    #: per-process virtual-device cap (0 = platform default). The cap is
    #: applied via XLA_FLAGS by the launcher BEFORE the process starts —
    #: it is carried here so ``describe()``-style surfaces can report it.
    local_devices: int = 0
    #: step-bus address (coordinator binds, workers connect); None when
    #: the pod runs without the serving bus (e.g. SPMD lockstep tests)
    bus_address: Optional[str] = None
    #: how long ``jax.distributed.initialize`` may wait for the full
    #: pod to assemble before giving up (a missing worker must become a
    #: clean error, not a forever-hang)
    init_timeout_s: float = 60.0

    def __post_init__(self):
        if not self.coordinator_address or ":" not in self.coordinator_address:
            raise PodConfigError(
                f"pod coordinator address must be host:port, got "
                f"{self.coordinator_address!r}"
            )
        if self.process_count < 1:
            raise PodConfigError(
                f"pod process_count must be >= 1, got {self.process_count}"
            )
        if not 0 <= self.process_index < self.process_count:
            raise PodConfigError(
                f"pod process_index {self.process_index} out of range for "
                f"process_count {self.process_count}"
            )

    @property
    def is_coordinator(self) -> bool:
        return self.process_index == 0

    @staticmethod
    def from_env(
        env: Optional[Mapping[str, str]] = None,
    ) -> Optional["PodConfig"]:
        """Parse the pod identity from the environment; ``None`` when the
        process is not a pod member (no coordinator variable set)."""
        env = os.environ if env is None else env
        address = env.get(ENV_COORDINATOR)
        if not address:
            return None
        try:
            index = int(env.get(ENV_PROCESS_INDEX, ""))
            count = int(env.get(ENV_PROCESS_COUNT, ""))
        except ValueError as e:
            raise PodConfigError(
                f"pod process index/count must be integers: {e}"
            ) from e
        local = int(env.get(ENV_LOCAL_DEVICES, "0") or "0")
        return PodConfig(
            coordinator_address=address,
            process_index=index,
            process_count=count,
            local_devices=local,
            bus_address=env.get(ENV_BUS) or None,
        )

    def env(self) -> Dict[str, str]:
        """The environment block a launcher merges into a pod process
        (the inverse of :meth:`from_env`)."""
        block = {
            ENV_COORDINATOR: self.coordinator_address,
            ENV_PROCESS_INDEX: str(self.process_index),
            ENV_PROCESS_COUNT: str(self.process_count),
            ENV_LOCAL_DEVICES: str(self.local_devices),
        }
        if self.bus_address:
            block[ENV_BUS] = self.bus_address
        return block


@dataclasses.dataclass(frozen=True)
class PodRuntime:
    """The live pod after :func:`initialize`: identity plus the observed
    global/local device split (what ``describe()`` surfaces report)."""

    config: PodConfig
    process_index: int
    process_count: int
    global_device_count: int
    local_device_count: int

    def describe(self) -> Dict[str, Any]:
        return {
            "process_index": self.process_index,
            "process_count": self.process_count,
            "global_device_count": self.global_device_count,
            "local_device_count": self.local_device_count,
            "coordinator": self.config.coordinator_address,
        }


# Abandoned distributed runtimes: (service, client) pairs whose
# destructors must NEVER run — a client destructor runs a shutdown
# barrier, and against a dead peer that barrier LOG(FATAL)s the
# surviving process. Leaking one socket pair per recovery is the price
# of staying alive; recoveries are rare by definition.
_ABANDONED: List[Tuple[Any, Any]] = []


#: seconds of silence before the coordination service declares a member
#: dead (service and client must agree)
_HEARTBEAT_TIMEOUT_S = 100


def _heartbeat_logger(process_index: int):
    """The client's missed-heartbeat callback. The stock one aborts the
    process; ours records the event and keeps serving — the supervisor
    (watching the step bus) owns the recovery decision, not the
    coordination-service heartbeat."""

    def on_missed(status) -> None:
        try:
            print(
                f"[pod proc {process_index}] coordination heartbeat "
                f"missed: {status}",
                file=sys.stderr,
                flush=True,
            )
        except Exception:  # noqa: BLE001 - a logger must never raise here
            pass

    return on_missed


def _pod_init(
    address: str,
    process_index: int,
    process_count: int,
    timeout_s: float,
) -> None:
    """Construct the distributed runtime by hand and install it as
    jax's global distributed state (see the module docstring for why
    not ``jax.distributed.initialize``). Process 0 additionally hosts
    the coordination service, bound on every interface at the
    address's port."""
    from jax._src import distributed
    from jax._src.lib import _jax

    state = distributed.global_state
    if process_index == 0:
        bind = "[::]:" + address.rsplit(":", 1)[1]
        state.service = _jax.get_distributed_runtime_service(
            bind, process_count, heartbeat_timeout=_HEARTBEAT_TIMEOUT_S
        )
    client = _jax.get_distributed_runtime_client(
        address,
        process_index,
        init_timeout=int(timeout_s),
        heartbeat_timeout=_HEARTBEAT_TIMEOUT_S,
        shutdown_on_destruction=False,
        missed_heartbeat_callback=_heartbeat_logger(process_index),
        use_compression=True,
    )
    client.connect()
    state.client = client
    state.process_id = process_index
    state.num_processes = process_count
    state.coordinator_address = address


def initialize(config: PodConfig, platform: Optional[str] = None) -> PodRuntime:
    """Join the pod: bring up ``jax.distributed`` for this process.

    Must run before the first jax backend init (the device count and the
    distributed runtime are frozen there). On the CPU platform the gloo
    collectives backend is selected so cross-process ``psum``/gather
    work on the fake pod; TPU pods take the platform default.

    Raises ``RuntimeError`` (from xla) when the pod cannot assemble
    within ``config.init_timeout_s`` — callers surface that as a load
    failure, not a hang.
    """
    import jax

    if platform:
        jax.config.update("jax_platforms", platform)
    effective = platform or os.environ.get("JAX_PLATFORMS", "")
    if "cpu" in effective or not effective:
        # the CPU fake pod needs a real collectives implementation; the
        # default ("none") refuses multi-process meshes outright
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    _pod_init(
        config.coordinator_address,
        config.process_index,
        config.process_count,
        config.init_timeout_s,
    )
    return PodRuntime(
        config=config,
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        global_device_count=len(jax.devices()),
        local_device_count=len(jax.local_devices()),
    )


def abandon() -> None:
    """Walk away from a broken distributed runtime without dying.

    Stashes the live service/client (so neither destructor — each fatal
    against a dead peer — ever runs), clears jax's compilation caches
    and live backends, and leaves the process ready for
    :func:`reinitialize`. Deliberately NOT ``jax.distributed.shutdown``:
    its barrier hangs-then-aborts when any peer is already dead, which
    is exactly the situation recovery starts from."""
    import jax
    from jax._src import distributed, xla_bridge

    state = distributed.global_state
    if state.service is not None or state.client is not None:
        _ABANDONED.append((state.service, state.client))
    state.service = None
    state.client = None
    jax.clear_caches()
    xla_bridge._clear_backends()


def reinitialize(config: PodConfig, platform: Optional[str] = None) -> PodRuntime:
    """Abandon the current runtime and assemble a fresh pod.

    ``config`` carries the NEW coordinator address (the old port may
    still be held by the abandoned service) and the member's identity in
    the new assembly. Sequencing matters: the coordinator must be inside
    ``reinitialize`` (new service bound) before a replacement process
    calls :func:`initialize` — a client whose RegisterTask times out
    aborts its process rather than raising."""
    abandon()
    return initialize(config, platform=platform)


def pod_info() -> Dict[str, int]:
    """This process's (process_index, process_count) as jax sees them —
    (0, 1) for a plain single-process replica. Safe to call whether or
    not the process ever joined a pod; used by the topology/metadata
    surfaces to stamp every devices block."""
    try:
        import jax

        return {
            "process_index": int(jax.process_index()),
            "process_count": int(jax.process_count()),
        }
    except Exception:  # noqa: BLE001 - no backend available
        return {"process_index": 0, "process_count": 1}
