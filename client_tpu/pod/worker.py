"""Pod serving entrypoint: ``python -m client_tpu.pod.worker``.

Every pod member runs this module with its identity in the environment
(the launcher's handoff). All members walk the SAME bootstrap in
lockstep — join ``jax.distributed``, build one tp-sharded
:class:`~client_tpu.llm.serving.LlmEngineModel` over the GLOBAL device
list, run warmup (whose probe device calls are collectives every member
must enter) — and then split:

- **process 0 (coordinator)** opens the step bus, installs the
  bus-broadcast ``device_fn_wrapper`` (each engine device call is
  broadcast to the workers BEFORE the coordinator executes its own
  copy), registers the model, and serves the ordinary HTTP/gRPC
  front-ends. To the fleet this process IS the pod: one replica, one
  model row, with per-member liveness/duty exported as
  ``tpu_pod_process_up`` / ``tpu_pod_process_duty_ratio``.
- **processes 1..N-1 (workers)** run the follower loop: execute every
  broadcast step against their local shards and ack with cumulative
  busy time. They serve no requests and export no metrics of their own.

The model itself deliberately stays the repo's tiny llama (float32 so
tp parity holds to 1e-5): the pod machinery is about WHERE the mesh
lives, not model scale.
"""

import dataclasses
import json
import os
import signal
import sys
import threading
import time
from typing import Callable, Dict, Optional

import numpy as np

from client_tpu.compile_cache import enable_compile_cache
from client_tpu.pod.bus import REINIT_OP, StepBus, StepFollower
from client_tpu.pod.runtime import (
    PodConfig,
    PodRuntime,
    initialize,
    reinitialize,
)
from client_tpu.utils import InferenceServerException

ENV_PORTS_FILE = "CLIENT_TPU_POD_PORTS_FILE"
ENV_MODEL_NAME = "CLIENT_TPU_POD_MODEL_NAME"
ENV_MAX_SEQ_LEN = "CLIENT_TPU_POD_MAX_SEQ_LEN"
#: supervisor -> coordinator recovery-plan handoff (JSON file; the
#: supervisor writes {"epoch", "coordinator_address", "member"} and
#: sends SIGUSR1 — see client_tpu.pod.supervisor)
ENV_CONTROL_FILE = "CLIENT_TPU_POD_CONTROL_FILE"


def build_model(runtime: PodRuntime):
    """The pod's model: tiny llama (float32 for tp parity), tp spanning
    the ENTIRE global mesh — which is what makes it unservable by any
    one device-capped member alone."""
    import jax.numpy as jnp

    from client_tpu.llm.serving import LlmEngineModel
    from client_tpu.models import llama

    name = os.environ.get(ENV_MODEL_NAME, "llm_pod")
    max_seq_len = int(os.environ.get(ENV_MAX_SEQ_LEN, "256"))
    config = llama.LlamaConfig.tiny(
        max_seq_len=max_seq_len, dtype=jnp.float32
    )
    model = LlmEngineModel(
        name, config=config, tp=runtime.global_device_count
    )
    # pod supervision owns recovery here: a solo engine reload cannot
    # fix a broken MESH, and the coordinator's recovery procedure
    # (member respawn + jax.distributed re-init + lockstep re-warmup)
    # replaces the tier-1 controller wholesale
    model.auto_recovery = False
    return model


class _Duty:
    """Coordinator-side busy-time accumulator (its own device calls —
    workers report theirs through step acks)."""

    def __init__(self, clock_ns: Callable[[], int] = time.monotonic_ns):
        self._clock_ns = clock_ns
        self.start_ns = clock_ns()
        self.busy_ns = 0
        self._lock = threading.Lock()

    def add(self, ns: int) -> None:
        with self._lock:
            self.busy_ns += ns

    def ratio(self) -> float:
        wall = max(1, self._clock_ns() - self.start_ns)
        with self._lock:
            return self.busy_ns / wall


def make_bus_wrapper(
    bus: StepBus,
    duty: _Duty,
    clock_ns: Callable[[], int] = time.monotonic_ns,
):
    """The coordinator's ``device_fn_wrapper``: broadcast each step's
    host args on the bus, then run the local copy. The broadcast-first
    order is the no-hang guarantee — a dead worker raises a retryable
    UNAVAILABLE here, before this process enters the collective."""
    import jax

    def wrapper(prefill, decode, decode_multi):
        def timed(fn, *args):
            t0 = clock_ns()
            out = fn(*args)
            jax.block_until_ready(out)
            duty.add(clock_ns() - t0)
            return out

        def wrapped_prefill(tokens, page_table, pages, last_index,
                            start_index):
            bus.broadcast(
                "prefill",
                (
                    np.asarray(tokens, np.int32),
                    np.asarray(page_table, np.int32),
                    int(last_index),
                    int(start_index),
                ),
            )
            return timed(
                prefill, tokens, page_table, pages, last_index, start_index
            )

        def wrapped_decode(prev_ids, lane_map, host_tokens, positions,
                           page_tables, pages):
            # prev_ids is not sent: it is the ids of the last decode
            # call, which every member has from its own copy of it
            bus.broadcast(
                "decode",
                (
                    np.asarray(lane_map, np.int32),
                    np.asarray(host_tokens, np.int32),
                    np.asarray(positions, np.int32),
                    np.asarray(page_tables, np.int32),
                ),
            )
            return timed(
                decode, prev_ids, lane_map, host_tokens, positions,
                page_tables, pages,
            )

        wrapped_multi = None
        if decode_multi is not None:
            def wrapped_multi(tokens, positions, lengths, page_tables,
                              pages):
                bus.broadcast(
                    "decode_multi",
                    (
                        np.asarray(tokens, np.int32),
                        np.asarray(positions, np.int32),
                        np.asarray(lengths, np.int32),
                        np.asarray(page_tables, np.int32),
                    ),
                )
                return timed(
                    decode_multi, tokens, positions, lengths, page_tables,
                    pages,
                )

        return wrapped_prefill, wrapped_decode, wrapped_multi

    return wrapper


def follower_handlers(model) -> Dict[str, Callable[..., None]]:
    """A worker's step handler table: each op re-runs the corresponding
    UNWRAPPED device fn against this process's page-pool shards. The
    block_until_ready keeps the ack's busy-time honest (and this member
    from queueing unboundedly far behind the coordinator)."""
    import jax

    prefill, decode, decode_multi = model._device_fns
    state = {
        "pages": model.engine._pages,
        # the ids of this member's last decode call: what the
        # coordinator's engine hands its own copy as prev_ids
        "ids": np.zeros([model.engine_config.ids_width], np.int32),
    }

    def on_prefill(tokens, page_table, last_index, start_index):
        logits, state["pages"] = prefill(
            tokens, page_table, state["pages"],
            int(last_index), int(start_index),
        )
        jax.block_until_ready(logits)

    def on_decode(lane_map, host_tokens, positions, page_tables):
        state["ids"], logits, state["pages"] = decode(
            state["ids"], lane_map, host_tokens, positions, page_tables,
            state["pages"],
        )
        jax.block_until_ready(logits)

    handlers = {"prefill": on_prefill, "decode": on_decode}
    if decode_multi is not None:
        def on_decode_multi(tokens, positions, lengths, page_tables):
            logits, state["pages"] = decode_multi(
                tokens, positions, lengths, page_tables, state["pages"]
            )
            jax.block_until_ready(logits)

        handlers["decode_multi"] = on_decode_multi
    return handlers


def _start_pod_reporter(
    metrics,
    duty: _Duty,
    get_state: Callable[[], tuple],
    stop: threading.Event,
) -> threading.Thread:
    """Refresh the per-member liveness/duty gauges once a second from
    the bus's ack bookkeeping. ``get_state`` returns the CURRENT
    (bus, runtime) pair — a recovery swaps both out underneath."""

    def run() -> None:
        while not stop.wait(1.0):
            metrics.set_pod_process(0, True, duty.ratio())
            bus, runtime = get_state()
            if bus is None:
                continue
            wall = max(1, duty._clock_ns() - duty.start_ns)
            busy = bus.worker_busy_ns()
            alive = set(bus.alive_workers())
            for index in range(1, runtime.process_count):
                metrics.set_pod_process(
                    index, index in alive, busy.get(index, 0) / wall
                )

    thread = threading.Thread(target=run, name="pod-reporter", daemon=True)
    thread.start()
    return thread


#: How long parked survivors wait for a recovery plan to claim them
#: before the coordinator gives up on rescue.  The supervisor claims
#: them within ~1s of a member death (0.2s poll + plan write + SIGUSR1),
#: so this only fires on an UNsupervised pod — where waiting any longer
#: just turns the quarantine into the hung stream it exists to prevent.
RESCUE_DEADLINE_ENV = "TPU_POD_RESCUE_DEADLINE_S"
_RESCUE_DEADLINE_S = 15.0


def _wire_pod_fatal_hook(engine, holder: dict, quarantined: threading.Event,
                         retry_after_s: float = 2.0,
                         loop=None,
                         clock: Callable[[], float] = time.monotonic) -> None:
    """Make the engine quarantine-not-fail on a fatal: survivors park in
    ``holder["survivors"]`` until the recovered engine adopts them, and
    submits answer 503 + Retry-After while the pod re-assembles.

    The park is deadline-bounded ("hung ≡ killed" applies to rescues
    too): if no recovery plan claims the survivors — ``_recover_pod``
    sets ``holder["rescued"]`` the moment it starts — within the rescue
    deadline, they fail with a clean retryable UNAVAILABLE and the
    engine drops its recovering promise, instead of holding client
    streams open for a supervisor that does not exist."""
    engine.retry_after_s = retry_after_s
    holder.setdefault("lock", threading.Lock())
    rescued = threading.Event()
    holder["rescued"] = rescued
    deadline_s = float(
        os.environ.get(RESCUE_DEADLINE_ENV, "") or _RESCUE_DEADLINE_S
    )

    def abandon(exc: BaseException, started: float) -> None:
        if rescued.wait(deadline_s):
            return
        with holder["lock"]:
            if rescued.is_set():
                return  # a recovery claimed them between wait and lock
            orphans = list(holder["survivors"])
            holder["survivors"][:] = []
        fail = InferenceServerException(
            f"pod quarantined ({exc}) and no recovery plan arrived "
            f"within {deadline_s:.0f}s; resubmit",
            status="UNAVAILABLE",
        )

        def finish() -> None:
            engine.recovering = False
            for seq in orphans:
                seq.fail(fail)

        delivered = False
        if loop is not None and not loop.is_closed():
            try:
                loop.call_soon_threadsafe(finish)
                delivered = True
            except RuntimeError:
                pass  # loop closed between the check and the call
        if not delivered:
            finish()
        metrics = getattr(engine, "metrics", None)
        if metrics is not None:
            metrics.observe_recovery("pod", "abandoned", clock() - started)
        print(
            f"pod rescue abandoned: {len(orphans)} parked sequences "
            f"failed after {deadline_s:.0f}s without a recovery plan",
            file=sys.stderr, flush=True,
        )

    def on_fatal(exc: BaseException) -> None:
        holder["survivors"].extend(engine.detach_survivors())
        quarantined.set()
        threading.Thread(
            target=abandon, args=(exc, clock()),
            name="pod-rescue-deadline", daemon=True,
        ).start()

    engine.on_fatal = on_fatal


def _write_ports(server, model, runtime: PodRuntime, epoch: int) -> None:
    from client_tpu.perf.fleet_runner import write_ports_file

    ports_path = os.environ.get(ENV_PORTS_FILE)
    if ports_path:
        write_ports_file(
            ports_path,
            {
                "http_port": server.http_port,
                "grpc_port": server.grpc_port,
                "model": model.name,
                "process_count": runtime.process_count,
                "global_device_count": runtime.global_device_count,
                "local_device_count": runtime.local_device_count,
                "epoch": epoch,
            },
        )


def _recover_pod(model, core, server, state: dict, quarantined:
                 threading.Event, clock: Callable[[], float]) -> bool:
    """The coordinator's half of a supervised pod recovery.

    The supervisor wrote the plan (new coordinator address + epoch) to
    the control file and signalled SIGUSR1.  Sequencing is load-bearing
    (see pod/runtime.py): quarantine → tell survivors where to re-join →
    tear down the old bus → *marker file* (the supervisor's cue to spawn
    the replacement, which must not call initialize before our new
    service exists) → re-init jax.distributed → lockstep re-warmup →
    accept everyone on a fresh bus → adopt the parked survivors.
    Returns False when recovery failed (the pod should exit and let the
    fleet tier replace the whole replica)."""
    from client_tpu.pod.bus import PodWorkerLostError  # noqa: F401

    config: PodConfig = state["config"]
    runtime: PodRuntime = state["runtime"]
    bus: Optional[StepBus] = state["bus"]
    duty: _Duty = state["duty"]
    metrics = core.metrics
    started = clock()
    control_path = os.environ.get(ENV_CONTROL_FILE, "")
    holder = state["holder"]
    # claim the parked survivors FIRST: the fatal hook's rescue-deadline
    # timer fails whatever is still unclaimed when it expires, and this
    # recovery now owns them
    with holder.setdefault("lock", threading.Lock()):
        rescued = holder.get("rescued")
        if rescued is not None:
            rescued.set()
    try:
        with open(control_path, "r", encoding="utf-8") as f:
            plan = json.load(f)
        epoch = int(plan["epoch"])
        new_address = str(plan["coordinator_address"])
        lost = int(plan.get("member", -1))
        print(
            f"pod recovery epoch {epoch}: member {lost} lost, "
            f"re-assembling at {new_address}",
            flush=True,
        )
        core.lifecycle.begin_drain()
        engine = model.engine
        if engine is not None and not engine._closed:
            # idle-pod loss: nothing tripped the step loop, so force the
            # quarantine (parks nothing if nothing was running)
            engine.quarantine(f"pod member {lost} lost")
        if not quarantined.wait(timeout=10.0):
            raise RuntimeError("engine did not quarantine within 10s")
        quarantined.clear()
        if bus is not None:
            # survivors ack, leave their follower loops, and head for
            # the new assembly; the dead member is silently dropped
            bus.broadcast_surviving(REINIT_OP, (new_address, epoch))
            bus.stop()
        # the supervisor's cue: our new coordination service is about to
        # bind, so the replacement process may now be spawned (it takes
        # a full interpreter+jax start to reach initialize — far longer
        # than our service bind)
        with open(control_path + f".started.{epoch}", "w",
                  encoding="utf-8") as f:
            f.write(str(epoch))
        new_config = dataclasses.replace(
            config, coordinator_address=new_address
        )
        runtime = reinitialize(new_config)
        state["config"] = new_config
        state["runtime"] = runtime
        # the old backend's arrays (params, KV pages) died with the old
        # runtime; dropping the cached params makes reload() re-init
        # them from the same PRNGKey(0) — bit-identical, which is what
        # keeps resumed streams token-identical across the respawn
        model._params = None
        with holder["lock"]:
            survivors = list(holder["survivors"])
            holder["survivors"][:] = []
        new_bus = None
        if new_config.process_count > 1:
            new_bus = StepBus(
                num_workers=new_config.process_count - 1,
                address=new_config.bus_address,
            )
            model.device_fn_wrapper = make_bus_wrapper(new_bus, duty)
        state["bus"] = new_bus
        # lockstep point: survivors + replacement mirror these probes
        model.reload()
        if new_bus is not None:
            new_bus.accept_workers()
        model.bind_core(core)
        _wire_pod_fatal_hook(model.engine, holder, quarantined,
                             loop=server._loop)
        if survivors:
            server._loop.call_soon_threadsafe(model.engine.adopt, survivors)
        # the replaced member's gauge children would otherwise linger at
        # their last pre-kill values forever; prune + re-seed
        for index in range(runtime.process_count):
            metrics.prune_pod_process(index)
            metrics.set_pod_process(index, True, 0.0)
        _write_ports(server, model, runtime, epoch)
        core.lifecycle.resume()
        duration = clock() - started
        metrics.observe_recovery("pod", "success", duration)
        print(
            f"pod recovery epoch {epoch} complete in {duration:.2f}s "
            f"({len(survivors)} sequences resumed)",
            flush=True,
        )
        return True
    except Exception as e:  # noqa: BLE001 - recovery is best-effort
        metrics.observe_recovery("pod", "failed", clock() - started)
        print(f"pod recovery failed: {e!r}", file=sys.stderr, flush=True)
        return False


def _serve_coordinator(model, config: PodConfig, runtime: PodRuntime) -> int:
    from client_tpu.server.core import ServerCore
    from client_tpu.server.model_repository import ModelRepository
    from client_tpu.testing.inprocess import InProcessServer

    bus = None
    duty = _Duty()
    if config.process_count > 1:
        bus = StepBus(
            num_workers=config.process_count - 1, address=config.bus_address
        )
        model.device_fn_wrapper = make_bus_wrapper(bus, duty)
    # lockstep point: every member runs warmup's probe collectives now
    model.warmup()
    if bus is not None:
        bus.accept_workers()
    # the repository re-runs warmup on add_model/load — a second probe
    # sequence here would run collectives the workers don't mirror, so
    # the already-warm model's warmup is pinned to a no-op (reload()
    # goes through the class, bypassing this pin on purpose)
    model.warmup = lambda: None  # type: ignore[method-assign]
    repository = ModelRepository()
    core = ServerCore(repository)
    repository.add_model(model)
    server = InProcessServer(
        core=core, builtin_models=False, grpc="aio"
    ).start()
    stop = threading.Event()
    metrics = core.metrics
    metrics.set_pod_process(0, True, 0.0)
    if bus is not None:
        for index in range(1, runtime.process_count):
            metrics.set_pod_process(index, True, 0.0)
    # supervised-recovery state: the fatal hook parks surviving
    # sequences; SIGUSR1 runs the recovery plan from the control file
    holder = {"survivors": []}
    quarantined = threading.Event()
    _wire_pod_fatal_hook(model.engine, holder, quarantined,
                         loop=server._loop)
    state = {
        "config": config, "runtime": runtime, "bus": bus, "duty": duty,
        "holder": holder,
    }
    reporter_state = lambda: (state["bus"], state["runtime"])  # noqa: E731
    _start_pod_reporter(metrics, duty, reporter_state, stop)
    _write_ports(server, model, runtime, epoch=0)
    print(
        f"pod coordinator up: {runtime.process_count} processes, "
        f"{runtime.global_device_count} global devices, "
        f"http={server.http_port} grpc={server.grpc_port}",
        flush=True,
    )
    wake = threading.Event()
    flags = {"stop": False, "recover": False}

    def on_stop(*_args) -> None:
        flags["stop"] = True
        wake.set()

    def on_recover(*_args) -> None:
        flags["recover"] = True
        wake.set()

    signal.signal(signal.SIGTERM, on_stop)
    signal.signal(signal.SIGINT, on_stop)
    signal.signal(signal.SIGUSR1, on_recover)
    rc = 0
    while True:
        wake.wait()
        wake.clear()
        if flags["stop"]:
            break
        if flags["recover"]:
            flags["recover"] = False
            if not _recover_pod(model, core, server, state, quarantined,
                                clock=time.monotonic):
                rc = 3
                break
    stop.set()
    if state["bus"] is not None:
        state["bus"].stop()
    # pod shutdown: drop every member's gauge children so a scrape of a
    # half-stopped coordinator never shows stale liveness
    for index in range(state["runtime"].process_count):
        metrics.prune_pod_process(index)
    server.stop()
    return rc


def _follow_worker(model, config: PodConfig) -> int:
    # lockstep point: mirrors the coordinator's warmup collectives
    model.warmup()
    follower = StepFollower(config.bus_address, config.process_index)
    while True:
        print(
            f"pod worker {config.process_index} following "
            f"{config.bus_address}",
            flush=True,
        )
        reason = follower.follow(follower_handlers(model))
        if reason != "reinit":
            print(
                f"pod worker {config.process_index} done: {reason}",
                flush=True,
            )
            follower.close()
            return 0
        # a surviving member's half of a supervised recovery: the
        # coordinator told us where the NEW assembly lives; mirror its
        # sequence — abandon the broken runtime, re-join at the new
        # address, rebuild the model (old backend arrays died with the
        # old runtime), re-enter the lockstep warmup probes, and rejoin
        # the bus (whose connect retries cover the coordinator's
        # re-warmup window)
        new_address, epoch = follower.reinit_args
        follower.close()
        print(
            f"pod worker {config.process_index} re-joining epoch {epoch} "
            f"at {new_address}",
            flush=True,
        )
        config = dataclasses.replace(
            config, coordinator_address=str(new_address)
        )
        runtime = reinitialize(config)
        model = build_model(runtime)
        model.warmup()
        follower = StepFollower(config.bus_address, config.process_index)


def main() -> int:
    config = PodConfig.from_env()
    if config is None:
        print(
            "not a pod member: CLIENT_TPU_POD_COORDINATOR is unset "
            "(use client_tpu.pod.PodLauncher)",
            file=sys.stderr,
        )
        return 2
    enable_compile_cache()
    runtime = initialize(config)
    print(f"pod member up: {runtime.describe()}", flush=True)
    model = build_model(runtime)
    if config.is_coordinator:
        return _serve_coordinator(model, config, runtime)
    if not config.bus_address:
        print("pod worker needs a bus address", file=sys.stderr)
        return 2
    return _follow_worker(model, config)


if __name__ == "__main__":
    sys.exit(main())
