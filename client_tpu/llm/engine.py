"""Continuous-batching generation engine (iteration-level scheduling).

The orchestration layer between the decoupled execution path and the
paged-attention model functions (``models/llama.py``):

- **iteration-level scheduler**: one decode step per loop iteration over
  EVERY running sequence; new requests are prefilled and join the running
  batch at the next step boundary, finished sequences exit every step —
  no sequence ever waits for the slowest member of a static batch (the
  Orca/vLLM continuous-batching shape, PAPER.md survey).
- **prefill/decode split**: admission pops the waiting queue in
  (priority, arrival) order and runs each prompt's prefill as its own
  device call (its first token streams immediately — TTFT is one prefill
  away, not one batch drain away), then the sequence decodes with the
  shared step.
- **paged KV admission**: a sequence is admitted only when the
  :class:`~client_tpu.llm.kv_cache.BlockAllocator` can cover its prompt;
  a full cache QUEUES new work (bounded by ``max_queue`` —
  429/RESOURCE_EXHAUSTED past the bound) instead of failing allocation.
  Decode allocates blocks on demand; a dry pool preempts the
  lowest-priority youngest sequence (its blocks free immediately, it
  re-queues and later resumes by re-prefilling its full context).
- **token streaming**: every sequence owns an asyncio queue the step loop
  feeds one ``(token, final)`` pair per step; the serving adapter yields
  them through ``ServerCore.infer_decoupled`` so each decode step emits
  one response per active sequence on the decoupled gRPC stream and the
  OpenAI SSE front-end.
- **speculative decoding** (``llm/speculation.py``): when the model opts
  in, each step drafts up to K candidate tokens per sequence and the
  target verifies all K+1 positions in ONE multi-query paged-attention
  call; accepted tokens stream as multiple queue entries per step.  The
  emitted stream is token-for-token identical to plain decoding (greedy
  and seeded sampling both) — see :meth:`LlmEngine._spec_decode`.
- **one step ahead of the device**: a greedy decode step's token ids
  stay on the device. With step k dispatched and its ids unread, the
  loop builds step k+1 from what it knows without them (positions + 1,
  blocks grown for them, lanes that end at k left out, a map from each
  lane to its lane in k), dispatches it, and only then reads, books and
  streams k's tokens: the host's work runs under the device's. An
  admission does not drain that queue: the prompt's prefill is
  dispatched behind the step in flight, un-waited, the loop runs ahead
  across it, and the new lane is booked when its logits arrive. See
  :class:`LlmEngine` for when the step in flight is consumed first.
- **lap spans**: the step loop's wall time is tiled by named phase
  (:data:`PHASES`; one clock read at each phase boundary), as monotone
  counters in ``stats()["phase_ns"]`` and as ``engine.<phase>``
  annotations on a ``jax.profiler`` trace's host line (which the
  trace's device lines lead by a millisecond or two: see
  :class:`~client_tpu.observability.profiling.LapSpans`).
- **a record a turn**: one iteration of the step loop is a turn, and the
  laps book each to the steady turns or, at 250 ms or more, to the
  stalls, each stall with a cause (the profiler, a compile, the
  collector, or a sampled stack): ``stats()["steady_phase_ns"]`` over
  ``["steady_steps"]`` is the loop's pace without its stalls, and the
  last stalls are ``stall_log()`` (``/v2/debug/state``) and
  ``llm_engine_stall`` log records.

Single-owner concurrency: every public method runs on the serving event
loop (the decoupled path executes models there); device calls hop to the
injected executor so the loop never blocks on the accelerator. Clock
reads go through the injected ``clock_ns`` (tools/clock_lint.py covers
this package), so deadline behavior is testable on fake clocks.
"""

import asyncio
import time
from operator import attrgetter
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from client_tpu.llm.kv_cache import (
    TRASH_BLOCK,
    BlockAllocator,
    CacheCapacityError,
    window_ring_blocks,
    window_tables,
)
from client_tpu.models.engine_model import STATE
from client_tpu.observability.profiling import WATCH, LapSpans
from client_tpu.scheduling import (
    PriorityQueue,
    QueueFullError,
    QueueTimeoutError,
    SchedulingError,
)
from client_tpu.utils import InferenceServerException


class EngineRecoveringError(SchedulingError):
    """The engine hit a fatal device failure and a background reload is
    in flight — the request is retryable, and ``Retry-After`` tells the
    client when the reload is expected to have finished.  Distinct from
    the closed-until-manual-reload UNAVAILABLE: this one promises the
    server is actively healing itself."""

    http_status = 503
    grpc_code = "UNAVAILABLE"
    reason = "recovering"

    def __init__(self, model_name: str, retry_after_s: float = 1.0):
        super().__init__(
            f"llm engine for '{model_name}' is recovering from a device "
            f"failure; retry shortly",
            retry_after_s=retry_after_s,
        )


class EngineConfig:
    """Engine sizing knobs.

    ``num_blocks`` counts physical blocks INCLUDING the reserved trash
    block; ``max_active`` bounds the decode batch (and the compiled batch
    buckets); ``max_queue`` bounds the waiting room (0 = unbounded);
    ``max_seq_len`` is the model's context limit (prompt + max_tokens
    validated against it at submit); ``priority_levels`` sizes the
    waiting queue's priority lanes; ``prefix_sharing`` turns the
    copy-on-write prompt-block index on (default) or off (the A/B
    baseline for the sharing bench); ``spec_k`` is the speculative
    lookahead — the most draft tokens one verify step may carry per
    sequence (0 disables speculation; admission counts the worst-case
    ``K+1`` growth for speculation-enabled sequences).

    ``cache_groups`` is the served model's (``models/engine_model.py``
    ``CacheGroup``; empty: one full group), set by ``LlmEngineModel``
    from the model. ``num_blocks`` sizes the full group's pool; a
    window group's is worked out: a ring for each of ``max_active``
    sequences, and the trash block, so a ring is there for whoever
    ``max_active`` admits. A ``state`` group's pool is counted in slots
    and not in blocks: ``1 + max_active`` of them, slot 0 the trash slot,
    one a sequence from admission until it ends or is preempted, never
    more as it grows; its row of the tables holds the slot in column 0.
    Prefix sharing and speculation are refused beside it
    (``LlmEngine``), as beside a window group: a state holds the whole
    prefix in one slot, so no block of it can be shared, and a draft
    that is turned down cannot be taken out of it again (the allocator's
    ``truncate`` rolls back blocks, never a state).
    How many blocks a group's allocator hands
    out at a time, and with it the ring's length, follows from the
    paged kernel's tile and the table's width (:meth:`group_runs`):
    shapes, not a setting.
    """

    __slots__ = (
        "block_size",
        "num_blocks",
        "max_active",
        "max_queue",
        "max_seq_len",
        "priority_levels",
        "default_max_tokens",
        "prefill_bucket_min",
        "prefix_sharing",
        "spec_k",
        "cache_groups",
    )

    def __init__(
        self,
        block_size: int = 16,
        num_blocks: int = 129,
        max_active: int = 8,
        max_queue: int = 64,
        max_seq_len: int = 512,
        priority_levels: int = 3,
        default_max_tokens: int = 16,
        prefill_bucket_min: int = 8,
        prefix_sharing: bool = True,
        spec_k: int = 0,
        cache_groups=(),
    ):
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        self.max_active = max(1, int(max_active))
        self.max_queue = max(0, int(max_queue))
        self.max_seq_len = int(max_seq_len)
        self.priority_levels = max(1, int(priority_levels))
        self.default_max_tokens = int(default_max_tokens)
        self.prefill_bucket_min = int(prefill_bucket_min)
        self.prefix_sharing = bool(prefix_sharing)
        self.spec_k = max(0, int(spec_k))
        self.cache_groups = tuple(cache_groups)

    @property
    def max_blocks_per_seq(self) -> int:
        return (self.max_seq_len + self.block_size - 1) // self.block_size

    @property
    def ids_width(self) -> int:
        """Width of the token-id vector a decode step returns and the
        next one takes: the batch bucket of ``max_active``, whatever
        the step's own bucket, so that a change of batch bucket makes
        no new program."""
        from client_tpu.server.models import pad_batch_bucket

        return pad_batch_bucket(self.max_active)

    def group_runs(self, tile_pages=()) -> List[int]:
        """Blocks each cache group's allocator hands out at a time
        (``kv_cache.BlockAllocator``'s ``run``), in the groups' order:
        the group's entry of ``tile_pages`` (the paged kernel's tile in
        pages, ``paged_attention.pages_per_tile`` of its pools) where a
        sequence's row holds more than one such tile (the tile is
        smaller than the table's ``max_blocks_per_seq`` columns and, in
        a window group, than the blocks its window can touch), else 1:
        a row of one tile is whole only if the whole sequence is one
        run, which is not attempted. Without ``tile_pages`` every run is
        1 and the allocators work block for block. A state group's
        slots go one at a time."""
        runs = []
        for index, group in enumerate(self.cache_groups or (None,)):
            if group is not None and group.kind == STATE:
                runs.append(1)
                continue
            most = self.max_blocks_per_seq
            if group is not None and group.window is not None:
                most = min(
                    most, window_ring_blocks(group.window, self.block_size)
                )
            pages = int(tile_pages[index]) if index < len(tile_pages) else 1
            runs.append(pages if 1 < pages < most else 1)
        return runs

    def group_num_blocks(self, tile_pages=()) -> List[int]:
        """Physical blocks of each cache group's pool, in the groups'
        order (one full group when none is declared). A window group's
        ring is a whole number of its allocator's runs
        (:meth:`group_runs` of ``tile_pages``: 129 blocks become 144 at
        tiles of 16, 33 become 40 at tiles of 8, 9 become 12 at tiles of
        4), so that no tile of a ring wraps; the full group's pool is
        ``num_blocks`` whatever the run, and its sequences hold up to a
        run less one of it in reserve each
        (``stats()["kv_blocks_reserved_by_group"]``). A state group's
        pool is ``1 + max_active`` slots.
        Refuses, with the numbers, sizes under which a window group
        cannot do its work:
        a ``max_seq_len`` whose page table has fewer columns than the
        group's ring has blocks (the window never fills, and the ring is
        memory no table row can name), and a full pool that cannot hold
        what ``max_active`` rings hold (the full group keeps every block
        a ring keeps and more, so lanes would be preempted before their
        windows filled). Both are policy and not an invariant: a window
        longer than ``max_seq_len`` is full attention under another
        name, and a smaller full pool works while traffic stays short,
        through preemption. What is forbidden is an oversubscribed pool
        and a ring that no request can fill, so that a configuration
        says what it will hold."""
        sizes = []
        runs = self.group_runs(tile_pages)
        for run, group in zip(runs, self.cache_groups or (None,)):
            if group is not None and group.kind == STATE:
                sizes.append(1 + self.max_active)
                continue
            if group is None or group.window is None:
                sizes.append(self.num_blocks)
                continue
            # the checks reckon with the window's own blocks, not with
            # what the ring is rounded up to
            ring = window_ring_blocks(group.window, self.block_size)
            if ring > self.max_blocks_per_seq + 1:
                raise ValueError(
                    f"max_seq_len={self.max_seq_len} gives a page table of "
                    f"{self.max_blocks_per_seq} columns, which cannot cover "
                    f"a window of {group.window} tokens (a ring of {ring} "
                    f"blocks of {self.block_size})"
                )
            if self.num_blocks - 1 < self.max_active * (ring - 1):
                raise ValueError(
                    f"num_blocks={self.num_blocks} holds "
                    f"{self.num_blocks - 1} blocks of the full group, fewer "
                    f"than the {self.max_active * (ring - 1)} that "
                    f"max_active={self.max_active} windows of "
                    f"{group.window} tokens fill ({ring - 1} blocks of "
                    f"{self.block_size} each)"
                )
            sizes.append(
                1 + self.max_active
                * window_ring_blocks(group.window, self.block_size, run)
            )
        return sizes


#: The step loop's phases (``stats()["phase_ns"]`` keys, ``engine.<phase>``
#: trace annotations). They tile the loop's un-parked wall time:
#: ``schedule`` prune, admission without its prefills (a new lane's
#: first token is drawn and streamed here), block growth and
#: preemption, building the step's arrays, the COW check, ``_publish``;
#: ``prefill`` each ``_prefill_one``: building a prompt's arrays and the
#: device call until it returns un-waited arrays (not the device's
#: time: the prefill is waited for where its admission is completed);
#: ``propose`` the speculative drafts; ``dispatch`` the device call until
#: it returns un-waited arrays; ``wait`` blocked on the result of the
#: step being consumed, which for a step that ran ahead is what is left
#: of the device's time once the host's own work is done (no longer the
#: device's step), and on the logits of an admission being completed;
#: ``readback`` the copy to the host of that step's ids and counters,
#: or of its logits where a lane samples, and of a prefill's logits
#: row; ``sample`` ``_sample_rows`` (on an all-greedy step it only passes
#: the program's argmax through); ``emit`` booking and streaming the
#: tokens, metrics hooks; ``yield`` the ``asyncio.sleep(0)``: everything
#: else on the event loop.
PHASES = (
    "schedule", "prefill", "propose", "dispatch", "wait", "readback",
    "sample", "emit", "yield",
)

_WAITING = "waiting"
_RUNNING = "running"
_DONE = "done"


def _wait_ready(result: Any) -> None:
    """Block until a device call's result is computed: the device's time,
    apart from the copy to the host that reading it then costs. Plain
    numpy (the test doubles' results) has nothing to wait for."""
    wait = getattr(result, "block_until_ready", None)
    if wait is not None:
        wait()


def _start_copy(result: Any) -> None:
    """Start the copy to the host of a device call's result that the
    host will read: it then begins when the program ends, not when the
    loop gets round to asking for it. Plain numpy has nothing to copy."""
    start_copy = getattr(result, "copy_to_host_async", None)
    if start_copy is not None:
        start_copy()


class _Admission:
    """One dispatched prefill whose sequence is not booked yet: it holds
    its blocks, rings and slots and is neither waiting nor running.
    ``logits`` is the prefill's ``[1, V]`` result as the un-waited
    device array it is."""

    __slots__ = ("seq", "logits")

    def __init__(self, seq, logits=None):
        self.seq: "Sequence" = seq
        self.logits = logits


class _Flight:
    """One dispatched decode step whose tokens are not booked yet: the
    lanes it ran (``lane_of``: sequence id to lane) and its results as
    the un-waited device arrays they are."""

    __slots__ = ("batch", "lane_of", "ids", "logits", "counted")

    def __init__(self, batch, ids, logits, counted):
        self.batch: List["Sequence"] = batch
        self.lane_of = {seq.seq_id: lane for lane, seq in enumerate(batch)}
        self.ids = ids
        self.logits = logits
        self.counted = counted


def _samples(batch) -> bool:
    """Whether a live lane of ``batch`` draws its token on the host."""
    return any(
        seq.temperature > 0.0 for seq in batch if not seq.cancelled
    )


def decode_fn_from_logits(step: Callable) -> Callable:
    """The engine's ``decode_fn`` over a host function of the plainer
    shape ``step(tokens[B], positions[B], page_tables, pages) ->
    (logits[B, V], pages[, counters])``: the token select, the argmax
    and the padding of the ids in numpy, as the jitted wrapper of
    ``serving.py::_build_device_fns`` has them on the device. For test
    doubles and reference loops; see :class:`LlmEngine` for the
    contract."""

    def decode_fn(prev_ids, lane_map, host_tokens, positions, page_tables,
                  pages):
        prev = np.asarray(prev_ids)
        tokens = np.where(
            lane_map >= 0, prev[np.maximum(lane_map, 0)], host_tokens
        ).astype(np.int32)
        logits, pages, *counted = step(tokens, positions, page_tables, pages)
        ids = np.zeros_like(prev)
        ids[: len(tokens)] = np.asarray(logits).argmax(axis=-1)
        return (ids, logits, pages, *counted)

    return decode_fn


def block_bucket(n: int) -> int:
    """Page-table width bucket: powers of two up to 8 blocks, multiples
    of 8 beyond. Finer than pure powers of two at the top (a 17-block
    context pays for 24, not 32) while still bounding the compiled
    program count to O(max_blocks / 8 + 3)."""
    n = max(1, int(n))
    if n <= 8:
        bucket = 1
        while bucket < n:
            bucket *= 2
        return bucket
    return ((n + 7) // 8) * 8


def _int_param(name: str, value: Any) -> int:
    """Coerce a wire request parameter; malformed values are a client
    error (400/INVALID_ARGUMENT), never an internal 500."""
    try:
        return int(value)
    except (TypeError, ValueError):
        raise InferenceServerException(
            f"request parameter {name!r} must be an integer, got {value!r}"
        ) from None


def _spec_param(value: Any) -> bool:
    """The per-request ``speculation`` parameter: ``on`` (default) /
    ``off`` — the genai-perf A/B switch. Anything else is a 400."""
    if value is None or value == "":
        return True
    if isinstance(value, bool):
        return value
    token = str(value).strip().lower()
    if token in ("on", "true", "1"):
        return True
    if token in ("off", "false", "0"):
        return False
    raise InferenceServerException(
        f"request parameter 'speculation' must be 'on' or 'off', "
        f"got {value!r}"
    )


def _recovery_param(value: Any) -> bool:
    """The per-request ``recovery`` parameter: ``resume`` (default)
    replays the sequence through an engine reload; ``fail`` opts out —
    the client would rather see a retryable error than a transparently
    resumed stream.  Anything else is a 400."""
    if value is None or value == "":
        return True
    token = str(value).strip().lower()
    if token == "resume":
        return True
    if token == "fail":
        return False
    raise InferenceServerException(
        f"request parameter 'recovery' must be 'resume' or 'fail', "
        f"got {value!r}"
    )


def _float_param(name: str, value: Any) -> float:
    """Like :func:`_int_param` for float-valued wire parameters."""
    try:
        result = float(value)
    except (TypeError, ValueError):
        raise InferenceServerException(
            f"request parameter {name!r} must be a number, got {value!r}"
        ) from None
    if result != result or result in (float("inf"), float("-inf")):
        raise InferenceServerException(
            f"request parameter {name!r} must be finite, got {value!r}"
        )
    return result


class Sequence:
    """One generation request: scheduling state + the token stream handle.

    Async-iterating a sequence yields ``(token_id, final)`` pairs as the
    step loop produces them. ``context`` (prompt + generated so far) is
    what a resume-after-preemption re-prefills.
    """

    __slots__ = (
        "seq_id",
        "prompt",
        "generated",
        "max_tokens",
        "priority_level",
        "deadline_ns",
        "timeout_us",
        "state",
        "blocks",
        "rings",
        "slots",
        "page_table",
        "last_token",
        "position",
        "cancelled",
        "preemptions",
        "temperature",
        "top_k",
        "seed",
        "block_hashes",
        "shared_blocks",
        "spec_enabled",
        "recovery_resume",
        "submitted_ns",
        "_out",
        "_engine",
    )

    def __init__(self, seq_id, prompt, max_tokens, priority_level,
                 deadline_ns, timeout_us, max_blocks: int, engine,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 spec_enabled: bool = True, recovery_resume: bool = True):
        self.seq_id = seq_id
        self.prompt: List[int] = prompt
        self.generated: List[int] = []
        self.max_tokens = max_tokens
        self.priority_level = priority_level
        self.deadline_ns = deadline_ns
        self.timeout_us = timeout_us
        self.state = _WAITING
        self.blocks: List[int] = []
        # one fixed ring of blocks for each WINDOW cache group, held
        # from admission to free (kv_cache.window_tables)
        self.rings: List[List[int]] = []
        # the slot held in each STATE cache group, as long as the rings
        self.slots: List[int] = []
        self.page_table = np.zeros([max_blocks], dtype=np.int32)
        self.last_token = 0
        self.position = 0
        self.cancelled = False
        self.preemptions = 0
        # sampling: temperature <= 0 is greedy; the PRNG key chain is
        # (seed, index-of-generated-token), so a preempt-and-resume
        # replays the exact same draws it would have made uninterrupted
        self.temperature = temperature
        self.top_k = top_k
        self.seed = seed
        # per-request speculation opt-out (the harness A/B switch); only
        # meaningful on an engine configured with spec_k > 0
        self.spec_enabled = spec_enabled
        # engine-fatal policy: True replays this sequence through a
        # reload (the PRNG chain keyed on (seed, token-index) makes the
        # resumed stream token-identical), False fails it immediately
        self.recovery_resume = recovery_resume
        # the submit instant, until the first admission has booked the
        # queue wait (then None: a resume after preemption is no wait
        # in the queue a client sees)
        self.submitted_ns: Optional[int] = None
        # chained content hashes of the prompt's FULL blocks (computed
        # once at submit; matched against / published to the allocator's
        # shared index at every admission, including resumes)
        self.block_hashes: List[bytes] = []
        # leading blocks this sequence references but must never write
        self.shared_blocks = 0
        self._out: asyncio.Queue = asyncio.Queue()
        self._engine = engine

    @property
    def context(self) -> List[int]:
        return self.prompt + self.generated

    def emit(self, token: int, final: bool) -> None:
        self._out.put_nowait(("tok", int(token), final))

    def fail(self, exc: BaseException) -> None:
        # _DONE keeps the adapter's unconditional release() from booking
        # a failed/expired sequence as a client cancellation
        self.state = _DONE
        self._out.put_nowait(("err", exc, True))

    def __aiter__(self):
        return self

    async def __anext__(self):
        if self.cancelled:
            raise StopAsyncIteration
        kind, value, final = await self._out.get()
        if kind == "end":
            raise StopAsyncIteration
        if kind == "err":
            raise value
        if final:
            # mark consumed-to-completion so release() is a no-op
            self.cancelled = True
            self.state = _DONE
            return value, True
        return value, False


class LlmEngine:
    """The continuous-batching engine; see the module docstring.

    ``prefill_fn(tokens[1, L], page_table[max_blocks], pages, last_index,
    start_index) -> (logits[1, V], pages)`` (``tokens`` holds ONLY the
    unshared suffix ``context[start_index:]``; ``last_index`` is its
    local last-token index; ``start_index`` is 0 when nothing matched)
    and ``decode_fn(prev_ids[W], lane_map[B], host_tokens[B],
    positions[B], page_tables[B, NB], pages) -> (ids[W], logits[B, V],
    pages)`` (``NB`` is the engine's ragged block bucket — any width up
    to ``max_blocks_per_seq``; ``W`` is ``engine_config.ids_width``) are
    the injected (jitted) device callables; ``pages`` is opaque to the
    engine. Lane ``i`` of a decode step reads the token
    ``prev_ids[lane_map[i]]`` where ``lane_map[i] >= 0`` and
    ``host_tokens[i]`` where it is -1; ``ids[:B]`` is the argmax of the
    float32 ``logits`` (first index on ties, as ``np.argmax``), int32,
    zero-padded to ``W``. ``prev_ids`` is the ``ids`` the last call
    returned, handed back as the (device) array it is, and zeros before
    the first call; every result may be an un-waited device array, and
    the engine copies to the host only ``ids`` (and the logits of a step
    in which a live lane samples). :func:`decode_fn_from_logits` builds
    such a callable in numpy from a plain logits function. A
    model with several cache groups (``engine_config.cache_groups``: one
    full group, the rest window or state groups) gets one table row a group,
    stacked in the groups' order: ``page_table[G, max_blocks]`` and
    ``page_tables[G, B, NB]``. ``decode_fn`` may return a fourth value, an
    int32 vector of per-step counters named by ``step_counters``; it is
    read back with the ids and summed into ``stats()``.
    ``attn_tile_pages`` is the paged kernel's tile in pages, a cache
    group (``paged_attention.pages_per_tile`` of the group's pools,
    which ``LlmEngineModel`` knows): with it ``stats()`` books the tile
    stops a step's attention walks, those of them that are whole, the
    slots they fetch and those of them that are live.
    ``kv_row_bytes`` is ``(stored, counted)`` bytes a cached token takes
    in one layer of each cache group, which ``stats()`` serves as
    ``kv_row_bytes_by_group`` for whoever turns the token counters into
    bytes.

    **The step in flight.** A decode step whose live lanes are all greedy
    is left *in flight* when dispatched: its ids stay on the device. The
    next iteration builds the following step without them (positions +
    1, blocks grown for those, lanes that end at the step in flight and
    cancelled lanes left out, ``lane_map`` naming each lane's place in
    it), dispatches it with ``prev_ids`` = the ids in flight, and only
    then waits for, books and streams the step in flight
    (``stats()["steps_ahead"]`` counts the steps dispatched so). At most
    one decode step **and any number of prefills** are unconsumed at any
    time. The loop decides from its own state, never from a setting, and
    consumes the step in flight *first* — so that everything below sees
    ``generated`` and the block lists as a loop that never ran ahead
    would — before it

    - fails a waiting request that can never fit (``_admit``);
    - preempts a victim or fails a sequence because the pool is dry
      (``_grow``);
    - parks.

    A step is consumed in the iteration that dispatched it, as before
    this existed, when a live lane has ``temperature > 0`` (its draw is
    numpy's over the logits, read back for it) and in a speculative
    engine (``_propose`` needs host tokens). A step still in flight when
    the engine closes, is quarantined or fails is dropped unbooked: no
    token of it was streamed, so a survivor's re-prefilled stream is
    what it would have been. A device failure therefore surfaces up to
    one step later, at the next dispatch or at the wait for the ids.

    **An admission pending.** A waiting request that fits as the books
    stand (its blocks are free; running lanes and pending admissions
    together are under ``max_active``) is admitted without consuming
    anything: ``_admit`` allocates its blocks, rings and slots and
    dispatches its prefill *behind* the step in flight, un-waited (the
    pools chain through ``pages``, so the device runs the step, then the
    prefill, in order; ``stats()["prefills_behind"]`` counts the prefills
    dispatched so), and publishes the prompt's blocks to the prefix
    index at once: whoever matches them is dispatched later and so runs
    behind the prefill. What consuming the step could have freed (a lane
    that ends at it) is seen one iteration later. The sequence is then an
    *admission pending* (``_admitting``, in admission order): neither
    waiting nor running, no lane of the step that ``_step`` dispatches
    next, which therefore runs ahead like any other. The top of the next
    ``_admit`` completes it: waits for the logits, draws the first token
    from them on the host (same key as ever), streams it, and the
    sequence joins the running lanes with ``last_token`` from the host
    (``lane_map`` -1), or ends there. A speculative engine completes an
    admission in the iteration that dispatched it. A pending sequence
    that is cancelled gives back what it holds when its turn to be
    completed comes, and streams nothing; ``close``, a failure and
    ``_quarantine`` see it as they see a running lane.
    ``metrics`` implements the ServerMetrics LLM hooks (set_kv_blocks /
    set_llm_sequences / observe_llm_step / observe_llm_preemption /
    observe_prefix_hits / observe_rejection / observe_llm_speculation);
    None disables export.

    Speculative decoding (``engine_config.spec_k > 0`` plus both
    ``decode_multi_fn`` and ``proposer``): each step first asks the
    proposer for up to K draft tokens per running sequence, then runs
    ``decode_multi_fn(tokens[B, T], positions[B, T], lengths[B],
    page_tables[B, NB], pages) -> (logits[B, T, V], pages)`` — ONE
    ragged verify call for all lanes — and walks each lane's logits
    with the same (seed, token_index) PRNG chain plain decoding uses,
    emitting sampled tokens while they match the drafts.  The emitted
    stream is therefore token-for-token identical to non-speculative
    decoding; speculation only changes how many tokens one device call
    yields.  Draft K/V lands in the sequence's exclusively-owned tail
    blocks only (the COW write assertion covers the whole speculative
    range) and lookahead blocks are rolled back to the plain-decode
    footprint after every verify step, so between steps a speculative
    engine holds exactly the blocks a non-speculative one would.
    """

    def __init__(
        self,
        prefill_fn: Callable,
        decode_fn: Callable,
        pages: Any,
        engine_config: EngineConfig,
        model_name: str = "llm_engine",
        metrics: Any = None,
        executor: Any = None,
        logger: Any = None,
        clock_ns: Callable[[], int] = time.monotonic_ns,
        decode_multi_fn: Optional[Callable] = None,
        proposer: Any = None,
        step_counters: Any = (),
        attn_tile_pages: Any = (),
        kv_row_bytes: Any = (),
    ):
        self.config = engine_config
        self.model_name = model_name
        # cache groups: the full group is `self.allocator` and the
        # sequences' `blocks`; each window group has an allocator of its
        # own that hands out whole rings. `_windows` holds (index among
        # the groups, ring length, allocator). Every allocator hands out
        # runs of its group's tile where the table holds several tiles
        # (`EngineConfig.group_runs`), so that the kernel finds them whole.
        # A state group's allocator hands out slots, one a sequence:
        # `_states` holds (index among the groups, allocator)
        self._tile_pages = tuple(int(pages) for pages in attn_tile_pages)
        groups = engine_config.cache_groups
        runs = engine_config.group_runs(self._tile_pages)
        sizes = engine_config.group_num_blocks(self._tile_pages)
        self._n_groups = max(1, len(groups))
        self._full_group = 0
        self._windows: List[tuple] = []
        self._states: List[tuple] = []
        for index, group in enumerate(groups):
            if group.kind == STATE:
                self._states.append((index, BlockAllocator(sizes[index], 1)))
                continue
            if group.window is None:
                self._full_group = index
                continue
            self._windows.append((
                index,
                window_ring_blocks(
                    group.window, engine_config.block_size, runs[index]
                ),
                BlockAllocator(
                    sizes[index], engine_config.block_size, runs[index]
                ),
            ))
        self.allocator = BlockAllocator(
            engine_config.num_blocks, engine_config.block_size,
            runs[self._full_group],
        )
        if groups and (
            len(groups) - len(self._windows) - len(self._states) != 1
        ):
            raise ValueError(
                "the engine serves exactly one full cache group beside "
                "any window and state groups, got "
                f"{[g.kind for g in groups]}"
            )
        if self._states and (
            engine_config.prefix_sharing or engine_config.spec_k
        ):
            raise ValueError(
                "a state cache group (one slot of "
                f"{1 + engine_config.max_active} a sequence) is served "
                f"without prefix sharing (prefix_sharing="
                f"{engine_config.prefix_sharing}) and without speculation "
                f"(spec_k={engine_config.spec_k}): a slot holds the whole "
                "prefix, so no block of it can be shared, and `truncate` "
                "cannot take a refused draft out of it"
            )
        if self._windows and (
            engine_config.prefix_sharing or engine_config.spec_k
        ):
            raise ValueError(
                "window cache groups are served without prefix sharing "
                "and without speculation: shared blocks and draft "
                "lookahead have no ring to live in"
            )
        self.metrics = metrics
        self.logger = logger
        self._clock_ns = clock_ns
        self._prefill = prefill_fn
        self._decode = decode_fn
        self._decode_multi = decode_multi_fn
        self._proposer = proposer
        # speculation requires all three legs; a partial wiring (k but
        # no verify fn, or vice versa) silently runs plain decode
        self._speculative = (
            engine_config.spec_k > 0
            and decode_multi_fn is not None
            and proposer is not None
        )
        self._pages = pages
        self._executor = executor
        self._waiting = PriorityQueue(levels=engine_config.priority_levels)
        self._running: List[Sequence] = []
        # the admissions pending, in admission order: each sequence's
        # prefill is dispatched and it owns blocks, but it is in neither
        # _waiting nor _running, so shutdown/failure cleanup must cover
        # them explicitly
        self._admitting: List[_Admission] = []
        self._seq_counter = 0
        self._task: Optional[asyncio.Task] = None
        self._wake: Optional[asyncio.Event] = None
        self._closed = False
        # engine-fatal recovery: when a supervisor wired on_fatal, a
        # fatal step failure QUARANTINES the engine (recovering=True,
        # submits 503 with Retry-After=retry_after_s) instead of failing
        # the waiting room — resumable sequences park in _survivors until
        # a reloaded engine adopt()s them
        self.on_fatal: Optional[Callable[[BaseException], None]] = None
        self.recovering = False
        self.retry_after_s = 1.0
        self.last_failure: Optional[BaseException] = None
        self._survivors: List[Sequence] = []
        # cumulative counters (also mirrored to the metrics registry)
        self.steps = 0
        self.tokens_generated = 0
        self.preemptions = 0
        self.completed = 0
        self.cancelled_count = 0
        self.expired = 0
        # the decode step dispatched and not yet booked (see the class
        # docstring), the newest ids any decode call returned (what the
        # next call takes as prev_ids), and the steps that were
        # dispatched while the step before them was still unconsumed
        self._flight: Optional[_Flight] = None
        self._ids: Any = np.zeros([engine_config.ids_width], dtype=np.int32)
        self.steps_ahead = 0
        # prefills dispatched behind a decode step in flight (which was
        # not consumed for them)
        self.prefills_behind = 0
        # decode-step emissions only (prefill first-tokens excluded) and
        # the lane-steps that produced them (one per live lane per
        # step): step_tokens / lane_steps is the tokens-per-step A/B
        # headline — exactly 1.0 for a non-speculative engine by
        # construction
        self.step_tokens = 0
        self.lane_steps = 0
        # speculation accounting: drafts verified, drafts accepted, and
        # how many steps ran the multi-query verify path
        self.spec_steps = 0
        # page-table columns the decode kernel was handed (bucket x nb)
        # and those of them that hold a live block: the share of the
        # table it has to stream, since it stops at each lane's length
        self.attn_blocks_live = 0
        self.attn_blocks_bucket = 0
        # window groups: blocks a whole-length cache would hold for the
        # lanes of every decode step (summed over window groups), and
        # those of them that the rings do not hold
        self.window_blocks_whole = 0
        self.window_blocks_unheld = 0
        # tokens of context the lanes of every decode step attend over
        # in a full layer (the whole context), and in a layer of each
        # window group (what of it the window reaches): the K/V a step's
        # attention has to read, a layer. One sum over all window groups:
        # a reader that multiplies it by a layer count needs the model to
        # have one window group (`kv_blocks_in_use_by_group` is a group's)
        self.attn_tokens_full = 0
        self.attn_tokens_window = 0
        # tile stops the paged kernel makes over the tables of every
        # decode and verify step (a layer of each cache group), and
        # those of them it fetches with one copy a pool: the kernel's
        # own rule (paged_attention.whole_tiles) on the tables as built;
        # the slots those stops bring in (a tile is copied whole, dead
        # slots behind a lane's last token and before its window with
        # it) and those of them some query row can see
        self._group_blocks = sizes
        self._kv_row_bytes = [
            {"stored": int(stored), "counted": int(counted)}
            for stored, counted in kv_row_bytes
        ]
        self.attn_tiles_walked = 0
        self.attn_tiles_whole = 0
        self.attn_slots_fetched = 0
        self.attn_slots_live = 0
        # the model's own per-step counters (decode_fn's third value)
        self._step_counter_names = tuple(step_counters)
        self.model_counters: Dict[str, int] = dict.fromkeys(
            self._step_counter_names, 0
        )
        self.spec_proposed = 0
        self.spec_accepted = 0
        # full prompt blocks demanded across admissions — with
        # allocator.prefix_hits this yields the true prefix hit rate
        # (hits / demand), since the allocator only ever sees the
        # pre-matched hash slice
        self.prefix_block_demand = 0
        # lap spans over the step loop (see PHASES), and what they are
        # divided by: prefill calls, first admissions with the time
        # their sequences spent queued
        self._laps = LapSpans(
            {phase: f"engine.{phase}" for phase in PHASES}, clock_ns=clock_ns,
            on_stall=self._log_stall,
        )
        self.prefills = 0
        self.admitted = 0
        self.queue_wait_ns = 0

    # -- submission / cancellation (serving-loop only) -----------------------

    def submit(
        self,
        prompt_ids: List[int],
        max_tokens: Optional[int] = None,
        parameters: Optional[Dict[str, Any]] = None,
    ) -> Sequence:
        """Admit one generation request into the waiting queue.

        Raises synchronously: :class:`InferenceServerException` for
        requests that can NEVER run (context exceeds the model's
        ``max_seq_len`` or the pool's total capacity) and
        :class:`QueueFullError` (429/RESOURCE_EXHAUSTED) once
        ``max_queue`` requests wait — the capacity-based admission the
        paged cache exists for.
        """
        if self._closed:
            if self.recovering:
                # quarantined with a reload in flight: same UNAVAILABLE
                # wire face, but with Retry-After so clients back off for
                # roughly one reload instead of hammering the 503
                raise EngineRecoveringError(
                    self.model_name, retry_after_s=self.retry_after_s
                )
            # UNAVAILABLE: a closed engine (shutdown, device failure, or
            # a lost pod worker) is a retryable replica-level condition —
            # the fleet's failover machinery routes around it
            raise InferenceServerException(
                f"llm engine for '{self.model_name}' is closed",
                status="UNAVAILABLE",
            )
        parameters = parameters or {}
        config = self.config
        if max_tokens is None:
            max_tokens = _int_param(
                "max_tokens",
                parameters.get("max_tokens", config.default_max_tokens),
            )
        prompt = [int(t) for t in prompt_ids]
        if not prompt:
            raise InferenceServerException("empty prompt")
        if max_tokens < 1:
            raise InferenceServerException(
                f"max_tokens must be >= 1, got {max_tokens}"
            )
        total = len(prompt) + max_tokens
        if total > config.max_seq_len:
            raise InferenceServerException(
                f"prompt ({len(prompt)}) + max_tokens ({max_tokens}) "
                f"exceeds max sequence length {config.max_seq_len}"
            )
        block_hashes = (
            self.allocator.chain_hashes(prompt)
            if config.prefix_sharing
            else []
        )
        # capacity fast-fail against POST-MATCH demand: blocks the shared
        # index already holds are referenced, not allocated, so a prompt
        # mostly covered by a live shared prefix must not be 400'd for a
        # worst-case block count it will never request (the index can
        # shrink before admission — then the request queues like any
        # other too-big-for-now work instead of failing)
        matched_now = min(
            self.allocator.match_count(block_hashes),
            self._match_cap(len(prompt)),
        )
        if self.allocator.demand(
            self.allocator.blocks_for(total), matched_now
        ) > self.allocator.capacity:
            raise InferenceServerException(
                f"request needs {self.allocator.blocks_for(total)} KV "
                f"blocks ({matched_now} shared) but the pool holds "
                f"{self.allocator.capacity}"
            )
        # parse the remaining wire parameters BEFORE the queue-full
        # check: a malformed request is a 400, not a 429
        level = _int_param("priority", parameters.get("priority", 0) or 0)
        if level <= 0:
            # 0/negative = unset -> the default (lowest) lane, matching
            # QueuePolicy.priority_of — a negative value must not clamp
            # to the HIGHEST lane (priority escalation) downstream
            level = config.priority_levels
        timeout_us = _int_param(
            "timeout_us",
            parameters.get("timeout_us", parameters.get("timeout", 0)) or 0,
        )
        temperature = _float_param(
            "temperature", parameters.get("temperature", 0.0) or 0.0
        )
        if temperature < 0.0:
            raise InferenceServerException(
                f"request parameter 'temperature' must be >= 0, "
                f"got {temperature}"
            )
        top_k = _int_param("top_k", parameters.get("top_k", 0) or 0)
        if top_k < 0:
            raise InferenceServerException(
                f"request parameter 'top_k' must be >= 0, got {top_k}"
            )
        spec_enabled = _spec_param(parameters.get("speculation"))
        recovery_resume = _recovery_param(parameters.get("recovery"))
        seed = _int_param("seed", parameters.get("seed", 0) or 0)
        if seed < 0:
            # np.random.default_rng rejects negative entropy — validate
            # here so a bad seed is a 400, not an engine-fatal crash at
            # first sample
            raise InferenceServerException(
                f"request parameter 'seed' must be >= 0, got {seed}"
            )
        if config.max_queue and len(self._waiting) >= config.max_queue:
            error = QueueFullError(self.model_name, config.max_queue)
            if self.metrics is not None:
                self.metrics.observe_rejection(self.model_name, error.reason)
            raise error
        now_ns = self._clock_ns()
        deadline_ns = now_ns + timeout_us * 1000 if timeout_us > 0 else None
        self._seq_counter += 1
        seq = Sequence(
            self._seq_counter,
            prompt,
            max_tokens,
            level,
            deadline_ns,
            timeout_us,
            config.max_blocks_per_seq,
            self,
            temperature=temperature,
            top_k=top_k,
            seed=seed,
            spec_enabled=spec_enabled,
            recovery_resume=recovery_resume,
        )
        seq.block_hashes = block_hashes
        seq.submitted_ns = now_ns
        self._waiting.push(seq, level=level, deadline_ns=deadline_ns)
        self._ensure_task()
        self._publish()
        return seq

    def release(self, seq: Sequence) -> None:
        """Drop a sequence (client cancellation / stream teardown).

        Idempotent; safe on finished sequences. The step loop frees the
        KV blocks and removes the sequence within one iteration."""
        if seq.state == _DONE:
            return
        if not seq.cancelled:
            seq.cancelled = True
            self.cancelled_count += 1
        # unblock a consumer parked on the queue
        seq._out.put_nowait(("end", None, True))
        self._wake_loop()

    def close(self) -> None:
        """Stop the step loop and fail everything still queued/running.

        Idempotent. Thread-safe: while the serving loop is alive, an
        off-loop caller (ServerCore.close from the main thread) hops
        onto it — cancelling the task and waking parked stream
        consumers from a foreign thread would race the loop. Once the
        loop is stopped/closed, teardown runs directly."""
        self._closed = True
        task = self._task
        if task is not None and not task.done():
            loop = task.get_loop()
            try:
                on_loop = asyncio.get_running_loop() is loop
            except RuntimeError:
                on_loop = False
            if not on_loop and not loop.is_closed():
                try:
                    loop.call_soon_threadsafe(self._close_on_loop)
                    return
                except RuntimeError:
                    pass  # loop closed between the check and the call
        self._close_on_loop()

    def _close_on_loop(self) -> None:
        self._closed = True
        if self._task is not None:
            try:
                self._task.cancel()
            except RuntimeError:
                pass  # owning loop already closed
            self._task = None
        self._fail_all(
            InferenceServerException(
                f"llm engine for '{self.model_name}' shut down"
            )
        )

    def _fail_all(self, error: BaseException) -> None:
        """Free and fail every live sequence — running, waiting, and
        those whose admission is pending — so no consumer hangs and no
        KV block leaks. Idempotent (free is; fail on a done sequence is
        inert)."""
        self._flight = None  # dropped unbooked: nothing of it streamed
        for pending in self._admitting:
            self._free_blocks(pending.seq)
            pending.seq.fail(error)
        self._admitting.clear()
        for seq in self._running:
            self._free_blocks(seq)
            seq.fail(error)
        self._running.clear()
        items = self._waiting.scan()
        for item in items:
            item.value.fail(error)
        self._waiting.remove(items)
        self._publish()

    # -- engine-fatal quarantine & recovery ----------------------------------

    def _quarantine(self, exc: BaseException) -> None:
        """Handle a fatal step-loop failure.

        A failed device call may have consumed donated buffers (the page
        pool is donated to the jitted step off-CPU), so the engine cannot
        safely serve against ``self._pages`` anymore — it stops taking
        work either way.  Without a supervisor (``on_fatal`` unset) this
        is the PR-9 behavior: fail everything, refuse new work until a
        manual ``warmup()``.  With one, live sequences that opted into
        resume park in ``_survivors`` (their consumers stay blocked on
        their token queues — nothing is failed, nothing streams) and the
        supervisor's reload eventually :meth:`adopt`\\ s them onto a fresh
        engine; everything else fails with the preserved status."""
        if self.logger is not None:
            self.logger.error("llm_engine_loop_failed", exc=exc,
                              model=self.model_name)
        # preserve the inner status so a lost pod worker (UNAVAILABLE)
        # stays retryable instead of collapsing to a bare 500
        status = (
            exc.status() if isinstance(exc, InferenceServerException)
            else None
        )
        error = InferenceServerException(
            f"llm engine step failed: {exc}", status=status
        )
        self.last_failure = exc
        self._closed = True
        # a step in flight is dropped unbooked: a survivor resumes from
        # the tokens it streamed, and re-prefilling those regenerates it
        self._flight = None
        resumable = self.on_fatal is not None
        survivors: List[Sequence] = []

        def triage(seq: Sequence) -> None:
            self._free_blocks(seq)
            seq.blocks = []
            seq.shared_blocks = 0
            seq.page_table[:] = TRASH_BLOCK
            if seq.cancelled or seq.state == _DONE:
                seq.state = _DONE
            elif resumable and seq.recovery_resume:
                seq.state = _WAITING
                survivors.append(seq)
            else:
                seq.fail(error)

        for pending in self._admitting:
            triage(pending.seq)
        self._admitting.clear()
        for seq in self._running:
            triage(seq)
        self._running.clear()
        items = self._waiting.scan()
        for item in items:
            triage(item.value)
        self._waiting.remove(items)
        self._survivors = survivors
        self.recovering = resumable
        self._publish()
        if resumable:
            try:
                self.on_fatal(exc)
            except Exception as hook_exc:  # noqa: BLE001 - no rescue -> fail
                if self.logger is not None:
                    self.logger.error("llm_engine_recovery_hook_failed",
                                      exc=hook_exc, model=self.model_name)
                self.recovering = False
                for seq in self._survivors:
                    seq.fail(error)
                self._survivors = []

    def quarantine(self, reason: str = "externally induced") -> None:
        """Force the engine-fatal path from OUTSIDE the step loop (the
        pod coordinator quarantines the engine before tearing down a
        broken mesh; chaos tests induce failures with it).  Thread-safe
        via the same loop-hop :meth:`close` uses; a direct call only
        when no loop/task is live."""
        error = InferenceServerException(
            f"llm engine for '{self.model_name}' failed: {reason}",
            status="UNAVAILABLE",
        )
        task = self._task
        if task is not None and not task.done():
            loop = task.get_loop()
            try:
                on_loop = asyncio.get_running_loop() is loop
            except RuntimeError:
                on_loop = False
            if not on_loop and not loop.is_closed():
                try:
                    loop.call_soon_threadsafe(self._quarantine_on_loop, error)
                    return
                except RuntimeError:
                    pass  # loop closed between the check and the call
        self._quarantine_on_loop(error)

    def _quarantine_on_loop(self, error: BaseException) -> None:
        if self._closed:
            return
        if self._task is not None:
            try:
                self._task.cancel()
            except RuntimeError:
                pass  # owning loop already closed
            self._task = None
        self._quarantine(error)

    def detach_survivors(self) -> List[Sequence]:
        """Hand the quarantined sequences to whoever will adopt them
        onto the replacement engine (clears the local list — exactly one
        recovery owns each survivor)."""
        survivors, self._survivors = self._survivors, []
        return survivors

    def fail_survivors(self, error: BaseException) -> None:
        """Recovery gave up: fail anything still parked and drop the
        recovering promise so submits report plain closed."""
        self.recovering = False
        for seq in self.detach_survivors():
            seq.fail(error)

    def adopt(self, survivors: List[Sequence]) -> None:
        """Re-queue sequences that survived a predecessor engine's
        quarantine (serving-loop only, like :meth:`submit`).

        Each survivor re-enters the waiting room exactly like a
        preempted sequence: its ``context`` (prompt + tokens already
        streamed) re-prefills in one call and decoding resumes on the
        same (seed, token-index) PRNG chain, so the resumed stream is
        token-identical to an uninterrupted one.  Sequences that already
        streamed tokens requeue WITHOUT a deadline (matching
        ``_preempt`` — their first tokens are live downstream; expiring
        them now would break streams the engine already committed to)."""
        for seq in survivors:
            if seq.cancelled or seq.state == _DONE:
                continue
            # adopted ids must not collide with this engine's own counter
            self._seq_counter = max(self._seq_counter, seq.seq_id)
            seq._engine = self
            seq.state = _WAITING
            deadline_ns = seq.deadline_ns if not seq.generated else None
            self._waiting.push(
                seq, level=seq.priority_level, deadline_ns=deadline_ns
            )
        self._ensure_task()
        self._publish()

    # -- introspection -------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        return {
            "active_sequences": len(self._running),
            "waiting_sequences": len(self._waiting),
            "recovering": self.recovering,
            "recovery_survivors": len(self._survivors),
            "kv_blocks_in_use": self.allocator.blocks_in_use,
            "kv_blocks_total": self.allocator.capacity,
            "kv_blocks_shared": self.allocator.blocks_shared,
            # every cache group's blocks in use, in the groups' order
            "kv_blocks_in_use_by_group": self._blocks_by_group(
                attrgetter("blocks_in_use")),
            # and the blocks no admission can have that hold no
            # reference: what sequences keep of their open runs
            "kv_blocks_reserved_by_group": self._blocks_by_group(
                attrgetter("blocks_reserved")),
            # bytes a cached token takes in one layer of each group
            "kv_row_bytes_by_group": self._kv_row_bytes,
            # state groups: slots that hold a sequence's recurrent state
            # (all state groups together), and the bytes they hold in
            # all of a group's layers (0 for a group of another kind)
            "state_slots_in_use": sum(
                slot_allocator.blocks_in_use
                for _, slot_allocator in self._states
            ),
            "state_bytes_by_group": self._state_bytes_by_group(),
            "window_blocks_whole": self.window_blocks_whole,
            "window_blocks_unheld": self.window_blocks_unheld,
            "attn_tokens_full": self.attn_tokens_full,
            "attn_tokens_window": self.attn_tokens_window,
            "attn_tiles_walked": self.attn_tiles_walked,
            "attn_tiles_whole": self.attn_tiles_whole,
            "attn_slots_fetched": self.attn_slots_fetched,
            "attn_slots_live": self.attn_slots_live,
            **self.model_counters,
            "block_size": self.allocator.block_size,
            "steps": self.steps,
            # decode steps dispatched before the step ahead of them was
            # read: steps_ahead / steps is the share that ran ahead
            "steps_ahead": self.steps_ahead,
            "tokens_generated": self.tokens_generated,
            "preemptions": self.preemptions,
            "completed": self.completed,
            "cancelled": self.cancelled_count,
            "expired": self.expired,
            "prefix_cache_hits": self.allocator.prefix_hits,
            "prefix_cache_queries": self.allocator.prefix_queries,
            "prefix_block_demand": self.prefix_block_demand,
            # speculation: tokens_per_step is the decode-only ratio (1.0
            # exactly for a non-speculative engine); acceptance is over
            # drafts actually verified, not merely proposed
            "speculative": self._speculative,
            "step_tokens": self.step_tokens,
            "lane_steps": self.lane_steps,
            "tokens_per_step": self.step_tokens / max(1, self.lane_steps),
            "spec_steps": self.spec_steps,
            "attn_blocks_live": self.attn_blocks_live,
            "attn_blocks_bucket": self.attn_blocks_bucket,
            "spec_proposed": self.spec_proposed,
            "spec_accepted": self.spec_accepted,
            "spec_acceptance_rate": (
                self.spec_accepted / max(1, self.spec_proposed)
            ),
            # lap spans: ns per phase of the step loop (PHASES), which
            # add up to its wall time not parked; all monotone. Beside
            # them the record of its turns: the same laps split into
            # steady turns and stalls, the stalls by cause, and the
            # process's collector, compile and profiler sums
            "phase_ns": dict(self._laps.ns),
            **self._laps.record(),
            "prefills": self.prefills,
            # those of them dispatched behind a decode step in flight
            "prefills_behind": self.prefills_behind,
            "admitted": self.admitted,
            "queue_wait_ns": self.queue_wait_ns,
        }

    def stall_log(self) -> List[Dict[str, Any]]:
        """The step loop's last stalls, oldest first (see ``LapSpans``):
        ``/v2/debug/state`` serves them as ``llm.<model>.stall_log``."""
        return list(self._laps.stall_log)

    def _log_stall(self, entry: Dict[str, Any]) -> None:
        if self.logger is not None:
            self.logger.warning(
                "llm_engine_stall",
                model=self.model_name,
                rate_key=("llm_engine_stall", self.model_name),
                **entry,
            )

    # -- cache groups ---------------------------------------------------------

    def _free_blocks(self, seq: Sequence) -> None:
        """Give back what ``seq`` holds in every cache group."""
        self.allocator.free(seq.seq_id)
        for _, _, ring_allocator in self._windows:
            ring_allocator.free(seq.seq_id)
        seq.rings = []
        for _, slot_allocator in self._states:
            slot_allocator.free(seq.seq_id)
        seq.slots = []

    def _blocks_by_group(self, count: Callable) -> List[int]:
        """``count`` of every cache group's allocator, in the groups'
        order."""
        counts = [count(self.allocator)] * self._n_groups
        for index, _, ring_allocator in self._windows:
            counts[index] = count(ring_allocator)
        for index, slot_allocator in self._states:
            counts[index] = count(slot_allocator)
        return counts

    def _state_bytes_by_group(self) -> List[int]:
        """Bytes the held slots of every state group take, over the
        group's layers, in the groups' order (0 for another kind)."""
        held = [0] * self._n_groups
        for index, slot_allocator in self._states:
            if index < len(self._kv_row_bytes):
                held[index] = (
                    slot_allocator.blocks_in_use
                    * self._kv_row_bytes[index]["stored"]
                    * len(self.config.cache_groups[index].layers)
                )
        return held

    def _group_tables(self, full: np.ndarray, seqs: List[Sequence],
                      last_positions) -> np.ndarray:
        """The tables a device call takes: ``full`` itself (``[..., NB]``)
        for a one-group model, else one such array a group, stacked in
        the groups' order. A window group's rows are written from the
        sequences' rings for the block of each ``last_positions`` entry
        (the newest position the call reads or writes); a state group's
        hold each sequence's slot in column 0 (a padding row the trash
        slot) and nothing else."""
        if self._n_groups == 1:
            return full
        rows = full.reshape(-1, full.shape[-1])
        tables = np.zeros((self._n_groups,) + rows.shape, dtype=np.int32)
        tables[self._full_group] = rows
        last_blocks = [
            int(p) // self.allocator.block_size for p in last_positions
        ]
        for window, (index, _, _) in enumerate(self._windows):
            tables[index, : len(seqs)] = window_tables(
                [seq.rings[window] for seq in seqs], last_blocks,
                rows.shape[1],
            )
        for state, (index, _) in enumerate(self._states):
            tables[index, : len(seqs), 0] = [
                seq.slots[state] for seq in seqs
            ]
        return tables.reshape((self._n_groups,) + full.shape)

    def _book_tiles(self, tables: np.ndarray, positions: np.ndarray) -> None:
        """Book the tile stops of a step over ``tables`` (what
        :meth:`_group_tables` returned) whose live rows hold query
        positions ``positions[n, T]``. A state group has no tiles: its
        layers' cost a step is per lane, not per cached token."""
        if not self._tile_pages:
            return
        from client_tpu.models.paged_attention import (
            count_tiles,
            visible_slots,
        )

        n = len(positions)
        if self._n_groups == 1:
            tables = tables[None]
        block_size = self.allocator.block_size
        for index, group in enumerate(self.config.cache_groups or (None,)):
            if group is not None and group.kind == STATE:
                continue
            first_slots, lengths = visible_slots(
                positions, group and group.window)
            pages = self._tile_pages[index]
            walked, whole = count_tiles(
                tables[index, :n], first_slots, lengths, pages, block_size,
                self._group_blocks[index],
            )
            self.attn_tiles_walked += walked
            self.attn_tiles_whole += whole
            # a table narrower than a tile is one tile of its own width
            self.attn_slots_fetched += walked * block_size * min(
                pages, tables.shape[-1])
            self.attn_slots_live += int((lengths - first_slots).sum())

    # -- step loop -----------------------------------------------------------

    def _ensure_task(self) -> None:
        if self._task is None or self._task.done():
            loop = asyncio.get_running_loop()
            # fresh Event per task: an asyncio.Event binds to the loop it
            # is first awaited on, and a restarted engine may be serving
            # a different loop than the task that just finished
            self._wake = asyncio.Event()
            self._task = loop.create_task(self._run())
        self._wake_loop()

    def _wake_loop(self) -> None:
        if self._wake is not None:
            self._wake.set()

    async def _run_device(self, fn, *args):
        if self._executor is None:
            return fn(*args)
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            self._executor, lambda: fn(*args)
        )

    async def _run(self) -> None:
        laps = self._laps
        WATCH.register(laps)
        try:
            while not self._closed:
                if (
                    not self._running
                    and not len(self._waiting)
                    and not self._admitting
                    and self._flight is None
                ):
                    laps.park(self.steps)
                    self._wake.clear()
                    await self._wake.wait()
                    continue
                # one iteration is one turn of the laps' record
                laps.enter("schedule")
                laps.turn(self.steps)
                self._prune()
                await self._admit()
                if self._running or self._flight is not None:
                    await self._step()
                laps.enter("schedule")
                self._publish()
                # one cooperative yield per iteration: stream consumers
                # on this loop drain their queues between steps
                laps.enter("yield")
                await asyncio.sleep(0)
        except asyncio.CancelledError:
            # shutdown mid-iteration (possibly mid-prefill): clean up on
            # the loop before unwinding so nothing leaks or hangs
            self._fail_all(
                InferenceServerException(
                    f"llm engine for '{self.model_name}' shut down"
                )
            )
            raise
        except Exception as e:  # noqa: BLE001 - engine must not die silently
            self._quarantine(e)
        finally:
            laps.park(self.steps)
            WATCH.unregister(laps)

    def _prune(self) -> None:
        """Drop cancelled sequences and expire waiting deadlines."""
        now_ns = self._clock_ns()
        for item in self._waiting.expire(now_ns):
            seq = item.value
            self.expired += 1
            if not seq.cancelled:
                error = QueueTimeoutError(self.model_name, seq.timeout_us)
                if self.metrics is not None:
                    self.metrics.observe_rejection(
                        self.model_name, error.reason
                    )
                seq.fail(error)
        stale = [i for i in self._waiting.scan() if i.value.cancelled]
        if stale:
            self._waiting.remove(stale)
        if any(seq.cancelled for seq in self._running):
            for seq in self._running:
                if seq.cancelled:
                    self._free_blocks(seq)
                    seq.state = _DONE
            self._running = [s for s in self._running if not s.cancelled]

    def _match_cap(self, context_len: int) -> int:
        """Most shared blocks a context of this length may reference: at
        least ONE token (the last) must always be recomputed, because the
        first sampled token needs its logits — an all-block-aligned full
        match would otherwise leave nothing to prefill."""
        return max(0, (context_len - 1) // self.allocator.block_size)

    async def _admit(self) -> None:
        """Book the admissions pending, then admit waiting sequences:
        in (priority, arrival) order, while the block pool and the
        ``max_active`` bound allow. The first blocker stops admission —
        a full cache queues behind it rather than skipping ahead (no
        starvation of large prompts). Prompt blocks already in the shared
        index are referenced instead of allocated (capacity math counts
        NEW blocks only) and their prefill is skipped: TTFT is one
        partial prefill of the unshared suffix.

        A request that fits is admitted as the books stand: its prefill
        is dispatched behind the step in flight, if there is one, and
        waited for by the next call (or by this one in a speculative
        engine). Only a request that can never fit has the step in
        flight consumed before it is failed, and the scan starts again
        over what that booked."""
        self._complete_admissions()
        await self._admit_waiting()

    async def _admit_waiting(self) -> None:
        allocator = self.allocator
        for item in self._waiting.scan():
            seq: Sequence = item.value
            if (
                len(self._running) + len(self._admitting)
                >= self.config.max_active
            ):
                break
            context = seq.context
            # +1: the first decode step writes the freshly-sampled
            # token's K/V at position len(context). Speculation adds its
            # worst-case lookahead on top (the first verify step writes
            # up to K draft positions beyond that), clamped by the
            # sequence's own context ceiling — draft writes never pass
            # position prompt+max_tokens-2, so total capacity math is
            # unchanged and the admission demand stays exact.
            need = allocator.blocks_for(
                min(
                    len(seq.prompt) + seq.max_tokens,
                    len(context) + 1 + self._spec_k_for(seq),
                )
            )
            cap = self._match_cap(len(context))
            usable = min(
                allocator.match_count(seq.block_hashes), cap, len(seq.block_hashes)
            )
            # what the allocation takes of the pool: whole runs
            demand = allocator.demand(need, usable)
            if demand > allocator.capacity:
                if self._flight is not None:
                    self._consume(self._flight)
                    await self._admit_waiting()
                    return
                # admitted on the strength of a shared prefix that has
                # since been reclaimed (its sharers finished): the
                # residual demand can never be satisfied — fail cleanly
                # instead of blocking the admission queue forever
                self._waiting.remove([item])
                error = CacheCapacityError(
                    f"request needs {need - usable} KV blocks but the "
                    f"pool holds {allocator.capacity} (a previously "
                    f"shared prefix is no longer resident)"
                )
                if self.metrics is not None:
                    self.metrics.observe_rejection(
                        self.model_name, "kv_capacity"
                    )
                seq.fail(error)
                continue
            if demand > allocator.free_blocks:
                break
            self._waiting.remove([item])
            if seq.cancelled:
                seq.state = _DONE
                continue
            self.prefix_block_demand += len(seq.block_hashes)
            blocks, matched = allocator.allocate_shared(
                seq.seq_id, need, seq.block_hashes[:usable]
            )
            seq.blocks = blocks
            seq.shared_blocks = matched
            seq.page_table[:] = TRASH_BLOCK
            seq.page_table[: len(blocks)] = blocks
            # a window pool holds a ring for each of max_active
            # sequences: this one's is free
            seq.rings = [
                ring_allocator.allocate(seq.seq_id, ring)
                for _, ring, ring_allocator in self._windows
            ]
            # and a slot of every state group; the prefill writes it whole
            seq.slots = [
                slot_allocator.allocate(seq.seq_id, 1)[0]
                for _, slot_allocator in self._states
            ]
            # visible to _fail_all and _quarantine from here on: the
            # sequence owns blocks but is in neither queue nor batch.
            # Deliberately NOT dropped in a finally — on cancellation or
            # device failure it must still be listed when the _run
            # handlers reclaim it; only its completion takes it off.
            pending = _Admission(seq)
            self._admitting.append(pending)
            now_ns = self._laps.enter("prefill")
            if seq.submitted_ns is not None:
                self.queue_wait_ns += now_ns - seq.submitted_ns
                self.admitted += 1
                seq.submitted_ns = None
            pending.logits = await self._prefill_one(
                seq, context, matched * allocator.block_size
            )
            self._laps.enter("schedule")
            # the sequence's full prompt blocks (matched + the ones the
            # dispatched prefill writes) are published for the next
            # identical prefix now: whatever reads them is dispatched
            # later, and the pools order it behind the prefill
            if self.config.prefix_sharing:
                allocator.publish(seq.seq_id, seq.block_hashes)
            if matched and self.metrics is not None:
                self.metrics.observe_prefix_hits(self.model_name, matched)
            if self._speculative:
                # no step is ever in flight: nothing to run ahead of
                self._complete_admissions()

    def _complete_admissions(self) -> None:
        """Book every admission pending, in admission order: wait for
        the prefill's logits, draw the sequence's first token from them,
        stream it, and let the sequence join the running lanes (or end
        there). A sequence cancelled meanwhile gives back what it holds
        and streams nothing; its prefill is not waited for."""
        laps = self._laps
        while self._admitting:
            pending = self._admitting[0]
            seq = pending.seq
            if seq.cancelled:
                self._free_blocks(seq)
                seq.state = _DONE
                del self._admitting[0]
                continue
            laps.enter("wait")
            _wait_ready(pending.logits)
            laps.enter("readback")
            logits = np.asarray(pending.logits)[0]
            laps.enter("schedule")
            del self._admitting[0]
            token = self._sample(seq, logits)
            seq.position = len(seq.prompt) + len(seq.generated)
            seq.generated.append(token)
            seq.last_token = token
            final = len(seq.generated) >= seq.max_tokens
            seq.emit(token, final)
            self.tokens_generated += 1
            if self.metrics is not None:
                self.metrics.observe_llm_tokens(self.model_name)
            if final:
                self._finish(seq)
            else:
                seq.state = _RUNNING
                self._running.append(seq)

    async def _prefill_one(self, seq: Sequence, context: List[int],
                           start: int) -> Any:
        """Dispatch the prefill of ``context[start:]`` (``start`` =
        matched shared blocks, always block-aligned and < len(context))
        and return its logits ``[1, V]``, the last real token's row, as
        the un-waited device array they are, their copy to the host
        started."""
        from client_tpu.server.models import pad_batch_bucket

        suffix = context[start:]
        bucket = min(
            pad_batch_bucket(
                len(suffix), minimum=self.config.prefill_bucket_min
            ),
            self.config.max_seq_len,
        )
        tokens = np.zeros([1, bucket], dtype=np.int32)
        tokens[0, : len(suffix)] = suffix
        self.prefills += 1
        if self._flight is not None:
            self.prefills_behind += 1
        # A failing device call is ENGINE-fatal, not sequence-fatal: the
        # inputs were engine-constructed (request validation happened at
        # submit) and the donated page pool may be gone — let it
        # propagate to the _run catch-all, which fails everything and
        # marks the engine for reload.
        logits, self._pages = await self._run_device(
            self._prefill,
            tokens,
            self._group_tables(seq.page_table, [seq], [len(context) - 1]),
            self._pages,
            len(suffix) - 1,
            start,
        )
        _start_copy(logits)
        return logits

    def _sample(self, seq: Sequence, logits: np.ndarray) -> int:
        """Next token from a logits row (the single-row prefill path);
        delegates to the batched sampler with this row's PRNG index."""
        return self._sample_rows([(seq, logits, len(seq.generated))])[0]

    def _sample_rows(self, items) -> List[int]:
        """Sample one token per ``(seq, logits_row, gen_index)`` item in
        ONE vectorized pass — the full-batch decode step and the K+1
        rows of a speculative verify all share it. Every token the
        engine streams comes out of here. The items of an all-greedy
        decode step carry, in the row's place, the id the decode
        program already chose over that row (its argmax): it passes
        through.

        The softmax/top-k pipeline runs batched in float64 (elementwise
        ops and per-row reductions, so each row's bits match the scalar
        pipeline exactly), but every row's DRAW still comes from its own
        ``np.random.default_rng((seed, gen_index))`` — the PRNG key is a
        pure function of the token's index in the generation, never of
        batch composition or speculation outcome, which is what makes
        preemption replay and spec-on/spec-off streams token-identical
        (tests pin the streams bit-exactly against the scalar path)."""
        n = len(items)
        out = [0] * n
        greedy = [i for i in range(n) if items[i][0].temperature <= 0.0]
        sampled = [i for i in range(n) if items[i][0].temperature > 0.0]
        if greedy:
            rows = np.stack([np.asarray(items[i][1]) for i in greedy])
            picks = rows if rows.ndim == 1 else rows.argmax(axis=-1)
            for i, pick in zip(greedy, picks):
                out[i] = int(pick)
        if sampled:
            rows = np.stack(
                [np.asarray(items[i][1]) for i in sampled]
            ).astype(np.float64)
            temps = np.array(
                [items[i][0].temperature for i in sampled], dtype=np.float64
            )
            scaled = rows / temps[:, None]
            vocab = scaled.shape[-1]
            for j, i in enumerate(sampled):
                top_k = items[i][0].top_k
                if top_k and top_k < vocab:
                    kth = np.partition(scaled[j], -top_k)[-top_k]
                    scaled[j] = np.where(scaled[j] < kth, -np.inf, scaled[j])
            scaled -= scaled.max(axis=-1, keepdims=True)
            probs = np.exp(scaled)
            probs /= probs.sum(axis=-1, keepdims=True)
            for j, i in enumerate(sampled):
                seq, _, gen_index = items[i]
                rng = np.random.default_rng((seq.seed, gen_index))
                out[i] = int(rng.choice(vocab, p=probs[j]))
        return out

    def _spec_k_for(self, seq: Sequence) -> int:
        """Draft tokens a verify step may carry for this sequence NOW:
        the engine's lookahead, clamped so speculation never writes K/V
        past position ``prompt + max_tokens - 2`` (the last token of a
        generation needs no lookahead, which also keeps total capacity
        math identical to the non-speculative engine's)."""
        if not self._speculative or not seq.spec_enabled:
            return 0
        remaining = seq.max_tokens - len(seq.generated)
        return max(0, min(self.config.spec_k, remaining - 1))

    def _pick_victim(self) -> Optional[Sequence]:
        """Preemption victim: lowest priority (highest level number)
        first, youngest (most blocks still to earn) among equals."""
        if not self._running:
            return None
        return max(
            self._running,
            key=lambda s: (s.priority_level, -len(s.generated), s.seq_id),
        )

    def _preempt(self, victim: Sequence) -> None:
        """Push a running sequence back to the waiting queue and free its
        blocks NOW; it resumes later by re-prefilling prompt+generated
        (tokens already streamed stay streamed — deterministic greedy
        decode regenerates the identical cache)."""
        self._free_blocks(victim)
        victim.blocks = []
        victim.shared_blocks = 0
        victim.page_table[:] = TRASH_BLOCK
        victim.state = _WAITING
        victim.preemptions += 1
        self.preemptions += 1
        self._running.remove(victim)
        # NO queue deadline on the requeue: timeout_us bounds time-to-
        # START, which this sequence already satisfied — expiring a
        # partially-streamed generation as "timed out in queue" would
        # turn delivered tokens into a spurious 504
        self._waiting.push(victim, level=victim.priority_level)
        if self.metrics is not None:
            self.metrics.observe_llm_preemption(self.model_name)
        if self.logger is not None:
            self.logger.verbose(
                "llm_sequence_preempted",
                model=self.model_name,
                seq=victim.seq_id,
                generated=len(victim.generated),
            )

    async def _step(self) -> None:
        """One iteration of the decode loop: dispatch the next step over
        every running lane, then book the step that was in flight."""
        batch = self._grow()
        flight = self._flight  # after _grow, which may have consumed it
        if not batch:
            if flight is not None:
                self._consume(flight)  # every lane ended at it
            return
        if self._speculative:
            self._laps.enter("propose")
            drafts = await self._propose(batch)
            self._laps.enter("schedule")
            if any(drafts):
                await self._spec_decode(batch, drafts)
            else:
                await self._plain_decode(batch)
            return
        ahead = await self._dispatch(batch, flight)
        if flight is not None:
            self._consume(flight)
        if _samples(batch):
            # the draw is numpy's, over logits read back for it, and
            # the next step needs its token from the host
            self._consume(ahead)
        else:
            self._flight = ahead

    def _grow(self) -> List[Sequence]:
        """The lanes of the next decode step, each with the blocks its
        write position needs (allocate-on-demand: a sequence whose next
        write enters a new block claims it now). A lane of the step in
        flight writes one position further on; one that ends at that
        step, and a cancelled one, is left out. A dry pool preempts
        until the step fits, once the step in flight is booked."""
        allocator = self.allocator
        flight = self._flight
        ahead = flight.lane_of if flight is not None else {}
        lanes = [
            seq for seq in self._running
            if not seq.cancelled  # pruned (and freed) next iteration
            and not (
                seq.seq_id in ahead
                and len(seq.generated) + 1 >= seq.max_tokens
            )
        ]
        for seq in lanes:
            if seq.state != _RUNNING:
                continue  # already preempted below
            position = seq.position + (seq.seq_id in ahead)
            while position // allocator.block_size >= len(seq.blocks):
                try:
                    block = allocator.extend(seq.seq_id)
                    seq.blocks.append(block)
                    seq.page_table[len(seq.blocks) - 1] = block
                except CacheCapacityError:
                    if flight is not None:
                        # who owns blocks has to change: book the step
                        # in flight first (a lane may end at it and free
                        # what is missing; a victim re-prefills what it
                        # streamed), then grow from what that left
                        self._consume(flight)
                        return self._grow()
                    if allocator.demand(
                        allocator.blocks_for(seq.position + 1)
                    ) > allocator.capacity:
                        # the whole pool could not hold this context:
                        # possible only for a request admitted against a
                        # shared prefix (post-match demand fit; gross
                        # footprint never can). Fail it BEFORE picking a
                        # victim — preempting peers for a request that
                        # can never fit would drain the whole batch
                        # first, and preempt-and-retry on itself would
                        # loop forever.
                        self._free_blocks(seq)
                        self._running.remove(seq)
                        seq.fail(
                            CacheCapacityError(
                                f"context ({seq.position + 1} tokens) "
                                f"outgrew the KV pool "
                                f"({allocator.capacity} blocks)"
                            )
                        )
                        break
                    victim = self._pick_victim()
                    self._preempt(victim)
                    if victim is seq:
                        break
        return [seq for seq in lanes if seq.state == _RUNNING]

    async def _plain_decode(self, batch: List[Sequence]) -> None:
        """One decode step dispatched and booked at once (the
        speculative engine's step without drafts)."""
        self._consume(await self._dispatch(batch))

    def _table_columns(self, batch: List[Sequence]) -> int:
        """Page-table width of a step over ``batch``. Ragged: the decode
        kernel's attention cost is proportional to the table width it
        sees, so the table is cut to a bucket of the LONGEST live
        sequence instead of always paying ``max_seq_len`` (bounded
        recompiles; see :func:`block_bucket`). The last bucket under the
        whole table takes the whole table: closed-loop streams that end
        at ``max_seq_len`` one after another keep the widest lane within
        a bucket or two of it, and a batch that dips under the edge for
        a few steps (the next stream a few tokens late) would run, and
        under load compile, a second program for 8 columns' saving."""
        most = self.config.max_blocks_per_seq
        nb = block_bucket(max(len(seq.blocks) for seq in batch))
        return most if nb > 8 and nb + 8 >= most else min(nb, most)

    async def _dispatch(self, batch: List[Sequence],
                        flight: Optional[_Flight] = None) -> _Flight:
        """Build and dispatch the non-speculative decode step over
        ``batch``, one token per lane, and return it un-waited. A lane
        that also ran in ``flight`` (the step before, not booked yet)
        takes its token from that step's ids on the device and writes
        one position past what is booked; every other lane takes
        ``last_token`` from the host."""
        from client_tpu.server.models import pad_batch_bucket

        allocator = self.allocator
        ahead = flight.lane_of if flight is not None else {}
        n = len(batch)
        bucket = pad_batch_bucket(n)
        nb = self._table_columns(batch)
        lane_map = np.full([bucket], -1, dtype=np.int32)
        host_tokens = np.zeros([bucket], dtype=np.int32)
        positions = np.zeros([bucket], dtype=np.int32)
        page_tables = np.zeros([bucket, nb], dtype=np.int32)
        self.attn_blocks_bucket += bucket * nb
        for i, seq in enumerate(batch):
            lane = ahead.get(seq.seq_id, -1)
            lane_map[i] = lane
            if lane < 0:
                host_tokens[i] = seq.last_token
            position = seq.position + (lane >= 0)
            positions[i] = position
            page_tables[i] = seq.page_table[:nb]
            self.attn_blocks_live += len(seq.blocks)
            self.attn_tokens_full += position + 1
            for index, ring, _ in self._windows:
                self.window_blocks_whole += len(seq.blocks)
                self.window_blocks_unheld += max(0, len(seq.blocks) - ring)
                self.attn_tokens_window += min(
                    position + 1, self.config.cache_groups[index].window
                )
            # COW invariant: the block this lane is about to write must
            # be exclusively owned (shared prefix blocks are read-only;
            # growth always lands in fresh blocks). A violation means
            # allocator state is corrupt — engine-fatal, not a lane skip.
            write_block = position // allocator.block_size
            if allocator.refcount(seq.blocks[write_block]) != 1:
                raise InferenceServerException(
                    f"COW violation: sequence {seq.seq_id} would write "
                    f"block {seq.blocks[write_block]} with refcount "
                    f"{allocator.refcount(seq.blocks[write_block])}"
                )
        tables = self._group_tables(page_tables, batch, positions[:n])
        self._book_tiles(tables, positions[:n, None])
        self._laps.enter("dispatch")
        ids, logits, self._pages, *counted = await self._run_device(
            self._decode, self._ids, lane_map, host_tokens, positions,
            tables, self._pages,
        )
        self._ids = ids
        if flight is not None:
            self.steps_ahead += 1
        for result in (ids, *counted):
            _start_copy(result)
        return _Flight(batch, ids, logits, counted)

    def _consume(self, flight: _Flight) -> None:
        """Wait for a dispatched step, then book and stream its tokens:
        ``_sample_rows`` over the ids the program chose or, where a
        live lane samples, over the logits read back for it."""
        laps = self._laps
        if self._flight is flight:
            self._flight = None
        live = [
            (lane, seq) for lane, seq in enumerate(flight.batch)
            if not seq.cancelled  # pruned (and freed) next iteration
        ]
        sampled = _samples(flight.batch)
        result = flight.logits if sampled else flight.ids
        laps.enter("wait")
        _wait_ready(result)
        laps.enter("readback")
        rows = np.asarray(result)
        if flight.counted:
            # computed by the same program: ready with the ids
            for name, value in zip(
                self._step_counter_names,
                np.asarray(flight.counted[0]).tolist(),
            ):
                self.model_counters[name] += value
        self.steps += 1
        laps.enter("sample")
        picks = self._sample_rows(
            [(seq, rows[lane], len(seq.generated)) for lane, seq in live]
        )
        self.lane_steps += len(live)
        laps.enter("emit")
        for (_, seq), token in zip(live, picks):
            self._emit_step_token(seq, token)
        if self.metrics is not None:
            # tokens: the live lanes, not the batch (cancelled lanes
            # decoded but streamed nothing, and the exported counter
            # must agree with stats())
            self.metrics.observe_llm_step(self.model_name, len(flight.batch))
            if live:
                self.metrics.observe_llm_tokens(self.model_name, len(live))
        self._running = [s for s in self._running if s.state == _RUNNING]

    def _emit_step_token(self, seq: Sequence, token: int) -> bool:
        """Book ONE decode-step emission (plain and speculative paths
        share this accounting — the tokens_per_step headline depends on
        both booking identically). Returns True when the sequence just
        finished."""
        seq.generated.append(token)
        seq.last_token = token
        seq.position += 1
        self.tokens_generated += 1
        self.step_tokens += 1
        final = len(seq.generated) >= seq.max_tokens
        seq.emit(token, final)
        if final:
            self._finish(seq)
        return final

    # -- speculative decode (draft-propose + batched paged-verify) -----------

    async def _propose(self, batch: List[Sequence]) -> List[List[int]]:
        """One draft proposal per running lane (empty = no speculation
        for that lane this step: opted out, final token pending, or the
        proposer found nothing). Proposer failures degrade that lane to
        plain decode — a broken draft model must never take down the
        engine, whose own page state it cannot touch."""
        lanes = [
            (self._spec_k_for(seq), seq.context if not seq.cancelled else [])
            for seq in batch
        ]
        # submit all lanes before awaiting any: the proposals are
        # independent, so with an executor the draft computations overlap
        # instead of serializing B round-trips ahead of the verify call
        results = await asyncio.gather(
            *[
                self._run_device(self._proposer.propose, context, k)
                for k, context in lanes
                if k >= 1 and context
            ],
            return_exceptions=True,
        )
        drafts: List[List[int]] = []
        it = iter(results)
        for k, context in lanes:
            if k < 1 or not context:
                drafts.append([])
                continue
            proposal = next(it)
            if isinstance(proposal, BaseException):
                # a broken draft model must never take down the engine,
                # whose own page state it cannot touch
                if self.logger is not None:
                    self.logger.warning(
                        "llm_spec_proposer_failed",
                        model=self.model_name,
                        error=str(proposal),
                        rate_key=("llm_spec_proposer_failed", self.model_name),
                    )
                proposal = []
            drafts.append([int(t) for t in proposal][:k])
        return drafts

    async def _spec_decode(
        self, batch: List[Sequence], drafts: List[List[int]]
    ) -> None:
        """One speculative step: verify every lane's draft tokens (plus
        its mandatory next position) in ONE multi-query decode call,
        then emit the longest sampled prefix that agrees with the
        drafts. Every emitted token is sampled from target logits with
        the same (seed, index) key chain as plain decode, so the stream
        is identical — acceptance only decides how FAR one step gets."""
        from client_tpu.server.models import pad_batch_bucket

        allocator = self.allocator
        block_size = allocator.block_size
        # opportunistic lookahead blocks: draft K/V needs coverage up to
        # position+k. A dry pool SHRINKS the lane's speculative window to
        # the blocks it already owns instead of preempting a peer —
        # speculation is an optimization and must never evict real work.
        k_effs: List[int] = []
        for seq, proposal in zip(batch, drafts):
            k_eff = min(len(proposal), self._spec_k_for(seq))
            while (
                k_eff > 0
                and (seq.position + k_eff) // block_size >= len(seq.blocks)
            ):
                try:
                    block = allocator.extend(seq.seq_id)
                    seq.blocks.append(block)
                    seq.page_table[len(seq.blocks) - 1] = block
                except CacheCapacityError:
                    k_eff = len(seq.blocks) * block_size - 1 - seq.position
            k_effs.append(max(0, k_eff))
        n = len(batch)
        k_max = max(k_effs)
        if k_max == 0:
            # every lane degraded (dry pool shrank all windows to zero):
            # this step is just a plain one
            await self._plain_decode(batch)
            return
        bucket = pad_batch_bucket(n)
        t_width = min(pad_batch_bucket(k_max + 1), self.config.spec_k + 1)
        nb = self._table_columns(batch)
        tokens = np.zeros([bucket, t_width], dtype=np.int32)
        positions = np.zeros([bucket, t_width], dtype=np.int32)
        lengths = np.zeros([bucket], dtype=np.int32)
        page_tables = np.zeros([bucket, nb], dtype=np.int32)
        self.attn_blocks_bucket += bucket * nb
        row_offsets = np.arange(t_width)
        for i, (seq, proposal, k_eff) in enumerate(
            zip(batch, drafts, k_effs)
        ):
            tokens[i, 0] = seq.last_token
            tokens[i, 1:1 + k_eff] = proposal[:k_eff]
            # padding rows clamp to the last real position: their writes
            # are masked off by `lengths`, and clamping keeps every page
            # lookup inside the lane's own table
            positions[i] = seq.position + np.minimum(row_offsets, k_eff)
            lengths[i] = k_eff + 1
            page_tables[i] = seq.page_table[:nb]
            self.attn_blocks_live += len(seq.blocks)
            # no window count to book: an engine with window groups is
            # refused speculation when it is built
            self.attn_tokens_full += seq.position + k_eff + 1
            # COW invariant over the WHOLE speculative write range: the
            # verify scatters K/V at position..position+k_eff, and none
            # of those blocks may be shared. Engine-fatal on violation,
            # exactly like the plain step's single-position assertion.
            for wb in range(
                seq.position // block_size,
                (seq.position + k_eff) // block_size + 1,
            ):
                if allocator.refcount(seq.blocks[wb]) != 1:
                    raise InferenceServerException(
                        f"COW violation: sequence {seq.seq_id} would "
                        f"speculatively write block {seq.blocks[wb]} "
                        f"with refcount "
                        f"{allocator.refcount(seq.blocks[wb])}"
                    )
        self._book_tiles(page_tables, positions[:n])
        laps = self._laps
        laps.enter("dispatch")
        logits, self._pages = await self._run_device(
            self._decode_multi, tokens, positions, lengths, page_tables,
            self._pages,
        )
        laps.enter("wait")
        _wait_ready(logits)
        laps.enter("readback")
        logits_rows = np.asarray(logits)
        self.steps += 1
        self.spec_steps += 1
        laps.enter("sample")
        # batched sampling across every candidate row of every live lane
        # (the verify consumes the vectorized sampler wholesale): rows
        # sampled past a lane's first mismatch are simply discarded —
        # each draw is keyed by (seed, index) alone, so sampling a row
        # never perturbs any later draw
        items = []
        spans = []
        for lane, (seq, k_eff) in enumerate(zip(batch, k_effs)):
            if seq.cancelled:
                spans.append((0, 0))
                continue
            start = len(items)
            n0 = len(seq.generated)
            items.extend(
                (seq, logits_rows[lane, t], n0 + t)
                for t in range(k_eff + 1)
            )
            spans.append((start, k_eff + 1))
        picks = self._sample_rows(items) if items else []
        self.lane_steps += sum(1 for _, count in spans if count)
        laps.enter("emit")
        emitted_total = 0
        proposed_total = 0
        accepted_total = 0
        lane_tokens: List[int] = []  # per-lane emissions (histogram feed)
        for seq, proposal, k_eff, (start, count) in zip(
            batch, drafts, k_effs, spans
        ):
            if count == 0:
                continue  # cancelled: decoded but streams nothing
            proposed_total += k_eff
            emitted = 0
            for t in range(count):
                token = picks[start + t]
                matched = t < k_eff and token == proposal[t]
                if matched:
                    accepted_total += 1
                emitted += 1
                if self._emit_step_token(seq, token) or not matched:
                    break
            emitted_total += emitted
            lane_tokens.append(emitted)
            # rejected-draft rollback: blocks claimed for lookahead that
            # the accepted prefix did not reach go straight back to the
            # pool, restoring the plain-decode footprint (truncate raises
            # engine-fatally if a rolled-back block were shared)
            if seq.state == _RUNNING:
                keep = allocator.blocks_for(seq.position + 1)
                if len(seq.blocks) > keep:
                    allocator.truncate(seq.seq_id, keep)
                    seq.page_table[keep:len(seq.blocks)] = TRASH_BLOCK
                    del seq.blocks[keep:]
        self.spec_proposed += proposed_total
        self.spec_accepted += accepted_total
        if self.metrics is not None:
            self.metrics.observe_llm_step(self.model_name, n)
            if emitted_total:
                self.metrics.observe_llm_tokens(self.model_name, emitted_total)
            self.metrics.observe_llm_speculation(
                self.model_name, proposed_total, accepted_total, lane_tokens
            )
        self._running = [s for s in self._running if s.state == _RUNNING]

    def _finish(self, seq: Sequence) -> None:
        self._free_blocks(seq)
        seq.state = _DONE
        self.completed += 1

    def _publish(self) -> None:
        if self.metrics is None:
            return
        self.metrics.set_kv_blocks(
            self.model_name,
            self.allocator.blocks_in_use,
            self.allocator.capacity,
            self.allocator.blocks_shared,
        )
        self.metrics.set_llm_sequences(
            self.model_name, len(self._running), len(self._waiting)
        )
