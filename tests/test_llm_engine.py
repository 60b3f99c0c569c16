"""LLM serving engine tests: continuous batching + paged KV + streaming.

Three tiers:

- hermetic scheduler units on a STUB model (numpy logits, no jax, fake
  clocks) — queue bounds, deadline expiry, preemption, block accounting;
- model-correctness tests on the float32 tiny llama (bf16 ties flip
  argmax between compiled batch shapes; float32 keeps greedy decode
  bit-stable across bucket sizes, so engine output must EXACTLY match
  the dense ``llama.generate`` reference);
- end-to-end through real front-ends: decoupled gRPC streaming with
  mid-generation cancellation, /metrics export, OpenAI satellites, and
  genai-perf driving the engine in streaming mode.
"""

import asyncio
import json
import urllib.request

import numpy as np
import pytest

from client_tpu.llm import (
    BlockAllocator,
    CacheCapacityError,
    EngineConfig,
    LlmEngine,
)
from client_tpu.llm.engine import decode_fn_from_logits
from client_tpu.llm.kv_cache import window_ring_blocks
from client_tpu.scheduling import QueueFullError, QueueTimeoutError
from client_tpu.utils import InferenceServerException

pytestmark = pytest.mark.llm

MS = 1_000_000  # ns


# ---------------------------------------------------------------------------
# block allocator units
# ---------------------------------------------------------------------------


def test_block_allocator_accounting():
    alloc = BlockAllocator(num_blocks=9, block_size=4)
    assert alloc.capacity == 8
    assert alloc.free_blocks == 8
    assert alloc.blocks_for(1) == 1
    assert alloc.blocks_for(4) == 1
    assert alloc.blocks_for(5) == 2
    a = alloc.allocate("a", 3)
    assert len(a) == 3 and 0 not in a  # trash block never handed out
    assert alloc.blocks_in_use == 3
    b = alloc.allocate("b", 5)
    assert alloc.free_blocks == 0
    with pytest.raises(CacheCapacityError):
        alloc.extend("a")
    with pytest.raises(CacheCapacityError):
        alloc.allocate("c", 1)
    assert alloc.free("b") == 5
    extended = alloc.extend("a")
    assert extended not in a
    assert alloc.blocks_in_use == 4
    assert alloc.free("a") == 4
    assert alloc.blocks_in_use == 0
    # idempotent free
    assert alloc.free("a") == 0
    assert alloc.free_blocks == 8
    assert set(b).isdisjoint(a)


def test_block_allocator_returned_list_not_aliased():
    """Appending to allocate()'s return value must not corrupt the
    ownership record (the double-free regression)."""
    alloc = BlockAllocator(num_blocks=5, block_size=4)
    blocks = alloc.allocate("s", 1)
    blocks.append(alloc.extend("s"))
    assert alloc.free("s") == 2
    assert alloc.blocks_in_use == 0
    assert alloc.free_blocks == 4


# ---------------------------------------------------------------------------
# hermetic scheduler units (stub model, fake clock, no jax)
# ---------------------------------------------------------------------------

VOCAB = 32


def _stub_engine(clock, **overrides):
    """An engine over stub device functions: prefill/decode emit a
    deterministic next token (sum of context mod VOCAB via the carried
    token), pages are an opaque token-independent object."""

    def prefill(tokens, page_table, pages, last_index, start):
        logits = np.zeros([1, VOCAB], dtype=np.float32)
        logits[0, (int(tokens.sum()) + start) % VOCAB] = 1.0
        return logits, pages

    def decode(tokens, positions, page_tables, pages):
        n = tokens.shape[0]
        logits = np.zeros([n, VOCAB], dtype=np.float32)
        for i in range(n):
            logits[i, int(tokens[i] + positions[i]) % VOCAB] = 1.0
        return logits, pages

    defaults = dict(
        block_size=4, num_blocks=9, max_active=4, max_queue=4, max_seq_len=32
    )
    defaults.update(overrides)
    return LlmEngine(
        prefill,
        decode_fn_from_logits(decode),
        pages=object(),
        engine_config=EngineConfig(**defaults),
        model_name="stub",
        clock_ns=clock,
    )


class _FakeClock:
    def __init__(self):
        self.now = 0

    def __call__(self):
        return self.now


async def _collect(seq):
    out = []
    async for token, final in seq:
        out.append(token)
        if final:
            break
    return out


def test_stub_engine_generates_and_reclaims():
    clock = _FakeClock()

    async def run():
        engine = _stub_engine(clock)
        seqs = [
            engine.submit([1, 2, 3], max_tokens=6),
            engine.submit([4, 5], max_tokens=6),
        ]
        results = await asyncio.gather(*[_collect(s) for s in seqs])
        assert all(len(r) == 6 for r in results)
        # deterministic stub: same submission reproduces the stream
        again = await _collect(engine.submit([1, 2, 3], max_tokens=6))
        assert again == results[0]
        # negative priority = unset -> default (LOWEST) lane; it must not
        # clamp to the highest lane downstream (priority escalation)
        neg = engine.submit([9], max_tokens=1, parameters={"priority": -5})
        assert neg.priority_level == engine.config.priority_levels
        assert len(await _collect(neg)) == 1
        stats = engine.stats()
        assert stats["kv_blocks_in_use"] == 0
        assert stats["completed"] == 4
        engine.close()

    asyncio.run(run())


def test_queue_full_rejects_with_429_shape():
    clock = _FakeClock()

    async def run():
        # admission happens at step boundaries, and the loop never ticks
        # between synchronous submits — so both requests sit in the
        # waiting room and the third submission overflows the bound
        engine = _stub_engine(clock, num_blocks=2, max_queue=2, max_seq_len=4)
        q1 = engine.submit([1], max_tokens=1)
        q2 = engine.submit([2], max_tokens=1)
        with pytest.raises(QueueFullError) as exc:
            engine.submit([3], max_tokens=1)
        assert exc.value.http_status == 429
        assert exc.value.grpc_code == "RESOURCE_EXHAUSTED"
        # impossible requests fail fast, not queue forever
        with pytest.raises(InferenceServerException):
            engine.submit([1] * 30, max_tokens=30)  # > max_seq_len
        # malformed wire parameters are a client error (400 shape),
        # never a bare ValueError escaping as an internal 500
        with pytest.raises(InferenceServerException, match="max_tokens"):
            engine.submit([1], parameters={"max_tokens": "abc"})
        with pytest.raises(InferenceServerException, match="priority"):
            engine.submit([1], max_tokens=1, parameters={"priority": "hi"})
        # queued (not rejected) work still runs to completion
        results = await asyncio.gather(_collect(q1), _collect(q2))
        assert all(len(r) == 1 for r in results)
        assert engine.stats()["kv_blocks_in_use"] == 0
        engine.close()

    asyncio.run(run())


def test_waiting_deadline_expires_on_fake_clock():
    clock = _FakeClock()

    async def run():
        # capacity is ONE 4-token block: `long` fills it exactly, so
        # `waiting` must queue behind the full cache
        engine = _stub_engine(clock, num_blocks=2, max_seq_len=8)
        long = engine.submit([1, 2], max_tokens=2)
        # queued behind a full cache with a 5 ms queue deadline
        waiting = engine.submit(
            [7], max_tokens=3, parameters={"timeout_us": 5000}
        )
        clock.now += 6 * MS
        with pytest.raises(QueueTimeoutError) as exc:
            await _collect(waiting)
        assert exc.value.http_status == 504
        await _collect(long)
        stats = engine.stats()
        assert stats["expired"] == 1
        assert stats["kv_blocks_in_use"] == 0
        engine.close()

    asyncio.run(run())


def test_preemption_frees_blocks_and_requeues():
    clock = _FakeClock()

    async def run():
        # 2 allocatable blocks of 4 tokens; two sequences that each
        # outgrow one block force preemption mid-decode
        engine = _stub_engine(
            clock, num_blocks=3, max_active=4, max_seq_len=8, max_queue=8
        )
        a = engine.submit([1, 2, 3], max_tokens=5)  # grows to 8 tokens
        b = engine.submit([4, 5, 6], max_tokens=5)
        ra, rb = await asyncio.gather(_collect(a), _collect(b))
        assert len(ra) == 5 and len(rb) == 5
        stats = engine.stats()
        assert stats["preemptions"] > 0
        assert stats["kv_blocks_in_use"] == 0
        assert stats["completed"] == 2
        # preempted resume reproduces the same deterministic stream
        again = await _collect(engine.submit([1, 2, 3], max_tokens=5))
        assert again == ra
        engine.close()

    asyncio.run(run())


def test_release_mid_generation_reclaims_within_one_iteration():
    clock = _FakeClock()

    async def run():
        engine = _stub_engine(clock)
        # max_tokens far beyond what we consume: release() must reclaim
        seq = engine.submit([1, 2, 3], max_tokens=29)
        collected = []
        async for token, final in seq:
            collected.append(token)
            if len(collected) == 3:
                break
        engine.release(seq)
        # the step loop drops the sequence within one iteration
        for _ in range(50):
            if engine.stats()["kv_blocks_in_use"] == 0:
                break
            await asyncio.sleep(0)
        stats = engine.stats()
        assert stats["kv_blocks_in_use"] == 0
        assert stats["active_sequences"] == 0
        assert stats["cancelled"] == 1
        engine.close()

    asyncio.run(run())


def test_kv_accounting_airtight_after_mixed_outcomes():
    """Completed + client-cancelled + deadline-expired generations in one
    engine: blocks_in_use must return to zero and the pool must admit
    fresh work afterwards."""
    clock = _FakeClock()

    async def run():
        engine = _stub_engine(
            clock, num_blocks=3, max_active=2, max_queue=8, max_seq_len=8
        )
        done = engine.submit([1, 2], max_tokens=3)
        cancelled = engine.submit([3, 4], max_tokens=6)
        expired = engine.submit(
            [5], max_tokens=2, parameters={"timeout_us": 2000}
        )

        async def cancel_after_two():
            seen = 0
            async for _token, _final in cancelled:
                seen += 1
                if seen == 2:
                    break
            engine.release(cancelled)

        clock.now += 3 * MS  # expires the queued deadline
        results = await asyncio.gather(
            _collect(done), cancel_after_two(), return_exceptions=True
        )
        assert not isinstance(results[0], Exception)
        with pytest.raises(QueueTimeoutError):
            await _collect(expired)
        for _ in range(100):
            if engine.stats()["kv_blocks_in_use"] == 0:
                break
            await asyncio.sleep(0)
        stats = engine.stats()
        assert stats["kv_blocks_in_use"] == 0
        assert stats["active_sequences"] == 0
        assert stats["waiting_sequences"] == 0
        # pool is healthy: a fresh generation still completes
        fresh = await _collect(engine.submit([6, 7], max_tokens=3))
        assert len(fresh) == 3
        assert engine.stats()["kv_blocks_in_use"] == 0
        engine.close()

    asyncio.run(run())


def test_preempted_sequence_outlives_its_queue_deadline():
    """timeout_us bounds time-to-START only: a sequence that was
    admitted, streamed tokens, and got preempted must NOT be expired as
    'timed out in queue' while it waits to resume — delivered tokens
    would turn into a spurious 504."""
    clock = _FakeClock()

    async def run():
        engine = _stub_engine(
            clock, num_blocks=3, max_active=4, max_seq_len=8, max_queue=8
        )
        a = engine.submit(
            [1, 2, 3], max_tokens=5, parameters={"timeout_us": 5000}
        )
        b = engine.submit(
            [4, 5, 6], max_tokens=5, parameters={"timeout_us": 5000}
        )

        async def collect_advancing(seq):
            # each consumed token pushes the clock far past every queue
            # deadline, so only the requeue-without-deadline fix keeps
            # the preempted sequence alive
            out = []
            async for token, final in seq:
                clock.now += 10 * MS
                out.append(token)
                if final:
                    break
            return out

        ra, rb = await asyncio.gather(collect_advancing(a), collect_advancing(b))
        assert len(ra) == 5 and len(rb) == 5
        stats = engine.stats()
        assert stats["preemptions"] > 0
        assert stats["expired"] == 0
        assert stats["kv_blocks_in_use"] == 0
        engine.close()

    asyncio.run(run())


def test_close_mid_prefill_reclaims_and_unblocks_consumer():
    """Shutdown while a prefill device call is in flight: the sequence
    is in neither the waiting queue nor the running batch but owns KV
    blocks — close() must free them and fail its stream (no leak, no
    consumer parked forever)."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    clock = _FakeClock()
    release_prefill = threading.Event()
    entered_prefill = threading.Event()

    def prefill(tokens, page_table, pages, last_index, start):
        entered_prefill.set()
        release_prefill.wait(timeout=30)
        logits = np.zeros([1, VOCAB], dtype=np.float32)
        return logits, pages

    def decode(tokens, positions, page_tables, pages):
        raise AssertionError("never reached")

    executor = ThreadPoolExecutor(max_workers=1)

    async def run():
        from client_tpu.llm import LlmEngine

        engine = LlmEngine(
            prefill,
            decode,
            pages=object(),
            engine_config=EngineConfig(
                block_size=4, num_blocks=9, max_seq_len=32
            ),
            model_name="stub",
            executor=executor,
            clock_ns=clock,
        )
        seq = engine.submit([1, 2, 3], max_tokens=4)
        # let the loop allocate blocks and park inside the prefill call
        while not entered_prefill.is_set():
            await asyncio.sleep(0)
        assert engine.stats()["kv_blocks_in_use"] > 0
        engine.close()
        release_prefill.set()
        with pytest.raises(InferenceServerException, match="shut down"):
            async for _token, _final in seq:
                pass
        assert engine.stats()["kv_blocks_in_use"] == 0

    try:
        asyncio.run(run())
    finally:
        release_prefill.set()
        executor.shutdown(wait=True)


# ---------------------------------------------------------------------------
# model correctness + throughput on the float32 tiny llama
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def llm_model():
    """A warmed float32 tiny-llama engine model (float32: greedy argmax
    must be identical across compiled batch shapes; bf16 leaves exact
    ties whose winner differs between the B=1 and B=8 programs)."""
    import jax.numpy as jnp

    from client_tpu.llm.serving import LlmEngineModel
    from client_tpu.models import llama

    config = llama.LlamaConfig.tiny(max_seq_len=64, dtype=jnp.float32)
    model = LlmEngineModel(
        config=config,
        engine_config=EngineConfig(
            block_size=8,
            num_blocks=1 + 8 * 8,
            max_active=8,
            max_queue=32,
            max_seq_len=64,
        ),
    )
    model.warmup()
    yield model
    model.shutdown()


def _dense_reference(model, prompt, max_tokens):
    from client_tpu.models import llama

    return np.asarray(
        llama.generate(
            model._params,
            np.array([prompt], dtype=np.int32),
            model._config,
            max_tokens,
        )
    )[0].tolist()


async def _model_generate(model, prompt, max_tokens):
    out = []
    async for response in model.execute_decoupled(
        {"INPUT_IDS": np.array(prompt, dtype=np.int32)},
        {"max_tokens": max_tokens},
    ):
        out.append(int(response["OUTPUT_IDS"][0]))
        if response["__final__"]:
            break
    return out


PROMPTS = [
    [5, 9, 17, 3, 8],
    [1, 2, 3],
    [40, 41, 42, 43, 44, 45, 46],
    [7],
    [9, 9, 9, 9],
    [100, 101],
    [55, 66, 77],
    [8, 1, 6, 2, 9, 4],
]


def test_concurrent_generations_match_dense_reference(llm_model):
    """8 concurrent generations through the shared paged cache produce
    EXACTLY the dense per-request ``llama.generate`` outputs — the
    no-cross-contamination proof for the block pool."""
    refs = [_dense_reference(llm_model, p, 12) for p in PROMPTS]

    async def run():
        results = await asyncio.gather(
            *[_model_generate(llm_model, p, 12) for p in PROMPTS]
        )
        for prompt, got, expected in zip(PROMPTS, results, refs):
            assert got == expected, f"prompt {prompt} diverged"
        stats = llm_model.engine.stats()
        assert stats["kv_blocks_in_use"] == 0

    asyncio.run(run())


def test_preemption_under_cache_pressure_stays_correct():
    """A pool far smaller than the working set forces preemptions; the
    resumed sequences must still match the dense reference and the pool
    must end empty."""
    import jax.numpy as jnp

    from client_tpu.llm.serving import LlmEngineModel
    from client_tpu.models import llama

    config = llama.LlamaConfig.tiny(max_seq_len=64, dtype=jnp.float32)
    model = LlmEngineModel(
        config=config,
        engine_config=EngineConfig(
            block_size=4,
            num_blocks=7,  # 6 allocatable blocks = 24 cached tokens total
            max_active=8,
            max_queue=16,
            max_seq_len=24,
        ),
    )
    model.warmup()
    try:
        prompts = [[5, 9, 17, 3], [1, 2, 3], [40, 41, 42], [7, 8]]
        refs = [_dense_reference(model, p, 12) for p in prompts]

        async def run():
            results = await asyncio.gather(
                *[_model_generate(model, p, 12) for p in prompts]
            )
            for prompt, got, expected in zip(prompts, results, refs):
                assert got == expected, f"prompt {prompt} diverged"
            stats = model.engine.stats()
            assert stats["preemptions"] > 0
            assert stats["kv_blocks_in_use"] == 0

        asyncio.run(run())
    finally:
        model.shutdown()


def test_continuous_batching_beats_serial_2x(llm_model):
    """ISSUE 9 acceptance: N=8 concurrent generations >= 2x the
    aggregate tokens/sec of the same 8 run serially. The engine decodes
    all running sequences in ONE jitted step, so the expected win is
    near-Nx on a dispatch-bound tiny model; 2x leaves slack for host
    noise. The measured ratio is recorded in PERF.md."""
    import time

    max_tokens = 32
    prompts = [[i + 1, i + 2, i + 3, i + 4] for i in range(8)]

    async def serial():
        for p in prompts:
            out = await _model_generate(llm_model, p, max_tokens)
            assert len(out) == max_tokens

    async def concurrent():
        results = await asyncio.gather(
            *[_model_generate(llm_model, p, max_tokens) for p in prompts]
        )
        assert all(len(r) == max_tokens for r in results)

    # warm both compiled shapes (decode buckets 1 and 8) outside timing
    asyncio.run(_model_generate(llm_model, [3, 1, 4, 1], max_tokens))
    asyncio.run(concurrent())

    # Noise-aware (repo convention for perf guards on this shared 1-core
    # host): best of 3 measurement pairs. A scheduling hiccup can halve
    # one concurrent sample, but a real batching regression pins EVERY
    # pair near 1x. Standalone this measures ~4x (recorded in PERF.md).
    total_tokens = 8 * max_tokens
    ratio = 0.0
    for _attempt in range(3):
        t0 = time.monotonic()
        asyncio.run(serial())
        serial_s = time.monotonic() - t0
        t0 = time.monotonic()
        asyncio.run(concurrent())
        concurrent_s = time.monotonic() - t0
        serial_tps = total_tokens / serial_s
        concurrent_tps = total_tokens / concurrent_s
        ratio = concurrent_tps / serial_tps
        print(
            f"\ncontinuous batching: serial {serial_tps:.0f} tok/s, "
            f"concurrent {concurrent_tps:.0f} tok/s, ratio {ratio:.2f}x"
        )
        if ratio >= 2.0:
            break
    assert ratio >= 2.0, (
        f"continuous batching ratio {ratio:.2f}x < 2.0x on the best of "
        f"3 pairs (last: serial {serial_tps:.0f} tok/s, concurrent "
        f"{concurrent_tps:.0f} tok/s)"
    )
    assert llm_model.engine.stats()["kv_blocks_in_use"] == 0


# ---------------------------------------------------------------------------
# end to end: real front-ends
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def llm_server(llm_model):
    from client_tpu.server.core import ServerCore
    from client_tpu.server.model_repository import ModelRepository
    from client_tpu.server.models import IdentityModel
    from client_tpu.testing import InProcessServer

    repository = ModelRepository()
    core = ServerCore(repository)
    repository.add_model(llm_model)
    # an UNAVAILABLE entry for the /v1/models READY filter satellite
    repository.add_model(IdentityModel("identity_unready"), ready=False)
    with InProcessServer(core=core, builtin_models=False) as server:
        yield server


def _http_get(server, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{server.http_port}{path}"
    ) as response:
        return json.loads(response.read().decode())


def test_grpc_stream_cancel_reclaims_kv_blocks(llm_server, llm_model):
    """ISSUE 9 satellite: cancelling a decoupled gRPC stream
    mid-generation reclaims the sequence's KV blocks (gauge returns to
    baseline) and the step loop drops the sequence within an iteration."""
    import client_tpu.grpc.aio as grpcclient

    engine = llm_model.engine

    async def run():
        async with grpcclient.InferenceServerClient(
            llm_server.grpc_url
        ) as client:

            async def requests():
                tensor = grpcclient.InferInput("INPUT_IDS", [4], "INT32")
                tensor.set_data_from_numpy(
                    np.array([5, 9, 17, 3], dtype=np.int32)
                )
                yield {
                    "model_name": "llm_engine",
                    "inputs": [tensor],
                    "parameters": {"max_tokens": 48},
                }

            stream = client.stream_infer(requests())
            received = 0
            async for result, error in stream:
                assert error is None, error
                assert result.as_numpy("OUTPUT_IDS").shape == (1,)
                received += 1
                if received == 3:
                    stream.cancel()
                    break
            assert received == 3
        # blocks-in-use returns to baseline within the step loop's next
        # iterations (bounded wait, loop-tick granularity)
        for _ in range(100):
            stats = engine.stats()
            if stats["kv_blocks_in_use"] == 0 and not stats["active_sequences"]:
                break
            await asyncio.sleep(0.05)
        stats = engine.stats()
        assert stats["kv_blocks_in_use"] == 0
        assert stats["active_sequences"] == 0
        assert stats["cancelled"] >= 1

    future = asyncio.run_coroutine_threadsafe(run(), llm_server._loop)
    future.result(timeout=120)


def test_engine_metrics_exported(llm_server, llm_model):
    """The engine families ride the existing registry and reflect the
    allocator's live state on /metrics."""
    with urllib.request.urlopen(
        f"http://127.0.0.1:{llm_server.http_port}/metrics"
    ) as response:
        text = response.read().decode()
    lines = text.splitlines()

    def value_of(prefix):
        for line in lines:
            if line.startswith(prefix):
                return float(line.rsplit(" ", 1)[1])
        raise AssertionError(f"no {prefix} sample in /metrics")

    assert value_of('tpu_kv_blocks_in_use{model="llm_engine"}') == 0.0
    assert value_of('tpu_kv_blocks_total{model="llm_engine"}') == float(
        llm_model.engine.allocator.capacity
    )
    # PR-14 sharing families ride the same registry (zero at idle; the
    # short-prompt workload here has no full prompt blocks to share)
    assert value_of('tpu_kv_blocks_shared{model="llm_engine"}') == 0.0
    assert "tpu_prefix_cache_hits_total" in text
    assert value_of('tpu_llm_active_sequences{model="llm_engine"}') == 0.0
    assert value_of('tpu_llm_generated_tokens_total{model="llm_engine"}') > 0
    assert value_of('tpu_llm_step_batch_size_count{model="llm_engine"}') > 0


def test_openai_models_lists_only_ready(llm_server):
    """Satellite: /v1/models filters the repository index to READY
    models — UNAVAILABLE/unloaded entries must not be advertised."""
    doc = _http_get(llm_server, "/v1/models")
    names = {entry["id"] for entry in doc["data"]}
    assert "llm_engine" in names
    assert "identity_unready" not in names


def test_openai_max_tokens_validation(llm_server):
    """Satellite: malformed max_tokens is a clean 400 with an OpenAI
    error body, never a 500 or a mid-stream failure."""
    import urllib.error

    def post(body):
        request = urllib.request.Request(
            f"http://127.0.0.1:{llm_server.http_port}/v1/chat/completions",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        try:
            with urllib.request.urlopen(request) as response:
                return response.status, json.loads(response.read().decode())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read().decode())

    base = {
        "model": "llm_engine",
        "messages": [{"role": "user", "content": "hi there"}],
    }
    for bad in ("sixteen", 0, -3, 2**31, 1.5, True):
        status, doc = post({**base, "max_tokens": bad})
        assert status == 400, f"max_tokens={bad!r} -> {status}"
        assert doc["error"]["type"] == "invalid_request_error"
        assert doc["error"]["param"] == "max_tokens"
    # above the model's context limit but under the global cap: the
    # engine's submit-time rejection must surface as a real 400 BEFORE
    # the SSE 200 commits, not as an in-band error event
    status, doc = post({**base, "max_tokens": 600, "stream": True})
    assert status == 400
    assert "max sequence length" in doc["error"]["message"]
    # a valid request still works (stream=False JSON completion)
    status, doc = post({**base, "max_tokens": 4})
    assert status == 200
    assert doc["usage"]["completion_tokens"] == 4


def test_openai_sampling_params_reach_engine(llm_server):
    """PR-14 satellite: temperature/seed/top_k in the OpenAI body reach
    the engine — equal seeds reproduce the completion, malformed values
    are clean 400s."""
    import urllib.error

    def post(body):
        request = urllib.request.Request(
            f"http://127.0.0.1:{llm_server.http_port}/v1/chat/completions",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        try:
            with urllib.request.urlopen(request) as response:
                return response.status, json.loads(response.read().decode())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read().decode())

    base = {
        "model": "llm_engine",
        "messages": [{"role": "user", "content": "sample me"}],
        "max_tokens": 8,
        "temperature": 1.0,
        "top_k": 16,
        "seed": 11,
    }
    status, first = post(base)
    assert status == 200
    status, second = post(base)
    assert status == 200
    assert (
        first["choices"][0]["message"]["content"]
        == second["choices"][0]["message"]["content"]
    )
    for field, bad in (("temperature", -1), ("temperature", "hot"),
                       ("seed", 1.5), ("top_k", -2)):
        status, doc = post({**base, field: bad})
        assert status == 400, f"{field}={bad!r} -> {status}"
        assert doc["error"]["param"] == field


def test_genai_perf_drives_engine_end_to_end(llm_server, tmp_path, capsys):
    """ISSUE 9 acceptance: genai-perf drives llm_engine through the real
    gRPC front-end in streaming mode and reports TTFT, inter-token
    latency, and tokens/sec — plus the --json-summary machine line."""
    from client_tpu.genai_perf.main import main

    from client_tpu.testing import retry_grpc_poller_flake

    def _one_pass():
        code = main(
            [
                "-m", "llm_engine",
                "-u", llm_server.grpc_url,
                "--num-prompts", "8",
                "--synthetic-input-tokens-mean", "8",
                "--output-tokens-mean", "10",
                "--concurrency", "4",
                "--measurement-interval", "1500",
                "--stability-percentage", "80",
                "--max-trials", "3",
                "--artifact-dir", str(tmp_path),
                "--json-summary",
            ]
        )
        assert code == 0
        return capsys.readouterr().out

    # a run that completes with zero requests is the grpcio poller
    # flake, not an engine regression — the shared shim retries once
    out = retry_grpc_poller_flake(
        _one_pass, lambda text: "time_to_first_token" in text
    )
    assert "time_to_first_token" in out
    assert "inter_token_latency" in out
    summary = None
    for line in out.splitlines():
        if line.startswith("{") and "tokens_per_sec" in line:
            summary = json.loads(line)
    assert summary is not None, "--json-summary line missing"
    assert summary["ttft_avg_ms"] > 0
    assert summary["itl_avg_ms"] > 0
    assert summary["tokens_per_sec"] > 0
    assert summary["request_count"] > 0
    report = json.loads((tmp_path / "llm_metrics.json").read_text())
    assert report["inter_token_latency"]["count"] > 0
    assert report["output_token_throughput_per_s"] == pytest.approx(
        summary["tokens_per_sec"], rel=0.01
    )


# ---------------------------------------------------------------------------
# a state cache group: one slot a sequence beside the full group's blocks
# ---------------------------------------------------------------------------


def _state_engine(clock, seen, row_bytes=((64, 64), (1000, 1000)),
                  tile_pages=(2, 1), **overrides):
    """A stub engine over a full group and a state group whose device
    functions record the tables they are handed (``seen``);
    ``row_bytes`` is the model's ``kv_row_bytes``."""
    from client_tpu.models.engine_model import FULL, STATE, CacheGroup

    def prefill(tokens, page_table, pages, last_index, start):
        seen.append(("prefill", np.array(page_table)))
        logits = np.zeros([1, VOCAB], dtype=np.float32)
        logits[0, (int(tokens.sum()) + start) % VOCAB] = 1.0
        return logits, pages

    holder = {}

    def decode(tokens, positions, page_tables, pages):
        seen.append(("decode", np.array(page_tables)))
        seen.append(("stats", holder["engine"].stats()))
        n = tokens.shape[0]
        logits = np.zeros([n, VOCAB], dtype=np.float32)
        for i in range(n):
            logits[i, int(tokens[i] + positions[i]) % VOCAB] = 1.0
        return logits, pages

    defaults = dict(
        block_size=4, num_blocks=17, max_active=3, max_queue=8,
        max_seq_len=32, prefix_sharing=False,
        cache_groups=(CacheGroup(FULL, (1,)), CacheGroup(STATE, (0, 2))),
    )
    defaults.update(overrides)
    holder["engine"] = LlmEngine(
        prefill,
        decode_fn_from_logits(decode),
        pages=object(),
        engine_config=EngineConfig(**defaults),
        model_name="stub",
        clock_ns=clock,
        attn_tile_pages=tile_pages,
        kv_row_bytes=row_bytes,
    )
    return holder["engine"]


def test_a_state_group_gives_every_sequence_one_slot_for_its_whole_life():
    """Five sequences over three slots: a table's state row holds the
    slot in column 0 and zeros elsewhere (a padding lane the trash
    slot), live lanes hold distinct slots that never change while they
    run, the slots are given back, the tile counters see the full group
    alone, and ``stats()`` serves the slots held and their bytes."""
    seen = []
    engine = _state_engine(_FakeClock(), seen)

    async def run():
        seqs = [engine.submit([1 + i, 2, 3], max_tokens=9) for i in range(5)]
        return [await _collect(s) for s in seqs]

    out = asyncio.run(run())
    assert all(len(tokens) == 9 for tokens in out)
    running = [entry for kind, entry in seen if kind == "stats"]
    assert max(s["state_slots_in_use"] for s in running) == 3
    for mid in running:
        assert mid["state_slots_in_use"] == mid["active_sequences"]
        assert mid["kv_blocks_in_use_by_group"][1] == mid["active_sequences"]
        assert mid["state_bytes_by_group"] == [
            0, mid["state_slots_in_use"] * 1000 * 2]
    for kind, tables in seen:
        if kind == "stats":
            continue
        assert tables.shape[0] == 2
        state = tables[1]
        assert not state[..., 1:].any()
        if kind == "prefill":
            assert 1 <= state[0] <= 3
        else:
            live = state[:, 0][state[:, 0] != 0]
            assert len(set(live.tolist())) == len(live) and live.max() <= 3
    stats = engine.stats()
    assert stats["state_slots_in_use"] == 0
    assert stats["kv_blocks_in_use_by_group"] == [0, 0]
    assert stats["state_bytes_by_group"] == [0, 0]
    assert stats["completed"] == 5
    # only the full group is walked: at most a tile a column pair a lane
    assert 0 < stats["attn_tiles_walked"] <= 2 * stats["lane_steps"]
    assert stats["attn_blocks_live"] > 0 and stats["window_blocks_whole"] == 0


def test_a_preempted_sequence_gives_its_slot_back_and_takes_one_to_resume():
    """A full pool too small for three sequences: the victim's slot is
    free while it waits (the slots held are the sequences running, at
    every step), it is re-prefilled into a slot when it resumes, and
    nothing is held at the end. (That the resumed stream is the
    undisturbed one takes a model whose prefill and decode agree:
    ``tests/test_qwen3_next.py``.)"""
    seen = []
    tight = _state_engine(_FakeClock(), seen, num_blocks=8)

    async def run(engine):
        seqs = [engine.submit([1 + i, 2, 3, 4], max_tokens=14)
                for i in range(3)]
        return [await _collect(s) for s in seqs]

    resumed = asyncio.run(run(tight))
    assert [len(tokens) for tokens in resumed] == [14, 14, 14]
    assert tight.stats()["preemptions"] >= 1
    running = [entry for kind, entry in seen if kind == "stats"]
    assert all(s["state_slots_in_use"] == s["active_sequences"]
               for s in running)
    assert min(s["state_slots_in_use"] for s in running) < 3
    prefills = sum(kind == "prefill" for kind, _ in seen)
    assert prefills == 3 + tight.stats()["preemptions"]
    assert tight.stats()["state_slots_in_use"] == 0


@pytest.mark.parametrize("setting,named", [
    (dict(prefix_sharing=True), "prefix_sharing=True"),
    (dict(spec_k=2), "spec_k=2"),
])
def test_a_state_group_refuses_sharing_and_speculation_by_name(setting, named):
    with pytest.raises(ValueError, match="state cache group") as refused:
        _state_engine(_FakeClock(), [], **setting)
    assert named in str(refused.value) and "truncate" in str(refused.value)
    assert "one slot of 4" in str(refused.value)


def test_two_full_groups_beside_a_state_group_are_refused():
    from client_tpu.models.engine_model import FULL, STATE, CacheGroup

    with pytest.raises(ValueError, match="exactly one full cache group"):
        _state_engine(_FakeClock(), [], cache_groups=(
            CacheGroup(FULL, (0,)), CacheGroup(FULL, (1,)),
            CacheGroup(STATE, (2,))))


@pytest.mark.parametrize("layers,slot", [
    # qwen3_next_80b: 12 DeltaNet layers of 2,146,304 B a slot to 4 full
    (((3, 7, 11, 15), tuple(i for i in range(16) if i % 4 != 3)), 2146304),
    # jamba2_3b: 26 Mamba layers of 358,400 B a slot to 2 attention layers
    (((7, 21), tuple(i for i in range(28) if i % 14 != 7)), 358400),
])
def test_state_bytes_follow_the_models_slot_and_its_layers(layers, slot):
    """Two state-group models of different shapes behind the one engine:
    what ``stats()`` serves of a state group is the slots held times the
    model's own bytes a slot times the group's layers, whichever model
    gave them; nothing under ``llm/`` holds a state layer's shape."""
    from client_tpu.models.engine_model import FULL, STATE, CacheGroup

    seen = []
    engine = _state_engine(
        _FakeClock(), seen, row_bytes=((512, 512), (slot, slot)),
        cache_groups=(CacheGroup(FULL, layers[0]),
                      CacheGroup(STATE, layers[1])))

    async def run():
        seqs = [engine.submit([1 + i, 2, 3], max_tokens=6) for i in range(4)]
        return [await _collect(s) for s in seqs]

    assert all(len(tokens) == 6 for tokens in asyncio.run(run()))
    running = [entry for kind, entry in seen if kind == "stats"]
    assert max(s["state_slots_in_use"] for s in running) == 3
    for mid in running:
        assert mid["state_bytes_by_group"] == [
            0, mid["state_slots_in_use"] * slot * len(layers[1])]
        assert mid["kv_row_bytes_by_group"][1] == {
            "stored": slot, "counted": slot}
    assert engine.stats()["state_bytes_by_group"] == [0, 0]


# phi4_mini_flash: ONE full layer whose pool eight layers read, eight
# window layers, nine Mamba layers; the other fourteen store nothing and
# are in no group
_SAMBAY_GROUPS = ((17,), tuple(range(1, 16, 2)), tuple(range(0, 17, 2)))


def _three_group_engine(seen, **overrides):
    from client_tpu.models.engine_model import (
        FULL, STATE, WINDOW, CacheGroup)

    full, window, state = _SAMBAY_GROUPS
    return _state_engine(
        _FakeClock(), seen,
        row_bytes=((5120, 5120), (5120, 5120), (358400, 358400)),
        tile_pages=(2, 2, 1),
        cache_groups=(CacheGroup(FULL, full),
                      CacheGroup(WINDOW, window, window=8),
                      CacheGroup(STATE, state)), **overrides)


def test_three_cache_groups_at_once_a_ring_beside_a_slot():
    """A full, a window and a state group together (`phi4_mini_flash`'s
    three): every device call gets tables ``[3, ...]`` in the groups'
    order, the window row a ring at its last columns, the state row the
    slot in column 0 and nothing else; a lane's slot and ring are its own
    for its whole life; `stats()` serves three entries a group, the full
    group's blocks those of the ONE layer that stores them, the state
    group's bytes its nine layers'; and all three are given back."""
    seen = []
    engine = _three_group_engine(seen)

    async def run():
        seqs = [engine.submit([1 + i, 2, 3], max_tokens=14) for i in range(5)]
        return [await _collect(s) for s in seqs]

    out = asyncio.run(run())
    assert all(len(tokens) == 14 for tokens in out)
    decodes = [t for kind, t in seen if kind == "decode"]
    prefills = [t for kind, t in seen if kind == "prefill"]
    assert all(t.shape[0] == 3 and t.ndim == 3 for t in decodes)
    assert all(t.shape[0] == 3 and t.ndim == 2 for t in prefills)
    ring = window_ring_blocks(8, 4, 2)
    for tables in decodes:
        live = tables[2, :, 0] > 0
        assert not tables[2, :, 1:].any()
        assert len(set(tables[2, live, 0])) == live.sum()
        # a ring's blocks at the last columns a lane has reached, none
        # shared with another lane's, and none of them the trash block's
        held = [set(row[row > 0]) for row in tables[1, live]]
        assert all(0 < len(blocks) <= ring for blocks in held)
        assert sum(map(len, held)) == len(set().union(*held))
    running = [entry for kind, entry in seen if kind == "stats"]
    assert max(s["state_slots_in_use"] for s in running) == 3
    for mid in running:
        full, window, state = mid["kv_blocks_in_use_by_group"]
        assert state == mid["state_slots_in_use"] == mid["active_sequences"]
        assert window == ring * mid["active_sequences"]
        assert mid["state_bytes_by_group"] == [0, 0, state * 358400 * 9]
        assert [row["stored"] for row in mid["kv_row_bytes_by_group"]] == [
            5120, 5120, 358400]
    groups = engine.config.cache_groups
    assert [len(g.layers) for g in groups] == [1, 8, 9]
    stats = engine.stats()
    assert stats["kv_blocks_in_use_by_group"] == [0, 0, 0]
    assert stats["state_slots_in_use"] == 0 and stats["completed"] == 5
    assert stats["window_blocks_whole"] > 0


def test_a_preempted_sequence_gives_back_its_blocks_its_ring_and_its_slot():
    """Three groups and a full pool too small for three sequences: the
    victim's ring and slot are free while it waits, it is re-prefilled
    over all three tables when it resumes, and nothing is held at the
    end."""
    seen = []
    tight = _three_group_engine(seen, num_blocks=9)

    async def run(engine):
        seqs = [engine.submit([1 + i, 2, 3, 4], max_tokens=14)
                for i in range(3)]
        return [await _collect(s) for s in seqs]

    resumed = asyncio.run(run(tight))
    assert [len(tokens) for tokens in resumed] == [14, 14, 14]
    assert tight.stats()["preemptions"] >= 1
    ring = window_ring_blocks(8, 4, 2)
    running = [entry for kind, entry in seen if kind == "stats"]
    for mid in running:
        assert mid["state_slots_in_use"] == mid["active_sequences"]
        assert mid["kv_blocks_in_use_by_group"][1] == (
            ring * mid["active_sequences"])
    assert min(s["state_slots_in_use"] for s in running) < 3
    prefills = sum(kind == "prefill" for kind, _ in seen)
    assert prefills == 3 + tight.stats()["preemptions"]
    assert tight.stats()["kv_blocks_in_use_by_group"] == [0, 0, 0]


@pytest.mark.parametrize("setting", [
    dict(prefix_sharing=True), dict(spec_k=2)])
def test_three_groups_refuse_sharing_and_speculation_at_load(setting):
    """Beside both a window and a state group the refusal is the state
    group's, by name (it is checked first)."""
    with pytest.raises(ValueError, match="state cache group"):
        _three_group_engine([], **setting)
