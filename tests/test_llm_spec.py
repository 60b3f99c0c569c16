"""PR-15: speculative decoding — draft-propose + batched paged-verify.

Five tiers:

- proposer units (no jax): n-gram prompt-lookup matching, draft-length
  clamping, allocator rollback (``truncate``) COW discipline;
- multi-query kernel parity (jax): every paged-attention function and
  ``decode_step_paged_multi`` within 1e-5 of K+1 SEQUENTIAL decode
  steps, including ragged page-table widths and padding rows;
- engine correctness on the float32 tiny llama: greedy spec-on output
  is TOKEN-IDENTICAL to spec-off (both proposers, K in {1, 2, 4}), the
  per-request ``speculation`` switch works, and KV accounting is
  airtight under mixed accept/reject/preempt traffic;
- sampling exactness (stub, fake clock): the vectorized sampler is
  bit-exact against the scalar reference implementation, and seeded
  sampled streams replay identically across preemption WITH speculation
  enabled;
- surfaces: spec counters in /metrics and ``/v2/debug/state``, the
  and the genai-perf ``--speculation`` passthrough + ``--json-summary``
  fields.
"""

import asyncio

import numpy as np
import pytest

from client_tpu.llm import (
    BlockAllocator,
    EngineConfig,
    LlmEngine,
    NgramProposer,
)
from client_tpu.llm.engine import decode_fn_from_logits
from client_tpu.utils import InferenceServerException

pytestmark = pytest.mark.llm


# ---------------------------------------------------------------------------
# proposer + allocator units
# ---------------------------------------------------------------------------


def test_ngram_proposer_prompt_lookup():
    proposer = NgramProposer(k=4, ngram=2)
    # trailing bigram (1, 2) recurs at the start: propose what followed
    assert proposer.propose([1, 2, 3, 4, 5, 1, 2], 4) == [3, 4, 5, 1]
    # k clamps the copy length
    assert proposer.propose([1, 2, 3, 4, 5, 1, 2], 2) == [3, 4]
    # no earlier occurrence of (9, 9), fall back to the shorter suffix
    # match on (9,): rightmost earlier 9 is followed by 9
    assert proposer.propose([5, 9, 9], 3) == [9]
    # nothing repeats -> no proposal (the engine then runs plain decode)
    assert proposer.propose([1, 2, 3], 4) == []
    assert proposer.propose([7], 4) == []
    with pytest.raises(ValueError):
        NgramProposer(k=0)
    with pytest.raises(ValueError):
        NgramProposer(k=2, ngram=1, min_ngram=2)


def test_ngram_proposer_prefers_longest_and_most_recent_match():
    proposer = NgramProposer(k=2, ngram=3)
    # trigram (1, 2, 3) occurs twice earlier; the MOST RECENT one (index
    # 4) wins, so the proposal is what followed it there
    ctx = [1, 2, 3, 9, 1, 2, 3, 8, 7, 1, 2, 3]
    assert proposer.propose(ctx, 2) == [8, 7]


def test_allocator_truncate_rolls_back_exclusive_tail_only():
    alloc = BlockAllocator(num_blocks=17, block_size=4)
    blocks = alloc.allocate("a", 5)
    assert alloc.truncate("a", 3) == 2
    assert alloc.owned("a") == blocks[:3]
    assert alloc.free_blocks == alloc.capacity - 3
    # idempotent at the boundary
    assert alloc.truncate("a", 3) == 0
    # a shared tail block is a COW violation, not a reclaim
    hashes = alloc.chain_hashes(list(range(12)))
    alloc.free("a")
    a, _ = alloc.allocate_shared("a", 3, hashes)
    alloc.publish("a", hashes)
    b, matched = alloc.allocate_shared("b", 3, hashes)
    assert matched == 3
    with pytest.raises(InferenceServerException, match="COW"):
        alloc.truncate("b", 1)
    # published (but single-referenced) blocks are protected too
    alloc.free("b")
    with pytest.raises(InferenceServerException, match="COW"):
        alloc.truncate("a", 1)


# ---------------------------------------------------------------------------
# multi-query kernel parity
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_llama():
    import jax
    import jax.numpy as jnp

    from client_tpu.models import llama

    config = llama.LlamaConfig.tiny(max_seq_len=64, dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), config)
    return config, params


def test_decode_multi_matches_sequential_oracle(tiny_llama):
    """The verification contract: one multi-query call's K+1 logits rows
    equal K+1 sequential decode steps feeding the same tokens — for
    every kernel implementation, at full AND ragged page-table width,
    with per-lane draft lengths and padding rows."""
    from client_tpu.models import llama
    from client_tpu.models import paged_attention as pa

    config, params = tiny_llama
    bs, max_blocks = 8, 8
    contexts = [[5, 9, 17, 3, 8], [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11], [7]]
    pages = llama.init_kv_pages(config, 33, bs)
    tables = np.zeros((len(contexts), max_blocks), dtype=np.int32)
    next_free = 1
    for i, ctx in enumerate(contexts):
        n_blocks = (len(ctx) + 4 + bs - 1) // bs
        tables[i, :n_blocks] = range(next_free, next_free + n_blocks)
        next_free += n_blocks
        toks = np.zeros([1, 16], dtype=np.int32)
        toks[0, : len(ctx)] = ctx
        _, pages = llama.prefill_into_pages(
            params, toks, tables[i], pages, len(ctx) - 1, config
        )
    last = np.array([11, 12, 13], dtype=np.int32)
    drafts = np.array([[3, 7], [9, 1], [2, 4]], dtype=np.int32)
    pos0 = np.array([len(c) for c in contexts], dtype=np.int32)

    # sequential oracle: feed last token then each draft, one step each
    seq_logits = []
    p_seq = pages
    toks, pos = last.copy(), pos0.copy()
    for step in range(3):
        lo, p_seq = llama.decode_step_paged_attn(
            params, toks, pos, tables, p_seq, config,
            pa.paged_attention_reference,
        )
        seq_logits.append(np.asarray(lo))
        if step < 2:
            toks = drafts[:, step].copy()
            pos = pos + 1
    oracle = np.stack(seq_logits, axis=1)  # [B, 3, V]

    t = 3
    tokens = np.concatenate([last[:, None], drafts], axis=1)
    positions = (pos0[:, None] + np.arange(t)[None, :]).astype(np.int32)
    lengths = np.full([3], t, dtype=np.int32)
    impls = {
        "reference": pa.paged_attention_reference,
        "fused_xla": pa.paged_attention_xla,
        "pallas_interpret": pa.resolve_decode_attention(
            "pallas_interpret", "cpu")[1],
    }
    for name, attn in impls.items():
        out, _ = llama.decode_step_paged_multi(
            params, tokens, positions, lengths, tables, pages, config, attn,
        )
        assert np.abs(np.asarray(out) - oracle).max() <= 1e-5, name

    # ragged width (2 blocks) + per-lane lengths with padding rows
    lengths2 = np.array([3, 2, 1], dtype=np.int32)
    clamped = (
        pos0[:, None]
        + np.minimum(np.arange(t)[None, :], (lengths2 - 1)[:, None])
    ).astype(np.int32)
    out, _ = llama.decode_step_paged_multi(
        params, tokens, clamped, lengths2, tables[:, :2], pages, config,
        pa.paged_attention_xla,
    )
    out = np.asarray(out)
    for i in range(3):
        err = np.abs(out[i, : lengths2[i]] - oracle[i, : lengths2[i]]).max()
        assert err <= 1e-5, f"lane {i}"


def test_padding_rows_never_clobber_live_pages(tiny_llama):
    """Rows beyond a lane's length are masked writes: the page pool's
    live content is bit-identical whether a lane verifies with padding
    rows or none at all."""
    from client_tpu.models import llama
    from client_tpu.models import paged_attention as pa

    config, params = tiny_llama
    bs = 8
    ctx = [5, 9, 17, 3, 8]
    pages = llama.init_kv_pages(config, 9, bs)
    table = np.zeros([4], dtype=np.int32)
    table[:2] = [1, 2]
    toks = np.zeros([1, 8], dtype=np.int32)
    toks[0, : len(ctx)] = ctx
    _, pages = llama.prefill_into_pages(
        params, toks, table, pages, len(ctx) - 1, config
    )
    tokens = np.array([[11, 0, 0]], dtype=np.int32)
    positions = np.array([[5, 5, 5]], dtype=np.int32)
    _, wide = llama.decode_step_paged_multi(
        params, tokens, positions, np.array([1], dtype=np.int32),
        table[None], pages, config, pa.paged_attention_xla,
    )
    _, narrow = llama.decode_step_paged_multi(
        params, tokens[:, :1], positions[:, :1],
        np.array([1], dtype=np.int32), table[None], pages, config,
        pa.paged_attention_xla,
    )
    # the ONLY slot a verify of length 1 may touch is (block 1, offset
    # 5); everything else must be BIT-identical to the padding-free run
    # (the written slot itself only agrees to float tolerance — same
    # math at a different batch shape), and in particular bit-identical
    # to the pre-verify pages everywhere the write mask says "masked"
    for (wk, wv), (nk, nv), (pk, pv) in zip(wide, narrow, pages):
        for w, n, p in ((wk, nk, pk), (wv, nv, pv)):
            w, n, p = np.asarray(w), np.asarray(n), np.asarray(p)
            mask = np.ones_like(w, dtype=bool)
            mask[1, 5] = False
            np.testing.assert_array_equal(w[1:3][mask[1:3]], n[1:3][mask[1:3]])
            np.testing.assert_array_equal(w[1:3][mask[1:3]], p[1:3][mask[1:3]])
            assert np.abs(w[1, 5] - n[1, 5]).max() <= 1e-5


# ---------------------------------------------------------------------------
# engine-level exactness on the tiny llama
# ---------------------------------------------------------------------------


def _spec_model(tiny_llama, speculation, **engine_overrides):
    from client_tpu.llm.serving import LlmEngineModel

    config, params = tiny_llama
    defaults = dict(
        block_size=8, num_blocks=1 + 8 * 8, max_active=8, max_queue=32,
        max_seq_len=64,
    )
    defaults.update(engine_overrides)
    if speculation and speculation.get("mode") == "draft":
        # the tests' draft shares the target's weights: acceptance is
        # high and, crucially, parity failures can't hide behind a weak
        # draft (every draft token exercises the verify path)
        speculation = dict(speculation, draft="self")
    model = LlmEngineModel(
        config=config,
        params=params,
        engine_config=EngineConfig(**defaults),
        speculation=speculation,
    )
    model.warmup()
    return model


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


async def _model_generate(model, prompt, max_tokens, parameters=None):
    params = {"max_tokens": max_tokens}
    params.update(parameters or {})
    out = []
    async for response in model.execute_decoupled(
        {"INPUT_IDS": np.array(prompt, dtype=np.int32)}, params
    ):
        out.append(int(response["OUTPUT_IDS"][0]))
        if response["__final__"]:
            break
    return out


PROMPTS = [
    [9, 3, 7, 1, 5, 2, 8, 4, 6, 1, 2, 3, 10],
    [5, 9, 17, 3, 8],
    [1, 2, 3, 1, 2, 3, 1, 2],
    [7],
]


@pytest.mark.parametrize("mode", ["draft", "ngram"])
@pytest.mark.parametrize("k", [1, 2, 4])
def test_greedy_spec_on_equals_spec_off(tiny_llama, mode, k):
    """The acceptance test: greedy speculative output is token-identical
    to non-speculative greedy (== the dense oracle) for both proposers
    at K in {1, 2, 4}, on every lane of a concurrent batch, and every
    KV block is reclaimed."""
    spec = {"mode": mode, "k": k}
    if mode == "ngram":
        spec["ngram"] = 2
    model = _spec_model(tiny_llama, spec)
    try:
        refs = [_dense_reference(model, p, 12) for p in PROMPTS]

        async def run():
            return await asyncio.gather(
                *[_model_generate(model, p, 12) for p in PROMPTS]
            )

        results = asyncio.run(run())
        for prompt, got, expected in zip(PROMPTS, results, refs):
            assert got == expected, f"{mode} k={k} diverged on {prompt}"
        stats = model.engine.stats()
        assert stats["kv_blocks_in_use"] == 0
        assert stats["speculative"] is True
        if mode == "draft":
            # the self-draft regime must actually speculate (and win)
            assert stats["spec_steps"] > 0
            assert stats["tokens_per_step"] > 1.0
    finally:
        model.shutdown()


def test_per_request_speculation_switch(tiny_llama):
    """`speculation: off` runs a sequence on the plain decode path (no
    verify steps booked for it) with identical output; malformed values
    are a 400."""
    model = _spec_model(tiny_llama, {"mode": "draft", "k": 3})
    try:
        prompt = PROMPTS[0]
        ref = _dense_reference(model, prompt, 10)

        async def run(params):
            return await _model_generate(model, prompt, 10, params)

        before = model.engine.stats()["spec_steps"]
        off = asyncio.run(run({"speculation": "off"}))
        assert off == ref
        assert model.engine.stats()["spec_steps"] == before
        on = asyncio.run(run({"speculation": "on"}))
        assert on == ref
        assert model.engine.stats()["spec_steps"] > before
        with pytest.raises(InferenceServerException, match="speculation"):
            model.engine.submit(
                [1, 2], max_tokens=2, parameters={"speculation": "maybe"}
            )
    finally:
        model.shutdown()


def test_spec_kv_airtight_under_mixed_traffic(tiny_llama):
    """KV discipline under accept/reject/preempt/cancel traffic with a
    pool far smaller than the gross working set: shared prefix blocks
    are never mutated, streams still match the dense oracle, and every
    block (including speculative lookahead) is reclaimed."""
    prefix = [9, 3, 7, 1, 5, 2, 8, 4]  # one full block @ 8
    model = _spec_model(
        tiny_llama, {"mode": "draft", "k": 3}, num_blocks=8
    )
    engine = model.engine
    try:
        prompts = [prefix + [30 + i] for i in range(4)]
        refs = [_dense_reference(model, p, 14) for p in prompts]

        async def run():
            # a holder pins the shared prefix blocks while spec traffic
            # churns around it. It has to outlast that traffic: the
            # engine decodes it whether or not its stream is read, so it
            # takes one token a step (speculation off), has room for
            # three times the steps the traffic needs, and the highest
            # priority keeps it from being the preemption's victim
            holder = engine.submit(
                prefix + [77, 78], max_tokens=32,
                parameters={"priority": 1, "speculation": "off"},
            )
            token, final = await holder.__anext__()
            assert not final
            shared_phys = list(engine.allocator.owned(holder.seq_id))[:1]

            def snapshot():
                return [
                    (
                        np.asarray(layer_pages[0][phys]).copy(),
                        np.asarray(layer_pages[1][phys]).copy(),
                    )
                    for layer_pages in engine._pages
                    for phys in shared_phys
                ]

            before = snapshot()
            # one cancelled mid-flight, the rest run to completion
            cancelled = engine.submit(prefix + [99], max_tokens=16)
            await cancelled.__anext__()
            engine.release(cancelled)
            results = await asyncio.gather(
                *[_model_generate(model, p, 14) for p in prompts]
            )
            after = snapshot()
            # the snapshots are of a block the holder still pins: a freed
            # block handed to a later sequence would be rewritten, by
            # right, and say nothing about copy-on-write
            assert engine.allocator.owned(holder.seq_id)[:1] == shared_phys
            assert holder.preemptions == 0
            for (bk, bv), (ak, av) in zip(before, after):
                np.testing.assert_array_equal(bk, ak)
                np.testing.assert_array_equal(bv, av)
            engine.release(holder)
            for _ in range(200):
                if engine.stats()["kv_blocks_in_use"] == 0:
                    break
                await asyncio.sleep(0)
            return results

        results = asyncio.run(run())
        for prompt, got, expected in zip(prompts, results, refs):
            assert got == expected, f"prompt {prompt} diverged"
        stats = engine.stats()
        assert stats["preemptions"] > 0
        assert stats["spec_steps"] > 0
        assert stats["kv_blocks_in_use"] == 0
    finally:
        model.shutdown()


# ---------------------------------------------------------------------------
# sampling exactness (stub engine, fake clock)
# ---------------------------------------------------------------------------

VOCAB = 32


def _scalar_sample_reference(seq, logits, gen_index):
    """The pre-vectorization scalar sampler, kept verbatim as the
    bit-exactness oracle for the batched pipeline."""
    if seq.temperature <= 0.0:
        return int(np.asarray(logits).argmax())
    scaled = np.asarray(logits, dtype=np.float64) / seq.temperature
    if seq.top_k and seq.top_k < scaled.shape[-1]:
        kth = np.partition(scaled, -seq.top_k)[-seq.top_k]
        scaled = np.where(scaled < kth, -np.inf, scaled)
    scaled = scaled - scaled.max()
    probs = np.exp(scaled)
    probs /= probs.sum()
    rng = np.random.default_rng((seq.seed, gen_index))
    return int(rng.choice(scaled.shape[-1], p=probs))


def test_vectorized_sampler_bit_exact_vs_scalar_reference():
    """The satellite regression test: the batched one-pass sampler pins
    EQUAL streams against the scalar per-row reference over mixed
    greedy/temperature/top-k lanes and many rows."""

    class _Seq:
        def __init__(self, temperature, top_k, seed):
            self.temperature = temperature
            self.top_k = top_k
            self.seed = seed

    engine = LlmEngine.__new__(LlmEngine)  # only _sample_rows is used
    rng = np.random.default_rng(7)
    seqs = [
        _Seq(0.0, 0, 0),
        _Seq(1.0, 0, 42),
        _Seq(0.7, 8, 42),
        _Seq(1.3, 4, 9),
        _Seq(2.0, 31, 1234567),
    ]
    items = []
    expected = []
    for step in range(20):
        for lane, seq in enumerate(seqs):
            row = rng.normal(size=VOCAB).astype(np.float32) * 3.0
            items.append((seq, row, step))
            expected.append(_scalar_sample_reference(seq, row, step))
    got = engine._sample_rows(items)
    assert got == expected


class _FakeClock:
    def __init__(self):
        self.now = 0

    def __call__(self):
        return self.now


def _stub_fns():
    """Prefill/decode/decode_multi that agree on one deterministic rule:
    the logits after token t at position p are peaked at (t + p) % VOCAB
    with enough spread that temperature sampling has real choices."""

    def logits_row(token, position):
        row = np.linspace(0.0, 1.0, VOCAB, dtype=np.float32)
        row[(int(token) + int(position)) % VOCAB] = 3.0
        return row

    def prefill(tokens, page_table, pages, last_index, start):
        return logits_row(tokens[0, last_index], start + last_index)[None], pages

    def decode(tokens, positions, page_tables, pages):
        return (
            np.stack([
                logits_row(t, p) for t, p in zip(tokens, positions)
            ]),
            pages,
        )

    def decode_multi(tokens, positions, lengths, page_tables, pages):
        b, t = tokens.shape
        out = np.zeros([b, t, VOCAB], dtype=np.float32)
        for i in range(b):
            for j in range(t):
                out[i, j] = logits_row(tokens[i, j], positions[i, j])
        return out, pages

    return prefill, decode, decode_multi


class _StubChainProposer:
    """Proposes the stub's exact greedy continuation — every draft
    verifies under greedy, so tokens/step hits K+1."""

    def propose(self, context, k):
        out = []
        tok, pos = context[-1], len(context) - 1
        for _ in range(k):
            tok = (tok + pos) % VOCAB
            pos += 1
            out.append(tok)
        return out


def _stub_engine(clock, spec_k=3, proposer=None, metrics=None, **overrides):
    prefill, decode, decode_multi = _stub_fns()
    defaults = dict(
        block_size=4, num_blocks=33, max_active=4, max_queue=8,
        max_seq_len=128, spec_k=spec_k,
    )
    defaults.update(overrides)
    return LlmEngine(
        prefill,
        decode_fn_from_logits(decode),
        pages=object(),
        engine_config=EngineConfig(**defaults),
        model_name="stub",
        metrics=metrics,
        clock_ns=clock,
        decode_multi_fn=decode_multi,
        proposer=proposer if proposer is not None else _StubChainProposer(),
    )


async def _collect(seq):
    out = []
    async for token, final in seq:
        out.append(token)
        if final:
            break
    return out


def test_seeded_sampled_stream_replays_across_preemption_with_spec():
    """ISSUE acceptance: seeded sampling replays identically across
    preemption with speculation enabled — accepted-count and all. A
    tight pool (forced preempt/resume mid-speculation) must emit the
    same streams as a roomy one, and both must match the engine with
    speculation disabled."""
    params = {"temperature": 1.0, "seed": 42, "top_k": 8}

    def run(num_blocks, spec_k):
        clock = _FakeClock()

        async def go():
            engine = _stub_engine(clock, spec_k=spec_k,
                                  num_blocks=num_blocks, max_seq_len=32)
            seqs = [
                engine.submit([1, 2, 3], max_tokens=10, parameters=params),
                engine.submit([4, 5, 6], max_tokens=10,
                              parameters={"temperature": 1.0, "seed": 9}),
            ]
            results = await asyncio.gather(*[_collect(s) for s in seqs])
            stats = engine.stats()
            assert stats["kv_blocks_in_use"] == 0
            engine.close()
            return results, stats

        return asyncio.run(go())

    plain, _ = run(num_blocks=33, spec_k=0)
    roomy, roomy_stats = run(num_blocks=33, spec_k=3)
    tight, tight_stats = run(num_blocks=5, spec_k=3)
    assert roomy_stats["preemptions"] == 0
    assert tight_stats["preemptions"] > 0
    assert roomy == plain
    assert tight == plain
    assert roomy_stats["spec_steps"] > 0
    assert tight_stats["spec_steps"] > 0


def test_spec_rollback_restores_plain_footprint_and_counts_admission():
    """Between steps a speculative engine owns exactly the blocks a
    plain one would (lookahead rolled back), and a wrong-every-time
    proposer still emits the exact plain stream at ~1 token/step."""

    class _WrongProposer:
        def propose(self, context, k):
            # provably wrong: the stub's next token is (t + p) % VOCAB,
            # this proposes (t + p + 1) % VOCAB
            return [(context[-1] + len(context)) % VOCAB] * k

    clock = _FakeClock()

    async def go():
        engine = _stub_engine(clock, proposer=_WrongProposer())
        plain = _stub_engine(clock, spec_k=0)
        seq = engine.submit([1, 2, 3], max_tokens=12)
        ref = plain.submit([1, 2, 3], max_tokens=12)
        got, expected = await asyncio.gather(_collect(seq), _collect(ref))
        assert got == expected
        stats = engine.stats()
        assert stats["spec_steps"] > 0
        assert stats["spec_accepted"] == 0
        # 11 decode tokens over 11 steps: every verify emitted exactly 1
        assert stats["tokens_per_step"] == 1.0
        assert stats["kv_blocks_in_use"] == 0
        engine.close()
        plain.close()

    asyncio.run(go())


def test_spec_metrics_exported():
    """The three PR-15 families ride the registry: proposed/accepted
    counters and the tokens-per-step histogram, plus stats() acceptance
    rate."""
    from client_tpu.server.metrics import ServerMetrics

    class _CoreStub:
        """Just enough ServerCore surface for a standalone registry."""

        device_busy_ns_total = 0

        def statistics(self):
            return {"model_stats": []}

    metrics = ServerMetrics(_CoreStub(), jax_module=None)
    clock = _FakeClock()

    async def go():
        engine = _stub_engine(clock, metrics=metrics)
        results = await asyncio.gather(
            _collect(engine.submit([1, 2, 3], max_tokens=8)),
            _collect(engine.submit([4, 5, 6], max_tokens=8)),
        )
        assert all(len(r) == 8 for r in results)
        stats = engine.stats()
        engine.close()
        return stats

    stats = asyncio.run(go())
    assert stats["spec_acceptance_rate"] == 1.0
    assert stats["tokens_per_step"] > 1.5
    text = metrics.render()
    assert 'tpu_llm_spec_proposed_total{model="stub"}' in text
    assert 'tpu_llm_spec_accepted_total{model="stub"}' in text
    assert "tpu_llm_spec_tokens_per_step_bucket" in text
    proposed = accepted = None
    for line in text.splitlines():
        if line.startswith('tpu_llm_spec_proposed_total{model="stub"}'):
            proposed = float(line.rsplit(" ", 1)[1])
        if line.startswith('tpu_llm_spec_accepted_total{model="stub"}'):
            accepted = float(line.rsplit(" ", 1)[1])
    assert proposed == stats["spec_proposed"]
    assert accepted == stats["spec_accepted"]


def test_debug_state_carries_llm_engine_stats(tiny_llama):
    """/v2/debug/state's llm block: engine stats (acceptance rate and
    all) per engine-backed model, straight from stats()."""
    from client_tpu.server.core import ServerCore
    from client_tpu.server.model_repository import ModelRepository

    model = _spec_model(tiny_llama, {"mode": "draft", "k": 2})
    try:
        repository = ModelRepository()
        core = ServerCore(repository)
        repository.add_model(model)
        asyncio.run(_model_generate(model, [5, 9, 17], 6))
        state = core.debug_state()
        block = state["llm"][model.name]
        assert block["spec_steps"] > 0
        assert 0.0 <= block["spec_acceptance_rate"] <= 1.0
        assert block["kv_blocks_in_use"] == 0
        core.close()
    finally:
        model.shutdown()


# ---------------------------------------------------------------------------
# harness / tooling satellites
# ---------------------------------------------------------------------------


def test_create_llm_inputs_speculation_passthrough(tmp_path):
    from client_tpu.genai_perf.inputs import create_llm_inputs

    doc = create_llm_inputs(
        str(tmp_path / "inputs.json"),
        num_prompts=3,
        input_tokens_mean=8,
        output_tokens_mean=4,
        speculation="off",
    )
    for entry in doc["data"]:
        assert entry["parameters"]["speculation"] == "off"
        assert entry["parameters"]["max_tokens"] >= 1  # merged, not clobbered
    plain = create_llm_inputs(
        "", num_prompts=1, input_tokens_mean=8, output_tokens_mean=4
    )
    assert "speculation" not in plain["data"][0].get("parameters", {})


def test_json_summary_spec_fields_and_delta():
    from client_tpu.genai_perf.main import (
        json_summary_line,
        spec_stats_delta,
    )
    from client_tpu.genai_perf.metrics import LLMMetrics

    metrics = LLMMetrics(request_count=1, benchmark_duration_ns=int(1e9))
    assert "tokens_per_step" not in json_summary_line(metrics)
    before = {
        "steps": 10, "lane_steps": 10, "step_tokens": 10,
        "spec_steps": 0, "spec_proposed": 0, "spec_accepted": 0,
    }
    after = {
        "steps": 20, "lane_steps": 22, "step_tokens": 40,
        "spec_steps": 10, "spec_proposed": 30, "spec_accepted": 24,
    }
    delta = spec_stats_delta(before, after)
    doc = json_summary_line(metrics, delta)
    assert doc["tokens_per_step"] == 2.5  # 30 tokens / 12 lane-steps
    assert doc["spec_acceptance_rate"] == 0.8
    # missing/reset counters degrade to no spec fields, never a crash
    assert spec_stats_delta(None, after) is None
    assert spec_stats_delta(after, before) is None  # negative = reset


def test_genai_perf_speculation_flag_rides_cli(tmp_path, monkeypatch):
    """--speculation reaches the generated corpus without a live server
    (the perf run itself is stubbed out)."""
    import json

    from client_tpu.genai_perf import main as genai_main

    captured = {}

    def fake_perf_main(argv):
        # grab the inputs file the harness would have replayed
        inputs_path = argv[argv.index("--input-data") + 1]
        with open(inputs_path) as f:
            captured["doc"] = json.load(f)
        export = argv[argv.index("--profile-export-file") + 1]
        with open(export, "w") as f:
            json.dump({"experiments": []}, f)
        return 0

    monkeypatch.setattr(
        "client_tpu.perf.cli.main", fake_perf_main
    )
    code = genai_main.main(
        [
            "-m", "llm_engine",
            "-u", "localhost:1",
            "--num-prompts", "2",
            "--speculation", "off",
            "--artifact-dir", str(tmp_path),
        ]
    )
    assert code == 0
    for entry in captured["doc"]["data"]:
        assert entry["parameters"]["speculation"] == "off"
