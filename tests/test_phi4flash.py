"""Phi-4-mini-flash-reasoning's decoder (``phi4flash``, SambaY) on the
engine at a toy size, float32, on the CPU: the program
(`client_tpu/models/phi4flash.py`, the value-pair call of
`models/paged_attention.py`, the ungated `models/selective_scan.py`, three
cache groups at once in `llm/engine.py`) against the plain reference the
benchmark keeps (`benchmark/lib/reference_phi4flash.py`), on seeded
weights.

Tolerances. Everything is float32 and the two sides differ in the order
of their sums and in FORM: the reference runs every layer over every
position, the recurrence token by token, each attention layer's two
softmaxes apart over the whole sequence, and caches nothing; the program
prefills the self-decoder alone (the cross-decoder on the last position),
then decodes through pages, rings and slots, both softmaxes of all pairs
in one paged call, seven cross layers over one pool. The logits, of size
about 4, came out within 1e-5 over twelve layers and 40 decoded tokens.
``TOLERANCE`` 1e-4 leaves that ten times of room; the smallest change any
departure below makes is 100 times over it.
"""

import asyncio
import dataclasses
import functools

import numpy as np
import pytest

pytestmark = pytest.mark.llm

TOLERANCE = 1e-4
BLOCK, SEED, TILE_PAGES = 8, 11, 2

TOY = dict(
    hidden_size=64, num_hidden_layers=12, num_attention_heads=8,
    num_key_value_heads=4, intermediate_size=128, sliding_window=16,
    mb_per_layer=2, layer_norm_eps=1e-5, vocab_size=256,
    max_position_embeddings=128, mamba_expand=2, mamba_d_state=16,
    mamba_d_conv=4, mamba_dt_rank=8, tie_word_embeddings=True,
    hidden_act="silu", mlp_bias=False, lm_head_bias=False,
    model_type="phi4flash",
)
#: layers 0 2 4 6 Mamba (6 the memory), 1 3 5 under the window, 7 full,
#: 8 10 gated memory units, 9 11 cross-attention
KINDS = ("mamba", "window") * 3 + ("mamba", "full") + ("gmu", "cross") * 2

#: (prompt, total) of the lanes one decode batch holds, ragged, each past
#: the window of 16 and past a tile of 2 pages of 8; a fourth lane of
#: every step is a batch bucket's padding and names the trash slot
LANES = ((21, 61), (5, 45), (60, 100))
SLOTS = (2, 3, 1)


@pytest.fixture(scope="module", autouse=True)
def small_tiles():
    """Tiles of :data:`TILE_PAGES` pages for every kernel call of this
    file (the toy's pages are 16 rows of 16 float32: 1 KiB), so that its
    contexts lie over several tiles and a ring is a whole number of them.
    The kernel is jitted: the cut holds for shapes first traced under it,
    which are this file's alone."""
    from client_tpu.models import paged_attention as pa

    budget = pa._KV_VMEM_BUDGET
    pa._KV_VMEM_BUDGET = 4 * TILE_PAGES * BLOCK * 2 * 16 * 4
    assert pa.pages_per_tile(BLOCK * 2, 1, 16, np.float32, 2) == TILE_PAGES
    yield
    pa._KV_VMEM_BUDGET = budget


def _kernels(name):
    from client_tpu.models import paged_attention
    from client_tpu.models.engine_model import Kernels

    return Kernels(*paged_attention.resolve_decode_attention(name, "cpu"))


def _to32(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _config(dtype=None, **keys):
    import jax.numpy as jnp

    from benchmark.lib.serving_phi4flash import phi4flash_config

    return dataclasses.replace(
        phi4flash_config({**TOY, **keys}), dtype=dtype or jnp.float32)


def _ring_blocks():
    from client_tpu.llm import kv_cache

    return kv_cache.window_ring_blocks(
        TOY["sliding_window"], BLOCK, TILE_PAGES)


def _tables(last_positions):
    """[3, lanes + 1, columns] for a call whose lanes' newest positions
    are ``last_positions``: row 0 the full group's blocks, a lane's pages
    shuffled; row 1 the window group's rings as the engine writes them
    (`kv_cache.window_tables`: the ring at the last columns, the trash
    block behind the window); row 2 each lane's slot in column 0; the
    last lane is padding (trash block, trash slot)."""
    from client_tpu.llm import kv_cache

    rng = np.random.default_rng(1)
    width = TOY["max_position_embeddings"] // BLOCK
    ring = _ring_blocks()
    tables = np.zeros((3, len(LANES) + 1, width), np.int32)
    blocks = 1 + np.arange(len(LANES) * width).reshape(len(LANES), width)
    rings = 1 + np.arange(len(LANES) * ring).reshape(len(LANES), ring)
    for lane in range(len(LANES)):
        tables[0, lane] = rng.permutation(blocks[lane])
        tables[2, lane, 0] = SLOTS[lane]
    tables[1, :len(LANES)] = kv_cache.window_tables(
        rings, [p // BLOCK for p in last_positions], width)
    return tables


@functools.lru_cache(maxsize=None)
def _served_rows(kernel_name, state_dtype=None):
    """(float32 params, each lane's token ids, each lane's logits from
    its prompt's last position on, the counters summed, the pages left):
    a prefill a lane, then decode steps of all lanes and one padding lane
    at once, each at its own position. ``state_dtype`` rounds every Mamba
    state to it after each step (the narrower state of the test below)."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import weights_phi4flash
    from client_tpu.models import phi4flash

    kernels = _kernels(kernel_name)
    config = _config()
    assert config.layer_kinds == KINDS
    params = _to32(weights_phi4flash.params(SEED, TOY))
    rng = np.random.default_rng(0)
    tokens = [rng.integers(1, 256, size=total) for _, total in LANES]
    width = TOY["max_position_embeddings"] // BLOCK
    pages = phi4flash.init_pages(
        config, [1 + len(LANES) * width, 1 + len(LANES) * _ring_blocks(),
                 1 + len(LANES)], BLOCK)

    def rounded(pages):
        if state_dtype is None:
            return pages
        return [(pools[0].astype(state_dtype).astype(jnp.float32), pools[1])
                if kind == "mamba" else pools
                for pools, kind in zip(pages, KINDS)]

    prefill = jax.jit(
        lambda *a: phi4flash.prefill_into_pages(*a, config, kernels))
    rows = []
    for lane, (prompt, _) in enumerate(LANES):
        padded = np.zeros((1, 64), np.int32)
        padded[0, :prompt] = tokens[lane][:prompt]
        # what lies past the prompt in its bucket is masked, not zero
        padded[0, prompt:] = rng.integers(1, 256, size=64 - prompt)
        tables = _tables([p - 1 for p, _ in LANES])
        logits, pages = prefill(
            params, padded, tables[:, lane], pages, prompt - 1)
        pages = rounded(pages)
        rows.append([np.asarray(logits[0])])
    decode = jax.jit(
        lambda *a: phi4flash.decode_step_paged(*a, config, kernels))
    steps = LANES[0][1] - LANES[0][0]
    assert all(total - prompt == steps for prompt, total in LANES)
    counted = np.zeros(len(phi4flash.COUNTERS), np.int64)
    for step in range(steps):
        positions = np.array([p + step for p, _ in LANES] + [0], np.int32)
        ids = np.array([t[p] for t, p in zip(tokens, positions)] + [0],
                       np.int32)
        logits, pages, counters = decode(
            params, ids, positions, _tables(positions[:-1]), pages)
        pages = rounded(pages)
        counted += np.asarray(counters)
        for lane in range(len(LANES)):
            rows[lane].append(np.asarray(logits[lane]))
    return (params, tokens, [np.stack(r) for r in rows],
            dict(zip(phi4flash.COUNTERS, counted.tolist())), pages)


@pytest.fixture(scope="module", params=["fused_xla", "pallas_interpret"])
def toy(request):
    """Once on the plain XLA path (gathers and scatters) and once through
    the two Pallas kernels under the interpreter; the third choice,
    ``pallas``, is Mosaic's: compiled here
    (`tests/test_mosaic_compile.py`) and held against XLA on the chip
    (`tests/test_tpu_platform.py`)."""
    return _served_rows(request.param) + (request.param,)


def _reference_logits(params, tokens, model):
    import jax

    from benchmark.lib import reference_phi4flash

    # jitted anew a call: a test may have patched the module
    return np.asarray(jax.jit(
        lambda t: reference_phi4flash.forward(
            t, params, params["layers"], model))(np.asarray(tokens)))


def _reference_rows(params, tokens, model, lane):
    return _reference_logits(params, tokens[lane], model)[
        LANES[lane][0] - 1:]


def _worst(params, tokens, served, model, lanes=range(len(LANES))):
    return max(
        np.abs(served[lane] - _reference_rows(params, tokens, model, lane)
               ).max() for lane in lanes)


def test_prefill_then_decode_through_pages_rings_and_slots_matches_the_plain_reference(toy):
    """Ragged lanes, each with shuffled pages of the full group, a ring of
    the window group and a slot of the state group, a padding lane beside
    them: the program's prefill (the cross-decoder on the last position
    alone) and decode (seven layers' worth of reads of one pool, here
    three) against the reference's full forward pass, which runs every
    layer over every position and carries nothing."""
    params, tokens, served, counted, pages, _ = toy
    for lane in range(len(LANES)):
        ref = _reference_rows(params, tokens, TOY, lane)
        assert np.abs(ref).max() > 1.0  # logits of a size worth comparing
        assert np.abs(served[lane] - ref).max() <= TOLERANCE
    steps = LANES[0][1] - LANES[0][0]
    # four Mamba layers turn three live lanes' states a step; the full
    # layer and two cross layers read each live lane's whole context
    contexts = sum(p + step + 1 for p, _ in LANES for step in range(steps))
    assert counted == {"ssm_state_updates": steps * 4 * len(LANES),
                       "shared_kv_rows_read": 3 * contexts}
    for pools, kind in zip(pages, KINDS):
        if kind == "mamba":
            # the trash slot holds zeros and every lane's slot a state
            state_pool, conv_pool = pools
            assert state_pool.shape == (4, 16, 128)
            assert conv_pool.shape == (4, 3 * 128)
            assert not np.asarray(state_pool[0]).any()
            assert not np.asarray(conv_pool[0]).any()
            assert all(np.abs(np.asarray(state_pool[s])).max() > 1e-3
                       for s in SLOTS)
        elif kind in ("gmu", "cross"):
            assert pools == ()  # the cross-decoder stores nothing
        else:
            blocks = 1 + 3 * (16 if kind == "full" else _ring_blocks())
            assert [p.shape for p in pools] == [(blocks, BLOCK * 2, 16)] * 2


def test_the_kernel_choices_agree_and_a_bf16_state_would_not_pass():
    """The plain XLA path and the Pallas kernels under the interpreter
    give the same logits; the same program with its recurrent state
    rounded to bf16 after every step (a state STORED narrower) lies far
    outside the tolerance, so it cannot pass for the float32 one."""
    import jax.numpy as jnp

    params, tokens, plain, _, _ = _served_rows("fused_xla")
    _, _, kernels, _, _ = _served_rows("pallas_interpret")
    for a, b in zip(plain, kernels):
        assert np.abs(a - b).max() <= TOLERANCE
    _, _, narrow, _, _ = _served_rows("fused_xla", jnp.bfloat16)
    assert _worst(params, tokens, narrow, TOY, lanes=(0,)) > 100 * TOLERANCE


@pytest.mark.parametrize("last_index", [0, 15, 16, 40, 63])
def test_the_yoco_prefill_equals_every_layer_over_every_position(last_index):
    """The program's prefill runs the layers past the full layer's K/V
    projection on the prompt's last position alone; its logits are those
    of the reference, which runs every layer over every position, at a
    prompt of one token, at the window's edge, one past it, in the middle
    of a bucket and at its end."""
    import jax

    from client_tpu.models import phi4flash

    params, tokens, _, _, _ = _served_rows("fused_xla")
    config, kernels = _config(), _kernels("fused_xla")
    ids = tokens[2][:64]
    tables = _tables([last_index] * len(LANES))[:, 0]
    pages = phi4flash.init_pages(
        config, [1 + 3 * 16, 1 + 3 * _ring_blocks(), 4], BLOCK)
    logits, _ = jax.jit(
        lambda *a: phi4flash.prefill_into_pages(*a, config, kernels))(
            params, ids[None].astype(np.int32), tables, pages, last_index)
    ref = _reference_logits(params, ids, TOY)[last_index]
    assert np.abs(np.asarray(logits[0]) - ref).max() <= TOLERANCE


# -- one case a departure: the reference with it changed is far away -----------


def _patch(name, replacement):
    def patch(monkeypatch):
        from benchmark.lib import reference_phi4flash

        monkeypatch.setattr(reference_phi4flash, name, replacement)
    return patch


def _own_fresh_kv(carry, a, full_w, control):
    """A cross layer that projects K and V from its OWN input (with the
    full layer's weights) and does not read the full layer's."""
    import jax.numpy as jnp

    from benchmark.lib.reference_mimo import _linear

    return tuple(_linear(a, full_w["w" + n], control)
                 + full_w["b" + n].astype(jnp.float32) for n in "kv")


def _norm_without(part):
    def norm(x, w, b, model):
        import jax
        import jax.numpy as jnp

        centred = x - (0.0 if part == "mean" else
                       jnp.mean(x, axis=-1, keepdims=True))
        unit = centred * jax.lax.rsqrt(
            jnp.mean(jnp.square(centred), axis=-1, keepdims=True) + 1e-5)
        return unit * w + (0.0 if part == "bias" else b)
    return norm


def _with_rotary(x):
    from benchmark.lib.reference_llm import _rope

    return _rope(x, 10000.0)


def _rms(x, name):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def _gated_memory(read, skipped, z):
    import jax

    return (read + skipped) * jax.nn.silu(z)


# a patch of the reference
DEPARTURES = {
    "lam A_2 dropped": _patch("lam_of", lambda w, init: 0.0),
    "lam_init the same in every layer": _patch("lambda_init", lambda i: 0.8),
    "no sub-norm": _patch(
        "sub_norm", lambda diff, w, model: diff * w["sub_norm"]),
    "no 1 - lam_init": _patch("out_scale", lambda init: 1.0),
    "q_1 with k_2": _patch("paired", lambda q, k: (
        q[:, 0::2], q[:, 1::2], k[:, 1::2], k[:, 0::2])),
    "a cross layer reads its own fresh K/V": _patch(
        "cross_source", _own_fresh_kv),
    "a cross layer reads a window layer's K/V": _patch(
        "cross_source", lambda carry, a, full_w, control: (
            carry["window_k"], carry["window_v"])),
    "the memory after its gate": _patch("memory_of", _gated_memory),
    "the memory of another Mamba layer": _patch(
        "memory_layer", lambda model: 4),
    "D u left out of the memory": _patch(
        "memory_of", lambda read, skipped, z: read),
    "LN without its mean": _patch("norm", _norm_without("mean")),
    "LN without its bias": _patch("norm", _norm_without("bias")),
    "a rotary on q and k": _patch("positioned", _with_rotary),
    "Jamba's norms on dl, B and C": _patch("inner_norm", _rms),
    "a window one token longer": _patch("window_of", lambda model: 17),
    "no window": _patch("window_of", lambda model: 1000),
}


@pytest.mark.parametrize("departure", DEPARTURES)
def test_each_departure_fails_the_comparison(departure, monkeypatch):
    """The comparison above is tight enough to tell: a reference with one
    part of the mathematics changed (which is the program with it, seen
    from the other side) lies far outside the tolerance, on the lane
    whose contexts are longest."""
    params, tokens, served, _, _ = _served_rows("fused_xla")
    DEPARTURES[departure](monkeypatch)
    assert _worst(params, tokens, served, TOY, lanes=(2,)) > 100 * TOLERANCE


def test_the_memory_is_of_visible_size_beside_the_gate():
    """The seeded weights put the gated memory unit's two factors within
    an order of each other: layer N/2's ungated sums ``m_t`` and the
    gate ``silu(a W_in)``. A memory of no size would hide every
    departure of the memory from the comparison."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference_phi4flash as ref

    params, tokens, _, _, _ = _served_rows("fused_xla")
    with jax.default_matmul_precision("highest"):
        x = ref.embed(tokens[0], params)
        carry = ref.start(len(tokens[0]), TOY)
        for index, w in enumerate(params["layers"][:9]):
            if index == 8:
                gate = jax.nn.silu(jnp.dot(
                    ref.norm(x, w["ln1_w"], w["ln1_b"], TOY), w["w_in"]))
            x, carry = ref.layer(x, carry, w, ref.lambda_init(index), TOY,
                                 KINDS[index], index == 6)
    memory, gate = (float(jnp.sqrt(jnp.mean(jnp.square(a))))
                    for a in (carry["memory"], gate))
    assert 0.1 < memory < 10 and 0.1 < gate < 10
    assert 0.1 < memory / gate < 10


# -- the engine ------------------------------------------------------------------


def _engine_model(features=None, **engine):
    """The toy behind `LlmEngineModel`; ``engine`` overrides
    `EngineConfig`'s sizes, ``features`` are the model's own arguments."""
    from client_tpu.llm.engine import EngineConfig
    from client_tpu.llm.serving import LlmEngineModel
    from client_tpu.models import phi4flash

    sizes = dict(block_size=8, num_blocks=1 + 3 * 16, max_active=3,
                 max_queue=8, max_seq_len=128, prefix_sharing=False)
    sizes.update(engine)
    return LlmEngineModel(
        name="phi4flash_toy", model=phi4flash.ENGINE_MODEL,
        config=phi4flash.Phi4FlashConfig.tiny(),
        engine_config=EngineConfig(**sizes), **(features or {}))


async def _generate(model, prompts, max_tokens):
    seqs = [model.engine.submit(p, max_tokens=max_tokens) for p in prompts]

    async def collect(seq):
        return [token async for token, _ in seq]

    return await asyncio.gather(*(collect(s) for s in seqs))


def _prompts(lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, size=n).tolist() for n in lengths]


#: a cached token of the toy in one storing attention layer (K and V of 4
#: heads of 8 in float32) and a slot of it in one Mamba layer
TOY_ROW = 2 * 4 * 8 * 4
TOY_SLOT = 16 * 128 * 4 + 3 * 128 * 4


def _served_is_the_references_best(model, prompts, served):
    params = _to32(model._params)
    for prompt, tokens in zip(prompts, served):
        logits = _reference_logits(params, prompt + tokens, TOY)
        at = logits[len(prompt) - 1: len(prompt) + len(tokens) - 1]
        gap = at.max(axis=-1) - at[np.arange(len(tokens)), tokens]
        assert gap.max() <= TOLERANCE


def test_engine_serves_three_cache_groups_at_once():
    """Five sequences through `LlmEngineModel` over three lanes: a full
    group of ONE layer, a window group of three, a state group of four,
    and four layers with no pool at all. The tiles are the kernel's (a
    page of either pool is 16 rows of 16), the rings whole tiles, the row
    bytes a token's and a slot's; greedy tokens equal the reference's on
    the same weights (the fourth and fifth sequence take the rings and
    slots the first three gave back: a ring and a slot reused carry
    nothing over), and everything is given back at the end."""
    from client_tpu.models import paged_attention as pa
    from client_tpu.models.engine_model import FULL, STATE, WINDOW

    model = _engine_model()
    model.warmup()
    try:
        engine = model.engine
        assert [(g.kind, g.layers, g.window)
                for g in engine.config.cache_groups] == [
            (FULL, (7,), None), (WINDOW, (1, 3, 5), 16),
            (STATE, (0, 2, 4, 6), None)]
        assert [len(pools) for pools in engine._pages] == [
            2, 2, 2, 2, 2, 2, 2, 2, 0, 0, 0, 0]
        assert engine._pages[0][0].shape == (4, 16, 128)
        assert all(p.shape == (49, 16, 16) for p in engine._pages[7])
        ring = _ring_blocks()
        assert all(p.shape == (1 + 3 * ring, 16, 16)
                   for p in engine._pages[1])
        assert engine._tile_pages == (TILE_PAGES, TILE_PAGES, 1)
        assert TILE_PAGES == pa.pages_per_tile(16, 1, 16, np.float32, 2)
        prompts = _prompts((30, 9, 17, 22, 5))
        served = asyncio.run(_generate(model, prompts, 40))
        stats = engine.stats()
        assert stats["kv_row_bytes_by_group"] == [
            {"stored": TOY_ROW, "counted": TOY_ROW},
            {"stored": TOY_ROW, "counted": TOY_ROW},
            {"stored": TOY_SLOT, "counted": TOY_SLOT}]
        assert stats["kv_blocks_in_use_by_group"] == [0, 0, 0]
        assert stats["state_slots_in_use"] == 0
        assert stats["state_bytes_by_group"] == [0, 0, 0]
        assert stats["completed"] == 5 and stats["preemptions"] == 0
        # four Mamba layers a live lane a step
        assert stats["ssm_state_updates"] == 4 * stats["lane_steps"]
        # the full layer and two cross layers over every lane's context
        assert stats["shared_kv_rows_read"] == 3 * stats["attn_tokens_full"]
        assert 0 < stats["attn_tokens_window"] < stats["attn_tokens_full"]
        _served_is_the_references_best(model, prompts, served)
    finally:
        model.shutdown()


def test_stored_bytes_count_the_full_group_one_layer_while_sequences_run():
    """What `stats()` serves while two sequences run: the full group's
    blocks in use are ONE layer's (eight layers read them), the state
    group's bytes its four layers'."""
    model = _engine_model()
    model.warmup()
    try:
        engine = model.engine

        async def run():
            seqs = [engine.submit(p, max_tokens=30)
                    for p in _prompts((12, 20))]
            async for _ in seqs[0]:
                break  # both are admitted once a token has come
            await asyncio.sleep(0)
            stats = engine.stats()
            for seq in seqs:
                async for _ in seq:
                    pass
            return stats

        stats = asyncio.run(run())
        slots = stats["state_slots_in_use"]
        assert 1 <= slots <= 2
        assert stats["state_bytes_by_group"] == [0, 0, slots * TOY_SLOT * 4]
        full, window, state = stats["kv_blocks_in_use_by_group"]
        assert state == slots and window == slots * _ring_blocks()
        assert 2 * slots <= full <= 4 * slots  # 13-24 tokens in blocks of 8
        stored = [
            blocks * BLOCK * row["stored"] * len(group.layers)
            for blocks, row, group in zip(
                (full, window), stats["kv_row_bytes_by_group"],
                engine.config.cache_groups)]
        assert stored == [full * BLOCK * TOY_ROW,
                          window * BLOCK * TOY_ROW * 3]
    finally:
        model.shutdown()


def test_preempt_and_resume_is_token_identical_with_three_groups():
    """A full pool too small for three growing sequences: victims give
    their blocks, their ring AND their slot back, wait, and are
    re-prefilled over prompt and generated tokens into whatever ring and
    slot are free then; every stream is what it is on an engine that
    never preempts."""
    prompts = _prompts((30, 9, 17))
    roomy = _engine_model()
    roomy.warmup()
    tight = _engine_model(num_blocks=1 + 12)
    tight.warmup()
    try:
        undisturbed = asyncio.run(_generate(roomy, prompts, 40))
        resumed = asyncio.run(_generate(tight, prompts, 40))
        assert tight.engine.stats()["preemptions"] >= 1
        assert roomy.engine.stats()["preemptions"] == 0
        assert resumed == undisturbed
        stats = tight.engine.stats()
        assert stats["state_slots_in_use"] == 0
        assert stats["kv_blocks_in_use_by_group"] == [0, 0, 0]
    finally:
        roomy.shutdown()
        tight.shutdown()


@pytest.mark.parametrize("features,engine,part", [
    (dict(speculation={"mode": "ngram", "k": 2}), {}, "verify"),
    ({}, dict(prefix_sharing=True), "prefill_suffix"),
    (dict(tp=2), {}, "param_specs"),
])
def test_phi4flash_is_refused_the_features_it_has_no_part_for(
        features, engine, part):
    from client_tpu.utils import InferenceServerException

    model = _engine_model(features, **engine)
    with pytest.raises(InferenceServerException, match=f"[`']{part}[`']"):
        model.warmup()


def test_the_config_declares_three_groups_at_the_published_sizes():
    """Of 32 layers one stores the full pool (17), eight a ring (1, 3, ..
    15), nine a slot (0, 2, .. 16), fourteen nothing; a cached token takes
    5,120 B in a storing layer, a slot 358,400 B; 3,853M parameters, the
    embedding once."""
    import jax

    from client_tpu.models import phi4flash
    from client_tpu.models.engine_model import FULL, STATE, WINDOW

    config = phi4flash.Phi4FlashConfig()
    full, window, state = phi4flash.cache_groups(config)
    assert (full.kind, full.layers) == (FULL, (17,))
    assert (window.kind, window.layers, window.window) == (
        WINDOW, tuple(range(1, 16, 2)), 512)
    assert (state.kind, state.layers) == (STATE, tuple(range(0, 17, 2)))
    kinds = config.layer_kinds
    assert kinds[18::2] == ("gmu",) * 7 and kinds[19::2] == ("cross",) * 7
    assert config.shared_readers == 8
    assert (config.d_inner, config.head_dim, config.kv_pairs) == (
        5120, 64, 10)
    assert phi4flash.kv_row_bytes(config) == [
        (5120, 5120), (5120, 5120), (358400, 358400)]
    assert round(config.lambda_init(1), 3) == 0.356
    assert round(config.lambda_init(17), 3) == 0.796
    shapes = jax.eval_shape(
        lambda: phi4flash.init_params(jax.random.PRNGKey(0), config))
    count = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(shapes))
    mamba = (2560 * 10240 + 4 * 5120 + 5120 + 5120 * 192 + 160 * 5120
             + 5120 + 16 * 5120 + 5120 + 5120 * 2560)
    attention = 2560 * 5120 + 5120 + 2560 * 2560 + 2560 + 4 * 64 + 128
    cross = 2 * (2560 * 2560 + 2560) + 4 * 64 + 128
    gmu, mlp = 2 * 2560 * 5120, 3 * 2560 * 10240
    assert (mamba, attention, cross, gmu, mlp) == (
        41_241_600, 19_668_864, 13_112_704, 26_214_400, 78_643_200)
    assert count == (9 * mamba + 9 * attention + 7 * cross + 7 * gmu
                     + 32 * (mlp + 4 * 2560) + 200064 * 2560 + 2 * 2560)
    assert round(count / 1e6) == 3853
    pages = jax.eval_shape(
        lambda: phi4flash.init_pages(config, [5, 3, 65], 16))
    assert pages[0][0].shape == (65, 16, 5120)
    assert pages[0][1].shape == (65, 3 * 5120)
    # ten rows of 128 a token in either pool: 40,960 B a page of 16
    assert [p.shape for p in pages[1]] == [(3, 160, 128)] * 2
    assert [p.shape for p in pages[17]] == [(5, 160, 128)] * 2
    assert all(pages[i] == () for i in range(18, 32))
    with pytest.raises(ValueError, match="pair up"):
        phi4flash.Phi4FlashConfig(n_kv_heads=5)
    with pytest.raises(ValueError, match="multiple of 4"):
        phi4flash.Phi4FlashConfig(n_layers=30)
