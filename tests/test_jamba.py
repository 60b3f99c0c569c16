"""AI21-Jamba2-3B's decoder (``jamba``) on the engine at a toy size,
float32, on the CPU: the program (`client_tpu/models/jamba.py`,
`models/selective_scan.py`, the ``state`` cache group of `llm/engine.py`)
against the plain reference the benchmark keeps
(`benchmark/lib/reference_jamba.py`), on seeded weights.

Tolerances. Everything is float32 and the two sides differ in the order
of their sums and in the FORM of the Mamba layers (the reference runs the
recurrence token by token from a zero state and caches nothing; the
program's prefill runs the scan in chunks and writes the final state into
a slot, its decode turns the slot a token a step, through the kernel or a
gather and a scatter): the logits, of size about 4, came out within 5e-6
over four layers and 40 decoded tokens. ``TOLERANCE`` 1e-4 leaves that
twenty times of room; the smallest change any departure left out below
makes is 100 times over it, and the same program with its state held in
bf16 lies a hundred times over it too.
"""

import asyncio
import dataclasses
import functools

import numpy as np
import pytest

pytestmark = pytest.mark.llm

TOLERANCE = 1e-4
BLOCK, SEED = 8, 11

TOY = dict(
    hidden_size=64, num_hidden_layers=4, attn_layer_period=4,
    attn_layer_offset=2, num_attention_heads=4, num_key_value_heads=1,
    intermediate_size=128, mamba_expand=2, mamba_d_state=16,
    mamba_d_conv=4, mamba_dt_rank=8, mamba_conv_bias=True,
    mamba_proj_bias=False, rms_norm_eps=1e-6, vocab_size=256,
    max_position_embeddings=128, num_experts=1, num_experts_per_tok=1,
    tie_word_embeddings=True, hidden_act="silu", sliding_window=None,
    model_type="jamba",
)

#: (prompt, total) of the lanes one decode batch holds, ragged; a fourth
#: lane of every step is a batch bucket's padding and names the trash slot
LANES = ((21, 61), (5, 45), (60, 100))
SLOTS = (2, 3, 1)


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

    from benchmark.lib.serving_jamba import jamba_config

    return dataclasses.replace(
        jamba_config({**TOY, **keys}), dtype=dtype or jnp.float32)


def _tables():
    """[2, lanes + 1, columns]: row 0 the full group's blocks, a lane's
    pages shuffled; row 1 each lane's slot in column 0; the last lane is
    padding (the trash block, the trash slot)."""
    rng = np.random.default_rng(1)
    width = TOY["max_position_embeddings"] // BLOCK
    tables = np.zeros((2, len(LANES) + 1, width), np.int32)
    blocks = 1 + np.arange(len(LANES) * width).reshape(len(LANES), width)
    for lane in range(len(LANES)):
        tables[0, lane] = rng.permutation(blocks[lane])
        tables[1, lane, 0] = SLOTS[lane]
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

    from benchmark.lib import weights_jamba
    from client_tpu.models import jamba

    kernels = _kernels(kernel_name)
    config = _config()
    params = _to32(weights_jamba.params(SEED, TOY))
    rng = np.random.default_rng(0)
    tokens = [rng.integers(1, 256, size=total) for _, total in LANES]
    tables = _tables()
    pages = jamba.init_pages(
        config, [1 + tables.shape[1] * tables.shape[2], 1 + len(LANES)],
        BLOCK)

    def rounded(pages):
        if state_dtype is None:
            return pages
        return [(pools[0].astype(state_dtype).astype(jnp.float32), pools[1])
                if kind else pools
                for pools, kind in zip(pages, config.layer_kinds)]

    prefill = jax.jit(
        lambda *a: jamba.prefill_into_pages(*a, config, kernels))
    rows = []
    for lane, (prompt, _) in enumerate(LANES):
        padded = np.zeros((1, 64), np.int32)
        padded[0, :prompt] = tokens[lane][:prompt]
        # what lies past the prompt in its bucket is masked, not zero
        padded[0, prompt:] = rng.integers(1, 256, size=64 - prompt)
        logits, pages = prefill(
            params, padded, tables[:, lane], pages, prompt - 1)
        pages = rounded(pages)
        rows.append([np.asarray(logits[0])])
    decode = jax.jit(
        lambda *a: jamba.decode_step_paged(*a, config, kernels))
    steps = LANES[0][1] - LANES[0][0]
    assert all(total - prompt == steps for prompt, total in LANES)
    counted = np.zeros(len(jamba.COUNTERS), np.int64)
    for step in range(steps):
        positions = np.array([p + step for p, _ in LANES] + [0], np.int32)
        ids = np.array([t[p] for t, p in zip(tokens, positions)] + [0],
                       np.int32)
        logits, pages, counters = decode(
            params, ids, positions, tables, pages)
        pages = rounded(pages)
        counted += np.asarray(counters)
        for lane in range(len(LANES)):
            rows[lane].append(np.asarray(logits[lane]))
    return (params, tokens, [np.stack(r) for r in rows],
            dict(zip(jamba.COUNTERS, counted.tolist())), pages)


@pytest.fixture(scope="module", params=["fused_xla", "pallas_interpret"])
def toy(request):
    """Once on the plain XLA path (a gather, the rule and a scatter) and
    once through the two Pallas kernels under the interpreter; the third
    choice, ``pallas``, is Mosaic's: compiled here
    (`tests/test_mosaic_compile.py`) and held against XLA on the chip
    (`tests/test_tpu_platform.py`)."""
    return _served_rows(request.param) + (request.param,)


def _reference_rows(params, tokens, model, lane):
    from benchmark.lib import reference_jamba

    logits = reference_jamba.forward(
        tokens[lane], params, params["layers"], model)
    return np.asarray(logits)[LANES[lane][0] - 1:]


def _worst(params, tokens, served, model):
    return max(
        np.abs(served[lane] - _reference_rows(params, tokens, model, lane)
               ).max() for lane in range(len(LANES)))


def test_prefill_then_decode_through_slots_matches_the_plain_reference(toy):
    """Ragged lanes, each with a slot of the state group and shuffled
    pages of the full group, a padding lane beside them: the program's
    prefill (the scan in chunks, the state written into the slot) and
    decode (the slot turned in place) against the reference's full
    forward pass, which carries nothing."""
    params, tokens, served, counted, pages, _ = toy
    for lane in range(len(LANES)):
        ref = _reference_rows(params, tokens, TOY, lane)
        assert np.abs(ref).max() > 1.0  # logits of a size worth comparing
        assert np.abs(served[lane] - ref).max() <= TOLERANCE
    # three Mamba layers turn three live lanes' states a step
    steps = LANES[0][1] - LANES[0][0]
    assert counted == {"ssm_state_updates": steps * 3 * len(LANES)}
    # the trash slot holds zeros and every lane's slot a state
    for (state_pool, conv_pool), kind in zip(pages, _config().layer_kinds):
        if kind:
            assert state_pool.shape == (4, 16, 128)
            assert conv_pool.shape == (4, 3 * 128)
            assert not np.asarray(state_pool[0]).any()
            assert not np.asarray(conv_pool[0]).any()
            assert all(np.abs(np.asarray(state_pool[s])).max() > 1e-3
                       for s in SLOTS)


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
    assert _worst(params, tokens, narrow, TOY) > 100 * TOLERANCE


# -- the scan's three forms -------------------------------------------------------


def _scan_inputs(length, channels=128, states=16, seed=0):
    """(u, delta, b, c, z [length, ...], a [N, D], d_skip [D]), float32:
    steps of 0.001-0.2 and ``A = -1 .. -N``, the seeded draws' range."""
    rng = np.random.default_rng(seed)
    u = rng.normal(size=(length, channels))
    z = rng.normal(size=(length, channels))
    delta = np.exp(rng.uniform(np.log(1e-3), np.log(0.2),
                               size=(length, channels)))
    b = rng.normal(size=(length, states))
    c = rng.normal(size=(length, states))
    a = -np.broadcast_to(np.arange(1, states + 1)[:, None],
                         (states, channels))
    d_skip = 1 + 0.1 * rng.normal(size=channels)
    return [x.astype(np.float32) for x in (u, delta, b, c, z, a, d_skip)]


def _token_by_token(u, delta, b, c, z, a, d_skip):
    import jax.numpy as jnp

    from client_tpu.models import selective_scan

    state = jnp.zeros(a.shape, jnp.float32)
    outs = []
    for t in range(len(u)):
        out, state = selective_scan.recurrent_step(
            state, u[t], delta[t], b[t], c[t], z[t], a, d_skip)
        outs.append(np.asarray(out))
    return np.stack(outs), np.asarray(state)


@pytest.mark.parametrize("length,bucket", [
    (150, 150), (64, 64), (1, 8), (37, 64), (100, 256), (129, 130),
    (65, 128)])
def test_the_chunked_scan_equals_the_recurrence(length, bucket):
    """Lengths that are no whole number of chunks of 64, and a prompt
    padded to its bucket with ``delta = 0`` past its end (the padding's
    u, B, C and z are whatever the projections of the padding tokens gave,
    not zeros): outputs up to the last token and the final state are the
    recurrence's."""
    from client_tpu.models import selective_scan

    u, delta, b, c, z, a, d_skip = _scan_inputs(bucket)
    delta = delta * (np.arange(bucket) < length)[:, None]
    out, state = selective_scan.chunked_selective_scan(
        u, delta, b, c, z, a, d_skip)
    ref_out, ref_state = _token_by_token(
        u[:length], delta[:length], b[:length], c[:length], z[:length], a,
        d_skip)
    assert np.abs(ref_out).max() > 0.1 and np.abs(ref_state).max() > 0.01
    assert np.abs(np.asarray(out)[:length] - ref_out).max() <= 1e-5
    assert np.abs(np.asarray(state) - ref_state).max() <= 1e-5
    # and with the gate off: the same sums before silu(z), the same state
    bare, bare_state = selective_scan.chunked_selective_scan(
        u, delta, b, c, None, a, d_skip)
    gate = np.asarray(z[:length]) / (1 + np.exp(-np.asarray(z[:length])))
    assert np.abs(np.asarray(bare)[:length] * gate - ref_out).max() <= 1e-5
    assert (np.asarray(bare_state) == np.asarray(state)).all()


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
@pytest.mark.parametrize("kernel", ["fused_xla", "pallas_interpret"])
@pytest.mark.parametrize("channels,states", [(128, 16), (5120, 16)])
def test_selective_scan_step_turns_each_lanes_slot_and_no_other(
        kernel, channels, states, gated):
    """The decode step over a pool against the recurrence on each lane's
    own state (at the toy's width and at the published 5,120 channels):
    live lanes' slots are turned, the slots of no lane are left as they
    were, and lanes that name the trash slot read out zeros and leave
    zeros there, whatever it held. With the gate off (``z = None``: what
    `models/phi4flash.py` asks for, whose memory layer hands its sums on
    before the gate) the sums come out as they are, not times
    ``silu(z)``."""
    import jax.numpy as jnp

    from client_tpu.models import selective_scan

    lanes = 5
    u, delta, b, c, z, a, d_skip = _scan_inputs(lanes, channels, states)
    if not gated:
        z = None
    rng = np.random.default_rng(4)
    pool = rng.normal(size=(7, states, channels)).astype(np.float32)
    slots = np.array([3, 0, 5, 1, 0], np.int32)
    out, new = selective_scan.selective_scan_step(
        u, delta, b, c, z, a, d_skip, slots, jnp.asarray(pool),
        kernel=kernel)
    out, new = np.asarray(out), np.asarray(new)
    for lane, slot in enumerate(slots):
        if slot == 0:
            assert not out[lane].any()
            continue
        ref_out, ref_state = selective_scan.recurrent_step(
            pool[slot], u[lane], delta[lane], b[lane], c[lane],
            z[lane] if gated else None, a, d_skip)
        if not gated:
            # the rule's sums themselves: the state read by C, and D u
            by_hand = ((np.asarray(ref_state) * c[lane][:, None]).sum(0)
                       + d_skip * u[lane])
            assert np.abs(np.asarray(ref_out) - by_hand).max() <= 1e-5
        assert np.abs(out[lane] - np.asarray(ref_out)).max() <= 1e-5
        assert np.abs(new[slot] - np.asarray(ref_state)).max() <= 1e-5
    assert not new[0].any()
    assert (new[[2, 4, 6]] == pool[[2, 4, 6]]).all()


def test_the_seeded_steps_spread_the_decays_from_forgetting_to_carrying():
    """``A_log``, ``b_dt`` and ``w_dt`` as the benchmark draws them:
    ``delta`` stays in about 0.001-0.2 for a normed ``dl`` of unit size,
    and a channel's decay a token ``exp(delta A)`` spreads from under 0.1
    (state 16 of a fast channel forgets within a token) to over 0.999
    (state 1 of a slow one carries for a thousand): a state that is never
    carried would pass every comparison, and one that never forgets is
    no Mamba."""
    import jax

    from benchmark.lib import weights_jamba
    from client_tpu.models import jamba

    draws = (
        weights_jamba.step_draw(jax.random.PRNGKey(5), 16, 512),
        jamba.step_draw(jax.random.PRNGKey(5),
                        jamba.JambaConfig(d_model=256, n_heads=4)),
    )
    for a_log, b_dt in draws:
        a_log, b_dt = np.asarray(a_log), np.asarray(b_dt)
        assert a_log.shape == (16, 512) and b_dt.shape == (512,)
        assert np.allclose(np.exp(a_log[:, 7]), np.arange(1, 17))
        for moved in (-0.7, 0.0, 0.7):  # dl @ w_dt at two sigma
            delta = np.log1p(np.exp(b_dt + moved))
            assert 4e-4 < delta.min() and delta.max() < 0.25
        decay = np.exp(-np.exp(a_log) * np.log1p(np.exp(b_dt)))
        assert decay.min() < 0.25 and decay.max() > 0.9985
        assert (decay < 0.9).mean() > 0.2 and (decay > 0.99).mean() > 0.1
    layer = weights_jamba.layer(SEED, 0, TOY)
    projected = np.asarray(layer["w_dt"], np.float32).T @ np.random.default_rng(
        0).normal(size=(8, 2000))
    assert 0.25 < projected.std() < 0.45


# -- one case a departure: the reference with it left out is far away ----------


def _patch(name, replacement):
    def patch(monkeypatch):
        from benchmark.lib import reference_jamba

        monkeypatch.setattr(reference_jamba, name, replacement)
    return patch


def _without_norm(left_out):
    def patch(monkeypatch):
        from benchmark.lib import reference_jamba as ref

        monkeypatch.setattr(
            ref, "inner_norm", lambda x, w, name, model: (
                x if name == left_out else ref.norm(x, w[name], model)))
    return patch


def _no_conv_bias(monkeypatch):
    from benchmark.lib import reference_jamba as ref

    with_bias = ref.convolution
    monkeypatch.setattr(
        ref, "convolution",
        lambda inputs, taps, bias: with_bias(inputs, taps, 0.0 * bias))


def _untied_head(monkeypatch):
    """A head of its own: the embedding's rows in another order."""
    import jax.numpy as jnp

    from benchmark.lib import reference_jamba as ref

    tied = ref.head
    monkeypatch.setattr(ref, "head", lambda x, top, model, control=False: tied(
        x, {**top, "embed": jnp.roll(top["embed"], 1, axis=0)}, model,
        control))


def _with_rotary(x):
    from benchmark.lib.reference_llm import _rope

    return _rope(x, 10000.0)


def _exp_step(projected, w):
    import jax.numpy as jnp

    return jnp.exp(projected + w["b_dt"].astype(jnp.float32))


def _step_without_b_dt(projected, w):
    import jax

    return jax.nn.softplus(projected)


def _no_decay(delta, w):
    import jax.numpy as jnp

    return jnp.ones((delta.shape[0],) + w["A_log"].shape, jnp.float32)


# a patch of the reference
DEPARTURES = {
    "no bias on the convolution": _no_conv_bias,
    "no convolution": _patch(
        "convolution", lambda inputs, taps, bias: inputs),
    "no norm on dl": _without_norm("dt_norm"),
    "no norm on B": _without_norm("b_norm"),
    "no norm on C": _without_norm("c_norm"),
    "exp where softplus stands": _patch("step_of", _exp_step),
    "no b_dt": _patch("step_of", _step_without_b_dt),
    "no D skip": _patch("skip", lambda u, w: 0.0 * u),
    "no silu(z) gate": _patch("gate", lambda z: 1.0 + 0.0 * z),
    "no decay": _patch("decay_of", _no_decay),
    "a head of its own": _untied_head,
    "a rotary on q and k": _patch("positioned", _with_rotary),
}


@pytest.mark.parametrize("departure", DEPARTURES)
def test_each_departure_left_out_fails_the_comparison(
        toy, departure, monkeypatch):
    """The comparison above is tight enough to tell: a reference with one
    part of the mathematics left out or put in (which is the program
    with it, seen from the other side) lies far outside the tolerance."""
    params, tokens, served, _, _, _ = toy
    DEPARTURES[departure](monkeypatch)
    assert _worst(params, tokens, served, TOY) > 100 * TOLERANCE


# -- the engine ------------------------------------------------------------------


def _engine_model(features=None, **engine):
    """The toy behind `LlmEngineModel`; ``engine`` overrides
    `EngineConfig`'s sizes, ``features`` are the model's own arguments."""
    from client_tpu.llm.engine import EngineConfig
    from client_tpu.llm.serving import LlmEngineModel
    from client_tpu.models import jamba

    sizes = dict(block_size=8, num_blocks=1 + 3 * 16, max_active=3,
                 max_queue=8, max_seq_len=128, prefix_sharing=False)
    sizes.update(engine)
    return LlmEngineModel(
        name="jamba_toy", model=jamba.ENGINE_MODEL,
        config=jamba.JambaConfig.tiny(),
        engine_config=EngineConfig(**sizes), **(features or {}))


async def _generate(model, prompts, max_tokens):
    seqs = [model.engine.submit(p, max_tokens=max_tokens) for p in prompts]

    async def collect(seq):
        return [token async for token, _ in seq]

    return await asyncio.gather(*(collect(s) for s in seqs))


def _prompts(lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, size=n).tolist() for n in lengths]


#: a slot of the toy: a float32 state [16, 128] and three convolution
#: inputs of 128 channels in float32
TOY_SLOT = 16 * 128 * 4 + 3 * 128 * 4


def _served_is_the_references_best(model, prompts, served):
    from benchmark.lib import reference_jamba

    params = _to32(model._params)
    for prompt, tokens in zip(prompts, served):
        logits = np.asarray(reference_jamba.forward(
            prompt + tokens, params, params["layers"], TOY))
        at = logits[len(prompt) - 1: len(prompt) + len(tokens) - 1]
        gap = at.max(axis=-1) - at[np.arange(len(tokens)), tokens]
        assert gap.max() <= TOLERANCE


def test_engine_serves_the_model_over_a_state_group_and_a_full_group():
    """Five sequences through `LlmEngineModel` over three slots: the
    state group's pools are ``1 + max_active`` slots whatever the
    sequences' lengths, its tile is 1 and it books no tile stops, the
    row bytes are a slot's, greedy tokens equal the reference's on the
    same weights (the fourth and fifth sequence take slots the first
    three gave back: a slot reused carries nothing over), and everything
    is given back at the end."""
    from client_tpu.models import paged_attention as pa

    model = _engine_model()
    model.warmup()
    try:
        engine = model.engine
        state_pool, conv_pool = engine._pages[0]
        assert state_pool.shape == (4, 16, 128)
        assert conv_pool.shape == (4, 3 * 128)
        assert all(pool.shape == (49, 8, 16) for pool in engine._pages[2])
        assert engine._tile_pages == (
            pa.pages_per_tile(8, 1, 16, np.float32, 2), 1)
        prompts = _prompts((30, 9, 17, 22, 5))
        served = asyncio.run(_generate(model, prompts, 40))
        stats = engine.stats()
        assert stats["kv_row_bytes_by_group"] == [
            {"stored": 2 * 16 * 4, "counted": 2 * 16 * 4},
            {"stored": TOY_SLOT, "counted": TOY_SLOT}]
        assert stats["kv_blocks_in_use_by_group"] == [0, 0]
        assert stats["state_slots_in_use"] == 0
        assert stats["state_bytes_by_group"] == [0, 0]
        assert stats["completed"] == 5 and stats["preemptions"] == 0
        # three Mamba layers a live lane a step
        assert stats["ssm_state_updates"] == 3 * stats["lane_steps"]
        # only the full group's layer walks tiles: a table of 16 columns
        # is one tile a lane a step
        assert stats["attn_tiles_walked"] == stats["lane_steps"]
        _served_is_the_references_best(model, prompts, served)
    finally:
        model.shutdown()


def test_slots_in_use_and_their_bytes_are_served_while_sequences_run():
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
        assert 1 <= stats["state_slots_in_use"] <= 2
        assert stats["state_bytes_by_group"] == [
            0, stats["state_slots_in_use"] * TOY_SLOT * 3]
        assert stats["kv_blocks_in_use_by_group"][1] == (
            stats["state_slots_in_use"])
    finally:
        model.shutdown()


def test_preempt_and_resume_is_token_identical():
    """A full pool too small for three growing sequences: victims give
    their blocks AND their slot back, wait, and are re-prefilled over
    prompt and generated tokens into whatever slot is free then; every
    stream is what it is on an engine that never preempts."""
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
        assert tight.engine.stats()["state_slots_in_use"] == 0
    finally:
        roomy.shutdown()
        tight.shutdown()


def test_two_state_group_models_of_different_slots_serve_in_one_process():
    """Jamba's toy (26 : 2 in the published model; a slot a Mamba layer
    ``[16, 128]`` float32 and a row of 384) beside Qwen3-Next's (a slot a
    DeltaNet layer ``[4, 16, 16]`` float32 and ``[3, 128]``) in one
    process, their requests interleaved: nothing under ``llm/`` holds a
    state group's shapes, each engine serves its own slots' bytes, and
    each model's tokens are those it serves alone."""
    from client_tpu.llm.engine import EngineConfig
    from client_tpu.llm.serving import LlmEngineModel
    from client_tpu.models import qwen3_next

    sizes = dict(block_size=8, num_blocks=1 + 3 * 16, max_active=3,
                 max_queue=8, max_seq_len=128, prefix_sharing=False)
    other = LlmEngineModel(
        name="qwen3next_toy", model=qwen3_next.ENGINE_MODEL,
        config=qwen3_next.Qwen3NextConfig.tiny(),
        engine_config=EngineConfig(**sizes))
    model = _engine_model()
    model.warmup()
    other.warmup()
    try:
        prompts = _prompts((14, 9, 23))
        alone = asyncio.run(_generate(model, prompts, 24))
        other_alone = asyncio.run(_generate(other, prompts, 24))

        async def both():
            return await asyncio.gather(
                _generate(model, prompts, 24), _generate(other, prompts, 24))

        together, other_together = asyncio.run(both())
        assert together == alone and other_together == other_alone
        assert alone != other_alone
        slots = [m.engine.stats()["kv_row_bytes_by_group"][1]["stored"]
                 for m in (model, other)]
        assert slots == [TOY_SLOT, 4 * 16 * 16 * 4 + 3 * 128 * 4]
        _served_is_the_references_best(model, prompts, together)
    finally:
        model.shutdown()
        other.shutdown()


@pytest.mark.parametrize("features,engine,part", [
    (dict(speculation={"mode": "ngram", "k": 2}), {}, "verify"),
    ({}, dict(prefix_sharing=True), "prefill_suffix"),
    (dict(tp=2), {}, "param_specs"),
])
def test_jamba_is_refused_the_features_it_has_no_part_for(
        features, engine, part):
    from client_tpu.utils import InferenceServerException

    model = _engine_model(features, **engine)
    with pytest.raises(InferenceServerException, match=f"[`']{part}[`']"):
        model.warmup()


def test_the_config_declares_a_full_and_a_state_group_at_the_published_sizes():
    """Layers 7 and 21 of 28 are attention (``i mod 14 = 7``), the other
    26 Mamba; a cached token takes 512 B an attention layer, a slot
    358,400 B a Mamba layer; 3,029M parameters, the embedding once."""
    import jax

    from client_tpu.models import jamba
    from client_tpu.models.engine_model import FULL, STATE

    config = jamba.JambaConfig()
    full, state = jamba.cache_groups(config)
    assert (full.kind, full.layers) == (FULL, (7, 21))
    assert state.kind == STATE and len(state.layers) == 26
    assert (config.d_inner, config.head_dim) == (5120, 128)
    assert jamba.kv_row_bytes(config) == [(512, 512), (358400, 358400)]
    shapes = jax.eval_shape(
        lambda: jamba.init_params(jax.random.PRNGKey(0), config))
    count = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(shapes))
    mamba = (2560 * 10240 + 4 * 5120 + 5120 + 5120 * 192 + 160 + 16 + 16
             + 160 * 5120 + 5120 + 16 * 5120 + 5120 + 5120 * 2560)
    attention = 2 * 2560 * 2560 + 2 * 2560 * 128
    mlp = 3 * 2560 * 8192
    assert mamba == 41_241_792 and attention == 13_762_560
    assert count == (26 * mamba + 2 * attention + 28 * (mlp + 2 * 2560)
                     + 65536 * 2560 + 2560)
    assert round(count / 1e6) == 3029
    pages = jax.eval_shape(lambda: jamba.init_pages(config, [3, 129], 16))
    assert pages[0][0].shape == (129, 16, 5120)
    assert pages[0][1].shape == (129, 3 * 5120)
    assert pages[7][0].shape == (3, 16, 128)
    with pytest.raises(ValueError, match="heads"):
        jamba.JambaConfig(n_heads=3)
