"""Qwen3-Next-80B-A3B-Instruct's decoder (``qwen3_next``) on the engine at
a toy size, float32, on the CPU: the program
(`client_tpu/models/qwen3_next.py`, `models/gated_delta.py`,
`models/moe.py`, the ``state`` cache group of `llm/engine.py`) against the
plain reference the benchmark keeps (`benchmark/lib/reference_qwen3next.py`),
on seeded weights.

Tolerances. Everything is float32 and the two sides differ in the order
of their sums and in the FORM of the DeltaNet (the reference runs the
recurrence token by token from a zero state and caches nothing; the
program's prefill runs the chunked rule and writes the final state into
a slot, its decode turns the slot a token a step, through the kernel or
a gather and a scatter): the logits, of size about 4, came out within
5e-6 over four layers and 40 decoded tokens. ``TOLERANCE`` 1e-4 leaves
that twenty times of room; the smallest change any departure left out
below makes is 100 times over it, and the same program with its state
held in bf16 lies a hundred times over it too.
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
    hidden_size=64, num_hidden_layers=4, full_attention_interval=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    partial_rotary_factor=0.25, linear_conv_kernel_dim=4,
    linear_key_head_dim=16, linear_value_head_dim=16,
    linear_num_key_heads=2, linear_num_value_heads=4,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    num_experts=16, experts_routed_over=16, experts_held_first=0,
    num_experts_per_tok=4, norm_topk_prob=True, rope_theta=100.0,
    rms_norm_eps=1e-6, vocab_size=256, max_position_embeddings=128,
    decoder_sparse_step=1, mlp_only_layers=[], intermediate_size=128,
    hidden_act="silu", rope_scaling=None, tie_word_embeddings=False,
    use_sliding_window=False, model_type="qwen3_next",
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

    from benchmark.lib.serving_qwen3next import qwen3next_config

    return dataclasses.replace(
        qwen3next_config({**TOY, **keys}), dtype=dtype or jnp.float32)


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
    at once, each at its own position. ``state_dtype`` rounds every
    DeltaNet state to it after each step (the narrower state of the
    test below)."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import weights_qwen3next
    from client_tpu.models import qwen3_next

    kernels = _kernels(kernel_name)
    config = _config()
    params = _to32(weights_qwen3next.params(SEED, TOY))
    rng = np.random.default_rng(0)
    tokens = [rng.integers(1, 256, size=total) for _, total in LANES]
    tables = _tables()
    pages = qwen3_next.init_pages(
        config, [1 + tables.shape[1] * tables.shape[2], 1 + len(LANES)],
        BLOCK)

    def rounded(pages):
        if state_dtype is None:
            return pages
        return [(pools[0].astype(state_dtype).astype(jnp.float32), pools[1])
                if kind else pools
                for pools, kind in zip(pages, config.layer_kinds)]

    prefill = jax.jit(
        lambda *a: qwen3_next.prefill_into_pages(*a, config, kernels))
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
        lambda *a: qwen3_next.decode_step_paged(*a, config, kernels))
    steps = LANES[0][1] - LANES[0][0]
    assert all(total - prompt == steps for prompt, total in LANES)
    counted = np.zeros(len(qwen3_next.COUNTERS), np.int64)
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
            dict(zip(qwen3_next.COUNTERS, counted.tolist())), pages)


@pytest.fixture(scope="module", params=["fused_xla", "pallas_interpret"])
def toy(request):
    """Once on the plain XLA path (a gather, the rule and a scatter) and
    once through the three Pallas kernels under the interpreter; the
    third choice, ``pallas``, is Mosaic's: compiled here
    (`tests/test_mosaic_compile.py`) and held against XLA on the chip
    (`tests/test_tpu_platform.py`)."""
    return _served_rows(request.param) + (request.param,)


def _reference_rows(params, tokens, model, lane):
    from benchmark.lib import reference_qwen3next

    logits = reference_qwen3next.forward(
        tokens[lane], params, params["layers"], model,
        (0, int(model["num_experts"])))
    return np.asarray(logits)[LANES[lane][0] - 1:]


def _worst(params, tokens, served, model):
    return max(
        np.abs(served[lane] - _reference_rows(params, tokens, model, lane)
               ).max() for lane in range(len(LANES)))


def test_prefill_then_decode_through_slots_matches_the_plain_reference(toy):
    """Ragged lanes, each with a slot of the state group and shuffled
    pages of the full group, a padding lane beside them: the program's
    prefill (the chunked rule, the state written into the slot) and
    decode (the slot turned in place) against the reference's full
    forward pass, which carries nothing."""
    params, tokens, served, counted, pages, kernel = toy
    for lane in range(len(LANES)):
        ref = _reference_rows(params, tokens, TOY, lane)
        assert np.abs(ref).max() > 1.0  # logits of a size worth comparing
        assert np.abs(served[lane] - ref).max() <= TOLERANCE
    steps, layers = LANES[0][1] - LANES[0][0], 4
    assert counted["moe_resident_calls"] == (
        steps * layers if kernel == "pallas_interpret" else 0)
    # every pair of the toy lands on a held expert (all 16 are held); the
    # padding lane routes too, as in every model
    assert counted["moe_pairs"] == steps * layers * (len(LANES) + 1) * 4
    # three DeltaNet layers turn three live lanes' states a step
    assert counted["gdn_state_updates"] == steps * 3 * len(LANES)
    # the trash slot holds zeros and every lane's slot a state
    for (state_pool, conv_pool), kind in zip(pages, _config().layer_kinds):
        if kind:
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


# -- a slot's convolution inputs ---------------------------------------------------


#: the prefill bucket and the slot of the one sequence below, of three
ROW_BUCKET, ROW_SLOT = 8, 2


def _layer0_inputs(params, ids):
    """The first layer's convolution inputs [T, channels] for token
    ``ids``: that layer is a DeltaNet layer and reads the embeddings, so
    these depend on no state and no convolution."""
    from client_tpu.models import qwen3_next

    config = _config()
    layer = params["layers"][0]
    normed = qwen3_next._norm(
        params["embed"][np.asarray(ids)], layer["mixer_norm"],
        config.norm_eps)
    inputs = qwen3_next._delta_inputs(layer, normed, config)[0]
    return np.asarray(inputs).reshape(len(ids), -1)


def _slot_rows(conv_pool):
    """A convolution pool [slots, taps - 1, heads, lanes] as one row a
    slot: the three inputs side by side, each its channels in order."""
    return np.asarray(conv_pool).reshape(conv_pool.shape[0], -1)


@functools.lru_cache(maxsize=None)
def _row_programs(kernel_name):
    """(float32 params, a jitted prefill at the bucket, a jitted decode
    step, empty pages of three slots and the trash slot)."""
    import jax

    from benchmark.lib import weights_qwen3next
    from client_tpu.models import qwen3_next

    kernels, config = _kernels(kernel_name), _config()
    params = _to32(weights_qwen3next.params(SEED, TOY))
    pages = qwen3_next.init_pages(
        config, [1 + _row_tables().shape[1], 4], BLOCK)
    prefill = jax.jit(
        lambda *a: qwen3_next.prefill_into_pages(*a, config, kernels))
    decode = jax.jit(
        lambda *a: qwen3_next.decode_step_paged(*a, config, kernels))
    return params, prefill, decode, pages


def _row_tables(slot=ROW_SLOT):
    """[2, columns]: the full group's blocks 1.., the slot in column 0."""
    width = TOY["max_position_embeddings"] // BLOCK
    table = np.zeros((2, width), np.int32)
    table[0] = 1 + np.arange(width)
    table[1, 0] = slot
    return table


def _prefilled(kernel_name, ids, slot=ROW_SLOT):
    """(logits [V], pages) after a prefill of ``ids`` into ``slot``, the
    bucket's rest filled with tokens that the masks must keep out."""
    params, prefill, _, pages = _row_programs(kernel_name)
    padded = np.full((1, ROW_BUCKET), 77, np.int32)
    padded[0, :len(ids)] = ids
    logits, pages = prefill(
        params, padded, _row_tables(slot), pages, len(ids) - 1)
    return np.asarray(logits[0]), pages


def _stepped(kernel_name, pages, token, position, padding_token=0):
    """(the lane's logits [V], pages) after one decode step of the
    sequence in ``ROW_SLOT`` beside a padding lane on the trash slot."""
    params, _, decode, _ = _row_programs(kernel_name)
    tables = np.stack([_row_tables(), _row_tables(0)], axis=1)
    tables[0, 1] = 0  # the padding lane writes its K/V to the trash block
    logits, pages, _ = decode(
        params, np.array([token, padding_token], np.int32),
        np.array([position, 0], np.int32), tables, pages)
    return np.asarray(logits[0]), pages


@pytest.mark.parametrize("length", [1, 2, 5])
def test_a_prefill_leaves_the_last_three_inputs_in_the_slot_oldest_first(
        length):
    """The slot after a prefill holds ``[input(last - 2), input(last -
    1), input(last)]``, each folded into its heads' rows, zeros standing
    for the inputs before the prompt's start, and none of the bucket's
    padding; no other slot is written, and a prefill into the trash slot
    leaves zeros there."""
    ids = list(range(40, 40 + length))
    params = _row_programs("fused_xla")[0]
    channels = _config().conv_dim
    inputs = _layer0_inputs(params, ids)
    assert np.abs(inputs).max() > 0.1
    expected = np.concatenate(
        [np.zeros((3, channels), np.float32), inputs])[-3:].reshape(-1)
    _, pages = _prefilled("fused_xla", ids)
    config = _config()
    assert pages[0][1].shape == (
        4, 3, channels // config.conv_lanes, config.conv_lanes)
    conv_pool = _slot_rows(pages[0][1])
    np.testing.assert_array_equal(conv_pool[ROW_SLOT], expected)
    assert np.abs(conv_pool[ROW_SLOT, -channels:]).max() > 0.1
    assert not conv_pool[[0, 1, 3]].any()
    _, trashed = _prefilled("fused_xla", ids, slot=0)
    for (state_pool, conv_pool), kind in zip(trashed, _config().layer_kinds):
        if kind:
            assert not np.asarray(state_pool).any()
            assert not np.asarray(conv_pool).any()


@pytest.mark.parametrize("kernel", ["fused_xla", "pallas_interpret"])
def test_a_decode_step_shifts_the_slot_by_one_input_and_spares_the_trash_slot(
        kernel):
    """A step drops the slot's oldest input and appends the token's own;
    the padding lane beside it (a token whose inputs are not zero) names
    the trash slot, whose row and state stay zero in every layer."""
    ids = [40, 41, 42, 43, 44]
    params = _row_programs(kernel)[0]
    channels = _config().conv_dim
    _, pages = _prefilled(kernel, ids)
    before = _slot_rows(pages[0][1])[ROW_SLOT]
    _, pages = _stepped(kernel, pages, 45, len(ids), padding_token=99)
    after = _slot_rows(pages[0][1])
    np.testing.assert_array_equal(
        after[ROW_SLOT, :2 * channels], before[channels:])
    np.testing.assert_array_equal(
        after[ROW_SLOT, 2 * channels:], _layer0_inputs(params, [45])[0])
    assert np.abs(_layer0_inputs(params, [99])).max() > 0.1
    assert not after[[1, 3]].any()
    for (state_pool, conv_pool), kind in zip(pages, _config().layer_kinds):
        if kind:
            assert not np.asarray(state_pool[0]).any()
            assert not np.asarray(conv_pool[0]).any()
            assert np.abs(np.asarray(conv_pool[ROW_SLOT])).max() > 0.1


@pytest.mark.parametrize("prompt", [2, 5])
@pytest.mark.parametrize("kernel", ["fused_xla", "pallas_interpret"])
def test_a_prefill_then_decode_steps_equal_the_recurrence_token_by_token(
        kernel, prompt):
    """A prompt prefilled whole (the chunked rule, the slot's inputs
    written at once) and then decoded equals the same tokens walked one a
    step from a prefill of the first alone (the slot filled an input a
    step, the state turned a token a step), in its logits, in every
    layer's inputs and state, and both equal the plain reference's
    logits."""
    from benchmark.lib import reference_qwen3next

    rng = np.random.default_rng(prompt)
    ids = rng.integers(1, 256, size=12).tolist()
    params = _row_programs(kernel)[0]

    def walk(start):
        logits, pages = _prefilled(kernel, ids[:start])
        rows = [logits]
        for position in range(start, len(ids)):
            logits, pages = _stepped(kernel, pages, ids[position], position)
            rows.append(logits)
        return np.stack(rows), pages

    whole, whole_pages = walk(prompt)
    stepped, stepped_pages = walk(1)
    assert np.abs(whole - stepped[prompt - 1:]).max() <= TOLERANCE
    ref = np.asarray(reference_qwen3next.forward(
        np.asarray(ids), params, params["layers"], TOY, (0, 16)))
    assert np.abs(ref).max() > 1.0
    assert np.abs(whole - ref[prompt - 1:]).max() <= TOLERANCE
    assert np.abs(stepped - ref).max() <= TOLERANCE
    for a, b, kind in zip(whole_pages, stepped_pages, _config().layer_kinds):
        if kind:
            assert np.abs(np.asarray(a[1])).max() > 0.1
            assert np.abs(np.asarray(a[1]) - np.asarray(b[1])).max() <= 1e-5
            assert np.abs(np.asarray(a[0]) - np.asarray(b[0])).max() <= 1e-5


# -- the rule's three forms ------------------------------------------------------


def _rule_inputs(length, key_heads=2, heads=4, dk=16, dv=16, seed=0):
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = unit(rng.normal(size=(length, key_heads, dk))) * dk ** -0.5
    k = unit(rng.normal(size=(length, key_heads, dk)))
    v = rng.normal(size=(length, heads, dv))
    g = -np.exp(rng.uniform(np.log(1e-3), np.log(0.3), size=(length, heads)))
    beta = rng.uniform(0.05, 0.95, size=(length, heads))
    return [x.astype(np.float32) for x in (q, k, v, g, beta)]


def _token_by_token(q, k, v, g, beta):
    import jax.numpy as jnp

    from client_tpu.models import gated_delta

    heads = v.shape[1]
    state = jnp.zeros((heads, k.shape[2], v.shape[2]), jnp.float32)
    outs = []
    for t in range(len(q)):
        out, state = gated_delta.recurrent_step(
            state, gated_delta._expand(q[t], heads),
            gated_delta._expand(k[t], heads), v[t], g[t], beta[t])
        outs.append(np.asarray(out))
    return np.stack(outs), np.asarray(state)


#: head counts and sizes of the rule's comparisons: the toy's, and the
#: published widths with the toy's heads and with the published 16 : 32
RULE_SHAPES = {
    "toy": dict(key_heads=2, heads=4, dk=16, dv=16),
    "toy-heads-128": dict(key_heads=2, heads=4, dk=128, dv=128),
    "published": dict(key_heads=16, heads=32, dk=128, dv=128),
}


def _adverse_inputs(kind, length, **shape):
    """Prompts on which a chunk's triangular system is as hard as a
    prompt can make it. ``collinear``: every key within 5% noise of one
    direction (a prompt that all but repeats a token), ``beta`` 0.9-0.999
    and a hundredth of the seeded decays, so the entries under the
    diagonal are near 1. ``repeated``: one key (and one query) for every
    token. ``padded``: ``beta = g = 0`` over the whole second chunk."""
    q, k, v, g, beta = _rule_inputs(length, **shape)
    rng = np.random.default_rng(7)
    if kind == "collinear":
        k = rng.normal(size=(1,) + k.shape[1:]) + 0.05 * rng.normal(
            size=k.shape)
        k = k / np.sqrt((k * k).sum(-1, keepdims=True))
        beta = rng.uniform(0.9, 0.999, size=beta.shape)
        g = 0.01 * g
    elif kind == "repeated":
        q, k = np.repeat(q[:1], length, 0), np.repeat(k[:1], length, 0)
    elif kind == "padded":
        real = ((np.arange(length) < 64) | (np.arange(length) >= 128))[:, None]
        g, beta = g * real, beta * real
    return [x.astype(np.float32) for x in (q, k, v, g, beta)]


@pytest.mark.parametrize("kind,shape,length,bucket", [
    ("seeded", "toy", 150, 150), ("seeded", "toy", 64, 64),
    ("seeded", "toy", 1, 8), ("seeded", "toy", 37, 64),
    ("seeded", "toy", 100, 256), ("seeded", "toy", 129, 130),
    ("seeded", "published", 150, 150),
    ("collinear", "toy-heads-128", 150, 150),
    ("collinear", "published", 150, 150),
    ("repeated", "toy-heads-128", 150, 150),
    ("repeated", "published", 150, 150),
    ("padded", "toy-heads-128", 150, 150),
    ("padded", "published", 150, 150)])
def test_the_chunked_rule_equals_the_recurrence(kind, shape, length, bucket):
    """Lengths that are no whole number of chunks of 64, and a prompt
    padded to its bucket with ``beta = 0`` and ``g = 0`` past its end
    (the padding's q, k and v are whatever the projections of the
    padding tokens gave, not zeros): outputs up to the last token and the
    final state are the recurrence's. So they are on the adverse prompts
    (:func:`_adverse_inputs`) at the published head size, where the
    chunk's system is inverted on numbers near 1."""
    from client_tpu.models import gated_delta

    q, k, v, g, beta = _adverse_inputs(kind, bucket, **RULE_SHAPES[shape])
    real = (np.arange(bucket) < length)[:, None]
    g, beta = g * real, beta * real
    out, state = gated_delta.chunked_gated_delta(q, k, v, g, beta)
    ref_out, ref_state = _token_by_token(
        q[:length], k[:length], v[:length], g[:length], beta[:length])
    assert np.abs(ref_out).max() > 0.01
    assert np.abs(np.asarray(out)[:length] - ref_out).max() <= 1e-5
    assert np.abs(np.asarray(state) - ref_state).max() <= 1e-5


def _chunk_systems(kind, monkeypatch):
    """The unit-lower-triangular systems ``chunked_gated_delta`` itself
    forms on a prompt of three chunks, and their right-hand sides:
    ``lower`` [H, 3, 64, 64] and ``rhs`` [H, 3, 64, Dv + Dk], float32."""
    from client_tpu.models import gated_delta

    seen = []
    monkeypatch.setattr(gated_delta, "_solve_unit_lower",
                        lambda lower, rhs: seen.append((lower, rhs)) or rhs)
    gated_delta.chunked_gated_delta(
        *_adverse_inputs(kind, 192, **RULE_SHAPES["toy-heads-128"]))
    monkeypatch.undo()
    return seen[0]


def _product_form(lower, rhs):
    """``(I - N)(I + N^2)(I + N^4)...(I + N^32)`` for ``lower = I + N``,
    the inverse that is NOT used: exact in exact arithmetic as well."""
    import jax.numpy as jnp

    from client_tpu.models.gated_delta import HIGHEST

    eye = jnp.eye(lower.shape[-1], dtype=lower.dtype)
    power = lower - eye
    inv = eye - power
    for _ in range(5):
        power = jnp.matmul(power, power, precision=HIGHEST)
        inv = jnp.matmul(inv, eye + power, precision=HIGHEST)
    return jnp.matmul(inv, rhs, precision=HIGHEST)


@pytest.mark.parametrize("kind", ["seeded", "collinear"])
def test_the_block_inverse_errs_no_more_than_the_triangular_solve(
        kind, monkeypatch):
    """``gated_delta._solve_unit_lower`` (the inverse by block recursion,
    applied with one product) against a float64 ``numpy.linalg.solve`` of
    the same systems: its error is within 4 x that of
    ``jax.scipy.linalg.solve_triangular``, which it replaced, on the
    seeded chunks and on chunks of nearly collinear keys. The plain
    product form passes on the first and, on the second, is off by
    more than a million (its powers grow like binomial coefficients and
    cancel): the reason it is not the inverse used."""
    from jax.scipy.linalg import solve_triangular

    from client_tpu.models import gated_delta

    lower, rhs = _chunk_systems(kind, monkeypatch)
    exact = np.linalg.solve(np.asarray(lower, np.float64),
                            np.asarray(rhs, np.float64))

    def err(solved):
        return float(np.abs(np.asarray(solved) - exact).max())

    solve = err(solve_triangular(lower, rhs, lower=True, unit_diagonal=True))
    assert 0 < solve < 1e-5
    assert err(gated_delta._solve_unit_lower(lower, rhs)) <= 4 * solve
    product = err(_product_form(lower, rhs))
    if kind == "seeded":
        assert product <= 4 * solve
    else:
        assert product > 1e6


@pytest.mark.parametrize("kernel", ["fused_xla", "pallas_interpret"])
@pytest.mark.parametrize("shape", [
    dict(key_heads=2, heads=4, dk=16, dv=16),
    dict(key_heads=16, heads=32, dk=8, dv=128),  # two head blocks of 16
])
def test_gated_delta_step_turns_each_lanes_slot_and_no_other(kernel, shape):
    """The decode step over a pool against the recurrence on each lane's
    own state: live lanes' slots are turned, the slots of no lane are
    left as they were, and lanes that name the trash slot read out zeros
    and leave zeros there, whatever it held."""
    import jax.numpy as jnp

    from client_tpu.models import gated_delta

    lanes = 5
    q, k, v, g, beta = _rule_inputs(lanes, **shape)
    rng = np.random.default_rng(4)
    pool = rng.normal(size=(7, shape["heads"], shape["dk"], shape["dv"])
                      ).astype(np.float32)
    slots = np.array([3, 0, 5, 1, 0], np.int32)
    out, new = gated_delta.gated_delta_step(
        q, k, v, g, beta, slots, jnp.asarray(pool), kernel=kernel)
    out, new = np.asarray(out), np.asarray(new)
    heads = shape["heads"]
    for lane, slot in enumerate(slots):
        if slot == 0:
            assert not out[lane].any()
            continue
        ref_out, ref_state = gated_delta.recurrent_step(
            pool[slot], gated_delta._expand(q[lane], heads),
            gated_delta._expand(k[lane], heads), v[lane], g[lane],
            beta[lane])
        assert np.abs(out[lane] - np.asarray(ref_out)).max() <= 1e-5
        assert np.abs(new[slot] - np.asarray(ref_state)).max() <= 1e-5
    assert not new[0].any()
    assert (new[[2, 4, 6]] == pool[[2, 4, 6]]).all()


def test_the_seeded_decays_carry_the_state_for_tens_to_hundreds_of_tokens():
    """``A_log`` and ``dt_bias`` as the benchmark draws them: a head's
    decay a token lies between about 0.75 and 0.9997 for projections of
    unit size, its half-life between a few tokens and some thousand, and
    the heads spread over that range; the published initial draw (``A``
    uniform on 0-16) would forget within a token."""
    import jax

    from benchmark.lib import weights_qwen3next
    from client_tpu.models import qwen3_next

    for draw in (weights_qwen3next.decay_draw, qwen3_next.decay_draw):
        a_log, dt_bias = map(np.asarray, draw(jax.random.PRNGKey(5), 512))
        for a in (-1.0, 0.0, 1.0):
            decay = np.exp(-np.exp(a_log) * np.log1p(np.exp(a + dt_bias)))
            assert 0.7 < decay.min() and decay.max() < 0.9998
        decay = np.exp(-np.exp(a_log) * 0.5)
        assert (decay < 0.95).mean() > 0.1 and (decay > 0.995).mean() > 0.1
    published = np.exp(-np.random.default_rng(0).uniform(0, 16, 512) * 0.5)
    assert np.median(published) < 0.05


# -- one case a departure: the reference with it left out is far away ----------


def _patch(name, replacement):
    def patch(monkeypatch, params):
        from benchmark.lib import reference_qwen3next

        monkeypatch.setattr(reference_qwen3next, name, replacement)
    return patch


def _plain_norms(monkeypatch, params):
    """``n(x) w`` for ``n(x) (1 + w)``."""
    from benchmark.lib import reference_qwen3next as ref

    monkeypatch.setattr(
        ref, "norm", lambda x, w, model: ref.unit(x, model) * w)


def _token_alone(inputs, taps):
    import jax
    import jax.numpy as jnp

    return jax.nn.silu(taps[-1].astype(jnp.float32) * inputs)


def _ungated_norm(out, z, w, model):
    import jax.numpy as jnp

    from benchmark.lib import reference_qwen3next as ref

    return ref.unit(out, model) * w.astype(jnp.float32)


def _gate_without_norm(out, z, w, model):
    import jax

    return out * jax.nn.silu(z)


# (changes to the model's keys, a patch of the reference or None)
DEPARTURES = {
    "no gate on the attention's output": (
        {}, _patch("attention_gate", lambda gate: 1.0)),
    "rope on every size of the head": (dict(partial_rotary_factor=1.0), None),
    "rope on half of the head": (dict(partial_rotary_factor=0.5), None),
    "norms that scale by w and not by 1 + w": ({}, _plain_norms),
    "no L2 norm on q and k": ({}, _patch("l2norm", lambda x: x)),
    "beta of one": ({}, _patch("beta_of", lambda b: 1.0 + 0.0 * b)),
    "no decay": ({}, _patch("decay_of", lambda a, w: 0.0 * a)),
    "no convolution": (
        {}, _patch("convolution", lambda inputs, taps: inputs)),
    "a convolution of the token alone": (
        {}, _patch("convolution", _token_alone)),
    "no gate in the DeltaNet's output norm": (
        {}, _patch("gated_output_norm", _ungated_norm)),
    "no norm before the DeltaNet's gate": (
        {}, _patch("gated_output_norm", _gate_without_norm)),
    "softmax weights not renormalised": (dict(norm_topk_prob=False), None),
    "no gate on the shared expert": (
        {}, _patch("shared_gate", lambda h, w: 1.0)),
    "a full layer every second layer": (dict(full_attention_interval=2),
                                        None),
}


@pytest.mark.parametrize("departure", DEPARTURES)
def test_each_departure_left_out_fails_the_comparison(
        toy, departure, monkeypatch):
    """The comparison above is tight enough to tell: a reference with one
    part of the mathematics left out (which is the program with it left
    out, seen from the other side) lies far outside the tolerance."""
    params, tokens, served, _, _, _ = toy
    keys, patch = DEPARTURES[departure]
    params = {**params, "layers": [dict(l) for l in params["layers"]]}
    if patch is not None:
        patch(monkeypatch, params)
    model = {**TOY, **keys}
    if departure == "a full layer every second layer":
        # layer 1's DeltaNet weights cannot run as attention: it borrows
        # layer 3's mixer
        mixer = ("q_norm", "k_norm", "wq", "wk", "wv", "wo")
        params["layers"][1] = {
            **params["layers"][1],
            **{name: params["layers"][3][name] for name in mixer}}
    assert _worst(params, tokens, served, model) > 100 * TOLERANCE


# -- the experts: softmax scores, the shared expert's gate, the shares ----------


@pytest.mark.parametrize("scale", [1.0, 2.826])
def test_route_by_sigmoid_traces_to_the_program_it_was(scale):
    """``score="sigmoid"`` with a bias is the default and leaves the
    older models' routing program as it was: the jaxpr is the one of the
    function before it took ``score`` (a softmax appears nowhere in it)."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models import moe

    h = jnp.ones((5, 64), jnp.float32)
    router = jnp.ones((64, 16), jnp.float32)
    bias = jnp.zeros((16,), jnp.float32)
    default = jax.make_jaxpr(
        lambda *a: moe.route(*a, 4, scale=scale))(h, router, bias)
    named = jax.make_jaxpr(
        lambda *a: moe.route(*a, 4, scale=scale, score="sigmoid"))(
        h, router, bias)
    assert str(default) == str(named)
    assert "logistic" in str(default) and "exp" not in str(default)
    with pytest.raises(ValueError, match="softmax"):
        moe.route(h, router, bias, 4, score="tanh")


def test_softmax_routing_matches_the_reference_and_differs_from_sigmoid():
    import jax.numpy as jnp

    from benchmark.lib import reference_qwen3next
    from client_tpu.models import moe

    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(40, 64)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(64, 16)) / 8, jnp.float32)
    ids, weights = moe.route(h, router, None, 4, score="softmax")
    chosen, weight, margin = reference_qwen3next.route(
        h, {"router": router}, TOY, (0, 16))
    assert (np.asarray(ids) == np.asarray(chosen)).all()
    assert np.abs(np.asarray(weights) - np.asarray(weight)).max() <= 1e-6
    assert np.abs(np.asarray(weights).sum(-1) - 1).max() <= 1e-6
    assert (np.asarray(margin) > 0).all()
    _, by_sigmoid = moe.route(h, router, jnp.zeros(16), 4)
    # the same experts (both scores rise with the logit), other weights
    assert np.abs(np.asarray(by_sigmoid) - np.asarray(weights)).max() > 1e-2


PATHS = ["fused_xla", "pallas_interpret"]


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("shares", [16, 4, 1])
def test_shares_and_the_gated_shared_expert_once_add_up_to_the_uncut_layer(
        path, shares):
    """The parts of the result that all the shares give, with what every
    chip computes alike (the gated shared expert) counted once, add up
    to what the uncut reference gives for the whole layer."""
    import jax.numpy as jnp

    from benchmark.lib import reference_qwen3next, weights_qwen3next
    from client_tpu.models import moe

    whole = _to32(weights_qwen3next.layer(SEED, 0, TOY))
    rng = np.random.default_rng(6)
    h = jnp.asarray(rng.normal(size=(24, 64)), jnp.float32)
    expected = np.asarray(reference_qwen3next.expert_layer(
        h, whole, TOY, (0, 16)))
    shared = np.asarray(moe.shared_expert(h, whole["shared"]))
    ids, weights = moe.route(h, whole["router"], None, 4, score="softmax")
    count = 16 // shares
    total = np.zeros_like(expected)
    for share in range(shares):
        held = (share * count, count)
        w = _to32(weights_qwen3next.layer(SEED, 0, TOY, held))
        out, _ = moe.expert_layer(
            h, ids, weights, w["experts"], held, kernel=path,
            shared=w["shared"])
        total += np.asarray(out) - shared
    assert np.abs(total + shared - expected).max() <= 1e-5
    # the gate weighs: without it the shared expert is another size
    ungated = {k: v for k, v in whole["shared"].items() if k != "w_sg"}
    assert np.abs(np.asarray(moe.shared_expert(h, ungated)) - shared
                  ).max() > 1e-2


# -- the engine ------------------------------------------------------------------


def _engine_model(features=None, **engine):
    """The toy behind `LlmEngineModel`; ``engine`` overrides
    `EngineConfig`'s sizes, ``features`` are the model's own arguments."""
    from client_tpu.llm.engine import EngineConfig
    from client_tpu.llm.serving import LlmEngineModel
    from client_tpu.models import qwen3_next

    sizes = dict(block_size=8, num_blocks=1 + 3 * 16, max_active=3,
                 max_queue=8, max_seq_len=128, prefix_sharing=False)
    sizes.update(engine)
    return LlmEngineModel(
        name="qwen3next_toy", model=qwen3_next.ENGINE_MODEL,
        config=qwen3_next.Qwen3NextConfig.tiny(),
        engine_config=EngineConfig(**sizes), **(features or {}))


async def _generate(model, prompts, max_tokens):
    seqs = [model.engine.submit(p, max_tokens=max_tokens) for p in prompts]

    async def collect(seq):
        return [token async for token, _ in seq]

    return await asyncio.gather(*(collect(s) for s in seqs))


def _prompts(lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, size=n).tolist() for n in lengths]


def test_engine_serves_the_model_over_a_state_group_and_a_full_group():
    """Five sequences through `LlmEngineModel` over three slots: the
    state group's pools are ``1 + max_active`` slots whatever the
    sequences' lengths, its tile is 1 and it books no tile stops, the
    row bytes are a slot's, greedy tokens equal the reference's on the
    same weights (the fourth and fifth sequence take slots the first
    three gave back: a slot reused carries nothing over), and everything
    is given back at the end."""
    from benchmark.lib import reference_qwen3next
    from client_tpu.models import paged_attention as pa

    model = _engine_model()
    model.warmup()
    try:
        engine = model.engine
        state_pool, conv_pool = engine._pages[0]
        assert state_pool.shape == (4, 4, 16, 16)
        assert conv_pool.shape == (4, 3, 8, 16)
        assert all(pool.shape == (49, 16, 16) for pool in engine._pages[3])
        assert engine._tile_pages == (
            pa.pages_per_tile(16, 1, 16, np.float32, 2), 1)
        prompts = _prompts((30, 9, 17, 22, 5))
        out = 40
        served = asyncio.run(_generate(model, prompts, out))
        stats = engine.stats()
        slot = 4 * 16 * 16 * 4 + 3 * 128 * 4
        assert stats["kv_row_bytes_by_group"] == [
            {"stored": 2 * 2 * 16 * 4, "counted": 2 * 2 * 16 * 4},
            {"stored": slot, "counted": slot}]
        assert stats["kv_blocks_in_use_by_group"] == [0, 0]
        assert stats["state_slots_in_use"] == 0
        assert stats["state_bytes_by_group"] == [0, 0]
        assert stats["completed"] == 5 and stats["preemptions"] == 0
        # three DeltaNet layers a live lane a step
        assert stats["gdn_state_updates"] == 3 * stats["lane_steps"]
        # only the full group's layer walks tiles: a table of 16 columns
        # is one tile a lane a step
        assert stats["attn_tiles_walked"] == stats["lane_steps"]
        params = _to32(model._params)
        for prompt, tokens in zip(prompts, served):
            logits = np.asarray(reference_qwen3next.forward(
                prompt + tokens, params, params["layers"], TOY, (0, 16)))
            at = logits[len(prompt) - 1: len(prompt) + len(tokens) - 1]
            gap = at.max(axis=-1) - at[np.arange(len(tokens)), tokens]
            assert gap.max() <= TOLERANCE  # the served token is the best
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
        slot = 4 * 16 * 16 * 4 + 3 * 128 * 4
        assert 1 <= stats["state_slots_in_use"] <= 2
        assert stats["state_bytes_by_group"] == [
            0, stats["state_slots_in_use"] * slot * 3]
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


@pytest.mark.parametrize("features,engine,part", [
    (dict(speculation={"mode": "ngram", "k": 2}), {}, "verify"),
    ({}, dict(prefix_sharing=True), "prefill_suffix"),
    (dict(tp=2), {}, "param_specs"),
])
def test_qwen3_next_is_refused_the_features_it_has_no_part_for(
        features, engine, part):
    from client_tpu.utils import InferenceServerException

    model = _engine_model(features, **engine)
    with pytest.raises(InferenceServerException, match=f"[`']{part}[`']"):
        model.warmup()


def test_the_config_declares_a_full_and_a_state_group_in_the_published_ratio():
    from client_tpu.models import qwen3_next
    from client_tpu.models.engine_model import FULL, STATE

    config = qwen3_next.Qwen3NextConfig(
        n_layers=16, held=(0, 32), vocab_size=18992)
    full, state = qwen3_next.cache_groups(config)
    assert (full.kind, full.layers) == (FULL, (3, 7, 11, 15))
    assert state.kind == STATE and len(state.layers) == 12
    assert config.conv_dim == 8192 and config.value_dim == 4096
    # a cached token's 2,048 B a full layer; a slot's 2,146,304 B
    assert qwen3_next.kv_row_bytes(config) == [
        (2048, 2048), (2146304, 2146304)]
    with pytest.raises(ValueError, match="share"):
        qwen3_next.Qwen3NextConfig(held=(500, 32))
