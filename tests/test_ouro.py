"""Ouro's looped decoder (``ouro``) on the engine at a toy size, float32,
on the CPU: the program (`client_tpu/models/ouro.py`: the passes and the
layers as two rolled loops over stacked weights, every (pass, layer)
pair's K/V at an offset into ONE pool pair) against the plain reference
the benchmark keeps (`benchmark/lib/reference_ouro.py`: Python loops, one
layer's weights at a time, no cache), on seeded weights.

Tolerances. Everything is float32 and the two sides differ in the order
of their sums and in FORM: the reference runs every pass of every layer
over every position and caches nothing; the program prefills through its
scatter, then decodes through pages at twelve offsets. The logits, of
size about 4, came out within 4e-6 over twelve layer-passes and 39
decoded tokens. ``TOLERANCE`` 1e-4 leaves that twenty-five times of
room; the smallest change any departure below makes is 100 times over it.
"""

import asyncio
import dataclasses
import functools

import numpy as np
import pytest

pytestmark = pytest.mark.llm

TOLERANCE = 1e-4
BLOCK, SEED, TILE_PAGES = 8, 5, 2

TOY = dict(
    hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
    num_key_value_heads=4, head_dim=16, intermediate_size=128,
    vocab_size=256, max_position_embeddings=128, rms_norm_eps=1e-6,
    rope_theta=1e6, rope_scaling=None, sliding_window=None,
    use_sliding_window=False, tie_word_embeddings=False, hidden_act="silu",
    total_ut_steps=4, early_exit_threshold=1, max_window_layers=3,
    layer_types=["full_attention"] * 3, model_type="ouro",
)
PAIRS = 12

#: (prompt, total) of the lanes one decode batch holds, ragged, each past
#: a tile of 2 pages of 8; a fourth lane of every step is a batch
#: bucket's padding and names the trash block of every pair
LANES = ((21, 61), (5, 45), (60, 100))
WIDTH = 16   # table columns: 128 positions in blocks of 8
POOL = 1 + len(LANES) * WIDTH


@pytest.fixture(scope="module", autouse=True)
def small_tiles():
    """Tiles of :data:`TILE_PAGES` pages for every kernel call of this
    file (the toy's pages are 8 x 4 rows of 16 float32: 2 KiB), so that
    its contexts lie over several tiles. The kernel is jitted: the cut
    holds for shapes first traced under it, which are this file's alone."""
    from client_tpu.models import paged_attention as pa

    budget = pa._KV_VMEM_BUDGET
    pa._KV_VMEM_BUDGET = 4 * TILE_PAGES * BLOCK * 4 * 16 * 4
    assert pa.pages_per_tile(BLOCK, 4, 16, np.float32, 2) == TILE_PAGES
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


def _config():
    import jax.numpy as jnp

    from benchmark.lib.serving_ouro import ouro_config

    return dataclasses.replace(ouro_config(TOY), dtype=jnp.float32)


def _tables():
    """[lanes + 1, WIDTH]: a lane's pages shuffled over the pool; the last
    lane is padding (every column the trash block)."""
    rng = np.random.default_rng(1)
    tables = np.zeros((len(LANES) + 1, WIDTH), np.int32)
    blocks = 1 + np.arange(len(LANES) * WIDTH).reshape(len(LANES), WIDTH)
    for lane in range(len(LANES)):
        tables[lane] = rng.permutation(blocks[lane])
    return tables


@functools.lru_cache(maxsize=None)
def _served_rows(kernel_name, shared_caches=False):
    """(float32 params, each lane's token ids, each lane's logits from
    its prompt's last position on, the counters summed, the pools left):
    a prefill a lane, then decode steps of all lanes and one padding lane
    at once, each at its own position. ``shared_caches`` runs the program
    with every pass of a layer at the SAME offset (the departure of the
    test below)."""
    import jax

    from benchmark.lib import weights_ouro
    from client_tpu.models import ouro

    kernels = _kernels(kernel_name)
    config = _config()
    params = _to32(weights_ouro.params(SEED, TOY))
    rng = np.random.default_rng(0)
    tokens = [rng.integers(1, 256, size=total) for _, total in LANES]
    pages = ouro.init_pages(config, [POOL], BLOCK)
    tables = _tables()
    own = ouro._pair_base
    if shared_caches:
        ouro._pair_base = lambda step, index, config, stride: own(
            0, index, config, stride)
    try:
        prefill = jax.jit(
            lambda *a: ouro.prefill_into_pages(*a, config, kernels))
        rows = []
        for lane, (prompt, _) in enumerate(LANES):
            padded = np.zeros((1, 64), np.int32)
            padded[0, :prompt] = tokens[lane][:prompt]
            # what lies past the prompt in its bucket is masked, not zero
            padded[0, prompt:] = rng.integers(1, 256, size=64 - prompt)
            logits, pages = prefill(
                params, padded, tables[lane], pages, prompt - 1)
            rows.append([np.asarray(logits[0])])
        decode = jax.jit(
            lambda *a: ouro.decode_step_paged(*a, config, kernels))
        steps = LANES[0][1] - LANES[0][0]
        assert all(total - prompt == steps for prompt, total in LANES)
        counted = np.zeros(len(ouro.COUNTERS), np.int64)
        for step in range(steps):
            positions = np.array([p + step for p, _ in LANES] + [0], np.int32)
            ids = np.array([t[p] for t, p in zip(tokens, positions)] + [0],
                           np.int32)
            logits, pages, counters = decode(
                params, ids, positions, tables, pages)
            counted += np.asarray(counters)
            for lane in range(len(LANES)):
                rows[lane].append(np.asarray(logits[lane]))
    finally:
        ouro._pair_base = own
    return (params, tokens, [np.stack(r) for r in rows],
            dict(zip(ouro.COUNTERS, counted.tolist())), pages[0])


@pytest.fixture(scope="module", params=["fused_xla", "pallas_interpret"])
def toy(request):
    """Once on the plain XLA path and once through the Pallas kernel
    under the interpreter; the third choice, ``pallas``, is Mosaic's:
    compiled in `tests/test_mosaic_compile.py`."""
    return _served_rows(request.param)


def _reference_logits(params, tokens, model):
    import jax

    from benchmark.lib import reference_ouro

    # jitted anew a call: a test may have patched the module
    return np.asarray(jax.jit(
        lambda t: reference_ouro.forward(
            t, params,
            lambda i: jax.tree_util.tree_map(lambda a: a[i],
                                             params["layers"]),
            model))(np.asarray(tokens)))


def _worst(params, tokens, served, model, lanes=range(len(LANES))):
    return max(
        np.abs(served[lane] - _reference_logits(
            params, tokens[lane], model)[LANES[lane][0] - 1:]).max()
        for lane in lanes)


def test_prefill_then_decode_through_offset_pages_matches_the_plain_reference(toy):
    """Ragged lanes with shuffled pages and a padding lane beside them:
    the program's prefill and decode, twelve (pass, layer) pairs at
    twelve offsets into one pool pair, against the reference's full
    forward pass at every position (logits, not tokens)."""
    params, tokens, served, counted, pools = toy
    for lane in range(len(LANES)):
        ref = _reference_logits(params, tokens[lane], TOY)[
            LANES[lane][0] - 1:]
        assert np.abs(ref).max() > 1.0  # logits of a size worth comparing
        assert np.abs(served[lane] - ref).max() <= TOLERANCE
    steps = LANES[0][1] - LANES[0][0]
    contexts = sum(p + step + 1 for p, _ in LANES for step in range(steps))
    assert counted == {"loop_layer_passes": steps * PAIRS,
                       "loop_kv_rows_read": PAIRS * contexts}
    for pool in pools:
        assert pool.shape == (PAIRS * POOL, BLOCK, 4, 16)
        held = np.abs(np.asarray(pool)).reshape(PAIRS, POOL, -1).max(axis=-1)
        used = np.concatenate([
            _tables()[lane, :-(-total // BLOCK)]
            for lane, (_, total) in enumerate(LANES)])
        # every pair holds rows in every block a lane filled, and in no
        # other but its own trash block: none is shared, none is skipped
        assert (held[:, used] > 1e-3).all()
        unused = np.setdiff1d(np.arange(1, POOL), used)
        assert not held[:, unused].any()


def test_the_kernel_choices_agree():
    _, _, plain, _, _ = _served_rows("fused_xla")
    _, _, kernel, _, _ = _served_rows("pallas_interpret")
    assert max(np.abs(a - b).max() for a, b in zip(plain, kernel)) <= 1e-5


# -- one case a departure: the reference with it changed is far away -----------


def _patch(name, replacement):
    def patch(monkeypatch):
        from benchmark.lib import reference_ouro

        monkeypatch.setattr(reference_ouro, name, replacement)
    return patch


def _without(dropped):
    """The sandwich with one of its output norms left out."""
    def sublayer_out(y, w, name, model):
        from benchmark.lib import reference_ouro

        return y if name == dropped else reference_ouro.norm(
            y, w[name], model)
    return sublayer_out


def _unit_norm(x, w, model):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def _close_the_last_pass_alone():
    """``RMS_f`` outside the loop over passes: only the fourth call of a
    forward pass norms."""
    calls = []

    def close_pass(x, top, model):
        calls.append(None)
        return _unit_norm(x, None, model) * top["final_norm"] if (
            len(calls) % TOY["total_ut_steps"] == 0) else x
    return close_pass


# a patch of the reference
DEPARTURES = {
    "the norm on the attention's output dropped": _patch(
        "sublayer_out", _without("attn_out_norm")),
    "the norm on the MLP's output dropped": _patch(
        "sublayer_out", _without("mlp_out_norm")),
    "the norms' learned scales left out": _patch("norm", _unit_norm),
    "RMS_f outside the loop over passes": _patch(
        "close_pass", _close_the_last_pass_alone()),
    "three passes for four": _patch("passes", lambda model: 3),
    "five passes for four": _patch("passes", lambda model: 5),
    "no rotary": _patch("_rope", lambda x, theta: x),
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


def test_passes_that_shared_a_layers_cache_would_fail_the_comparison():
    """The program with every pass of a layer at the SAME offset (one
    cache a layer, each pass overwriting the last) lies far from the
    reference, whose passes attend over their own keys and values."""
    params, tokens, served, _, _ = _served_rows("fused_xla", True)
    assert _worst(params, tokens, served, TOY, lanes=(2,)) > 100 * TOLERANCE


def test_the_exit_gate_moves_no_logit_at_the_published_threshold():
    """At ``early_exit_threshold`` 1 the reference, which computes the
    gate, gives the same logits whatever the gate's weights are, so the
    program need not compute it; under a threshold of 0.5 the gate picks
    earlier passes' states and the logits move (and the benchmark's
    model file refuses such a threshold)."""
    import jax.numpy as jnp

    from benchmark.lib.serving_ouro import ouro_config

    params, tokens, served, _, _ = _served_rows("fused_xla")
    ids = tokens[0]
    ref = _reference_logits(params, ids, TOY)
    other = dict(params, exit_w=-3.0 * params["exit_w"],
                 exit_b=jnp.float32(4.0))
    assert np.array_equal(_reference_logits(other, ids, TOY), ref)
    early = _reference_logits(params, ids, {**TOY, "early_exit_threshold": 0.5})
    assert np.abs(early - ref).max() > 100 * TOLERANCE
    with pytest.raises(ValueError, match="exit"):
        ouro_config({**TOY, "early_exit_threshold": 0.5})
    for key, value in (("tie_word_embeddings", True), ("sliding_window", 64),
                       ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError):
            ouro_config({**TOY, key: value})


# -- the program is as long as a layer, not as the loop ------------------------


def _equations(jaxpr) -> int:
    """Every equation of a jaxpr and of the jaxprs inside it."""
    count = 0
    for eqn in jaxpr.eqns:
        count += 1
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (list, tuple))
                          else [value]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    count += _equations(inner)
    return count


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_the_programs_do_not_grow_with_the_pass_count_or_the_depth(program):
    """`decode`'s and `prefill`'s jaxprs hold the same number of
    equations at 1 and 4 passes and at 2 and 48 layers, and `decode` ONE
    paged-attention call: a start traces and lowers one layer body."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models import ouro

    kernels = _kernels("pallas_interpret")
    ints = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731

    def traced(**keys):
        config = ouro.OuroConfig.tiny(**keys)
        shapes = jax.eval_shape(lambda: (
            ouro.init_params(jax.random.PRNGKey(0), config),
            ouro.init_pages(config, [9], BLOCK)))
        if program == "decode":
            return jax.make_jaxpr(
                lambda p, pages: ouro.decode_step_paged(
                    p, ints(2), ints(2), ints(2, 4), pages, config, kernels)
            )(*shapes)
        return jax.make_jaxpr(
            lambda p, pages: ouro.prefill_into_pages(
                p, ints(1, 16), ints(4), pages, 11, config, kernels)
        )(*shapes)

    texts = [traced(ut_steps=1), traced(ut_steps=4),
             traced(n_layers=2), traced(n_layers=48)]
    counts = [_equations(text.jaxpr) for text in texts]
    assert len(set(counts)) == 1 and counts[0] > 100
    if program == "decode":
        assert all(str(text).count("pallas_call[") == 1 for text in texts)


# -- the engine ------------------------------------------------------------------


def _engine_model(features=None, **engine):
    """The toy behind `LlmEngineModel`; ``engine`` overrides
    `EngineConfig`'s sizes, ``features`` are the model's own arguments."""
    from client_tpu.llm.engine import EngineConfig
    from client_tpu.llm.serving import LlmEngineModel
    from client_tpu.models import ouro

    sizes = dict(block_size=8, num_blocks=1 + 4 * 16, max_active=4,
                 max_queue=8, max_seq_len=128, prefix_sharing=False)
    sizes.update(engine)
    return LlmEngineModel(
        name="ouro_toy", model=ouro.ENGINE_MODEL,
        config=ouro.OuroConfig.tiny(),
        engine_config=EngineConfig(**sizes), **(features or {}))


async def _generate(model, prompts, max_tokens):
    seqs = [model.engine.submit(p, max_tokens=max_tokens) for p in prompts]

    async def collect(seq):
        return [token async for token, _ in seq]

    return await asyncio.gather(*(collect(s) for s in seqs))


def _prompts(lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, size=n).tolist() for n in lengths]


#: a cached token of the toy over its 12 pairs (K and V of 4 heads of 16
#: in float32 a pair)
TOY_ROW = PAIRS * 2 * 4 * 16 * 4


def test_engine_serves_one_pool_pair_at_four_lanes():
    """Five sequences through `LlmEngineModel` over four lanes: one full
    group of ONE storing layer whose pools hold all twelve pairs; the
    tile is the kernel's at the pools' shapes, the row bytes a token's
    over all pairs; greedy tokens are the reference's best on the same
    weights (the fifth sequence takes blocks the first four gave back),
    and everything is given back at the end."""
    from client_tpu.models import ouro, paged_attention as pa
    from client_tpu.models.engine_model import FULL

    model = _engine_model()
    model.warmup()
    try:
        engine = model.engine
        assert [(g.kind, g.layers) for g in engine.config.cache_groups] == [
            (FULL, (0,))]
        assert [len(pools) for pools in engine._pages] == [2, 0, 0]
        assert all(p.shape == (PAIRS * 65, 8, 4, 16)
                   for p in engine._pages[0])
        assert engine._tile_pages == (TILE_PAGES,)
        assert TILE_PAGES == pa.pages_per_tile(8 * 4, 1, 16, np.float32, 2)
        prompts = _prompts((30, 9, 17, 22, 5))
        served = asyncio.run(_generate(model, prompts, 40))
        stats = engine.stats()
        assert stats["kv_row_bytes_by_group"] == [
            {"stored": TOY_ROW, "counted": TOY_ROW}]
        assert stats["kv_blocks_in_use_by_group"] == [0]
        assert stats["completed"] == 5 and stats["preemptions"] == 0
        assert stats["loop_layer_passes"] == PAIRS * stats["steps"]
        assert stats["loop_kv_rows_read"] == PAIRS * stats["attn_tokens_full"]
        params = _to32(model._params)
        model_dict = {**TOY,
                      "num_hidden_layers": ouro.OuroConfig.tiny().n_layers}
        for prompt, tokens in zip(prompts, served):
            logits = _reference_logits(params, prompt + tokens, model_dict)
            at = logits[len(prompt) - 1: len(prompt) + len(tokens) - 1]
            gap = at.max(axis=-1) - at[np.arange(len(tokens)), tokens]
            assert gap.max() <= TOLERANCE
    finally:
        model.shutdown()


def test_preempt_and_resume_is_token_identical_at_four_lanes():
    """A pool too small for four growing sequences: victims give their
    blocks back, wait, and are re-prefilled over prompt and generated
    tokens, ALL twelve pairs rewritten through the new table; every
    stream is what it is on an engine that never preempts."""
    prompts = _prompts((30, 9, 17, 22))
    roomy = _engine_model()
    roomy.warmup()
    tight = _engine_model(num_blocks=1 + 16)
    tight.warmup()
    try:
        undisturbed = asyncio.run(_generate(roomy, prompts, 40))
        resumed = asyncio.run(_generate(tight, prompts, 40))
        assert tight.engine.stats()["preemptions"] >= 1
        assert roomy.engine.stats()["preemptions"] == 0
        assert resumed == undisturbed
        assert tight.engine.stats()["kv_blocks_in_use_by_group"] == [0]
    finally:
        roomy.shutdown()
        tight.shutdown()


@pytest.mark.parametrize("features,engine,part", [
    (dict(speculation={"mode": "ngram", "k": 2}), {}, "verify"),
    ({}, dict(prefix_sharing=True), "prefill_suffix"),
    (dict(tp=2), {}, "param_specs"),
])
def test_ouro_is_refused_the_features_it_has_no_part_for(
        features, engine, part):
    from client_tpu.utils import InferenceServerException

    model = _engine_model(features, **engine)
    with pytest.raises(InferenceServerException, match=f"[`']{part}[`']"):
        model.warmup()


def test_the_config_declares_one_storing_layer_at_the_published_sizes():
    """192 (pass, layer) pairs in one pool pair: a cached token takes
    1,572,864 B, 337 blocks 8.48 GB; 2,667,974,657 parameters, every
    per-layer tensor stacked 48 deep; the benchmark's configuration file
    builds this config; nothing is allocated (`jax.eval_shape`)."""
    import json
    import os

    import jax

    from benchmark.lib import bytes_ops_ouro, weights_ouro
    from benchmark.lib.serving_ouro import ouro_config
    from client_tpu.models import ouro
    from client_tpu.models.engine_model import FULL

    config = ouro.OuroConfig(max_seq_len=512)
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "ouro_2_6b", "config.json")) as f:
        stated = json.load(f)
    assert ouro_config(stated["model"]) == config
    (group,) = ouro.cache_groups(config)
    assert (group.kind, group.layers) == (FULL, (0,))
    assert config.cache_pairs == 192
    assert ouro.kv_row_bytes(config) == [(1_572_864, 1_572_864)]
    assert bytes_ops_ouro.kv_bytes_per_token(stated["model"]) == 1_572_864
    pages = jax.eval_shape(lambda: ouro.init_pages(config, [337], 16))
    assert [p.shape for p in pages[0]] == [(192 * 337, 16, 16, 128)] * 2
    assert all(pages[i] == () for i in range(1, 48))
    assert sum(p.size * p.dtype.itemsize for p in pages[0]) == 8_480_882_688
    for init in (lambda: ouro.init_params(jax.random.PRNGKey(0), config),
                 lambda: weights_ouro.params(0, stated["model"])):
        shapes = jax.eval_shape(init)
        assert all(a.shape[0] == 48
                   for a in jax.tree_util.tree_leaves(shapes["layers"]))
        count = sum(int(np.prod(a.shape))
                    for a in jax.tree_util.tree_leaves(shapes))
        assert count == 2_667_974_657
        assert count == bytes_ops_ouro.model_params(stated["model"])
    # the program's own draw and the benchmark's have the same tree
    assert (jax.tree_util.tree_structure(jax.eval_shape(
        lambda: ouro.init_params(jax.random.PRNGKey(0), config)))
        == jax.tree_util.tree_structure(shapes))
    with pytest.raises(ValueError, match="passes"):
        ouro.OuroConfig(ut_steps=0)
