"""Trinity-Mini's decoder (``afmoe``) on the engine at a toy size,
float32, on the CPU: the program (`client_tpu/models/afmoe.py`,
`models/moe.py`, the engine's cache groups) against the plain reference
the benchmark keeps (`benchmark/lib/reference_afmoe.py`), on seeded
weights.

Tolerances. Everything is float32 and the two sides differ only in the
order of their sums (paged and chunked attention against one softmax a
block of queries, resident or grouped experts against a loop over
experts): the logits, of size about 4, came out within 5e-6 over six
layers. ``TOLERANCE`` 1e-4 leaves that twenty times of room; the
smallest change any departure left out below makes is 1.2, ten thousand
times over it, and the same program computed in bf16 lies at 0.64, six
thousand times over it.
"""

import asyncio
import dataclasses

import numpy as np
import pytest

pytestmark = pytest.mark.llm

TOLERANCE = 1e-4
BLOCK, SEED = 8, 11
PROMPT, TOTAL = 21, 100  # a window of 24 over blocks of 8: a ring of 4
#                          blocks; the window wraps at 24, the ring at 32

TOY = dict(
    hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
    head_dim=16, intermediate_size=128, moe_intermediate_size=32,
    num_hidden_layers=6, num_dense_layers=2,
    layer_types=["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention", "full_attention"],
    num_experts=16, experts_routed_over=16, experts_held_first=0,
    num_experts_per_tok=4, num_shared_experts=1, route_norm=True,
    route_scale=2.826, score_func="sigmoid", sliding_window=24,
    rope_theta=1e4, rope_scaling=None, rms_norm_eps=1e-5,
    max_position_embeddings=128, vocab_size=256, mup_enabled=True,
    tie_word_embeddings=False, hidden_act="silu", n_group=1, topk_group=1,
    num_expert_groups=1, num_limited_groups=1,
)


def _kernels(name):
    from client_tpu.models import paged_attention
    from client_tpu.models.engine_model import Kernels

    return Kernels(*paged_attention.resolve_decode_attention(name, "cpu"))


def _to32(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _served_rows(kernel_name, dtype=None):
    """(float32 params, token ids, the program's logits at positions
    PROMPT-1 .. TOTAL-1): one prefill, then decode steps through both
    cache groups with the engine's own window tables."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import weights_afmoe
    from benchmark.lib.serving_afmoe import afmoe_config
    from client_tpu.llm import kv_cache
    from client_tpu.models import afmoe

    kernels = _kernels(kernel_name)
    config = dataclasses.replace(afmoe_config(TOY), dtype=dtype or jnp.float32)
    params = weights_afmoe.params(SEED, TOY)
    served = params if dtype is not None else _to32(params)
    tokens = np.random.default_rng(0).integers(1, 256, size=TOTAL)
    ring_blocks = kv_cache.window_ring_blocks(TOY["sliding_window"], BLOCK)
    width = TOY["max_position_embeddings"] // BLOCK
    pages = afmoe.init_pages(config, [1 + width, 1 + ring_blocks], BLOCK)
    full = np.zeros(width, np.int32)
    full[: -(-TOTAL // BLOCK)] = 1 + np.arange(-(-TOTAL // BLOCK))
    ring = [list(1 + np.arange(ring_blocks))]

    def tables(position):
        return np.stack([full, kv_cache.window_tables(
            ring, [position // BLOCK], width)[0]])

    padded = np.zeros((1, 32), np.int32)
    padded[0, :PROMPT] = tokens[:PROMPT]
    logits, pages = jax.jit(
        lambda *a: afmoe.prefill_into_pages(*a, config, kernels)
    )(served, padded, tables(PROMPT - 1), pages, PROMPT - 1)
    rows = [np.asarray(logits[0])]
    decode = jax.jit(
        lambda *a: afmoe.decode_step_paged(*a, config, kernels))
    for position in range(PROMPT, TOTAL):
        logits, pages, _ = decode(
            served, tokens[position:position + 1].astype(np.int32),
            np.array([position], np.int32), tables(position)[:, None],
            pages)
        rows.append(np.asarray(logits[0]))
    return _to32(params), tokens, np.stack(rows)


@pytest.fixture(scope="module", params=["fused_xla", "pallas_interpret"])
def toy(request):
    """Once on the plain XLA path and once through both Pallas kernels."""
    return _served_rows(request.param)


def _reference_rows(params, tokens, model):
    from benchmark.lib import reference_afmoe

    logits = reference_afmoe.forward(
        tokens, params, params["layers"], model, (0, 16))
    return np.asarray(logits)[PROMPT - 1:]


def test_prefill_then_decode_through_both_groups_matches_reference(toy):
    params, tokens, served = toy
    ref = _reference_rows(params, tokens, TOY)
    assert np.abs(ref).max() > 1.0  # logits of a size worth comparing
    assert np.abs(served - ref).max() <= TOLERANCE


def test_the_kernel_choices_agree_and_bf16_would_not_pass():
    """The plain XLA path and the Pallas kernels under the interpreter
    give the same logits (the third choice, ``pallas``, is Mosaic's and
    is held against XLA on the chip, `tests/test_tpu_platform.py`); the
    same program computed in bf16 lies far outside the tolerance, so a
    lower precision than the configuration states cannot pass."""
    import jax.numpy as jnp

    params, tokens, plain = _served_rows("fused_xla")
    _, _, kernels = _served_rows("pallas_interpret")
    assert np.abs(plain - kernels).max() <= TOLERANCE
    _, _, rounded = _served_rows("fused_xla", dtype=jnp.bfloat16)
    ref = _reference_rows(params, tokens, TOY)
    assert np.abs(rounded - ref).max() > 100 * TOLERANCE


# -- one case a departure: the reference with it left out is far away ----------


def _skip_norm(names):
    """`reference_afmoe._norm` that passes ``x`` through where the scale
    is one of the layer's ``names`` (found by identity in ``layers``)."""
    def patch(monkeypatch, params):
        from benchmark.lib import reference_afmoe

        skipped = {id(layer[name]) for layer in params["layers"]
                   for name in names}
        normed = reference_afmoe._norm
        monkeypatch.setattr(
            reference_afmoe, "_norm",
            lambda x, scale, model: x if id(scale) in skipped
            else normed(x, scale, model))
    return patch


def _rope_everywhere(monkeypatch, params):
    from benchmark.lib import reference_afmoe

    turn = reference_afmoe.position_signal
    monkeypatch.setattr(
        reference_afmoe, "position_signal",
        lambda q, k, model, window: turn(q, k, model, True))


def _no_gate(monkeypatch, params):
    import jax.numpy as jnp

    from benchmark.lib import reference_afmoe

    monkeypatch.setattr(
        reference_afmoe, "output_gate",
        lambda a, w, control=False: jnp.ones(()))


def _bias_weighs(monkeypatch, params):
    """The one fault that the selection bias weighs the experts."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference_afmoe

    def route(h, w, model, held):
        scores = jax.nn.sigmoid(jnp.matmul(
            h, w["router"].astype(jnp.float32),
            precision=reference_afmoe.HIGHEST)) + w["router_bias"]
        _, chosen = jax.lax.top_k(scores, int(model["num_experts_per_tok"]))
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        weight = (float(model["route_scale"]) * picked
                  / picked.sum(axis=-1, keepdims=True))
        return chosen, weight, jnp.zeros(h.shape[0])

    monkeypatch.setattr(reference_afmoe, "route", route)


def _no_bias(monkeypatch, params):
    for layer in params["layers"]:
        if "router_bias" in layer:
            layer["router_bias"] = layer["router_bias"] * 0


# (changes to the model's keys, a patch of the reference or None)
DEPARTURES = {
    "no QK norm": ({}, _skip_norm(("q_norm", "k_norm"))),
    "rope in full layers too": ({}, _rope_everywhere),
    "no output gate": ({}, _no_gate),
    "no post-attention norm": ({}, _skip_norm(("post_attn_norm",))),
    "no post-MLP norm": ({}, _skip_norm(("post_mlp_norm",))),
    "no embedding scale": (dict(mup_enabled=False), None),
    "no shared expert": (dict(num_shared_experts=0), None),
    "no route_scale": (dict(route_scale=1.0), None),
    "bias left out": ({}, _no_bias),
    "bias as a weight": ({}, _bias_weighs),
    "window one short": (dict(sliding_window=23), None),
    "window one long": (dict(sliding_window=25), None),
}


@pytest.mark.parametrize("departure", DEPARTURES)
def test_each_departure_left_out_fails_the_comparison(
        toy, departure, monkeypatch):
    """The comparison above is tight enough to tell: a reference with one
    part of the mathematics left out (which is the program with it left
    out, seen from the other side) lies far outside the tolerance."""
    params, tokens, served = toy
    keys, patch = DEPARTURES[departure]
    params = {**params, "layers": [dict(l) for l in params["layers"]]}
    if patch is not None:
        patch(monkeypatch, params)
    ref = _reference_rows(params, tokens, {**TOY, **keys})
    assert np.abs(served - ref).max() > 100 * TOLERANCE


# -- the expert layer: shares, the shared expert counted once ------------------

PATHS = {"fused_xla": "fused_xla", "resident": "pallas_interpret",
         "planned": "pallas_interpret"}


def _expert_layer(path, monkeypatch):
    import functools

    from client_tpu.models import moe

    if path == "planned":
        monkeypatch.setattr(moe, "_RESIDENT_ROWS", 0)
    return functools.partial(moe.expert_layer, kernel=PATHS[path])


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("shares", [8, 2, 1])
def test_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(
        shares, path, monkeypatch):
    """Each of ``shares`` chips holds 16 / shares experts of a layer,
    routes over all 16 with ``route_scale`` and computes its own
    experts' part AND the shared expert whole; the routed parts, with
    the shared expert counted once, add up to the uncut reference's
    layer output."""
    import jax.numpy as jnp

    from benchmark.lib import reference_afmoe, weights_afmoe
    from client_tpu.models import moe

    expert_layer = _expert_layer(path, monkeypatch)
    whole = _to32(weights_afmoe.layer(SEED, 2, TOY, held_experts=(0, 16)))
    h = jnp.asarray(np.random.default_rng(1).normal(size=(40, 64)),
                    jnp.float32)
    ref = np.asarray(reference_afmoe.expert_layer(h, whole, TOY, (0, 16)))
    ids, weights = moe.route(h, whole["router"], whole["router_bias"], 4,
                             scale=TOY["route_scale"])
    assert abs(float(weights.sum(axis=-1).mean()) - 2.826) < 1e-4
    shared = np.asarray(moe.shared_expert(h, whole["shared"]))
    count = 16 // shares
    total, pairs = 0.0, 0
    for share in range(shares):
        held = (share * count, count)
        mine = _to32(weights_afmoe.layer(SEED, 2, TOY, held_experts=held))
        assert (np.asarray(mine["shared"]["w_up"])
                == np.asarray(whole["shared"]["w_up"])).all()
        out, counters = expert_layer(
            h, ids, weights, mine["experts"], held, shared=mine["shared"])
        routed, _ = expert_layer(h, ids, weights, mine["experts"], held)
        # a share's output holds the shared expert whole
        assert np.abs(np.asarray(out) - np.asarray(routed) - shared).max() \
            <= TOLERANCE
        total = total + np.asarray(out)
        pairs += int(counters[0])
    assert pairs == 40 * 4  # every pair lands on exactly one share
    assert np.abs(shared).max() > 0.1 and np.abs(ref).max() > 0.1
    # counted once: the other shares' copies of the shared expert go
    total = total - (shares - 1) * shared
    assert np.abs(total - ref).max() <= TOLERANCE


def test_route_scale_of_one_leaves_the_router_as_it_was():
    """`moe.route` at ``scale`` 1 is the function `mimo_v2` has always
    called: the same values, and weights that sum to one."""
    import jax.numpy as jnp

    from benchmark.lib import weights_afmoe
    from client_tpu.models import moe

    w = _to32(weights_afmoe.layer(SEED, 3, TOY))
    h = jnp.asarray(np.random.default_rng(2).normal(size=(9, 64)), jnp.float32)
    ids, weights = moe.route(h, w["router"], w["router_bias"], 4)
    scaled_ids, scaled = moe.route(h, w["router"], w["router_bias"], 4,
                                   scale=2.826)
    assert (np.asarray(ids) == np.asarray(scaled_ids)).all()
    assert np.abs(np.asarray(weights).sum(axis=-1) - 1).max() < 1e-6
    assert np.abs(np.asarray(scaled) - 2.826 * np.asarray(weights)).max() < 1e-6


# -- the engine ------------------------------------------------------------------


def _engine_model(features=None, **engine):
    """The toy behind `LlmEngineModel`; ``engine`` overrides
    `EngineConfig`'s sizes, ``features`` are the model's own arguments."""
    from client_tpu.llm.engine import EngineConfig
    from client_tpu.llm.serving import LlmEngineModel
    from client_tpu.models import afmoe

    sizes = dict(block_size=BLOCK, num_blocks=1 + 2 * 16, max_active=2,
                 max_queue=8, max_seq_len=128, prefix_sharing=False)
    sizes.update(engine)
    return LlmEngineModel(
        name="afmoe_toy", model=afmoe.ENGINE_MODEL,
        config=afmoe.AfmoeConfig.tiny(), engine_config=EngineConfig(**sizes),
        **(features or {}))


async def _generate(model, prompts, max_tokens):
    seqs = [model.engine.submit(p, max_tokens=max_tokens) for p in prompts]

    async def collect(seq):
        return [token async for token, _ in seq]

    return await asyncio.gather(*(collect(s) for s in seqs))


def test_engine_serves_the_model_and_counts_what_attention_reads():
    """Two sequences through `LlmEngineModel` over the seam: greedy
    tokens equal the reference's on the same weights, and
    ``attn_tokens_full`` / ``attn_tokens_window`` are the contexts the
    decode steps attended over, whole and as far as the window reaches."""
    from benchmark.lib import reference_afmoe

    model = _engine_model()
    model.warmup()
    try:
        rng = np.random.default_rng(3)
        lengths, out = (30, 9), 60
        prompts = [rng.integers(1, 256, size=n).tolist() for n in lengths]
        served = asyncio.run(_generate(model, prompts, out))
        stats = model.engine.stats()
        assert stats["kv_blocks_in_use_by_group"] == [0, 0]
        assert stats["window_blocks_whole"] > stats["window_blocks_unheld"] > 0
        assert stats["moe_pairs"] > 0 and stats["moe_resident_calls"] == 0
        # a sequence's decode steps read contexts prompt+1 .. prompt+out-1;
        # a step that ran ahead of a finished lane is counted as well
        full = sum(sum(range(n + 1, n + out)) for n in lengths)
        window = sum(sum(min(c, 24) for c in range(n + 1, n + out))
                     for n in lengths)
        assert full <= stats["attn_tokens_full"] <= full + 2 * (128 + 1)
        assert window <= stats["attn_tokens_window"] <= window + 2 * 24
        params = _to32(model._params)
        # `AfmoeConfig.tiny()` is TOY's shape
        for prompt, tokens in zip(prompts, served):
            logits = np.asarray(reference_afmoe.forward(
                prompt + tokens, params, params["layers"], TOY, (0, 16)))
            at = logits[len(prompt) - 1: len(prompt) + len(tokens) - 1]
            gap = at.max(axis=-1) - at[np.arange(len(tokens)), tokens]
            assert gap.max() <= TOLERANCE  # the served token is the best
    finally:
        model.shutdown()


def test_a_one_group_model_counts_full_attention_only():
    from client_tpu.llm.engine import EngineConfig
    from client_tpu.llm.serving import LlmEngineModel

    model = LlmEngineModel(engine_config=EngineConfig(
        block_size=BLOCK, num_blocks=33, max_active=2, max_seq_len=128))
    model.warmup()
    try:
        asyncio.run(_generate(model, [[1, 2, 3, 4, 5]], 6))
        stats = model.engine.stats()
        assert stats["attn_tokens_full"] >= sum(range(6, 11))
        assert stats["attn_tokens_window"] == 0
    finally:
        model.shutdown()


@pytest.mark.parametrize("sizes,message", [
    (dict(num_blocks=1 + 5), r"num_blocks=6 holds 5 blocks.*fewer "
     r"than the 6 that max_active=2 windows of 24 tokens"),
    (dict(max_seq_len=8), r"max_seq_len=8 gives a page table of 1 "
     r"columns.*a window of 24 tokens \(a ring of 4 blocks"),
])
def test_sizes_a_window_group_cannot_work_under_are_refused_at_load(
        sizes, message):
    """With the numbers, at load: not at the first long request."""
    model = _engine_model(**sizes)
    with pytest.raises(ValueError, match=message):
        model.warmup()
    assert model.engine is None


def test_sizes_that_just_hold_the_windows_are_served():
    from client_tpu.llm.engine import EngineConfig
    from client_tpu.models import afmoe

    groups = tuple(afmoe.cache_groups(afmoe.AfmoeConfig.tiny()))
    sizes = EngineConfig(block_size=BLOCK, num_blocks=1 + 6, max_active=2,
                         max_seq_len=24, cache_groups=groups)
    assert sizes.group_num_blocks() == [7, 1 + 2 * 4]
    # the cell's: 64 lanes, a window of 2,048 over blocks of 16
    cell = EngineConfig(
        block_size=16, num_blocks=20481, max_active=64, max_seq_len=8192,
        cache_groups=tuple(afmoe.cache_groups(afmoe.AfmoeConfig())))
    assert cell.group_num_blocks() == [20481, 1 + 64 * 129]
    assert cell.max_blocks_per_seq == 512
    # told the kernel's tiles (16 pages in both groups' pools) the ring
    # is 144 blocks, nine whole tiles, and the checks still reckon with
    # the window's own 128
    assert cell.group_runs((16, 16)) == [16, 16]
    assert cell.group_num_blocks((16, 16)) == [20481, 1 + 64 * 144]
    tight = EngineConfig(
        block_size=16, num_blocks=1 + 64 * 128, max_active=64,
        max_seq_len=8192, cache_groups=cell.cache_groups)
    assert tight.group_num_blocks((16, 16)) == [8193, 9217]


@pytest.mark.parametrize("features,engine,part", [
    (dict(speculation={"mode": "ngram", "k": 2}), {}, "verify"),
    ({}, dict(prefix_sharing=True), "prefill_suffix"),
    (dict(tp=2), {}, "param_specs"),
])
def test_afmoe_is_refused_the_features_it_has_no_part_for(
        features, engine, part):
    from client_tpu.utils import InferenceServerException

    model = _engine_model(features, **engine)
    with pytest.raises(InferenceServerException, match=f"[`']{part}[`']"):
        model.warmup()


def test_the_config_declares_its_groups_and_refuses_a_wrong_share():
    from client_tpu.models import afmoe
    from client_tpu.models.engine_model import FULL, WINDOW

    config = afmoe.AfmoeConfig(layer_kinds=(1, 1, 1, 0) * 4, held=(0, 16))
    full, window = afmoe.cache_groups(config)
    assert (full.kind, full.layers, full.window) == (FULL, (3, 7, 11, 15), None)
    assert window.kind == WINDOW and window.window == 2048
    assert window.layers == (0, 1, 2, 4, 5, 6, 8, 9, 10, 12, 13, 14)
    assert abs(config.embed_scale - 2048 ** 0.5) < 1e-9
    with pytest.raises(ValueError, match="not a share"):
        afmoe.AfmoeConfig.tiny(held=(12, 8))
    with pytest.raises(ValueError, match="n_dense_layers"):
        afmoe.AfmoeConfig.tiny(n_dense_layers=7)
