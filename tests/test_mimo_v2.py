"""MiMo-V2-Flash on the engine at a toy size, float32, on the CPU: the
program (`client_tpu/models/mimo_v2.py`, `models/moe.py`, the engine's
cache groups) against the plain reference the benchmark keeps
(`benchmark/lib/reference_mimo.py`), on seeded weights.

Tolerances. Everything is float32 and the two sides differ only in the
order of their sums (paged and chunked attention against one dense
softmax, grouped experts against a loop over experts): the logits, of
size about 4, came out within 3e-6 over four layers. ``TOLERANCE`` 1e-4
leaves that thirty times of room, and the smallest change any omission
below makes is a hundred times over it.
"""

import asyncio
import dataclasses

import numpy as np
import pytest

pytestmark = pytest.mark.llm

TOLERANCE = 1e-4
BLOCK, SEED = 8, 7
PROMPT, TOTAL = 21, 100  # a window of 24 over blocks of 8: a ring of 4
#                          blocks, wrapped nine times by position 100

TOY = dict(
    hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
    swa_num_attention_heads=8, swa_num_key_value_heads=4, head_dim=24,
    swa_head_dim=24, v_head_dim=16, swa_v_head_dim=16,
    partial_rotary_factor=0.334, hybrid_layer_pattern=[0, 1, 1, 0],
    moe_layer_freq=[0, 1, 1, 1], intermediate_size=128,
    moe_intermediate_size=32, n_routed_experts=16, experts_routed_over=16,
    experts_held_first=0, num_experts_per_tok=4, sliding_window=24,
    rope_theta=5e6, swa_rope_theta=1e4, attention_value_scale=0.707,
    layernorm_epsilon=1e-5, max_position_embeddings=128, vocab_size=256,
    num_hidden_layers=4, n_shared_experts=None, routed_scaling_factor=None,
    n_group=1, topk_group=1, scoring_func="sigmoid", norm_topk_prob=True,
    add_full_attention_sink_bias=False, add_swa_attention_sink_bias=True,
)


def _kernels(name):
    from client_tpu.models import paged_attention
    from client_tpu.models.engine_model import Kernels

    return Kernels(*paged_attention.resolve_decode_attention(name, "cpu"))


@pytest.fixture(scope="module", params=["fused_xla", "pallas_interpret"])
def toy(request):
    """(program config, float32 params, token ids, the program's logits
    at positions PROMPT-1 .. TOTAL-1: one prefill, then decode steps
    through both cache groups with the engine's own window tables), once
    on the plain XLA path and once through both Pallas kernels."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import weights_mimo
    from benchmark.lib.serving_mimo import mimo_config
    from client_tpu.llm import kv_cache
    from client_tpu.models import mimo_v2

    kernels = _kernels(request.param)

    config = dataclasses.replace(mimo_config(TOY), dtype=jnp.float32)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), weights_mimo.params(SEED, TOY))
    tokens = np.random.default_rng(0).integers(1, 256, size=TOTAL)
    ring_blocks = kv_cache.window_ring_blocks(TOY["sliding_window"], BLOCK)
    width = TOY["max_position_embeddings"] // BLOCK
    pages = mimo_v2.init_pages(config, [1 + width, 1 + ring_blocks], BLOCK)
    full = np.zeros(width, np.int32)
    full[: -(-TOTAL // BLOCK)] = 1 + np.arange(-(-TOTAL // BLOCK))
    ring = [list(1 + np.arange(ring_blocks))]

    def tables(position):
        return np.stack([full, kv_cache.window_tables(
            ring, [position // BLOCK], width)[0]])

    padded = np.zeros((1, 32), np.int32)
    padded[0, :PROMPT] = tokens[:PROMPT]
    logits, pages = jax.jit(
        lambda *a: mimo_v2.prefill_into_pages(*a, config, kernels)
    )(params, padded, tables(PROMPT - 1), pages, PROMPT - 1)
    rows = [np.asarray(logits[0])]
    decode = jax.jit(
        lambda *a: mimo_v2.decode_step_paged(*a, config, kernels))
    for position in range(PROMPT, TOTAL):
        logits, pages, _ = decode(
            params, tokens[position:position + 1].astype(np.int32),
            np.array([position], np.int32), tables(position)[:, None],
            pages)
        rows.append(np.asarray(logits[0]))
    return config, params, tokens, np.stack(rows)


def _reference_rows(params, tokens, model):
    from benchmark.lib import reference_mimo

    logits = reference_mimo.forward(
        tokens, params, params["layers"], model, (0, 16))
    return np.asarray(logits)[PROMPT - 1:]


def test_prefill_then_decode_through_both_groups_matches_reference(toy):
    _, params, tokens, served = toy
    ref = _reference_rows(params, tokens, TOY)
    assert np.abs(ref).max() > 1.0  # logits of a size worth comparing
    assert np.abs(served - ref).max() <= TOLERANCE


def _bias_weighs(h, w, model, held, control=False):
    """`reference_mimo.expert_layer` with the one fault that the
    correction bias weighs the experts it selected."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference_mimo

    scores = jax.nn.sigmoid(jnp.matmul(
        h, w["router"].astype(jnp.float32),
        precision=reference_mimo.HIGHEST)) + w["router_bias"]
    _, chosen = jax.lax.top_k(scores, int(model["num_experts_per_tok"]))
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = picked / picked.sum(axis=-1, keepdims=True)
    out = jnp.zeros_like(h)
    for local in range(held[1]):
        share = (weight * (chosen == held[0] + local)).sum(
            axis=-1, keepdims=True)
        out = out + share * reference_mimo._swiglu(
            h, *(w["experts"][n][local]
                 for n in ("w_gate", "w_up", "w_down")), False)
    return out


OMISSIONS = {
    "no sink": dict(add_swa_attention_sink_bias=False),
    "no value scale": dict(attention_value_scale=1.0),
    "rope on every size": dict(partial_rotary_factor=1.0),
    "one theta for both kinds": dict(swa_rope_theta=5e6),
    "window one short": dict(sliding_window=23),
    "window one long": dict(sliding_window=25),
    "bias as a weight": {},
}


@pytest.mark.parametrize("omission", OMISSIONS)
def test_each_omission_alone_fails_the_comparison(toy, omission, monkeypatch):
    """The comparison above is tight enough to tell: a reference with one
    part of the mathematics changed lies far outside the tolerance."""
    from benchmark.lib import reference_mimo

    _, params, tokens, served = toy
    if omission == "bias as a weight":
        monkeypatch.setattr(reference_mimo, "expert_layer", _bias_weighs)
    ref = _reference_rows(params, tokens, {**TOY, **OMISSIONS[omission]})
    assert np.abs(served - ref).max() > 100 * TOLERANCE


# the expert layer's paths: the plain XLA one, and under the Pallas choice
# the one the call's row count picks, or the planned one at every size
PATHS = {"fused_xla": "fused_xla", "resident": "pallas_interpret",
         "planned": "pallas_interpret"}


def _expert_layer(path, monkeypatch):
    """`moe.expert_layer` on ``path``. The planned path is steered here,
    in the test, to the short calls the row count would keep resident."""
    import functools

    from client_tpu.models import moe

    if path == "planned":
        monkeypatch.setattr(moe, "_RESIDENT_ROWS", 0)
    return functools.partial(moe.expert_layer, kernel=PATHS[path])


def _to32(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("shares", [16, 4, 1])
def test_shares_of_the_expert_layer_add_up_to_the_uncut_reference(
        shares, path, monkeypatch):
    """The share test: each of ``shares`` chips holds 16 / shares experts
    of a layer, routes over all 16 and computes its own experts' part;
    the parts add up to the uncut reference's layer output."""
    import jax.numpy as jnp

    from benchmark.lib import reference_mimo, weights_mimo
    from client_tpu.models import moe

    expert_layer = _expert_layer(path, monkeypatch)
    whole = _to32(weights_mimo.layer(SEED, 1, TOY, held_experts=(0, 16)))
    h = jnp.asarray(np.random.default_rng(1).normal(size=(40, 64)),
                    jnp.float32)
    ref = reference_mimo.expert_layer(h, whole, TOY, (0, 16))
    ids, weights = moe.route(h, whole["router"], whole["router_bias"], 4)
    count = 16 // shares
    total, pairs, resident = 0.0, 0, 0
    for share in range(shares):
        held = (share * count, count)
        mine = _to32(weights_mimo.layer(SEED, 1, TOY, held_experts=held))
        out, counters = expert_layer(h, ids, weights, mine["experts"], held)
        total = total + out
        pairs += int(counters[0])
        resident += int(counters[3])
    assert pairs == 40 * 4  # every pair lands on exactly one share
    assert resident == (shares if path == "resident" else 0)
    assert np.abs(np.asarray(ref)).max() > 0.1
    assert np.abs(np.asarray(total) - np.asarray(ref)).max() <= TOLERANCE


@pytest.mark.parametrize("tokens", [1, 5, 64, 128, 129])
def test_the_row_count_alone_picks_the_path_and_both_give_the_reference(
        tokens, monkeypatch):
    """Up to `moe._RESIDENT_ROWS` rows a call under the Pallas choice
    keeps its rows resident, a longer one is planned; either gives what
    the reference's loop over the held experts gives, and where both can
    run the same call they count the same pairs, experts and load."""
    import jax.numpy as jnp

    from benchmark.lib import reference_mimo, weights_mimo
    from client_tpu.models import moe

    held = (4, 8)
    mine = _to32(weights_mimo.layer(SEED, 2, TOY, held_experts=held))
    h = jnp.asarray(np.random.default_rng(tokens).normal(size=(tokens, 64)),
                    jnp.float32)
    ref = np.asarray(reference_mimo.expert_layer(h, mine, TOY, held))
    ids, weights = moe.route(h, mine["router"], mine["router_bias"], 4)
    args = (h, ids, weights, mine["experts"], held)
    out, counters = _expert_layer("resident", monkeypatch)(*args)
    assert int(counters[3]) == (tokens <= 128)
    assert np.abs(ref).max() > 0.05
    assert np.abs(np.asarray(out) - ref).max() <= TOLERANCE
    plain, plain_counters = _expert_layer("fused_xla", monkeypatch)(*args)
    assert np.abs(np.asarray(plain) - ref).max() <= TOLERANCE
    planned, planned_counters = _expert_layer("planned", monkeypatch)(*args)
    assert int(planned_counters[3]) == 0
    assert np.abs(np.asarray(planned) - ref).max() <= TOLERANCE
    for other in (plain_counters, planned_counters):
        assert np.asarray(other)[:3].tolist() == np.asarray(
            counters)[:3].tolist()
    on = (np.asarray(ids) >= 4) & (np.asarray(ids) < 12)
    load = np.bincount(np.asarray(ids)[on] - 4, minlength=8)
    assert np.asarray(counters)[:3].tolist() == [
        on.sum(), (load > 0).sum(), load.max()]


@pytest.mark.parametrize("path", ["resident", "planned"])
def test_an_expert_no_lane_chose_is_never_computed(path, monkeypatch):
    """The held experts no row chose are poisoned with NaN: the output
    stays finite and equal, so neither kernel ran them (a product with a
    zero weight would not have hidden a NaN)."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import weights_mimo

    held = (0, 16)
    mine = _to32(weights_mimo.layer(SEED, 1, TOY, held_experts=held))
    h = jnp.asarray(np.random.default_rng(5).normal(size=(3, 64)),
                    jnp.float32)
    # no row chose expert 0 (before the first chosen one), 3, 4, 7, 8 and
    # 10 to 12 (between two) nor 14 and 15 (after the last): the walk
    # names a block already there in each of the three ways
    ids = jnp.asarray([[1, 2, 5, 6], [2, 5, 9, 13], [1, 6, 9, 13]], jnp.int32)
    weights = jnp.asarray(
        np.random.default_rng(6).uniform(0.1, 0.4, size=(3, 4)), jnp.float32)
    chosen = np.zeros(16, bool)
    chosen[np.asarray(ids).reshape(-1)] = True
    poisoned = jax.tree_util.tree_map(
        lambda a: jnp.where(chosen[:, None, None], a, jnp.nan),
        mine["experts"])
    expert_layer = _expert_layer(path, monkeypatch)
    clean, _ = expert_layer(h, ids, weights, mine["experts"], held)
    out, counters = expert_layer(h, ids, weights, poisoned, held)
    assert int(counters[1]) == chosen.sum()
    assert np.isfinite(np.asarray(out)).all()
    assert np.abs(np.asarray(clean)).max() > 0.05
    assert (np.asarray(out) == np.asarray(clean)).all()


@pytest.mark.parametrize("path", PATHS)
def test_a_share_no_token_chose_gives_zero(path, monkeypatch):
    import jax.numpy as jnp

    from benchmark.lib import weights_mimo
    from client_tpu.models import moe, mimo_v2

    experts = weights_mimo.layer(SEED, 1, TOY, held_experts=(0, 4))["experts"]
    h = jnp.ones((5, 64), jnp.bfloat16)
    ids = jnp.asarray(np.random.default_rng(2).integers(4, 16, size=(5, 4)),
                      jnp.int32)
    out, counters = _expert_layer(path, monkeypatch)(
        h, ids, jnp.full((5, 4), 0.25), experts, (0, 4))
    assert out.shape == (5, 64) and not np.asarray(out).any()
    assert moe.COUNTERS[3] == "moe_resident_calls"
    assert np.asarray(counters).tolist() == [0, 0, 0, path == "resident"]
    with pytest.raises(ValueError, match="not a share"):
        mimo_v2.MimoV2Config.tiny(held=(12, 8))


# -- the engine's cache groups -------------------------------------------------


def _engine_model(**engine):
    from client_tpu.llm.engine import EngineConfig
    from client_tpu.llm.serving import LlmEngineModel
    from client_tpu.models import mimo_v2

    sizes = dict(block_size=BLOCK, num_blocks=1 + 2 * 16, max_active=2,
                 max_queue=8, max_seq_len=128, prefix_sharing=False)
    sizes.update(engine)
    return LlmEngineModel(
        name="mimo_toy", model=mimo_v2.ENGINE_MODEL,
        config=mimo_v2.MimoV2Config.tiny(), engine_config=EngineConfig(**sizes))


async def _generate(model, prompts, max_tokens, watch=None):
    seqs = [model.engine.submit(p, max_tokens=max_tokens) for p in prompts]

    async def collect(seq):
        out = []
        async for token, _ in seq:
            out.append(token)
            if watch is not None:
                watch(model.engine)
        return out

    return await asyncio.gather(*(collect(s) for s in seqs))


def test_engine_serves_both_groups_and_matches_the_reference():
    """Two sequences through `LlmEngineModel` over the seam: greedy tokens
    equal the reference's on the same weights, the window group never
    holds more than its ring a sequence, and its counters say what it
    did not hold."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference_mimo

    model = _engine_model()
    model.warmup()
    try:
        engine = model.engine
        ring = 4  # ceil(24 / 8) + 1
        seen = []

        def watch(e):
            in_use = e.stats()["kv_blocks_in_use_by_group"]
            seen.append(in_use)
            assert in_use[1] <= 2 * ring
            assert all(len(r) == ring for s in e._running for r in s.rings)

        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, 256, size=n).tolist() for n in (30, 9)]
        served = asyncio.run(_generate(model, prompts, 60, watch))
        stats = engine.stats()
        assert max(s[1] for s in seen) == 2 * ring
        assert max(s[0] for s in seen) > 2 * ring  # the full group grows
        assert stats["kv_blocks_in_use_by_group"] == [0, 0]  # all returned
        assert stats["window_blocks_whole"] > stats["window_blocks_unheld"] > 0
        assert stats["moe_pairs"] > 0 and stats["moe_load_max"] > 0
        # off the TPU the load-time choice is the plain XLA path
        assert stats["moe_resident_calls"] == 0
        assert 0 < stats["moe_experts_touched"] <= 16 * 3 * stats["steps"]
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), model._params)
        for prompt, tokens in zip(prompts, served):
            logits = np.asarray(reference_mimo.forward(
                prompt + tokens, params, params["layers"], TOY, (0, 16)))
            at = logits[len(prompt) - 1: len(prompt) + len(tokens) - 1]
            gap = at.max(axis=-1) - at[np.arange(len(tokens)), tokens]
            assert gap.max() <= TOLERANCE  # the served token is the best
    finally:
        model.shutdown()


def test_preemption_and_release_return_both_groups_blocks():
    """A full pool too small for two long sequences: one is preempted,
    both groups' blocks come back, and it resumes by re-prefill to the
    same tokens a roomy engine serves."""
    tight = _engine_model(num_blocks=1 + 14)
    roomy = _engine_model()
    tight.warmup()
    roomy.warmup()
    try:
        rng = np.random.default_rng(4)
        prompts = [rng.integers(1, 256, size=n).tolist() for n in (40, 36)]
        low = []
        served = asyncio.run(_generate(
            tight, prompts, 40,
            lambda e: low.append(e.stats()["kv_blocks_in_use_by_group"][1])))
        assert tight.engine.stats()["preemptions"] >= 1
        assert min(low) <= 4  # a preempted sequence's ring came back
        assert tight.engine.stats()["kv_blocks_in_use_by_group"] == [0, 0]
        assert served == asyncio.run(_generate(roomy, prompts, 40))

        async def cancel():
            seq = roomy.engine.submit(prompts[0], max_tokens=50)
            async for _ in seq:
                break
            roomy.engine.release(seq)
            for _ in range(20):
                await asyncio.sleep(0.01)
            return roomy.engine.stats()["kv_blocks_in_use_by_group"]

        assert asyncio.run(cancel()) == [0, 0]
    finally:
        tight.shutdown()
        roomy.shutdown()


def test_window_pool_holds_a_ring_for_each_of_max_active():
    """A window group's pool is worked out, not set: the trash block and
    a ring for each of ``max_active`` sequences. With more sequences
    waiting than lanes, every ring is out and none is ever missing."""
    model = _engine_model(max_active=2)
    assert model.engine_config.group_num_blocks() == [1 + 2 * 16]
    model.warmup()
    try:
        ring = 4  # ceil(24 / 8) + 1
        assert model.engine_config.group_num_blocks() == [
            1 + 2 * 16, 1 + 2 * ring]
        seen = []
        served = asyncio.run(_generate(
            model, [[1, 2, 3], [4, 5, 6], [7, 8, 9]], 12,
            lambda e: seen.append(
                (len(e._running),
                 e.stats()["kv_blocks_in_use_by_group"][1]))))
        assert all(len(s) == 12 for s in served)
        assert max(n for n, _ in seen) == 2
        assert max(held for _, held in seen) == 2 * ring
        assert model.engine.stats()["kv_blocks_in_use_by_group"] == [0, 0]
    finally:
        model.shutdown()


@pytest.mark.parametrize("feature,part", [
    (dict(speculation={"mode": "ngram", "k": 2}), "verify"),
    (dict(engine=dict(prefix_sharing=True)), "prefill_suffix"),
    (dict(tp=2), "param_specs"),
])
def test_a_model_without_the_part_is_refused_the_feature_at_load(
        feature, part):
    from client_tpu.llm.engine import EngineConfig
    from client_tpu.llm.serving import LlmEngineModel
    from client_tpu.models import mimo_v2
    from client_tpu.utils import InferenceServerException

    sizes = dict(block_size=BLOCK, num_blocks=33, max_active=2,
                 max_seq_len=128, prefix_sharing=False)
    feature = dict(feature)
    sizes.update(feature.pop("engine", {}))
    model = LlmEngineModel(
        name="mimo_toy", model=mimo_v2.ENGINE_MODEL,
        config=mimo_v2.MimoV2Config.tiny(),
        engine_config=EngineConfig(**sizes), **feature)
    with pytest.raises(InferenceServerException, match=f"[`']{part}[`']"):
        model.warmup()


def test_window_tables_hold_the_ring_at_the_last_columns_only():
    from client_tpu.llm import kv_cache

    assert kv_cache.window_ring_blocks(128, 16) == 9
    assert kv_cache.window_ring_blocks(24, 8) == 4
    # a ring is a whole number of the allocator's runs: tiles of 4 pages
    # in the cell's window pools, so 12 blocks and none wraps in a tile
    assert kv_cache.window_ring_blocks(128, 16, 4) == 12
    assert kv_cache.window_ring_blocks(24, 8, 4) == 4
    tables = kv_cache.window_tables([[5, 6, 7], [8, 9, 10]], [4, 1], 8)
    # lane 0 at block 4: columns 2..4 hold ring entries 2, 0, 1
    assert tables[0].tolist() == [0, 0, 7, 5, 6, 0, 0, 0]
    # lane 1 at block 1: only blocks 0 and 1 exist yet
    assert tables[1].tolist() == [8, 9, 0, 0, 0, 0, 0, 0]
    # a table narrower than the newest block's column drops that column
    assert kv_cache.window_tables([[5, 6, 7]], [4], 4)[0].tolist() == [0, 0, 7, 5]


def test_llama_declares_one_full_group_and_serves_as_before():
    from client_tpu.llm.engine import EngineConfig
    from client_tpu.models import llama
    from client_tpu.models.engine_model import FULL

    config = llama.LlamaConfig.tiny()
    (group,) = llama.ENGINE_MODEL.cache_groups(config)
    assert group.kind == FULL and group.layers == (0, 1)
    assert group.window is None
    assert llama.ENGINE_MODEL.missing_for(
        speculation=True, prefix_sharing=True, tp=4) is None
    sizes = EngineConfig(num_blocks=65, cache_groups=(group,))
    assert sizes.group_num_blocks() == [65]
    # the full pool is what was set, whatever the kernel's tile
    assert sizes.group_num_blocks((8,)) == [65]
    assert sizes.group_runs((8,)) == [8] and sizes.group_runs() == [1]
