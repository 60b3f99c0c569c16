"""GigaChat3.1-702B-A36B's decoder (``deepseek_v3``) on the engine at a
toy size, float32, on the CPU: the program
(`client_tpu/models/deepseek_v3.py`, `models/moe.py`, the one-pool call
of `models/paged_attention.py`) against the plain reference the
benchmark keeps (`benchmark/lib/reference_dsv3.py`), on seeded weights.

Tolerances. Everything is float32 and the two sides differ in the order
of their sums and in the FORM of the attention (the reference expands
every cached latent to per-head keys and values; the program's decode
absorbs the two up-projections into the query and the output and
attends over the latent itself, through paged tiles): the logits, of
size about 4, came out within 4e-6 over three layers. ``TOLERANCE`` 1e-4
leaves that twenty-five times of room; the smallest change any departure
left out below makes is 100 times over it, and the same program computed
in bf16 lies hundreds of times over it.
"""

import asyncio
import dataclasses
import functools

import numpy as np
import pytest

pytestmark = pytest.mark.llm

TOLERANCE = 1e-4
BLOCK, SEED = 8, 11
#: pages a tile holds once the module's fixture has cut the kernel's
#: budget: 32 tokens, so contexts to 100 walk four tiles
TILE_PAGES = 4

TOY = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=24, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=3, first_k_dense_replace=1,
    n_routed_experts=16, experts_routed_over=16, experts_held_first=0,
    num_experts_per_tok=4, n_shared_experts=1, n_group=4, topk_group=2,
    norm_topk_prob=True, routed_scaling_factor=2.5, scoring_func="sigmoid",
    topk_method="noaux_tc", rope_theta=100.0,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=8, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=512,
                      rope_type="yarn"),
    rms_norm_eps=1e-6, max_position_embeddings=128, vocab_size=256,
    tie_word_embeddings=False, attention_bias=False, hidden_act="silu",
    moe_layer_freq=1, num_nextn_predict_layers=0,
)

#: (prompt, total) of the lanes one decode batch holds, ragged
LANES = ((21, 61), (5, 45), (60, 100))


@pytest.fixture(scope="module", autouse=True)
def small_tiles():
    """Tiles of :data:`TILE_PAGES` pages for every kernel call of this
    file at blocks of :data:`BLOCK` (the toy's rows are 128 float32
    wide: a page is 4 KiB), so that its contexts lie over several tiles;
    the columns a one-pool tile is lengthened to shrink with the budget
    (32 here: the same 4 pages).
    The kernel is jitted: the cut holds for shapes first traced under
    it, which are this file's alone."""
    from client_tpu.models import paged_attention as pa

    budget = pa._KV_VMEM_BUDGET
    pa._KV_VMEM_BUDGET = 2 * TILE_PAGES * BLOCK * 128 * 4
    assert pa.pages_per_tile(BLOCK, 1, 128, np.float32, 1) == TILE_PAGES
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

    from benchmark.lib.serving_dsv3 import dsv3_config

    return dataclasses.replace(
        dsv3_config({**TOY, **keys}), dtype=dtype or jnp.float32)


def _tables(rng):
    """A page table a lane: lane 0's pages side by side in the pool
    (whole tiles, one copy each), the others' shuffled (page by page)."""
    width = TOY["max_position_embeddings"] // BLOCK
    tables = 1 + np.arange(len(LANES) * width).reshape(len(LANES), width)
    for lane in range(1, len(LANES)):
        tables[lane] = rng.permutation(tables[lane])
    return tables.astype(np.int32)


@functools.lru_cache(maxsize=None)
def _served_rows(kernel_name, dtype=None):
    """(float32 params, each lane's token ids, each lane's logits from
    its prompt's last position on): a prefill a lane, then decode steps
    of all lanes at once, each at its own position. Computed once a
    kernel choice and precision; nobody writes into what it returns."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import weights_dsv3
    from client_tpu.models import deepseek_v3

    kernels = _kernels(kernel_name)
    config = _config(dtype)
    params = weights_dsv3.params(SEED, TOY)
    served = params if dtype is not None else _to32(params)
    rng = np.random.default_rng(0)
    tokens = [rng.integers(1, 256, size=total) for _, total in LANES]
    tables = _tables(rng)
    pages = deepseek_v3.init_pages(config, [1 + tables.size], BLOCK)
    prefill = jax.jit(
        lambda *a: deepseek_v3.prefill_into_pages(*a, config, kernels))
    rows = []
    for lane, (prompt, _) in enumerate(LANES):
        padded = np.zeros((1, 64), np.int32)
        padded[0, :prompt] = tokens[lane][:prompt]
        logits, pages = prefill(
            served, padded, tables[lane], pages, prompt - 1)
        rows.append([np.asarray(logits[0])])
    decode = jax.jit(
        lambda *a: deepseek_v3.decode_step_paged(*a, config, kernels))
    steps = LANES[0][1] - LANES[0][0]
    assert all(total - prompt == steps for prompt, total in LANES)
    counted = np.zeros(len(deepseek_v3.COUNTERS), np.int64)
    for step in range(steps):
        positions = np.array([p + step for p, _ in LANES], np.int32)
        ids = np.array([t[p] for t, p in zip(tokens, positions)], np.int32)
        logits, pages, counters = decode(
            served, ids, positions, tables, pages)
        counted += np.asarray(counters)
        for lane in range(len(LANES)):
            rows[lane].append(np.asarray(logits[lane]))
    return (_to32(params), tokens, [np.stack(r) for r in rows],
            dict(zip(deepseek_v3.COUNTERS, counted.tolist())))


@pytest.fixture(scope="module", params=["fused_xla", "pallas_interpret"])
def toy(request):
    """Once on the plain XLA path and once through both Pallas kernels."""
    return _served_rows(request.param) + (request.param,)


def _reference_rows(params, tokens, model, lane):
    from benchmark.lib import reference_dsv3

    logits = reference_dsv3.forward(
        tokens[lane], params, params["layers"], model, (0, 16))
    return np.asarray(logits)[LANES[lane][0] - 1:]


def _worst(params, tokens, served, model):
    return max(
        np.abs(served[lane] - _reference_rows(params, tokens, model, lane)
               ).max() for lane in range(len(LANES)))


def test_prefill_then_absorbed_decode_matches_the_plain_reference(toy):
    """Ragged lanes through the one-pool cache, contexts over four tiles,
    whole and page by page: the program's prefill (plain form) and
    decode (absorbed form) against the reference's full forward pass."""
    params, tokens, served, counted, kernel = toy
    for lane in range(len(LANES)):
        ref = _reference_rows(params, tokens, TOY, lane)
        assert np.abs(ref).max() > 1.0  # logits of a size worth comparing
        assert np.abs(served[lane] - ref).max() <= TOLERANCE
    steps, layers = LANES[0][1] - LANES[0][0], 2
    assert counted["moe_resident_calls"] == (
        steps * layers if kernel == "pallas_interpret" else 0)
    # every pair of the toy lands on a held expert (all 16 are held), so
    # every lane has one in every expert layer of every step
    assert counted["moe_pairs"] == steps * layers * len(LANES) * 4
    assert counted["moe_lanes_here"] == steps * layers * len(LANES)


def test_the_kernel_choices_agree_and_bf16_would_not_pass():
    """The plain XLA path and the Pallas kernels under the interpreter
    give the same logits (the third choice, ``pallas``, is Mosaic's and
    is held against XLA on the chip, `tests/test_tpu_platform.py`); the
    same program computed in bf16 lies far outside the tolerance, so a
    lower precision than the configuration states cannot pass."""
    import jax.numpy as jnp

    params, tokens, plain, _ = _served_rows("fused_xla")
    _, _, kernels, _ = _served_rows("pallas_interpret")
    for a, b in zip(plain, kernels):
        assert np.abs(a - b).max() <= TOLERANCE
    _, _, rounded, _ = _served_rows("fused_xla", dtype=jnp.bfloat16)
    assert _worst(params, tokens, rounded, TOY) > 100 * TOLERANCE


def test_lanes_here_counts_the_lanes_with_a_pair_on_a_held_expert():
    """A chip that holds the first half of group 0 of 4: a lane has a
    pair here only where group 0 is kept and one of its first two
    experts is chosen; ``moe_lanes_here`` counts those lanes and is
    what the router's own ids say."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models import deepseek_v3, moe

    config = _config(n_routed_experts=2)
    assert config.held == (0, 2)
    params = deepseek_v3.init_params(jax.random.PRNGKey(3), config)
    layer = params["layers"][1]
    x = jnp.asarray(np.random.default_rng(5).normal(size=(24, 64)),
                    jnp.float32)
    _, counters = deepseek_v3._ffn(layer, x, config, 1, "fused_xla")
    counted = dict(zip(deepseek_v3.COUNTERS, np.asarray(counters).tolist()))
    normed = deepseek_v3.rms_norm(x, layer["mlp_norm"], config.norm_eps)
    ids, _ = moe.route(normed, layer["router"], layer["router_bias"], 4,
                       n_group=4, topk_group=2)
    here = (np.asarray(ids) < 2).any(axis=1)
    assert 0 < here.sum() < 24
    assert counted["moe_lanes_here"] == here.sum()
    assert counted["moe_pairs"] == (np.asarray(ids) < 2).sum()


# -- the absorption, on the same cache -----------------------------------------


def test_absorbed_decode_equals_the_plain_form_on_the_same_cache():
    """One layer's attention over one cache, two ways: the program's
    absorbed form (queries through ``w_uk``, attention over the stored
    rows, values out of their leading columns, ``w_uv`` on the way out)
    and the plain form on rows read back out of the pool and expanded to
    per-head keys and values."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models import deepseek_v3, paged_attention as pa

    config = _config()
    params = deepseek_v3.init_params(jax.random.PRNGKey(1), config)
    layer = params["layers"][0]
    rng = np.random.default_rng(2)
    lanes, width = 3, 16
    positions = np.array([99, 30, 7], np.int32)
    tables = _tables(rng)
    pool = jnp.zeros((1 + tables.size, BLOCK, config.row_width), jnp.float32)
    context = jnp.asarray(rng.normal(size=(lanes, width * BLOCK, config.row)),
                          jnp.float32)
    pool = pool.at[tables].set(jnp.pad(
        context, ((0, 0), (0, 0), (0, config.row_width - config.row))
    ).reshape(lanes, width, BLOCK, -1))
    q_nope = jnp.asarray(rng.normal(size=(lanes, 4, 16)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(lanes, 4, 8)), jnp.float32)
    for attn in (pa.paged_attention_xla,
                 lambda *a, **k: pa.paged_attention_pallas(
                     *a, interpret=True, **k)):
        absorbed = deepseek_v3._attend_absorbed(
            layer, q_nope, q_rope, pool, tables, positions, config, attn)
        for lane in range(lanes):
            rows = context[lane, :positions[lane] + 1]
            k_nope = jnp.einsum("tc,chn->thn", rows[:, :32], layer["w_uk"])
            v = jnp.einsum("tc,chv->thv", rows[:, :32], layer["w_uv"])
            scores = (jnp.einsum("hn,thn->ht", q_nope[lane], k_nope)
                      + jnp.einsum("hr,tr->ht", q_rope[lane], rows[:, 32:]))
            plain = jnp.einsum("ht,thv->hv", jax.nn.softmax(
                scores * config.softmax_scale, axis=-1), v)
            assert np.abs(np.asarray(absorbed[lane] - plain)).max() < 1e-5


def test_the_one_pool_call_reads_each_row_once_under_every_function():
    """`paged_attention`'s one-pool call (values inside the key rows) on
    a consecutive and a shuffled table: the Pallas kernel under the
    interpreter, plain XLA and the oracle agree, and equal the two-pool
    call on a copy of the values; asked for both a second pool and
    ``v_width``, or neither, it refuses."""
    import jax.numpy as jnp

    from client_tpu.models import paged_attention as pa

    rng = np.random.default_rng(4)
    lanes, heads, width, dv = 3, 4, 128, 32
    tables = _tables(rng)
    pool = jnp.asarray(rng.normal(size=(1 + tables.size, BLOCK, width)),
                       jnp.float32)
    q = jnp.asarray(rng.normal(size=(lanes, 1, heads, width)), jnp.float32)
    positions = np.array([[100], [63], [0]], np.int32)
    asked = dict(scale=0.2, kv_heads=1, v_width=dv)
    kernel = pa.paged_attention_pallas(
        q, pool, None, tables, positions, interpret=True, **asked)
    plain = pa.paged_attention_xla(q, pool, None, tables, positions, **asked)
    oracle = pa.paged_attention_reference(
        q, pool[:, :, None], None, tables, positions, scale=0.2, v_width=dv)
    two = pa.paged_attention_xla(
        q, pool, pool[..., :dv], tables, positions, scale=0.2, kv_heads=1)
    assert kernel.shape == (lanes, 1, heads, dv)
    for other in (plain, oracle, two):
        assert np.abs(np.asarray(kernel - other)).max() < 1e-5
    with pytest.raises(ValueError, match="v_pages=None and v_width"):
        pa.paged_attention_xla(q, pool, pool, tables, positions, **asked)
    with pytest.raises(ValueError, match="v_pages=None and v_width"):
        pa.paged_attention_xla(q, pool, None, tables, positions, kv_heads=1)


def test_one_pool_takes_the_score_columns_of_a_two_pool_tile(monkeypatch):
    """Two slots of one pool where there were two of two, and a tile
    lengthened to the score block's columns the budget gives K and V at
    KV 8 / D 128: 64 pages (1,024 tokens) at the latent cache's rows of
    640, where the bytes alone hold 25.6 and K and V pools of such rows
    would take 16 (12.8 a slot: the power of two nearest it); the served
    two-pool tiles, whose pages divide the budget, are what they were."""
    import jax.numpy as jnp

    from client_tpu.models import paged_attention as pa

    # the shipped budget, whatever the module's fixture set
    monkeypatch.setattr(pa, "_KV_VMEM_BUDGET", 1 << 20)
    assert pa.pages_per_tile(16, 1, 640, jnp.bfloat16, 1) == 64
    assert pa.pages_per_tile(16, 1, 640, jnp.bfloat16) == 16
    assert pa.pages_per_tile(16, 8, 128, jnp.bfloat16) == 8
    assert pa.pages_per_tile(16, 32, 128, jnp.bfloat16) == 2
    assert pa.pages_per_tile(16, 2, 128, jnp.bfloat16) == 32
    # MiMo's two groups as `LlmEngineModel` asks for them
    assert pa.pages_per_tile(16 * 4, 1, 256, jnp.bfloat16) == 8
    assert pa.pages_per_tile(16 * 8, 1, 256, jnp.bfloat16) == 4


# -- one case a departure: the reference with it left out is far away ----------


def _skip_latent_norm(names):
    def patch(monkeypatch, params):
        from benchmark.lib import reference_dsv3

        skipped = {id(layer[name]) for layer in params["layers"]
                   for name in names}
        normed = reference_dsv3.latent_norm
        monkeypatch.setattr(
            reference_dsv3, "latent_norm",
            lambda x, scale, model: x if id(scale) in skipped
            else normed(x, scale, model))
    return patch


def _no_mscale(monkeypatch, params):
    from benchmark.lib import reference_dsv3

    monkeypatch.setattr(
        reference_dsv3, "softmax_scale",
        lambda model: (int(model["qk_nope_head_dim"])
                       + int(model["qk_rope_head_dim"])) ** -0.5)


def _no_bias(monkeypatch, params):
    for layer in params["layers"]:
        if "router_bias" in layer:
            layer["router_bias"] = layer["router_bias"] * 0


def _yarn(**keys):
    return dict(rope_scaling={**TOY["rope_scaling"], **keys})


# (changes to the model's keys, a patch of the reference or None)
DEPARTURES = {
    "no norm on the query latent": ({}, _skip_latent_norm(("q_norm",))),
    "no norm on the kv latent": ({}, _skip_latent_norm(("kv_norm",))),
    "no mscale in the softmax scale": ({}, _no_mscale),
    "plain rope for YaRN's": (_yarn(factor=1), None),
    "YaRN's ramp two pairs late": (_yarn(beta_fast=4), None),
    "no routed scale": (dict(routed_scaling_factor=1.0), None),
    "bias left out": ({}, _no_bias),
    "no group limit": (dict(n_group=1, topk_group=1), None),
    "three groups kept for two": (dict(topk_group=3), None),
    "no shared expert": (dict(n_shared_experts=0), None),
    "two dense layers for one": (dict(first_k_dense_replace=0), None),
}


@pytest.mark.parametrize("departure", DEPARTURES)
def test_each_departure_left_out_fails_the_comparison(
        toy, departure, monkeypatch):
    """The comparison above is tight enough to tell: a reference with one
    part of the mathematics left out (which is the program with it left
    out, seen from the other side) lies far outside the tolerance."""
    params, tokens, served, _, _ = toy
    keys, patch = DEPARTURES[departure]
    params = {**params, "layers": [dict(l) for l in params["layers"]]}
    if patch is not None:
        patch(monkeypatch, params)
    model = {**TOY, **keys}
    if departure == "two dense layers for one":
        # layer 0's dense weights cannot run as experts: the other way
        # round, layer 1 as a dense layer of the shared expert's weights
        model = {**TOY, "first_k_dense_replace": 2}
        params["layers"][1] = {**params["layers"][1],
                               **params["layers"][1]["shared"]}
    assert _worst(params, tokens, served, model) > 100 * TOLERANCE


# -- YaRN and the scale against the closed form ----------------------------------


def test_yarn_frequencies_and_the_scale_at_the_published_numbers():
    """θ 1e5 on 64 rope sizes, factor 64 over 4,096 original positions,
    β 32 / 1: the ramp runs from pair 8 to pair 19; pairs under it turn
    as plain rope, pairs over it 64 times slower; the softmax scale is
    192^-0.5 (0.1 ln 64 + 1)^2 = 0.14468. The program's and the
    reference's agree to float32."""
    import math

    from benchmark.lib import reference_dsv3
    from client_tpu.models import deepseek_v3

    config = deepseek_v3.DeepseekV3Config()
    got = deepseek_v3.yarn_inv_freq(config)
    plain = 1e5 ** (-np.arange(32) / 32.0)
    corr = lambda n: 64 * math.log(4096 / (2 * math.pi * n)) / (  # noqa: E731
        2 * math.log(1e5))
    assert (math.floor(corr(32)), math.ceil(corr(1))) == (8, 19)
    ramp = np.clip((np.arange(32) - 8) / 11.0, 0, 1)
    closed = plain * (1 - ramp) + plain / 64 * ramp
    assert got.dtype == np.float32 and got.shape == (32,)
    assert np.abs(got / closed - 1).max() < 1e-6
    assert np.abs(got[:9] / plain[:9] - 1).max() < 1e-6
    assert np.abs(got[19:] * 64 / plain[19:] - 1).max() < 1e-6
    assert 1 / 64 < got[13] / plain[13] < 1  # on the ramp
    scale = 192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2
    assert abs(scale - 0.14468) < 1e-5
    assert abs(config.softmax_scale - scale) < 1e-12
    published = dict(
        qk_nope_head_dim=128, qk_rope_head_dim=64, rope_theta=1e5,
        rope_scaling=dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
                          mscale_all_dim=1,
                          original_max_position_embeddings=4096))
    assert abs(reference_dsv3.softmax_scale(published) - scale) < 1e-12
    assert np.abs(reference_dsv3.inv_freq(published) / got - 1).max() < 1e-6
    # what is stored: 576 numbers a token a layer in rows of 640
    assert (config.row, config.row_width) == (576, 640)
    assert deepseek_v3.kv_row_bytes(config) == [(1280, 1152)]


# -- routing: groups ------------------------------------------------------------


def _route_as_it_was(h, router, bias, top_k: int, scale: float = 1.0):
    """`moe.route` of the parent commit, letter for letter."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(jnp.dot(
        h.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ))
    _, ids = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    weights = picked / picked.sum(axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return ids.astype(jnp.int32), weights


@pytest.mark.parametrize("scale", [1.0, 2.826])
def test_route_at_one_group_traces_to_the_program_it_was(scale):
    import functools

    import jax
    import jax.numpy as jnp

    from client_tpu.models import moe

    args = (jnp.zeros((9, 64)), jnp.zeros((64, 16)), jnp.zeros((16,)))
    now = jax.make_jaxpr(functools.partial(
        moe.route, top_k=4, scale=scale, n_group=1, topk_group=1))(*args)
    was = jax.make_jaxpr(functools.partial(
        _route_as_it_was, top_k=4, scale=scale))(*args)
    assert str(now) == str(was)
    grouped = jax.make_jaxpr(functools.partial(
        moe.route, top_k=4, scale=scale, n_group=4, topk_group=2))(*args)
    assert str(grouped) != str(was)


def test_group_limited_routing_matches_the_reference_and_differs_from_top_k():
    """The program's router against the reference's over the toy's 16
    experts in 4 groups of which 2 are kept, on random rows and on a
    made one: experts 0 and 1 (group 0) hold the two largest scores, but
    group 0's two largest sum to less than three other groups' pairs, so
    group-limited routing drops both where plain top-k takes them."""
    import jax.numpy as jnp

    from benchmark.lib import reference_dsv3, weights_dsv3
    from client_tpu.models import moe

    w = _to32(weights_dsv3.layer(SEED, 1, TOY))
    h = jnp.asarray(np.random.default_rng(6).normal(size=(200, 64)),
                    jnp.float32)
    ids, weights = moe.route(
        h, w["router"], w["router_bias"], 4, scale=2.5, n_group=4,
        topk_group=2, eps=1e-20)
    chosen, weight, margin = reference_dsv3.route(h, w, TOY, (0, 16))
    assert (np.sort(np.asarray(ids)) == np.sort(np.asarray(chosen))).all()
    assert np.abs(np.sort(np.asarray(weights)) - np.sort(np.asarray(weight))
                  ).max() < 1e-6
    assert np.abs(np.asarray(weights).sum(axis=-1) - 2.5).max() < 1e-5
    groups = np.asarray(ids) // 4
    assert all(len(set(row)) <= 2 for row in groups)
    plain, _ = moe.route(h, w["router"], w["router_bias"], 4)
    assert (np.sort(np.asarray(plain)) != np.sort(np.asarray(ids))).any()
    assert np.isfinite(np.asarray(margin)).all() and (margin >= 0).all()

    # the made case: an identity "router" reads the scores off the row
    logits = np.full((1, 16), -2.0, np.float32)
    logits[0, [0, 1]] = [3.0, -1.9]          # group 0: one high, one low
    logits[0, [4, 5, 8, 9, 12, 13]] = 1.0    # groups 1-3: two middling each
    eye, none = jnp.eye(16, dtype=jnp.float32), jnp.zeros(16)
    plain, _ = moe.route(jnp.asarray(logits), eye, none, 4)
    limited, _ = moe.route(jnp.asarray(logits), eye, none, 4,
                           n_group=4, topk_group=2)
    assert 0 in np.asarray(plain)[0]
    assert sorted(np.asarray(limited)[0].tolist()) == [4, 5, 8, 9]
    made = {**TOY, "routed_scaling_factor": 1.0}
    chosen, _, margin = reference_dsv3.route(
        jnp.asarray(logits), {"router": eye, "router_bias": none}, made,
        (0, 16))
    assert sorted(np.asarray(chosen)[0].tolist()) == [4, 5, 8, 9]
    # groups 2 and 3 tie: the group selection stands on no margin
    assert float(margin[0]) == 0.0


# -- the expert layer: shares, the shared expert counted once ------------------

PATHS = {"fused_xla": "fused_xla", "resident": "pallas_interpret",
         "planned": "pallas_interpret"}


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("shares", [8, 2, 1])
def test_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(
        shares, path, monkeypatch):
    """Each of ``shares`` chips holds 16 / shares experts of a layer
    (half a group, two groups, all four), routes over all 16 in groups
    at the routed scale and computes its own experts' part AND the
    shared expert whole; the routed parts, with the shared expert
    counted once, add up to the uncut reference's layer output."""
    import functools

    import jax.numpy as jnp

    from benchmark.lib import reference_dsv3, weights_dsv3
    from client_tpu.models import moe

    if path == "planned":
        monkeypatch.setattr(moe, "_RESIDENT_ROWS", 0)
    expert_layer = functools.partial(moe.expert_layer, kernel=PATHS[path])
    whole = _to32(weights_dsv3.layer(SEED, 2, TOY, held_experts=(0, 16)))
    h = jnp.asarray(np.random.default_rng(1).normal(size=(40, 64)),
                    jnp.float32)
    ref = np.asarray(reference_dsv3.expert_layer(h, whole, TOY, (0, 16)))
    ids, weights = moe.route(
        h, whole["router"], whole["router_bias"], 4, scale=2.5, n_group=4,
        topk_group=2, eps=1e-20)
    shared = np.asarray(moe.shared_expert(h, whole["shared"]))
    count = 16 // shares
    total, pairs = 0.0, 0
    for share in range(shares):
        held = (share * count, count)
        mine = _to32(weights_dsv3.layer(SEED, 2, TOY, held_experts=held))
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


def test_a_wide_model_narrows_the_expert_tile_and_no_other():
    """The expert kernels' grid step follows ``d``: 512 columns where the
    three weight blocks, twice buffered, fit what they take at d 4096
    (MiMo's and Trinity's programs as they were), 256 at d 7,168."""
    import jax.numpy as jnp

    from client_tpu.models import moe

    assert moe._f_tile(4096, 2048, jnp.bfloat16) == 512
    assert moe._f_tile(2048, 1024, jnp.bfloat16) == 512
    assert moe._f_tile(7168, 2048, jnp.bfloat16) == 256
    assert moe._f_tile(64, 32, jnp.float32) == 32
    blocks = 6 * 7168 * 256 * 2
    assert blocks <= moe._WEIGHT_VMEM < moe._VMEM_LIMIT


# -- the engine ------------------------------------------------------------------


def _engine_model(features=None, **engine):
    """The toy behind `LlmEngineModel`; ``engine`` overrides
    `EngineConfig`'s sizes, ``features`` are the model's own arguments."""
    from client_tpu.llm.engine import EngineConfig
    from client_tpu.llm.serving import LlmEngineModel
    from client_tpu.models import deepseek_v3

    sizes = dict(block_size=16, num_blocks=1 + 2 * 8, max_active=2,
                 max_queue=8, max_seq_len=128, prefix_sharing=False)
    sizes.update(engine)
    return LlmEngineModel(
        name="dsv3_toy", model=deepseek_v3.ENGINE_MODEL,
        config=deepseek_v3.DeepseekV3Config.tiny(),
        engine_config=EngineConfig(**sizes), **(features or {}))


async def _generate(model, prompts, max_tokens):
    seqs = [model.engine.submit(p, max_tokens=max_tokens) for p in prompts]

    async def collect(seq):
        return [token async for token, _ in seq]

    return await asyncio.gather(*(collect(s) for s in seqs))


def test_engine_serves_the_model_over_one_pool_a_layer():
    """Two sequences through `LlmEngineModel` over the seam: a layer's
    pages are one pool, the tile the engine counts with is the one-pool
    kernel's, the row bytes are served as stored and as counted, greedy
    tokens equal the reference's on the same weights, and the counters
    of the model and of the attention are booked."""
    from benchmark.lib import reference_dsv3
    from client_tpu.models import paged_attention as pa

    model = _engine_model()
    model.warmup()
    try:
        engine = model.engine
        assert all(pool.shape == (17, 16, 128) for pool in engine._pages)
        assert engine._tile_pages == (pa.pages_per_tile(
            16, 1, 128, np.float32, 1),)
        rng = np.random.default_rng(3)
        lengths, out = (30, 9), 60
        prompts = [rng.integers(1, 256, size=n).tolist() for n in lengths]
        served = asyncio.run(_generate(model, prompts, out))
        stats = engine.stats()
        assert stats["kv_row_bytes_by_group"] == [
            {"stored": 128 * 4, "counted": 40 * 4}]
        assert stats["kv_blocks_in_use_by_group"] == [0]
        assert stats["moe_pairs"] > 0 and stats["moe_resident_calls"] == 0
        assert 0 < stats["moe_lanes_here"] <= 2 * 2 * stats["steps"]
        assert stats["attn_tiles_walked"] >= stats["attn_tiles_whole"] > 0
        full = sum(sum(range(n + 1, n + out)) for n in lengths)
        assert full <= stats["attn_tokens_full"] <= full + 2 * (128 + 1)
        assert stats["attn_tokens_window"] == 0
        params = _to32(model._params)
        # `DeepseekV3Config.tiny()` is TOY's shape
        for prompt, tokens in zip(prompts, served):
            logits = np.asarray(reference_dsv3.forward(
                prompt + tokens, params, params["layers"], TOY, (0, 16)))
            at = logits[len(prompt) - 1: len(prompt) + len(tokens) - 1]
            gap = at.max(axis=-1) - at[np.arange(len(tokens)), tokens]
            assert gap.max() <= TOLERANCE  # the served token is the best
    finally:
        model.shutdown()


def test_a_two_pool_model_serves_its_row_bytes_as_stored():
    """The Llama family gives no ``kv_row_bytes``: K and V of 4 heads of
    16 in bf16, as its pools hold them, counted as stored."""
    from client_tpu.llm.engine import EngineConfig
    from client_tpu.llm.serving import LlmEngineModel

    model = LlmEngineModel(engine_config=EngineConfig(
        block_size=8, num_blocks=33, max_active=2, max_seq_len=128))
    model.warmup()
    try:
        assert model.engine.stats()["kv_row_bytes_by_group"] == [
            {"stored": 2 * 4 * 16 * 2, "counted": 2 * 4 * 16 * 2}]
    finally:
        model.shutdown()


def test_the_engine_runs_128_lanes_in_one_step():
    """Twice the lanes any cell had: the ids vector, the batch bucket
    and the tables of a step are 128 wide, every lane decodes beside
    the others, and the lanes' pairs are counted a lane."""
    from client_tpu.llm.engine import EngineConfig

    assert EngineConfig(max_active=128).ids_width == 128
    assert EngineConfig(max_active=96).ids_width == 128
    model = _engine_model(num_blocks=1 + 128 * 2, max_active=128,
                          max_queue=128, max_seq_len=32)
    model.warmup()
    try:
        rng = np.random.default_rng(8)
        prompts = [rng.integers(1, 256, size=5 + lane % 7).tolist()
                   for lane in range(128)]
        served = asyncio.run(_generate(model, prompts, 12))
        assert all(len(tokens) == 12 for tokens in served)
        stats = model.engine.stats()
        assert stats["attn_blocks_bucket"] >= 128 * 2  # a 128-lane step ran
        assert stats["moe_lanes_here"] <= 128 * 2 * stats["steps"]
        assert stats["completed"] == 128 and stats["preemptions"] == 0
    finally:
        model.shutdown()


@pytest.mark.parametrize("features,engine,part", [
    (dict(speculation={"mode": "ngram", "k": 2}), {}, "verify"),
    ({}, dict(prefix_sharing=True), "prefill_suffix"),
    (dict(tp=2), {}, "param_specs"),
])
def test_deepseek_v3_is_refused_the_features_it_has_no_part_for(
        features, engine, part):
    from client_tpu.utils import InferenceServerException

    model = _engine_model(features, **engine)
    with pytest.raises(InferenceServerException, match=f"[`']{part}[`']"):
        model.warmup()


def test_the_config_declares_one_full_group_and_refuses_a_wrong_share():
    from client_tpu.models import deepseek_v3
    from client_tpu.models.engine_model import FULL

    config = deepseek_v3.DeepseekV3Config(n_layers=5, n_dense_layers=1,
                                          held=(0, 16))
    (group,) = deepseek_v3.cache_groups(config)
    assert (group.kind, group.layers, group.window) == (
        FULL, (0, 1, 2, 3, 4), None)
    with pytest.raises(ValueError, match="not a share"):
        deepseek_v3.DeepseekV3Config.tiny(held=(12, 8))
    with pytest.raises(ValueError, match="n_dense_layers"):
        deepseek_v3.DeepseekV3Config.tiny(n_dense_layers=4)
    with pytest.raises(ValueError, match="n_group"):
        deepseek_v3.DeepseekV3Config.tiny(n_group=3)
