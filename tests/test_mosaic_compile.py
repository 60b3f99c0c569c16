"""The paged-attention kernel compiled by Mosaic for a v5e that is
described, not attached: what the interpreter cannot see (tiling, VMEM,
DMA shapes) is refused here, in seconds and at no chip time.

Nothing runs, so nothing here is a result or a time. The topology is
described inside a fixture (never at import: the TPU library belongs to
one process at a time, and every xdist worker imports this file), and
all such tests live in this one file.
"""

import functools
import re

import pytest

pytestmark = pytest.mark.llm

BLOCK, HEAD_DIM = 16, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


CASES = {
    # batch, query rows, heads, kv heads, table columns, pool blocks
    "cell-decode": (16, 1, 32, 8, 64, 2049),   # mistral7b.batch
    "cell-verify": (16, 5, 32, 8, 64, 2049),   # T = spec_k + 1
    "mha-decode": (8, 1, 32, 32, 128, 1025),   # Llama-2-7B widths, to 2,048
    "tp-shard-decode": (4, 1, 8, 2, 24, 1025),  # GQA 32/8 over tp=4
    "tp-shard-verify": (4, 5, 8, 2, 24, 1025),
    "one-column": (1, 1, 32, 8, 1, 33),        # a table narrower than a tile
}


MASKING_CASES = {
    # batch, heads, kv heads, table columns, pool blocks, window, sink:
    # mimo_v2_flash.reason's two cache groups (K rows 192, V rows 128;
    # the window pool 64 rings of 12 blocks: 9 in whole tiles of 4)
    "mimo-full": (64, 64, 4, 128, 8193, None, False),
    "mimo-window": (64, 64, 8, 128, 769, 128, True),
    "mimo-window-one-lane": (1, 64, 8, 8, 769, 128, True),
}


@pytest.mark.parametrize("case", MASKING_CASES)
def test_mosaic_compiles_unequal_rows_window_and_sink(one_chip, case):
    """Mosaic's verdict on K rows of 256 (192 padded: it refuses to copy
    a 192-wide row out of HBM's 128-lane tiles) beside V rows of 128,
    the window's third prefetched vector and the sink column."""
    import functools

    import jax
    import jax.numpy as jnp

    from client_tpu.models import paged_attention as pa

    batch, heads, kv_heads, columns, blocks, window, sink = MASKING_CASES[case]

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [
        shaped((batch, 1, heads, 256), jnp.bfloat16),
        # flat pools: at KV 4 a [.., 4, D] pool is padded to 8 rows in
        # HBM and its flat view is a copy of the whole pool
        shaped((blocks, BLOCK * kv_heads, 256), jnp.bfloat16),
        shaped((blocks, BLOCK * kv_heads, 128), jnp.bfloat16),
        shaped((batch, columns), jnp.int32),
        shaped((batch, 1), jnp.int32),
    ]
    masking = {"window": window, "scale": 192 ** -0.5, "kv_heads": kv_heads}
    fn = functools.partial(pa.paged_attention_pallas, **masking)
    if sink:
        args.append(shaped((heads,), jnp.float32))
        fn = lambda *a: pa.paged_attention_pallas(  # noqa: E731
            *a[:5], sink=a[5], **masking)
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "%paged_attention" in text
    assert f"bf16[{blocks},{BLOCK * kv_heads},256]" in text
    assert f"bf16[{blocks},{BLOCK * kv_heads},128]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


# d, expert width, experts routed over, route scale, a shared expert?
EXPERT_SIZES = {
    "mimo": (4096, 2048, 256, 1.0, False),     # mimo_v2_flash.reason
    "trinity": (2048, 1024, 128, 2.826, True),  # trinity_mini.reason8k
    "gigachat": (7168, 2048, 256, 2.5, True),  # gigachat3_702b.reason8k_128
}
#: (n_group, topk_group) of the models whose routing is limited to groups
EXPERT_GROUPS = {"gigachat": (8, 4)}


@functools.lru_cache(maxsize=None)
def _expert_layer_with_its_router(one_chip, tokens, model="mimo"):
    """`moe.route` and `moe.expert_layer` under the load-time choice
    ``pallas`` at a cell's sizes (:data:`EXPERT_SIZES`; 16 held experts
    in bf16, 8 a token), compiled for the described v5e."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models import moe

    d, f, routed, scale, with_shared = EXPERT_SIZES[model]
    held, top_k = (0, 16), 8

    def shaped(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n_group, topk_group = EXPERT_GROUPS.get(model, (1, 1))

    def layer(h, router, bias, experts, shared):
        ids, weights = moe.route(h, router, bias, top_k, scale=scale,
                                 n_group=n_group, topk_group=topk_group)
        return moe.expert_layer(h, ids, weights, experts, held,
                                kernel="pallas", shared=shared)

    experts = {"w_gate": shaped((16, d, f)), "w_up": shaped((16, d, f)),
               "w_down": shaped((16, f, d))}
    shared = {"w_gate": shaped((d, f)), "w_up": shaped((d, f)),
              "w_down": shaped((f, d))} if with_shared else None
    return jax.jit(layer).lower(
        shaped((tokens, d)), shaped((d, routed)),
        shaped((routed,), jnp.float32), experts, shared).compile()


def _device_operations(compiled):
    """[(opcode, name)] of the compiled program's entry computation in
    the order it runs them, without what is no operation on the device
    (parameters, literals, tuples and their elements, bitcasts)."""
    import re

    text = compiled.as_text()
    assert "is_scheduled=true" in text
    entry = text[text.index("\nENTRY "):]
    ran = []
    for line in entry.splitlines():
        found = re.search(r"^\s*(?:ROOT )?(%\S+) = .*? ([a-z][a-z-]*)\((?:%|\))",
                          line)
        if found and found.group(2) not in (
                "parameter", "constant", "tuple", "get-tuple-element",
                "bitcast"):
            ran.append((found.group(2), found.group(1)))
    return ran


@pytest.mark.parametrize("model,tokens", [
    ("mimo", 1), ("mimo", 16), ("mimo", 64), ("mimo", 128), ("mimo", 512),
    ("mimo", 2048), ("trinity", 64), ("trinity", 512), ("trinity", 8192),
    ("gigachat", 128), ("gigachat", 512), ("gigachat", 2048),
])
def test_mosaic_compiles_the_expert_kernel(one_chip, model, tokens):
    """Mosaic's verdict on both ``moe_experts`` kernels at both cells'
    sizes (:data:`EXPERT_SIZES`; at Trinity's an expert is two grid steps
    of 6 MB where MiMo's takes four of 12, and the shared expert runs in
    plain XLA beside the kernel): a decode batch (one lane, a sublane
    tile, the cells' 64 lanes, the 128 rows up to which rows stay
    resident) takes the resident kernel and holds no more than a few
    ``T x d`` buffers beside it; a 512-token prefill and each cell's
    longest (2,048 and 8,192 tokens) are planned in row tiles of 128.
    At GigaChat's d 7,168 the grid step's three weight blocks narrow to
    256 columns (eight steps an expert, 44 MB twice buffered would not
    fit beside the rows): the cell's 128 lanes resident, a 512-token
    prefill and the 2,048 rows a longer prompt's feed-forward takes at
    once planned, the router limited to 4 of 8 groups."""
    from client_tpu.models import moe

    compiled = _expert_layer_with_its_router(one_chip, tokens, model)
    text, (d, f) = compiled.as_text(), EXPERT_SIZES[model][:2]
    # float32 [rows, d] buffers a resident call may hold, and a planned
    # one: MiMo's bounds as they were; the shared expert's rows come on
    # top of them, and at 8,192 tokens each pair's row in float32 (1.1 GB,
    # the prefill's largest scratch)
    resident, planned = {"mimo": (3, 2), "trinity": (4, 5),
                         "gigachat": (4, 5)}[model]
    assert "%moe_experts" in text
    temp = compiled.memory_analysis().temp_size_in_bytes
    if tokens <= moe._RESIDENT_ROWS:
        rows = -(-tokens // 16) * 16
        assert f"f32[{rows},{d}]" in text  # the kernel's own output
        pairs = text
        if model != "mimo":
            # XLA streams the shared expert's [d, f] weights in slices
            # whose rows may equal lanes x 8 (512 for Trinity's 64 lanes,
            # 1,024 for GigaChat's 128)
            pairs = text.replace(f"bf16[{rows * 8},{f}]", "")
        assert f"bf16[{rows * 8}," not in pairs  # no row a pair
        assert "while" not in text
        assert temp < resident * 4 * rows * d
    else:
        # the rows gathered by expert and their outputs, no dense pass
        rows = moe._ROW_TILE * (-(-tokens * 8 // moe._ROW_TILE) + 16)
        assert f"bf16[{rows},{d}]" in text
        assert temp < planned * 4 * rows * d


TRINITY_ATTENTION = {
    # pool blocks, window: trinity_mini.reason8k's two cache groups, 64
    # lanes, 32 query heads over 4 KV heads of 128, a table of 512
    "full": (20481, None),
    # a ring of 144 blocks a lane: the window's 129 in whole tiles of 16
    "window": (1 + 64 * 144, 2048),
}


@pytest.mark.parametrize("group", TRINITY_ATTENTION)
def test_mosaic_compiles_the_paged_kernel_at_trinitys_shapes(one_chip, group):
    """KV 4 / D 128 in flat pools: tiles of 16 pages; a page table of
    512 columns a lane (128 KB of scalar prefetch, four times MiMo's);
    the window group's walk over a ring of 144 blocks (nine tiles)."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models import paged_attention as pa

    blocks, window = TRINITY_ATTENTION[group]
    assert pa.pages_per_tile(BLOCK, 4, HEAD_DIM, jnp.bfloat16) == 16

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = shaped((blocks, BLOCK * 4, HEAD_DIM), jnp.bfloat16)
    compiled = jax.jit(functools.partial(
        pa.paged_attention_pallas, window=window, kv_heads=4)).lower(
        shaped((64, 1, 32, HEAD_DIM), jnp.bfloat16), pool, pool,
        shaped((64, 512), jnp.int32), shaped((64, 1), jnp.int32)).compile()
    text = compiled.as_text()
    assert "%paged_attention" in text
    assert f"bf16[{blocks},{BLOCK * 4},{HEAD_DIM}]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


def test_the_compiled_kernel_holds_both_ways_of_fetching_a_tile(one_chip):
    """Trinity's window group again, for what went into Mosaic: a tile
    starts and is waited for either as one copy of 16 pages from each
    pool or as 16 page copies from each, at the three places a tile
    starts (the call's first two stops, and the stop two ahead of the
    one being folded) and at the one where it is waited for; the page
    loops stay rolled, so the kernel holds each copy once a site."""
    import re

    import jax
    import jax.numpy as jnp

    from client_tpu.models import paged_attention as pa

    blocks, window = TRINITY_ATTENTION["window"]
    kernel = functools.partial(
        pa.paged_attention_pallas, window=window, kv_heads=4)
    shapes = [((64, 1, 32, HEAD_DIM), jnp.bfloat16),
              ((blocks, BLOCK * 4, HEAD_DIM), jnp.bfloat16),
              ((blocks, BLOCK * 4, HEAD_DIM), jnp.bfloat16),
              ((64, 512), jnp.int32), ((64, 1), jnp.int32)]
    jax.jit(kernel).lower(*[
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes]).compile()
    traced = str(jax.make_jaxpr(kernel)(*[
        jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in shapes]))
    starts = re.findall(r"dma_start\S* \w+\[([^\]]*)\] -> ", traced)
    waits = re.findall(r"dma_wait \w+\[([^\]]*)\] ", traced)
    # K and V at each site; a whole tile's source is a slice of 16 pages,
    # a page's source one index
    assert sorted(":" in source.split(",")[0] for source in starts) == (
        [False] * 6 + [True] * 6), starts
    assert sum("+16" in source for source in starts) == 6
    # a wait is written on its destination: slot and page, or the slot
    assert sorted(
        target.split(",")[1] == ":" for target in waits
    ) == [False] * 2 + [True] * 2, waits


def test_trinitys_longest_prefill_and_decode_fit_the_chip(one_chip):
    """`afmoe`'s 8,192-token prefill and its 64-lane decode step compiled
    whole for the described v5e at the cell's sizes (16 layers, 16 held
    experts, 25,024 rows of vocabulary, pools of 20,481 and 9,217
    blocks: 64 rings of 144). The bound: 10.6 GB of arguments (4.23 of
    weights, 6.31 of cache) and under 1.25 GB of scratch, 11.9 GB of the
    chip's 16; read here at 10,539,564,544 and 1,131,382,784 B (the
    decode step: 43,803,648 B of scratch)."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models import afmoe, paged_attention
    from client_tpu.models.engine_model import Kernels

    config = afmoe.AfmoeConfig(
        vocab_size=25024, layer_kinds=(1, 1, 1, 0) * 4, held=(0, 16))
    kernels = Kernels("pallas", paged_attention.paged_attention_pallas)

    def shaped(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = shaped(jax.eval_shape(
        lambda: afmoe.init_params(jax.random.PRNGKey(0), config)))
    pages = shaped(jax.eval_shape(
        lambda: afmoe.init_pages(config, [20481, 1 + 64 * 144], BLOCK)))
    ints = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip)
    prefill = jax.jit(
        lambda p, t, tables, pages, last: afmoe.prefill_into_pages(
            p, t, tables, pages, last, config, kernels),
        donate_argnums=(3,)).lower(
        params, ints(1, 8192), ints(2, 512), pages, ints()).compile()
    memory = prefill.memory_analysis()
    assert memory.argument_size_in_bytes < 10.6e9
    assert memory.temp_size_in_bytes < 1.25e9
    decode = jax.jit(
        lambda p, t, at, tables, pages: afmoe.decode_step_paged(
            p, t, at, tables, pages, config, kernels),
        donate_argnums=(4,)).lower(
        params, ints(64), ints(64), ints(2, 64, 512), pages).compile()
    text = decode.as_text()
    assert text.count("%paged_attention") >= 16
    assert text.count("%moe_experts") >= 14
    assert decode.memory_analysis().temp_size_in_bytes < 64e6


def test_the_decode_size_expert_layer_is_compiled_without_a_plan(one_chip):
    """The finding of PR 30, held: at the cell's 64 lanes the compiled
    layer has no loop, one sort (the router's ``top_k``) and, between
    the router's matmul and the kernel, eighteen small device
    operations (eleven of them the router's own ``top_k`` and
    ``take_along_axis``) where the planned layer runs more than sixty,
    and after the kernel none."""
    opcodes = _device_operations(_expert_layer_with_its_router(one_chip, 64))
    names = [name for _, name in opcodes]
    kinds = [kind for kind, _ in opcodes]
    assert "while" not in kinds and kinds.count("sort") == 1
    (kernel,) = [i for i, name in enumerate(names)
                 if name.startswith("%moe_experts")]
    assert kinds[kernel] == "custom-call"
    assert kernel == len(opcodes) - 1
    router = kinds.index("sort") - 1  # what the sort reads: s + b
    assert kinds[router] == "fusion"
    between = [kind for kind in kinds[router + 1:kernel]
               if kind not in ("copy-start", "copy-done")]  # prefetches
    assert len(between) <= 18, opcodes[router + 1:kernel]
    planned = _device_operations(_expert_layer_with_its_router(one_chip, 512))
    assert len(planned) > 60
    assert [kind for kind, _ in planned].count("sort") == 2


@pytest.mark.parametrize("case", CASES)
def test_mosaic_compiles_the_paged_attention_kernel(one_chip, case):
    import jax
    import jax.numpy as jnp

    from client_tpu.models import paged_attention as pa

    batch, rows, heads, kv_heads, columns, blocks = CASES[case]

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = shaped((blocks, BLOCK, kv_heads, HEAD_DIM), jnp.bfloat16)
    tables = shaped((batch, columns), jnp.int32)
    q = shaped((batch, rows, heads, HEAD_DIM), jnp.bfloat16)
    positions = shaped((batch, rows), jnp.int32)
    compiled = jax.jit(pa.paged_attention_pallas).lower(
        q, pool, pool, tables, positions).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    assert "%paged_attention" in text  # the name the trace readers select
    # the pools reach the kernel as they lie in HBM: re-viewed, not copied
    assert f"bf16[{blocks},{BLOCK * kv_heads},{HEAD_DIM}]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


GIGACHAT = dict(vocab_size=16032, n_layers=5, n_dense_layers=1, held=(0, 16))


@pytest.mark.parametrize("lanes,columns", [(128, 512), (1, 8), (8, 96)])
def test_mosaic_compiles_the_one_pool_latent_call(one_chip, lanes, columns):
    """`gigachat3_702b.reason8k_128` as the kernel sees it: one pool of
    640-wide rows at KV 1 (576 held: the latent's 512, which are also
    the values, and the roped key's 64), 64 query rows a lane, tiles of
    64 pages (a `[64, 1024]` score block a stop, three slots of 1.25 MB;
    a table narrower than a tile is one tile, one of 96 columns a tile
    and a half); ONE HBM operand and one VMEM buffer of three slots,
    where two pools of such rows would be two of each."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models import paged_attention as pa

    assert pa.pages_per_tile(BLOCK, 1, 640, jnp.bfloat16, 1) == 64
    assert pa.pages_per_tile(BLOCK, 8, 128, jnp.bfloat16) == 8

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    call = functools.partial(
        pa.paged_attention_pallas, scale=0.14468, kv_heads=1, v_width=512)
    compiled = jax.jit(lambda q, pool, tables, positions: call(
        q, pool, None, tables, positions)).lower(
        shaped((lanes, 1, 64, 640), jnp.bfloat16),
        shaped((40961, BLOCK, 640), jnp.bfloat16),
        shaped((lanes, columns), jnp.int32),
        shaped((lanes, 1), jnp.int32)).compile()
    text = compiled.as_text()
    assert "%paged_attention" in text
    assert text.count("bf16[40961,16,640]") >= 1
    assert f"bf16[{lanes},64,512]" in text  # the latent output, V's width
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)
    traced = str(jax.make_jaxpr(call)(
        jax.ShapeDtypeStruct((lanes, 1, 64, 640), jnp.bfloat16),
        jax.ShapeDtypeStruct((40961, BLOCK, 640), jnp.bfloat16), None,
        jax.ShapeDtypeStruct((lanes, columns), jnp.int32),
        jax.ShapeDtypeStruct((lanes, 1), jnp.int32)))
    # one copy a tile (or a page) at each of the three places a tile
    # starts (the call's first two stops, and the stop two ahead of the
    # one being folded), where K and V pools make two
    assert traced.count("dma_start") == 6
    assert traced.count("dma_wait") == 2


def test_gigachats_longest_prefill_and_decode_fit_the_chip(one_chip):
    """`deepseek_v3`'s 8,192-token prefill and its 128-lane decode step
    compiled whole for the described v5e at the cell's sizes (5 layers,
    16 held experts, 16,032 rows of vocabulary, one pool of 40,961
    blocks a layer). The bound: 12.8 GB of arguments (8.58 of weights,
    4.19 of cache) and under 1.9 GB of scratch in the prefill, 14.7 GB
    of the chip's 16; read here at 12,776,961,536 and 1,794,586,112 B
    (the decode step: 16,064,000 B of scratch)."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models import deepseek_v3, paged_attention
    from client_tpu.models.engine_model import Kernels

    config = deepseek_v3.DeepseekV3Config(**GIGACHAT)
    kernels = Kernels("pallas", paged_attention.paged_attention_pallas)

    def shaped(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = shaped(jax.eval_shape(
        lambda: deepseek_v3.init_params(jax.random.PRNGKey(0), config)))
    pages = shaped(jax.eval_shape(
        lambda: deepseek_v3.init_pages(config, [40961], BLOCK)))
    ints = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip)
    prefill = jax.jit(
        lambda p, t, table, pages, last: deepseek_v3.prefill_into_pages(
            p, t, table, pages, last, config, kernels),
        donate_argnums=(3,)).lower(
        params, ints(1, 8192), ints(512), pages, ints()).compile()
    memory = prefill.memory_analysis()
    assert memory.argument_size_in_bytes < 12.8e9
    assert memory.temp_size_in_bytes < 1.9e9
    decode = jax.jit(
        lambda p, t, at, tables, pages: deepseek_v3.decode_step_paged(
            p, t, at, tables, pages, config, kernels),
        donate_argnums=(4,)).lower(
        params, ints(128), ints(128), ints(128, 512), pages).compile()
    text = decode.as_text()
    assert text.count("%paged_attention") >= 5
    assert text.count("%moe_experts") >= 4
    # the cache is never expanded: no per-head key or value of a context
    assert "bf16[128,8192,64," not in text
    assert decode.memory_analysis().temp_size_in_bytes < 64e6


QWEN3_NEXT = dict(vocab_size=18992, n_layers=16, held=(0, 32),
                  max_seq_len=2048)


@pytest.mark.parametrize("lanes,slots", [(128, 129), (1, 129), (8, 9)])
def test_mosaic_compiles_the_state_update_kernel(one_chip, lanes, slots):
    """``gated_delta_step`` at Qwen3-Next's widths (16 key heads and 32
    value heads of 128, a float32 state of 2 MB a lane) for the described
    v5e: the pool is aliased input to output and the compiled program
    holds no copy of it and no scratch beside it."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models import gated_delta

    def shaped(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = (slots, 32, 128, 128)
    compiled = jax.jit(
        lambda q, k, v, g, beta, at, pool: gated_delta.gated_delta_step(
            q, k, v, g, beta, at, pool, kernel="pallas"),
        donate_argnums=(6,)).lower(
        shaped((lanes, 16, 128)), shaped((lanes, 16, 128)),
        shaped((lanes, 32, 128)), shaped((lanes, 32)), shaped((lanes, 32)),
        shaped((lanes,), jnp.int32), shaped(pool)).compile()
    text = compiled.as_text()
    assert "%gated_delta_step" in text and "tpu_custom_call" in text
    memory = compiled.memory_analysis()
    pool_bytes = slots * 32 * 128 * 128 * 4
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < pool_bytes // 8
    assert not [line for line in text.splitlines()
                if " copy(" in line and f"f32[{slots},32,128,128]" in line]


@functools.lru_cache(maxsize=None)
def _qwen3_next_compiled(one_chip, program, size):
    """(optimised HLO text, memory analysis) of `qwen3_next`'s decode step
    of ``size`` lanes or its prefill of ``size`` tokens at the cell's
    sizes, compiled whole for the described v5e; once a program however
    many tests read it."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models import paged_attention, qwen3_next
    from client_tpu.models.engine_model import Kernels

    config = qwen3_next.Qwen3NextConfig(**QWEN3_NEXT)
    kernels = Kernels("pallas", paged_attention.paged_attention_pallas)

    def shaped(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = shaped(jax.eval_shape(
        lambda: qwen3_next.init_params(jax.random.PRNGKey(0), config)))
    pages = shaped(jax.eval_shape(
        lambda: qwen3_next.init_pages(config, [16385, 129], BLOCK)))
    ints = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip)
    if program == "decode":
        lowered = jax.jit(
            lambda p, t, at, tables, pages: qwen3_next.decode_step_paged(
                p, t, at, tables, pages, config, kernels),
            donate_argnums=(4,)).lower(
            params, ints(size), ints(size), ints(2, size, 128), pages)
    else:
        lowered = jax.jit(
            lambda p, t, table, pages, last: qwen3_next.prefill_into_pages(
                p, t, table, pages, last, config, kernels),
            donate_argnums=(3,)).lower(
            params, ints(1, size), ints(2, 128), pages, ints())
    compiled = lowered.compile()
    return compiled.as_text(), compiled.memory_analysis()


def test_qwen3_nexts_longest_prefill_and_decode_fit_the_chip(one_chip):
    """`qwen3_next`'s 2,048-token prefill and its 128-lane decode step
    compiled whole for the described v5e at the cell's sizes (16 layers,
    32 held experts, 18,992 rows of vocabulary, a full group of 16,385
    blocks, a state group of 129 slots). The bound: 10.1 GB of arguments
    (4.54 of weights, 2.15 of K/V, 3.32 of states) and under 1 GB of
    scratch in the prefill, 11 GB of the chip's 16; read here at
    10,008,816,640 B of arguments, 144,186,880 B of scratch in the decode
    step (10,012,945,408 B and 248,639,488 B while the convolution pools
    were ``[129, 3, 8192]``, padded to whole tiles of 16 slots, and each
    was copied whole, twice a step) and under 1 GB in the prefill."""
    text, memory = _qwen3_next_compiled(one_chip, "decode", 128)
    assert memory.argument_size_in_bytes < 10.1e9
    assert memory.temp_size_in_bytes < 175e6
    assert text.count("%gated_delta_step") >= 12
    assert text.count("%paged_attention") >= 4
    assert text.count("%moe_experts") >= 16
    _, memory = _qwen3_next_compiled(one_chip, "prefill", 2048)
    assert memory.argument_size_in_bytes < 10.1e9
    assert memory.temp_size_in_bytes < 1.0e9


@pytest.mark.parametrize("program,size", [
    ("decode", 128), ("prefill", 512), ("prefill", 2048)])
def test_qwen3_next_updates_its_state_pools_where_they_lie(
        one_chip, program, size):
    """No whole state pool (277 MB a layer) and no whole convolution pool
    (6.3 MB: ``[129, 3, 64, 128]``, a slot's three inputs folded into
    their heads' rows, so that a slot owns 12 whole tiles and a gather or
    a scatter by slot moves those alone) is copied in the cell's decode
    step or in its prefills: every one is updated where it lies. As
    ``[129, 3, 8192]`` the pool lay in HBM with its slots second to last
    and was copied whole before every gather and after every scatter, 24
    copies a decode step; as one row ``[129, 24576]`` a slot it was not
    copied, and 16 slots shared every tile (a scatter of 110-200 us a
    layer on the chip). What the prefill holds of a pool is one
    ``dynamic-update-slice``, the slot's write into the donated buffer."""
    text, _ = _qwen3_next_compiled(one_chip, program, size)
    rows = r"bf16\[129,3,64,128\]"
    if program == "prefill":
        held = rf"(f32\[129,32,128,128\]|{rows})\S* copy"
    else:
        # the convolution pool's scatter is the slots' own, in place
        held = (rf"(f32\[129,32,128,128\]\S* (copy|dynamic-update-slice|"
                rf"scatter|broadcast)|{rows}\S* (copy|dynamic-update-slice|"
                rf"broadcast))")
    pool = re.compile(rf"= {held}\(")
    assert "bf16[129,3,64,128]{3,2,1,0:T(8,128)(2,1)}" in text
    assert "bf16[129,3,8192]" not in text and "bf16[129,24576]" not in text
    assert not [line for line in text.splitlines() if pool.search(line)]


def test_qwen3_nexts_prefill_holds_no_triangular_solve(one_chip):
    """The cell's 512-token prefill compiled whole for the described
    v5e: the chunked rule inverts its chunks with batched products, so
    the program holds no ``triangular-solve`` and none of the custom
    calls XLA expands one into (``InvertDiagBlocksLowerTriangular``,
    which walks a block's rows one after another: 0.69 ms a layer on the
    chip). Its only custom calls on the device are the named Mosaic
    kernels; the four others are the compiler's own bookkeeping of
    buffers and gather indices and run nothing."""
    text, _ = _qwen3_next_compiled(one_chip, "prefill", 512)
    assert " triangular-solve(" not in text
    calls = [line for line in text.splitlines() if " custom-call(" in line]
    targets = {re.search(r'custom_call_target="([^"]+)"', line).group(1)
               for line in calls}
    assert targets - {"AllocateBuffer", "ConcatBitcast",
                      "AssumeGatherIndicesInBound",
                      "GatherScatterIndicesBitpacked"} == {"tpu_custom_call"}
    kernels = {re.search(r"(%[A-Za-z_]+)[.\d]* = ", line).group(1)
               for line in calls if '"tpu_custom_call"' in line}
    assert kernels == {"%moe_experts"}


JAMBA_POOL = 65537  # blocks: 128 lanes of 8,192 tokens and the trash block


@pytest.mark.parametrize("lanes,slots", [(128, 129), (1, 129), (8, 9)])
def test_mosaic_compiles_the_selective_scan_kernel(one_chip, lanes, slots):
    """``selective_scan_step`` at Jamba2-3B's widths (5,120 channels of 16
    states, a float32 state of 327,680 B a lane) for the described v5e:
    the pool is aliased input to output and the compiled program holds no
    copy of it and no scratch beside it."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models import selective_scan

    def shaped(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows, vectors = shaped((lanes, 5120)), shaped((lanes, 16))
    compiled = jax.jit(
        lambda u, delta, b, c, z, a, d_skip, at, pool:
        selective_scan.selective_scan_step(
            u, delta, b, c, z, a, d_skip, at, pool, kernel="pallas"),
        donate_argnums=(8,)).lower(
        rows, rows, vectors, vectors, rows, shaped((16, 5120)),
        shaped((5120,)), shaped((lanes,), jnp.int32),
        shaped((slots, 16, 5120))).compile()
    text = compiled.as_text()
    assert "%selective_scan_step" in text and "tpu_custom_call" in text
    memory = compiled.memory_analysis()
    pool_bytes = slots * 16 * 5120 * 4
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < max(pool_bytes // 8, 1 << 20)
    assert not [line for line in text.splitlines()
                if " copy(" in line and f"f32[{slots},16,5120]" in line]


@pytest.mark.parametrize("lanes,columns", [(128, 512), (1, 8)])
def test_mosaic_compiles_the_paged_kernel_at_jambas_shapes(
        one_chip, lanes, columns):
    """`jamba2_3b.reason8k_128` as the paged kernel sees it: ONE KV head
    of 128 under 20 query heads (20 query rows a lane, no head mask), flat
    pools whose page of 16 tokens is 4 KB, tiles of 64 pages (1,024
    tokens, a `[20, 1024]` score block a stop; a table narrower than a
    tile is one tile)."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models import paged_attention as pa

    assert pa.pages_per_tile(BLOCK, 1, HEAD_DIM, jnp.bfloat16, 2) == 64

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = shaped((JAMBA_POOL, BLOCK, HEAD_DIM), jnp.bfloat16)
    compiled = jax.jit(functools.partial(
        pa.paged_attention_pallas, kv_heads=1)).lower(
        shaped((lanes, 1, 20, HEAD_DIM), jnp.bfloat16), pool, pool,
        shaped((lanes, columns), jnp.int32),
        shaped((lanes, 1), jnp.int32)).compile()
    text = compiled.as_text()
    assert "%paged_attention" in text
    assert f"bf16[{JAMBA_POOL},{BLOCK},{HEAD_DIM}]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


def test_jambas_longest_prefill_and_decode_fit_the_chip(one_chip):
    """`jamba`'s 8,192-token prefill and its 128-lane decode step compiled
    whole for the described v5e at the cell's sizes: all 28 layers, all
    65,536 rows, a full group of 65,537 blocks, a state group of 129
    slots. The bound: 8.4 GB of arguments (6.06 of weights, 1.07 of K/V,
    1.20 of states) and under 3.3 GB of scratch in the prefill, 11.7 GB
    of the chip's 16; read here at 8,345,503,744 B of arguments,
    193,160,704 B of scratch in the decode step and 3,042,985,984 B in
    the prefill. No whole state pool (42 MB a layer) and no convolution
    pool (4 MB) is copied: every one is updated where it lies."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models import jamba, paged_attention
    from client_tpu.models.engine_model import Kernels

    config = jamba.JambaConfig()
    kernels = Kernels("pallas", paged_attention.paged_attention_pallas)

    def shaped(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = shaped(jax.eval_shape(
        lambda: jamba.init_params(jax.random.PRNGKey(0), config)))
    pages = shaped(jax.eval_shape(
        lambda: jamba.init_pages(config, [JAMBA_POOL, 129], BLOCK)))
    ints = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip)
    decode = jax.jit(
        lambda p, t, at, tables, pages: jamba.decode_step_paged(
            p, t, at, tables, pages, config, kernels),
        donate_argnums=(4,)).lower(
        params, ints(128), ints(128), ints(2, 128, 512), pages).compile()
    memory = decode.memory_analysis()
    assert memory.argument_size_in_bytes < 8.4e9
    assert memory.temp_size_in_bytes < 250e6
    text = decode.as_text()
    assert text.count("%selective_scan_step") >= 26
    assert text.count("%paged_attention") >= 2
    pool = re.compile(
        r"= (f32\[129,16,5120\]|bf16\[129,15360\])\S* "
        r"(copy|dynamic-update-slice|broadcast)\(")
    assert not [line for line in text.splitlines() if pool.search(line)]
    prefill = jax.jit(
        lambda p, t, table, pages, last: jamba.prefill_into_pages(
            p, t, table, pages, last, config, kernels),
        donate_argnums=(3,)).lower(
        params, ints(1, 8192), ints(2, 512), pages, ints()).compile()
    memory = prefill.memory_analysis()
    assert memory.argument_size_in_bytes < 8.4e9
    assert memory.temp_size_in_bytes < 3.3e9
    copied = re.compile(r"= (f32\[129,16,5120\]|bf16\[129,15360\])\S* copy\(")
    assert not [line for line in prefill.as_text().splitlines()
                if copied.search(line)]


#: phi4_mini_flash.reason8k's pools: the full group's 64 lanes of 8,192
#: tokens and the trash block, the window group's 64 rings of 40 blocks
#: (the window's 33 in whole tiles of 8)
PHI4_FULL, PHI4_RINGS = 32769, 1 + 64 * 40


@pytest.mark.parametrize("lanes,columns,window,blocks", [
    (64, 512, None, PHI4_FULL), (64, 512, 512, PHI4_RINGS),
    (1, 8, None, PHI4_FULL), (8, 64, 512, PHI4_RINGS)])
def test_mosaic_compiles_the_shared_value_call(
        one_chip, lanes, columns, window, blocks):
    """The paged kernel as `phi4flash` calls it (``keys_per_value`` 2): 40
    query heads of 64 over 20 key heads of 64 and 10 value heads of 128,
    the pools 10 rows of 128 a token each (a page 40,960 B in either, 6.4
    of them the budget's share of a slot, so a tile of 8 pages, 128
    tokens, 1.31 MB in the four buffers), with and without the window of
    512. The
    kernel is the one every other model runs, at KV 10 / D 128: the
    widening of the queries is XLA's, outside it."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models import paged_attention as pa

    assert pa.pages_per_tile(BLOCK, 10, 128, jnp.bfloat16, 2) == 8

    def shaped(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(functools.partial(
        pa.paged_attention_pallas, window=window, kv_heads=10,
        keys_per_value=2)).lower(
        shaped((lanes, 1, 40, 64)), shaped((blocks, BLOCK * 10, 128)),
        shaped((blocks, BLOCK * 10, 128)), shaped((lanes, columns), jnp.int32),
        shaped((lanes, 1), jnp.int32)).compile()
    text = compiled.as_text()
    assert "%paged_attention" in text
    assert f"bf16[{blocks},{BLOCK * 10},128]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)
    assert compiled.memory_analysis().output_size_in_bytes == (
        lanes * 40 * 128 * 2)


def test_phi4flashs_longest_prefill_and_decode_fit_the_chip(one_chip):
    """`phi4flash`'s 8,192-token prefill and its 64-lane decode step
    compiled whole for the described v5e at the cell's sizes: all 32
    layers, all 200,064 rows, ONE full pool of 32,769 blocks, eight ring
    pools of 2,561 (rings of 40 blocks: five tiles of 8), nine state
    pools of 65 slots, and NO pool for the fourteen layers of the
    cross-decoder. The bound: 12.4 GB of arguments (7.71 of weights, 2.68
    of the full pool, 1.68 of rings, 0.21 of states) and under 2.2 GB of
    scratch in the prefill, 14.6 GB of the chip's 16; read here at
    12,281,916,416 B of arguments (12,114,144,256 at PR 44's rings of
    36), 62,872,064 B of scratch in the decode step and 1,935,856,128 B
    in the prefill. Sixteen
    layers attend through the paged kernel, eight of them over the one
    pool, and that pool is never copied: it is updated where it lies, as
    every ring, state and convolution pool is."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models import paged_attention, phi4flash
    from client_tpu.models.engine_model import Kernels

    config = phi4flash.Phi4FlashConfig()
    kernels = Kernels("pallas", paged_attention.paged_attention_pallas)

    def shaped(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = shaped(jax.eval_shape(
        lambda: phi4flash.init_params(jax.random.PRNGKey(0), config)))
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert weights == 7_706_792_960
    pages = shaped(jax.eval_shape(lambda: phi4flash.init_pages(
        config, [PHI4_FULL, PHI4_RINGS, 65], BLOCK)))
    stored = [sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(layer))
              for layer in pages]
    assert stored[17] == PHI4_FULL * BLOCK * 5120  # one layer stores it
    assert stored[1] == PHI4_RINGS * BLOCK * 5120
    assert stored[0] == 65 * 358400 and not any(stored[18:])
    ints = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip)
    decode = jax.jit(
        lambda p, t, at, tables, pages: phi4flash.decode_step_paged(
            p, t, at, tables, pages, config, kernels),
        donate_argnums=(4,)).lower(
        params, ints(64), ints(64), ints(3, 64, 512), pages).compile()
    memory = decode.memory_analysis()
    assert memory.argument_size_in_bytes < 12.4e9
    assert memory.temp_size_in_bytes < 100e6
    text = decode.as_text()
    calls = re.findall(r"custom_call_target=\"tpu_custom_call\"", text)
    assert len(calls) == 16 + 9  # sixteen attention calls, nine scans
    assert text.count("%selective_scan_step") >= 9
    assert text.count("%paged_attention") >= 16
    pool = re.compile(
        rf"= (bf16\[{PHI4_FULL},160,128\]|bf16\[{PHI4_RINGS},160,128\]"
        r"|f32\[65,16,5120\]|bf16\[65,15360\])\S* "
        r"(copy|dynamic-update-slice|broadcast)\(")
    assert not [line for line in text.splitlines() if pool.search(line)]
    prefill = jax.jit(
        lambda p, t, table, pages, last: phi4flash.prefill_into_pages(
            p, t, table, pages, last, config, kernels),
        donate_argnums=(3,)).lower(
        params, ints(1, 8192), ints(3, 512), pages, ints()).compile()
    memory = prefill.memory_analysis()
    assert memory.argument_size_in_bytes < 12.4e9
    assert memory.temp_size_in_bytes < 2.2e9
    copied = re.compile(
        rf"= (bf16\[{PHI4_FULL},160,128\]|bf16\[{PHI4_RINGS},160,128\]"
        r"|f32\[65,16,5120\]|bf16\[65,15360\])\S* copy\(")
    assert not [line for line in prefill.as_text().splitlines()
                if copied.search(line)]


def test_ouros_rolled_decode_and_prefill_fit_the_chip(one_chip):
    """`ouro`'s 16-lane decode step and its 512-token prefill compiled
    whole for the described v5e at the cell's sizes: all 48 layers stacked
    and rolled (192 layer-passes a token), all 49,152 rows, ONE K and ONE
    V pool of 192 x 337 blocks. The bound: 14.0 GB of arguments (5.34 of
    weights, 8.48 of the pools), read here at 13,816,837,120 B with
    484,352 B of scratch in the decode step and 2,679,296 B in the
    prefill. The decode program holds ONE paged kernel for its 192 calls;
    neither program copies a pool (each is carried through both loops and
    updated where it lies) or a stacked weight (q, k and v weights held
    ``[in, out]`` cost a copy of all 48 layers' every step, 1.2 GB)."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models import ouro, paged_attention
    from client_tpu.models.engine_model import Kernels

    config = ouro.OuroConfig(max_seq_len=512)
    kernels = Kernels("pallas", paged_attention.paged_attention_pallas)
    blocks = 337

    def shaped(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = shaped(jax.eval_shape(
        lambda: ouro.init_params(jax.random.PRNGKey(0), config)))
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert weights == 5_335_949_314
    pages = shaped(jax.eval_shape(
        lambda: ouro.init_pages(config, [blocks], BLOCK)))
    assert sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(pages)) == 8_480_882_688
    ints = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip)
    copied = re.compile(
        rf"= bf16\[({192 * blocks},16,16,128|48,\d+,\d+)\]\S* "
        r"(copy|dynamic-update-slice|broadcast)\(")
    # the cell's step, and the warm-up probe's lone lane, whose write
    # would be a dynamic-update-slice (and the pools re-laid, 34 GB each)
    # if the program did not run it as the two-lane bucket
    for lanes, columns in ((16, 32), (1, 1)):
        decode = jax.jit(
            lambda p, t, at, tables, pages: ouro.decode_step_paged(
                p, t, at, tables, pages, config, kernels),
            donate_argnums=(4,)).lower(
            params, ints(lanes), ints(lanes), ints(lanes, columns),
            pages).compile()
        memory = decode.memory_analysis()
        assert memory.argument_size_in_bytes < 14.0e9
        assert memory.temp_size_in_bytes < 100e6
        text = decode.as_text()
        assert len(re.findall(
            r"custom_call_target=\"tpu_custom_call\"", text)) == 1
        assert text.count("%paged_attention") >= 1
        assert not [line for line in text.splitlines()
                    if copied.search(line)]
    prefill = jax.jit(
        lambda p, t, table, pages, last: ouro.prefill_into_pages(
            p, t, table, pages, last, config, kernels),
        donate_argnums=(3,)).lower(
        params, ints(1, 512), ints(32), pages, ints()).compile()
    memory = prefill.memory_analysis()
    assert memory.argument_size_in_bytes < 14.0e9
    assert memory.temp_size_in_bytes < 100e6
    assert not [line for line in prefill.as_text().splitlines()
                if copied.search(line)]
