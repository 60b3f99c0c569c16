"""The paged-attention kernel compiled by Mosaic for a v5e that is
described, not attached: what the interpreter cannot see (tiling, VMEM,
DMA shapes) is refused here, in seconds and at no chip time.

Nothing runs, so nothing here is a result or a time. The topology is
described inside a fixture (never at import: the TPU library belongs to
one process at a time, and every xdist worker imports this file), and
all such tests live in this one file.
"""

import functools

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
    # mimo_v2_flash.reason's two cache groups (K rows 192, V rows 128)
    "mimo-full": (64, 64, 4, 128, 8193, None, False),
    "mimo-window": (64, 64, 8, 128, 577, 128, True),
    "mimo-window-one-lane": (1, 64, 8, 8, 577, 128, True),
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


@functools.lru_cache(maxsize=None)
def _expert_layer_with_its_router(one_chip, tokens):
    """`moe.route` and `moe.expert_layer` under the load-time choice
    ``pallas`` at ``mimo_v2_flash.reason``'s sizes (16 held of 256 experts
    of 4096 x 2048 in bf16, 8 a token), compiled for the described v5e."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models import moe

    d, f, held, top_k = 4096, 2048, (0, 16), 8

    def shaped(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(h, router, bias, experts):
        ids, weights = moe.route(h, router, bias, top_k)
        return moe.expert_layer(h, ids, weights, experts, held,
                                kernel="pallas")

    experts = {"w_gate": shaped((16, d, f)), "w_up": shaped((16, d, f)),
               "w_down": shaped((16, f, d))}
    return jax.jit(layer).lower(
        shaped((tokens, d)), shaped((d, 256)), shaped((256,), jnp.float32),
        experts).compile()


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


@pytest.mark.parametrize("tokens", [1, 16, 64, 128, 512, 2048])
def test_mosaic_compiles_the_expert_kernel(one_chip, tokens):
    """Mosaic's verdict on both ``moe_experts`` kernels: a decode batch
    (one lane, a sublane tile, the cell's 64 lanes, the 128 rows up to
    which rows stay resident) takes the resident kernel and holds no
    more than a few ``T x d`` buffers beside it; a 512- and a
    2,048-token prefill are planned in row tiles of 128."""
    from client_tpu.models import moe

    compiled = _expert_layer_with_its_router(one_chip, tokens)
    text, d = compiled.as_text(), 4096
    assert "%moe_experts" in text
    temp = compiled.memory_analysis().temp_size_in_bytes
    if tokens <= moe._RESIDENT_ROWS:
        rows = -(-tokens // 16) * 16
        assert f"f32[{rows},{d}]" in text  # the kernel's own output
        assert f"bf16[{rows * 8}," not in text  # no row a pair
        assert temp < 3 * 4 * rows * d
    else:
        # the rows gathered by expert and their outputs, no dense pass
        rows = moe._ROW_TILE * (-(-tokens * 8 // moe._ROW_TILE) + 16)
        assert f"bf16[{rows},{d}]" in text
        assert temp < 8 * rows * d


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
