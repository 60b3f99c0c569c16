"""The paged-attention kernel compiled by Mosaic for a v5e that is
described, not attached: what the interpreter cannot see (tiling, VMEM,
DMA shapes) is refused here, in seconds and at no chip time.

Nothing runs, so nothing here is a result or a time. The topology is
described inside a fixture (never at import: the TPU library belongs to
one process at a time, and every xdist worker imports this file), and
all such tests live in this one file.
"""

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
    if rows == 1:
        fn = pa.paged_attention_pallas
        q = shaped((batch, heads, HEAD_DIM), jnp.bfloat16)
        positions = shaped((batch,), jnp.int32)
    else:
        fn = pa.paged_attention_pallas_mq
        q = shaped((batch, rows, heads, HEAD_DIM), jnp.bfloat16)
        positions = shaped((batch, rows), jnp.int32)
    compiled = jax.jit(fn).lower(q, pool, pool, tables, positions).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    assert "%paged_attention" in text  # the name the trace readers select
    # the pools reach the kernel as they lie in HBM: re-viewed, not copied
    assert f"bf16[{blocks},{BLOCK * kv_heads},{HEAD_DIM}]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)
