"""Real-device (TPU) test tier — select with:

    CLIENT_TPU_TEST_PLATFORM=tpu python -m pytest tests/ -m tpu -q

Covers what the hermetic CPU suite cannot see: the Pallas paged-attention
kernels COMPILED by Mosaic (the CPU tier only interprets them) against
the fused XLA reference at the shapes the server serves, a full-width
decode step through both, ``mimo_v2_flash.reason``'s attention groups
and expert layer at the cell's sizes, ``gigachat3_702b.reason8k_128``'s
one-pool latent call, expert layer and decode step,
``qwen3_next_80b.reason2k_128``'s state-update kernel, chunked rule,
paged call and decode step, ``jamba2_3b.reason8k_128``'s scan kernel,
chunked scan, paged call and decode step, the client→server infer path
executing on the real platform, and the tpu-shm staging round-trip.
``python chip_smoke.py`` runs this tier on the chip as one of its phases.
"""

import asyncio

import numpy as np
import pytest

pytestmark = pytest.mark.tpu

# the widths chip_smoke.py serves (Llama-2-7B): 32 heads of 128, block 16
HEADS, HEAD_DIM, BLOCK = 32, 128, 16


@pytest.fixture(scope="module")
def device():
    """The accelerator. Asking for the device tier where there is none
    is a FAILURE, not a skip: a tier that skips itself passes vacuously."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        pytest.fail(
            "CLIENT_TPU_TEST_PLATFORM asks for the device tier but JAX "
            f"found no accelerator (devices: {jax.devices()})"
        )
    return dev


# ---------------------------------------------------------------------------
# compiled Pallas kernels vs the fused XLA reference
# ---------------------------------------------------------------------------


def _ragged_pool(rng, batch, kv_heads, n_blocks, rows, last=None):
    """A bf16 block pool plus ragged page tables: sequence ``b`` owns just
    the blocks its context needs (the rest of its row is the trash block
    0). Contexts are random unless ``last`` gives them, with the longest
    filling the table and the shortest one token, so full, partial and
    all-trash rows all occur.
    Returns (k_pages, v_pages, tables, last) with ``last[b]`` the context's
    final position less ``rows - 1`` of headroom for verify rows."""
    import jax.numpy as jnp

    limit = n_blocks * BLOCK - rows
    if last is None:
        last = rng.integers(0, limit + 1, size=batch)
        last[0] = limit
        if batch > 1:
            last[-1] = 0
    last = np.asarray(last)
    shape = (1 + batch * n_blocks, BLOCK, kv_heads, HEAD_DIM)
    k_pages = jnp.asarray(rng.normal(size=shape), dtype=jnp.bfloat16)
    v_pages = jnp.asarray(rng.normal(size=shape), dtype=jnp.bfloat16)
    tables = np.zeros((batch, n_blocks), dtype=np.int32)
    for b in range(batch):
        owned = (int(last[b]) + rows - 1) // BLOCK + 1
        tables[b, :owned] = 1 + b * n_blocks + np.arange(owned)
    return k_pages, v_pages, tables, last.astype(np.int32)


def _assert_bf16_close(out, ref, what):
    """Both sides sum in float32 with bf16 MXU operands, in different
    orders (one online softmax over tiles of pages, one full-width), and
    round the result to bf16, whose spacing is 2^-7 relative at worst. Two
    such steps at the output's largest magnitude are allowed; a wrong
    mask, head mapping or page shows as O(0.1..1)."""
    out = np.asarray(out, dtype=np.float32)
    ref = np.asarray(ref, dtype=np.float32)
    assert out.shape == ref.shape and np.isfinite(out).all(), what
    tolerance = 2.0 ** -6 * max(1.0, float(np.abs(ref).max()))
    worst = float(np.abs(out - ref).max())
    assert worst <= tolerance, f"{what}: max |diff| {worst} > {tolerance}"


SHAPES = [(1, 1), (4, 24), (8, 128)]  # (batch, page-table width): to 2048


@pytest.mark.parametrize("kv_heads", [32, 8], ids=["mha", "gqa"])
@pytest.mark.parametrize("batch,n_blocks", SHAPES)
def test_compiled_pallas_decode_matches_fused_xla(device, kv_heads, batch,
                                                  n_blocks):
    import jax
    import jax.numpy as jnp

    from client_tpu.models import paged_attention as pa

    rng = np.random.default_rng(batch * 1000 + n_blocks + kv_heads)
    k_pages, v_pages, tables, positions = _ragged_pool(
        rng, batch, kv_heads, n_blocks, rows=1
    )
    q = jnp.asarray(
        rng.normal(size=(batch, 1, HEADS, HEAD_DIM)), dtype=jnp.bfloat16
    )
    args = (q, k_pages, v_pages, tables, positions[:, None])
    _assert_bf16_close(
        jax.jit(pa.paged_attention_pallas)(*args),
        jax.jit(pa.paged_attention_xla)(*args),
        f"single-query KV={kv_heads} B={batch} NB={n_blocks}",
    )


@pytest.mark.parametrize("kv_heads", [32, 8], ids=["mha", "gqa"])
@pytest.mark.parametrize("batch,n_blocks", SHAPES)
def test_compiled_pallas_verify_matches_fused_xla(device, kv_heads, batch,
                                                  n_blocks):
    """The multi-query (speculative verify) kernel at T = k+1 = 5, with a
    padding lane whose rows clamp to its last real position."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models import paged_attention as pa

    rows = 5
    rng = np.random.default_rng(batch * 1000 + n_blocks + kv_heads + 7)
    k_pages, v_pages, tables, first = _ragged_pool(
        rng, batch, kv_heads, n_blocks, rows=rows
    )
    lengths = rng.integers(1, rows + 1, size=batch)
    lengths[0] = rows
    positions = (
        first[:, None]
        + np.minimum(np.arange(rows)[None, :], (lengths - 1)[:, None])
    ).astype(np.int32)
    q = jnp.asarray(
        rng.normal(size=(batch, rows, HEADS, HEAD_DIM)), dtype=jnp.bfloat16
    )
    args = (q, k_pages, v_pages, tables, positions)
    _assert_bf16_close(
        jax.jit(pa.paged_attention_pallas)(*args),
        jax.jit(pa.paged_attention_xla)(*args),
        f"multi-query KV={kv_heads} B={batch} NB={n_blocks}",
    )


@pytest.mark.parametrize("rows", [1, 5], ids=["decode", "verify"])
def test_compiled_pallas_at_the_benchmark_cells_shapes(device, rows):
    """``mistral7b.batch`` as the kernel sees it: 16 lanes, GQA 32/8, a
    64-column table, contexts staggered over 512..1,024 (so every lane
    stops at another tile, and the columns past its last block hold the
    trash block), and two padding lanes at position 0."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models import paged_attention as pa

    batch, n_blocks, kv_heads = 16, 64, 8
    rng = np.random.default_rng(26 + rows)
    last = 511 + 32 * np.arange(batch) + rng.integers(0, 32, size=batch)
    last = np.minimum(last, n_blocks * BLOCK - rows)
    last[-2:] = 0
    k_pages, v_pages, tables, first = _ragged_pool(
        rng, batch, kv_heads, n_blocks, rows=rows, last=last
    )
    tables[-2:] = 0
    positions = (first[:, None] + np.arange(rows)[None, :]).astype(np.int32)
    positions[-2:] = 0
    q = jnp.asarray(
        rng.normal(size=(batch, rows, HEADS, HEAD_DIM)), dtype=jnp.bfloat16
    )
    args = (q, k_pages, v_pages, tables, positions)
    _assert_bf16_close(
        jax.jit(pa.paged_attention_pallas)(*args),
        jax.jit(pa.paged_attention_xla)(*args),
        f"the cell's shapes, T={rows}",
    )


def test_tp_sharded_pallas_matches_unsharded_fused_xla(device):
    """``make_tp_attention`` over four chips: each runs the compiled
    kernel on its 8 heads and its kv-head shard of the pool (below the
    bf16 sublane tile), single- and multi-query."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from client_tpu.models import paged_attention as pa

    if len(jax.devices()) < 4:
        pytest.skip(f"needs 4 devices, found {len(jax.devices())}")
    mesh = Mesh(np.array(jax.devices()[:4]), ("tp",))
    rng = np.random.default_rng(4)
    batch, n_blocks, rows = 4, 24, 5
    k_pages, v_pages, tables, first = _ragged_pool(
        rng, batch, HEADS, n_blocks, rows=rows
    )
    q = jnp.asarray(
        rng.normal(size=(batch, rows, HEADS, HEAD_DIM)), dtype=jnp.bfloat16
    )
    positions = (first[:, None] + np.arange(rows)[None, :]).astype(np.int32)
    sharded = jax.jit(pa.make_tp_attention(pa.paged_attention_pallas, mesh))
    for what, t in (("single-query", 1), ("multi-query", rows)):
        args = (q[:, :t], k_pages, v_pages, tables, positions[:, :t])
        _assert_bf16_close(
            sharded(*args), jax.jit(pa.paged_attention_xla)(*args),
            f"tp=4 {what}",
        )


def test_full_width_decode_logits_match_through_both_kernels(device):
    """Prefill then decode through the paged cache at the Llama-2-7B
    widths (two layers): the logits of the Pallas path agree with the
    fused XLA path. Logits, not sampled tokens — over random weights the
    largest logit changes on rounding."""
    import functools

    import jax

    from client_tpu.models import llama
    from client_tpu.models import paged_attention as pa

    config = llama.LlamaConfig(n_layers=2, max_seq_len=512)
    params = llama.init_params(jax.random.PRNGKey(0), config)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, config.vocab_size, size=n) for n in (40, 7)]
    pages = llama.init_kv_pages(config, 1 + 4 * len(prompts), BLOCK)
    tables = np.zeros((len(prompts), 4), dtype=np.int32)
    prefill = jax.jit(
        functools.partial(llama.prefill_into_pages, config=config)
    )
    for i, prompt in enumerate(prompts):
        tables[i] = 1 + 4 * i + np.arange(4)
        tokens = np.zeros([1, 64], dtype=np.int32)
        tokens[0, : len(prompt)] = prompt
        _, pages = prefill(
            params, tokens, tables[i], pages, np.int32(len(prompt) - 1)
        )
    tokens = np.array([3, 5], dtype=np.int32)
    positions = np.array([len(p) for p in prompts], dtype=np.int32)

    def step(attn):
        fn = jax.jit(
            lambda p, t, pos, tbl, pg: llama.decode_step_paged_attn(
                p, t, pos, tbl, pg, config, attn
            )
        )
        return fn(params, tokens, positions, tables, pages)[0]

    pallas = np.asarray(step(pa.paged_attention_pallas))
    fused = np.asarray(step(pa.paged_attention_xla))
    assert pallas.shape == (2, config.vocab_size)
    assert np.isfinite(pallas).all()
    # The two attention outputs differ by bf16 roundings (see
    # _assert_bf16_close) that pass through two more bf16 layers; logits
    # over these weights are O(1), so a few bf16 steps of absolute error.
    # Reading the wrong pages moves them by O(1).
    worst = float(np.abs(pallas - fused).max())
    assert worst <= 2.0 ** -4, f"logits differ by {worst}"


# ---------------------------------------------------------------------------
# mimo_v2_flash.reason's kernels at the cell's shapes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("group", ["full", "window"])
def test_compiled_pallas_at_the_mimo_cells_shapes(device, group):
    """``mimo_v2_flash.reason`` as the kernel sees it: 64 lanes, 64 query
    heads over K rows of 256 (192 and zeros) and V rows of 128 in flat
    pools, contexts staggered over 512..2,048; the full group's 4 KV
    heads over every block, the window group's 8 over a ring of 9
    blocks a lane behind the engine's own window tables, with a sink.
    Prints the kernel's ms a call and us a lane."""
    import jax
    import jax.numpy as jnp

    from client_tpu.llm.kv_cache import window_ring_blocks, window_tables
    from client_tpu.models import paged_attention as pa

    batch, heads, columns = 64, 64, 128
    rng = np.random.default_rng(27)
    positions = (511 + 24 * np.arange(batch)).astype(np.int32)
    masking = {"scale": 192 ** -0.5}
    if group == "full":
        kv_heads, blocks = 4, 1 + batch * columns
        tables = (1 + np.arange(batch * columns)).reshape(batch, columns)
    else:
        ring = window_ring_blocks(128, BLOCK)
        kv_heads, blocks = 8, 1 + batch * ring
        tables = window_tables(
            1 + np.arange(batch * ring).reshape(batch, ring),
            positions // BLOCK, columns)
        masking.update(
            window=128,
            sink=jnp.asarray(2 * rng.normal(size=heads), jnp.float32))
    masking["kv_heads"] = kv_heads

    def rows(shape, stored, real):
        full = np.zeros(shape + (stored,), np.float32)
        full[..., :real] = rng.normal(size=shape + (real,))
        return jnp.asarray(full, jnp.bfloat16)

    q = rows((batch, 1, heads), 256, 192)
    k_pages = rows((blocks, BLOCK * kv_heads), 256, 192)
    v_pages = rows((blocks, BLOCK * kv_heads), 128, 128)
    args = (q, k_pages, v_pages, tables.astype(np.int32), positions[:, None])
    kernel = jax.jit(lambda *a: pa.paged_attention_pallas(*a, **masking))
    _assert_bf16_close(
        kernel(*args),
        jax.jit(lambda *a: pa.paged_attention_xla(*a, **masking))(*args),
        f"mimo's {group} group",
    )
    print(f"mimo {group} group: {_lone_call(kernel, *args)}")


def _ms_a_call(fn, *args, calls=20, repeats=3):
    """Milliseconds a call of a jitted function, the best of ``repeats``
    means over ``calls`` back-to-back calls (compiled before)."""
    import time

    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(repeats):
        began = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - began) / calls)
    return 1e3 * best


def _lone_call(kernel, q, *rest):
    """``"<ms> ms a call, <us> us a lane"`` of a paged call alone on the
    chip, by the HOST's clock around back-to-back calls: what a lane (a
    grid step: its walk and what it does outside it) costs, to hold
    against the lane's bytes. A jit of one call is not dispatched faster
    than ~0.43 ms, so a call with less device work than that reads the
    floor (the three window calls: 0.43-0.46 ms for 0.13-0.37 on the
    device, PERF.md, PR 46); such a call's device time comes from a
    trace."""
    ms = _ms_a_call(kernel, q, *rest)
    return f"{ms:.3f} ms a call, {1e3 * ms / q.shape[0]:.2f} us a lane"


@pytest.mark.parametrize("tokens,kernel", [
    (64, "pallas"), (128, "pallas"), (512, "pallas"), (2048, "pallas"),
    (64, "fused_xla"),
])
def test_expert_layer_matches_a_dense_pass(device, tokens, kernel,
                                           monkeypatch):
    """``moe.expert_layer`` at the cell's sizes (16 held of 256 experts
    of 4096 x 2048, 8 a token; a decode step's 64 lanes, the 128 rows up
    to which a call keeps its rows resident, and a 512- and a
    2,048-token prefill) through the compiled ``moe_experts`` kernels,
    and a decode step through the plain XLA path, against every held
    expert run over every token and kept where the router chose it.

    Where the call is short enough to keep its rows resident, the whole
    layer (router's outputs in, ``[T, d]`` out) is also timed: planned
    as before (``_RESIDENT_ROWS`` 0, row tiles of 16) it must give the
    same and take longer, and so must the dense pass. That is the
    measured reason beside ``moe._RESIDENT_ROWS``."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models import moe

    d, f, held = 4096, 2048, (0, 16)
    keys = iter(jax.random.split(jax.random.PRNGKey(tokens), 6))

    def normal(shape, scale, dtype=jnp.bfloat16):
        # drawn on the device: 400M numbers from the host's generator
        # and their transfer cost this tier minutes
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    experts = {"w_gate": normal((16, d, f), d ** -0.5),
               "w_up": normal((16, d, f), d ** -0.5),
               "w_down": normal((16, f, d), f ** -0.5)}
    router = normal((d, 256), d ** -0.5)
    bias = normal((256,), 0.02, jnp.float32)
    h = normal((tokens, d), 1.0)
    ids, weights = jax.jit(lambda h: moe.route(h, router, bias, 8))(h)

    def dense(h, ids, weights, experts):
        out = jnp.zeros(h.shape, jnp.float32)
        for e in range(held[1]):
            share = (weights * (ids == e)).sum(-1, keepdims=True)
            gate = jax.nn.silu(jnp.dot(
                h, experts["w_gate"][e], preferred_element_type=jnp.float32))
            up = jnp.dot(
                h, experts["w_up"][e], preferred_element_type=jnp.float32)
            out = out + share * jnp.dot(
                (gate * up).astype(h.dtype), experts["w_down"][e],
                preferred_element_type=jnp.float32)
        return out

    def layer():
        return jax.jit(lambda *a: moe.expert_layer(
            *a, held, kernel=kernel))

    args = (h, ids, weights, experts)
    out, counters = layer()(*args)
    on = np.asarray(ids) < held[1]
    resident = kernel == "pallas" and tokens <= moe._RESIDENT_ROWS
    assert int(counters[0]) == on.sum() > 0
    assert int(counters[3]) == resident
    wanted = jax.jit(dense)(*args)
    _assert_bf16_close(
        out, wanted, f"expert layer, {tokens} tokens, {kernel}")
    if not resident:
        return
    took = {"resident": _ms_a_call(layer(), *args),
            "dense": _ms_a_call(jax.jit(dense), *args)}
    monkeypatch.setattr(moe, "_RESIDENT_ROWS", 0)
    monkeypatch.setattr(moe, "_ROW_TILE", 16)
    planned, planned_counters = layer()(*args)
    assert int(planned_counters[3]) == 0
    assert (np.asarray(planned_counters[:3])
            == np.asarray(counters[:3])).all()
    _assert_bf16_close(planned, wanted, f"planned, {tokens} tokens")
    took["planned"] = _ms_a_call(layer(), *args)
    print(f"expert layer, {tokens} rows, ms a call: {took}")
    assert took["resident"] < took["planned"], took
    assert took["resident"] < took["dense"], took


# ---------------------------------------------------------------------------
# trinity_mini.reason8k's kernels and its whole step at the cell's shapes
# ---------------------------------------------------------------------------


def _device_normal(key, shape, scale, dtype=None):
    import jax
    import jax.numpy as jnp

    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
        dtype or jnp.bfloat16)


def _trinity_group(group):
    """``trinity_mini.reason8k`` as the kernel sees one cache group: 64
    lanes, 32 query heads over 4 KV heads of 128 in flat pools, contexts
    staggered over 512..8,192 behind a page table of 512 columns; the
    full group over every block of a lane, the window group's window of
    2,048 over a ring of 129 blocks a lane behind the engine's own
    window tables. Every lane's blocks are a run of consecutive pool
    pages. Returns (q, k_pages, v_pages, tables, positions[B, 1]) and
    the masking arguments."""
    import jax

    from client_tpu.llm.kv_cache import window_ring_blocks, window_tables

    batch, heads, kv_heads, columns = 64, 32, 4, 512
    positions = (511 + 120 * np.arange(batch)).astype(np.int32)
    masking = {"kv_heads": kv_heads}
    if group == "full":
        owned = positions // BLOCK + 1
        starts = 1 + np.concatenate([[0], np.cumsum(owned)[:-1]])
        blocks = 1 + int(owned.sum())
        tables = np.zeros((batch, columns), np.int32)
        for lane in range(batch):
            tables[lane, :owned[lane]] = starts[lane] + np.arange(owned[lane])
    else:
        ring = window_ring_blocks(2048, BLOCK)
        assert ring == 129
        blocks = 1 + batch * ring
        tables = window_tables(
            1 + np.arange(batch * ring).reshape(batch, ring),
            positions // BLOCK, columns)
        masking["window"] = 2048
    keys = jax.random.split(jax.random.PRNGKey(31), 3)
    q = _device_normal(keys[0], (batch, 1, heads, HEAD_DIM), 1.0)
    k_pages = _device_normal(keys[1], (blocks, BLOCK * kv_heads, HEAD_DIM), 1.0)
    v_pages = _device_normal(keys[2], (blocks, BLOCK * kv_heads, HEAD_DIM), 1.0)
    args = (q, k_pages, v_pages, tables.astype(np.int32), positions[:, None])
    return args, masking


@pytest.mark.parametrize("group", ["full", "window"])
def test_compiled_pallas_at_the_trinity_cells_shapes(device, group):
    """The kernel against plain XLA on :func:`_trinity_group`. Prints the
    kernel's ms a call and us a lane beside plain XLA's ms."""
    import jax

    from client_tpu.models import paged_attention as pa

    args, masking = _trinity_group(group)
    kernel = jax.jit(lambda *a: pa.paged_attention_pallas(*a, **masking))
    plain = jax.jit(lambda *a: pa.paged_attention_xla(*a, **masking))
    _assert_bf16_close(kernel(*args), plain(*args), f"trinity's {group} group")
    print(f"trinity {group} group: pallas {_lone_call(kernel, *args)}, "
          f"xla {_ms_a_call(plain, *args):.3f} ms a call")


def _shuffled_pool(rng, k_pages, v_pages, tables):
    """The same contents behind a shuffled table: every pool page but
    the trash block moves to a random place and the table follows, so
    that no two columns of a tile hold neighbours."""
    moved = np.concatenate([[0], 1 + rng.permutation(len(k_pages) - 1)])
    back = np.argsort(moved)  # new_pool[moved[p]] = pool[p]
    return k_pages[back], v_pages[back], moved[tables].astype(np.int32)


@pytest.mark.parametrize("group", ["full", "window"])
def test_a_whole_tile_is_one_copy_a_pool_at_the_trinity_cells_shapes(
        device, group):
    """The kernel's two ways of fetching a tile on :func:`_trinity_group`:
    its table of consecutive pages (every tile whole but where a ring's
    wrap falls inside one: one copy a pool) and the same contents behind
    a shuffled table (page by page, 16 copies a pool, every tile but
    those with one live column, which nothing can scatter). The
    arithmetic on a tile does not know how it came, so the bits are
    equal. Prints ms a call and us a tile stop for both paths."""
    import jax

    from client_tpu.models import paged_attention as pa

    args, masking = _trinity_group(group)
    q, k_pages, v_pages, tables, positions = args
    shuffled = _shuffled_pool(
        np.random.default_rng(33), k_pages, v_pages, tables)
    kernel = jax.jit(lambda *a: pa.paged_attention_pallas(*a, **masking))
    pages = pa.pages_per_tile(BLOCK, 4, HEAD_DIM, k_pages.dtype)
    first, lengths = pa.visible_slots(positions, masking.get("window"))
    outs = []
    for layout, (k, v, table) in (("consecutive", (k_pages, v_pages, tables)),
                                  ("shuffled", shuffled)):
        walked, whole = pa.count_tiles(
            table, first, lengths, pages, BLOCK, len(k_pages))
        ms = _ms_a_call(kernel, q, k, v, table, positions)
        outs.append(np.asarray(kernel(q, k, v, table, positions)))
        print(f"trinity {group} group, {layout} table: {whole} of {walked} "
              f"tile stops whole, {ms:.3f} ms a call, "
              f"{1e3 * ms / walked:.3f} us a stop")
    assert (outs[0] == outs[1]).all()


@pytest.mark.parametrize("tokens,kernel", [
    (64, "pallas"), (512, "pallas"), (8192, "pallas"), (64, "fused_xla"),
])
def test_trinitys_expert_layer_matches_a_dense_pass(device, tokens, kernel):
    """``moe.expert_layer`` at the cell's sizes (16 held of 128 experts
    of 2048 x 1024, 8 a token at ``route_scale`` 2.826, and the shared
    expert): a decode step's 64 lanes resident, the 512- and the
    8,192-token prefill planned, and the plain XLA path, against every
    held expert run over every token and kept where the router chose it,
    with the shared expert added once. Prints the layer's ms a call."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models import moe

    d, f, held = 2048, 1024, (0, 16)
    keys = iter(jax.random.split(jax.random.PRNGKey(tokens), 9))
    swiglu = lambda *lead: {  # noqa: E731
        "w_gate": _device_normal(next(keys), lead + (d, f), d ** -0.5),
        "w_up": _device_normal(next(keys), lead + (d, f), d ** -0.5),
        "w_down": _device_normal(next(keys), lead + (f, d), f ** -0.5)}
    experts, shared = swiglu(16), swiglu()
    router = _device_normal(next(keys), (d, 128), d ** -0.5)
    bias = _device_normal(next(keys), (128,), 0.02, jnp.float32)
    h = _device_normal(next(keys), (tokens, d), 1.0)
    ids, weights = jax.jit(
        lambda h: moe.route(h, router, bias, 8, scale=2.826))(h)

    def one(h, w):
        gate = jax.nn.silu(jnp.dot(
            h, w["w_gate"], preferred_element_type=jnp.float32))
        up = jnp.dot(h, w["w_up"], preferred_element_type=jnp.float32)
        return jnp.dot((gate * up).astype(h.dtype), w["w_down"],
                       preferred_element_type=jnp.float32)

    def dense(h, ids, weights, experts, shared):
        out = one(h, shared)
        for e in range(held[1]):
            share = (weights * (ids == e)).sum(-1, keepdims=True)
            out = out + share * one(
                h, {name: experts[name][e] for name in experts})
        return out

    layer = jax.jit(lambda h, ids, weights, experts, shared: moe.expert_layer(
        h, ids, weights, experts, held, kernel=kernel, shared=shared))
    args = (h, ids, weights, experts, shared)
    out, counters = layer(*args)
    assert int(counters[0]) == (np.asarray(ids) < held[1]).sum() > 0
    assert int(counters[3]) == (kernel == "pallas" and tokens <= 128)
    _assert_bf16_close(out, jax.jit(dense)(*args),
                       f"trinity's expert layer, {tokens} tokens, {kernel}")
    print(f"trinity expert layer, {tokens} rows, {kernel}, touched "
          f"{int(counters[1])} of 16: {_ms_a_call(layer, *args):.3f} ms a call")


def test_trinitys_decode_step_agrees_through_both_kernel_choices(device):
    """`afmoe`'s whole decode step at the published widths (one period
    of the layer pattern with both dense layers: two expert layers; 16
    held experts) through the load-time choices ``pallas`` and
    ``fused_xla``: the logits agree to a few bf16 steps, with contexts
    on both sides of the window."""
    import jax

    from client_tpu.llm.kv_cache import window_ring_blocks, window_tables
    from client_tpu.models import afmoe, paged_attention as pa
    from client_tpu.models.engine_model import Kernels

    config = afmoe.AfmoeConfig(vocab_size=4096, held=(0, 16))
    params = afmoe.init_params(jax.random.PRNGKey(5), config)
    lanes, columns = 4, 256
    positions = np.array([17, 1500, 2047, 4000], np.int32)
    ring = window_ring_blocks(config.window, BLOCK)
    full = (1 + np.arange(lanes * columns)).reshape(lanes, columns)
    tables = np.stack([full, window_tables(
        1 + np.arange(lanes * ring).reshape(lanes, ring),
        positions // BLOCK, columns)]).astype(np.int32)
    keys = jax.random.split(jax.random.PRNGKey(6), 8)
    pages = [
        tuple(_device_normal(keys[2 * kind + side], (
            1 + lanes * (ring if kind else columns),
            BLOCK * config.n_kv_heads, config.head_dim), 1.0)
            for side in (0, 1))
        for kind in config.layer_kinds]
    tokens = np.array([5, 6, 7, 8], np.int32)

    def step(name, attn):
        logits, _, counters = jax.jit(
            lambda *a: afmoe.decode_step_paged(
                *a, config, Kernels(name, attn)))(
            params, tokens, positions, tables, pages)
        return np.asarray(logits), np.asarray(counters)

    kernel, counted = step("pallas", pa.paged_attention_pallas)
    plain, plain_counted = step("fused_xla", pa.paged_attention_xla)
    assert counted[3] == 2 and plain_counted[3] == 0
    assert (counted[:3] == plain_counted[:3]).all()
    assert np.isfinite(kernel).all() and np.abs(plain).max() > 1.0
    worst = float(np.abs(kernel - plain).max())
    assert worst <= 2.0 ** -4 * max(1.0, float(np.abs(plain).max())), worst


# ---------------------------------------------------------------------------
# gigachat3_702b.reason8k_128's kernels and its whole step at the cell's shapes
# ---------------------------------------------------------------------------


def _latent_lanes():
    """``gigachat3_702b.reason8k_128`` as the kernel sees a layer: 128
    lanes, 64 query heads whose rows ``[q_lat 512 | q_rope 64 | zeros]``
    all read ONE pool of 640-wide rows at KV 1, contexts staggered over
    512..8,132 behind a page table of 512 columns, every lane's blocks
    consecutive pool pages in whole runs of the kernel's tile, as the
    engine's allocator hands them out. Returns (q, pool, tables,
    positions[B, 1]), the call's arguments and the tile in pages."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models import paged_attention as pa

    batch, heads, columns = 128, 64, 512
    tile = pa.pages_per_tile(BLOCK, 1, 640, jnp.bfloat16, 1)
    positions = (511 + 60 * np.arange(batch)).astype(np.int32)
    owned = positions // BLOCK + 1
    held = -(-owned // tile) * tile
    starts = 1 + np.concatenate([[0], np.cumsum(held)[:-1]])
    tables = np.zeros((batch, columns), np.int32)
    for lane in range(batch):
        tables[lane, :owned[lane]] = starts[lane] + np.arange(owned[lane])
    keys = jax.random.split(jax.random.PRNGKey(41), 2)
    q = _device_normal(keys[0], (batch, 1, heads, 640), 1.0)
    pool = _device_normal(keys[1], (1 + int(held.sum()), BLOCK, 640), 1.0)
    asked = {"scale": 0.14468, "kv_heads": 1, "v_width": 512}
    return (q, pool, tables, positions[:, None]), asked, tile


def test_the_latent_call_at_the_gigachat_cells_shapes(device):
    """The one-pool call compiled by Mosaic against plain XLA on
    :func:`_latent_lanes`, on its table of consecutive pages (whole
    tiles: one copy each) and on the same contents behind a shuffled
    table (64 page copies a tile): equal bits both ways, a few bf16
    steps from XLA. Prints ms a call, us a tile stop, ns a cached token
    (stops of different lengths do not compare; a token does), the live
    share of the slots the stops fetch, and the call's share of the
    longer of its bytes (a row read once, counted at 576) and its FLOPs
    (64 heads x (576 + 512) x 2 a cached token)."""
    import jax

    from client_tpu.models import paged_attention as pa

    (q, pool, tables, positions), asked, pages = _latent_lanes()
    # the tile the call runs at: 1,024 cached tokens a stop
    assert pages == 64
    kernel = jax.jit(lambda q, pool, tables, positions: (
        pa.paged_attention_pallas(q, pool, None, tables, positions, **asked)))
    plain = jax.jit(lambda q, pool, tables, positions: (
        pa.paged_attention_xla(q, pool, None, tables, positions, **asked)))
    moved = np.concatenate([[0], 1 + np.random.default_rng(43).permutation(
        len(pool) - 1)])
    shuffled = (pool[np.argsort(moved)], moved[tables].astype(np.int32))
    first, lengths = pa.visible_slots(positions, None)
    tokens = int(lengths.sum())
    least_ms = 1e3 * max(tokens * 576 * 2 / 819e9,
                         tokens * 64 * (576 + 512) * 2 / 197e12)
    outs = []
    for layout, (pages_, table) in (("consecutive", (pool, tables)),
                                    ("shuffled", shuffled)):
        walked, whole = pa.count_tiles(
            table, first, lengths, pages, BLOCK, len(pool))
        if layout == "consecutive":
            assert whole == walked  # runs of the tile: every stop whole
        ms = _ms_a_call(kernel, q, pages_, table, positions)
        outs.append(np.asarray(kernel(q, pages_, table, positions)))
        print(f"gigachat latent call, tiles of {pages} pages, {layout} "
              f"table: {whole} of {walked} tile stops whole, "
              f"{ms:.3f} ms a call, {1e3 * ms / walked:.3f} us a stop, "
              f"{1e6 * ms / tokens:.3f} ns a cached token, "
              f"{100 * tokens / (walked * pages * BLOCK):.1f}% of the "
              f"fetched slots live, "
              f"{100 * least_ms / ms:.1f}% of max(bytes, FLOPs)")
    assert (outs[0] == outs[1]).all()
    _assert_bf16_close(outs[0], plain(q, pool, tables, positions),
                       "gigachat's latent call")
    print(f"gigachat latent call, plain XLA: "
          f"{_ms_a_call(plain, q, pool, tables, positions, calls=5):.3f} "
          f"ms a call")


@pytest.mark.parametrize("tokens,kernel", [
    (128, "pallas"), (512, "pallas"), (2048, "pallas"), (128, "fused_xla"),
])
def test_gigachats_expert_layer_matches_a_dense_pass(device, tokens, kernel):
    """``moe.expert_layer`` at the cell's sizes (16 held of 256 experts of
    7168 x 2048 routed in 8 groups of which 4 are kept, 8 a token at the
    routed scale 2.5, and the shared expert): a decode step's 128 lanes
    resident (grid steps of 256 columns), a 512-token prefill and a long
    prompt's 2,048-row chunk planned, and the plain XLA path, against
    every held expert run over every token and kept where the router
    chose it, with the shared expert added once. Prints the layer's ms
    a call and the touched experts' share of HBM's bandwidth."""
    import jax
    import jax.numpy as jnp

    from client_tpu.models import moe

    d, f, held = 7168, 2048, (0, 16)
    keys = iter(jax.random.split(jax.random.PRNGKey(tokens), 9))
    swiglu = lambda *lead: {  # noqa: E731
        "w_gate": _device_normal(next(keys), lead + (d, f), d ** -0.5),
        "w_up": _device_normal(next(keys), lead + (d, f), d ** -0.5),
        "w_down": _device_normal(next(keys), lead + (f, d), f ** -0.5)}
    experts, shared = swiglu(16), swiglu()
    router = _device_normal(next(keys), (d, 256), d ** -0.5)
    bias = _device_normal(next(keys), (256,), 0.02, jnp.float32)
    h = _device_normal(next(keys), (tokens, d), 1.0)
    ids, weights = jax.jit(lambda h: moe.route(
        h, router, bias, 8, scale=2.5, n_group=8, topk_group=4,
        eps=1e-20))(h)
    groups = np.asarray(ids) // 32
    assert all(len(set(row)) <= 4 for row in groups)

    def one(h, w):
        gate = jax.nn.silu(jnp.dot(
            h, w["w_gate"], preferred_element_type=jnp.float32))
        up = jnp.dot(h, w["w_up"], preferred_element_type=jnp.float32)
        return jnp.dot((gate * up).astype(h.dtype), w["w_down"],
                       preferred_element_type=jnp.float32)

    def dense(h, ids, weights, experts, shared):
        out = one(h, shared)
        for e in range(held[1]):
            share = (weights * (ids == e)).sum(-1, keepdims=True)
            out = out + share * one(
                h, {name: experts[name][e] for name in experts})
        return out

    layer = jax.jit(lambda h, ids, weights, experts, shared: moe.expert_layer(
        h, ids, weights, experts, held, kernel=kernel, shared=shared))
    args = (h, ids, weights, experts, shared)
    out, counters = layer(*args)
    assert int(counters[0]) == (np.asarray(ids) < held[1]).sum() > 0
    assert int(counters[3]) == (kernel == "pallas" and tokens <= 128)
    _assert_bf16_close(out, jax.jit(dense)(*args),
                       f"gigachat's expert layer, {tokens} tokens, {kernel}")
    ms = _ms_a_call(layer, *args)
    streamed = (int(counters[1]) + 1) * 3 * d * f * 2  # touched + shared
    print(f"gigachat expert layer, {tokens} rows, {kernel}, touched "
          f"{int(counters[1])} of 16: {ms:.3f} ms a call, "
          f"{100 * streamed / 819e9 / (ms / 1e3):.1f}% of HBM")


def test_gigachats_decode_step_agrees_through_both_kernel_choices(device):
    """`deepseek_v3`'s whole decode step at the published widths (the
    dense layer and one expert layer; 16 held experts) through the
    load-time choices ``pallas`` and ``fused_xla``, both reading the one
    pool a layer: the logits agree to a few bf16 steps, with contexts
    from inside one tile to the cell's longest."""
    import jax

    from client_tpu.models import deepseek_v3, paged_attention as pa
    from client_tpu.models.engine_model import Kernels

    config = deepseek_v3.DeepseekV3Config(
        vocab_size=4096, n_layers=2, n_dense_layers=1, held=(0, 16))
    params = deepseek_v3.init_params(jax.random.PRNGKey(5), config)
    lanes, columns = 4, 512
    positions = np.array([17, 1500, 4000, 8131], np.int32)
    tables = (1 + np.arange(lanes * columns)).reshape(
        lanes, columns).astype(np.int32)
    keys = jax.random.split(jax.random.PRNGKey(6), config.n_layers)
    # rows as the program stores them: zeros behind the 576 it holds
    pages = [
        _device_normal(key, (1 + lanes * columns, BLOCK, config.row_width),
                       1.0).at[..., config.row:].set(0)
        for key in keys]
    tokens = np.array([5, 6, 7, 8], np.int32)

    def step(name, attn):
        logits, _, counters = jax.jit(
            lambda *a: deepseek_v3.decode_step_paged(
                *a, config, Kernels(name, attn)))(
            params, tokens, positions, tables, pages)
        return np.asarray(logits), np.asarray(counters)

    kernel, counted = step("pallas", pa.paged_attention_pallas)
    plain, plain_counted = step("fused_xla", pa.paged_attention_xla)
    assert counted[3] == 1 and plain_counted[3] == 0
    assert (counted[:3] == plain_counted[:3]).all()
    assert counted[4] == plain_counted[4] <= lanes
    assert np.isfinite(kernel).all() and np.abs(plain).max() > 1.0
    worst = float(np.abs(kernel - plain).max())
    assert worst <= 2.0 ** -4 * max(1.0, float(np.abs(plain).max())), worst


# ---------------------------------------------------------------------------
# qwen3_next_80b.reason2k_128: the state-update kernel, the paged kernel at
# KV 2 / D 256, the decode step
# ---------------------------------------------------------------------------


def _delta_lanes(lanes=128, slots=129, seed=21):
    """A decode step's inputs of one DeltaNet layer at the published
    widths (16 key heads and 32 value heads of 128): unit q and k, decays
    between 0.74 and 0.9996, a pool of states of unit size whose slot 0
    is zero, every fourth lane a batch bucket's padding."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
        jnp.square(x).sum(-1, keepdims=True) + 1e-6)
    q = unit(jax.random.normal(keys[0], (lanes, 16, 128))) * 128 ** -0.5
    k = unit(jax.random.normal(keys[1], (lanes, 16, 128)))
    v = jax.random.normal(keys[2], (lanes, 32, 128))
    g = -jnp.exp(jax.random.uniform(
        keys[3], (lanes, 32), minval=np.log(4e-4), maxval=np.log(0.3)))
    beta = jax.random.uniform(keys[4], (lanes, 32), minval=0.05, maxval=0.95)
    pool = jax.random.normal(keys[5], (slots, 32, 128, 128)).at[0].set(0.0)
    order = 1 + np.random.default_rng(seed).permutation(slots - 1)[:lanes]
    order[::4] = 0
    return q, k, v, g, beta, jnp.asarray(order, jnp.int32), pool


def test_gated_delta_step_compiled_matches_the_gather_and_scatter(device):
    """The state-update kernel compiled by Mosaic against the plain XLA
    form at the cell's shapes (128 lanes over a pool of 129 slots, a
    quarter of the lanes padding): float32 both ways, sums in another
    order, so outputs and states agree to 1e-5 of their size (a state in
    bf16 would be 4e-3 off); the slots of no lane are left bit for bit,
    the trash slot holds zeros. Prints us a live lane and the share of
    HBM's bandwidth of the states moved in and out."""
    import jax

    from client_tpu.models import gated_delta

    q, k, v, g, beta, slots, pool = _delta_lanes()
    kernel = jax.jit(lambda *a: gated_delta.gated_delta_step(
        *a, kernel="pallas"))
    plain = jax.jit(lambda *a: gated_delta.gated_delta_step(
        *a, kernel="fused_xla"))
    out, new = kernel(q, k, v, g, beta, slots, pool)
    ref_out, ref_new = plain(q, k, v, g, beta, slots, pool)
    out, new, ref_out, ref_new = map(np.asarray, (out, new, ref_out, ref_new))
    assert np.isfinite(out).all() and np.abs(ref_out).max() > 0.1
    assert np.abs(out - ref_out).max() <= 1e-5 * np.abs(ref_out).max()
    assert np.abs(new - ref_new).max() <= 1e-5 * np.abs(ref_new).max()
    live = np.asarray(slots)[np.asarray(slots) != 0]
    untouched = np.setdiff1d(np.arange(1, len(new)), live)
    assert (new[untouched] == np.asarray(pool)[untouched]).all()
    assert not new[0].any() and not out[np.asarray(slots) == 0].any()
    moved = len(live) * 2 * 32 * 128 * 128 * 4
    for name, fn in (("kernel", kernel), ("plain XLA", plain)):
        ms = _ms_a_call(fn, q, k, v, g, beta, slots, pool, calls=10)
        print(f"gated_delta_step, {len(live)} live of 128 lanes, {name} "
              f"(pool not donated: a copy of it rides along): {ms:.3f} ms "
              f"a call, {1e3 * ms / len(live):.2f} us a live lane, "
              f"{100 * moved / 819e9 / (ms / 1e3):.1f}% of HBM")


def test_gated_delta_step_in_place_is_timed_with_the_pool_donated(device):
    """The same call with its pool donated, as the decode program has it:
    no copy of the pool, the step's own time. Prints ms a call, us a live
    lane and the states' share of HBM's bandwidth."""
    import time

    import jax

    from client_tpu.models import gated_delta

    q, k, v, g, beta, slots, pool = _delta_lanes()
    live = int((np.asarray(slots) != 0).sum())
    step = jax.jit(
        lambda q, k, v, g, beta, slots, pool: gated_delta.gated_delta_step(
            q, k, v, g, beta, slots, pool, kernel="pallas"),
        donate_argnums=(6,))
    text = step.lower(q, k, v, g, beta, slots, pool).compile().as_text()
    assert not [line for line in text.splitlines()
                if " copy(" in line and "f32[129,32,128,128]" in line]
    out, pool = step(q, k, v, g, beta, slots, pool)
    jax.block_until_ready(pool)
    best = float("inf")
    for _ in range(3):
        began = time.perf_counter()
        for _ in range(20):
            out, pool = step(q, k, v, g, beta, slots, pool)
        jax.block_until_ready(pool)
        best = min(best, (time.perf_counter() - began) / 20)
    assert np.isfinite(np.asarray(out)).all()
    moved = live * 2 * 32 * 128 * 128 * 4
    print(f"gated_delta_step in place, {live} live of 128 lanes: "
          f"{1e3 * best:.3f} ms a call, {1e6 * best / live:.2f} us a live "
          f"lane, {100 * moved / 819e9 / best:.1f}% of HBM")


@pytest.mark.parametrize("tokens", [512, 2048])
def test_the_chunked_rule_inverts_its_chunks_faster_than_a_solve(
        device, monkeypatch, tokens):
    """``chunked_gated_delta`` alone at the cell's shapes (a prompt of
    512 tokens, and the longest re-prefill's 2,048; 16 key and 32 value
    heads of 128) against the same function with
    ``jax.scipy.linalg.solve_triangular`` put back where the inverse by
    block recursion is: float32 both ways, so outputs and the state left
    agree to 1e-5, and a call is at least 0.3 ms shorter (XLA expands
    the solve into a custom call that walks a block's rows one after
    another). Prints both times."""
    import jax
    from jax.scipy.linalg import solve_triangular

    from client_tpu.models import gated_delta

    args = _delta_lanes(lanes=tokens, slots=2)[:5]
    inverse = jax.jit(lambda *a: gated_delta.chunked_gated_delta(*a))
    out, state = inverse(*args)
    inverse_ms = _ms_a_call(inverse, *args)
    monkeypatch.setattr(
        gated_delta, "_solve_unit_lower", lambda lower, rhs: solve_triangular(
            lower, rhs, lower=True, unit_diagonal=True))
    solve = jax.jit(lambda *a: gated_delta.chunked_gated_delta(*a))
    ref_out, ref_state = solve(*args)
    solve_ms = _ms_a_call(solve, *args)
    assert " custom-call(" in solve.lower(*args).compile().as_text()
    assert np.isfinite(np.asarray(out)).all()
    assert np.abs(np.asarray(ref_state)).max() > 0.1
    assert np.abs(np.asarray(out) - np.asarray(ref_out)).max() <= 1e-5
    assert np.abs(np.asarray(state) - np.asarray(ref_state)).max() <= 1e-5
    print(f"chunked_gated_delta, {tokens} tokens, 32 heads of 128 x 128: "
          f"{inverse_ms:.3f} ms a call by the block inverse, "
          f"{solve_ms:.3f} ms by solve_triangular")
    assert solve_ms - inverse_ms >= 0.3


def test_compiled_pallas_at_the_qwen3_next_cells_shapes(device):
    """The paged kernel at KV 2 / D 256 (tiles of 16 pages, 8 query rows
    a KV head), 128 lanes over contexts to 2,048, against plain XLA."""
    import jax

    from client_tpu.models import paged_attention as pa

    rng = np.random.default_rng(31)
    lanes, columns, kv, dim, heads = 128, 128, 2, 256, 16
    assert pa.pages_per_tile(BLOCK * kv, 1, dim, np.dtype("bfloat16"), 2) == 16
    keys = jax.random.split(jax.random.PRNGKey(31), 3)
    pools = [_device_normal(key, (1 + lanes * columns, BLOCK * kv, dim), 1.0)
             for key in keys[:2]]
    tables = (1 + np.arange(lanes * columns)).reshape(
        lanes, columns).astype(np.int32)
    positions = rng.integers(0, columns * BLOCK, size=(lanes, 1)).astype(
        np.int32)
    positions[0] = columns * BLOCK - 1
    live = positions // BLOCK + 1
    tables = np.where(np.arange(columns)[None] < live, tables, 0).astype(
        np.int32)
    q = _device_normal(keys[2], (lanes, 1, heads, dim), 1.0)
    kernel = jax.jit(lambda *a: pa.paged_attention_pallas(*a, kv_heads=kv))
    plain = jax.jit(lambda *a: pa.paged_attention_xla(*a, kv_heads=kv))
    args = (q, *pools, tables, positions)
    _assert_bf16_close(kernel(*args), plain(*args),
                       "qwen3_next's gated full attention")
    ms = _ms_a_call(kernel, *args)
    tokens = int((positions + 1).sum())
    print(f"qwen3_next paged call, KV 2 / D 256, {tokens} cached tokens: "
          f"{ms:.3f} ms a call, "
          f"{100 * tokens * 2048 / 819e9 / (ms / 1e3):.1f}% of HBM")


def test_qwen3_nexts_decode_step_agrees_through_both_kernel_choices(device):
    """`qwen3_next`'s whole decode step at the published widths (one
    period: three DeltaNet layers and a gated full-attention layer; 32
    held experts of 512) after a prefill, through the load-time choices
    ``pallas`` and ``fused_xla``: the logits agree to a few bf16 steps
    and the states the two leave agree in float32."""
    import jax

    from client_tpu.models import paged_attention as pa, qwen3_next
    from client_tpu.models.engine_model import Kernels

    config = qwen3_next.Qwen3NextConfig(
        vocab_size=4096, n_layers=4, held=(0, 32))
    params = qwen3_next.init_params(jax.random.PRNGKey(5), config)
    lanes, columns = 4, 128
    prompts = [17, 300, 64, 511]

    def run(name, attn):
        kernels = Kernels(name, attn)
        pages = qwen3_next.init_pages(
            config, [1 + lanes * columns, 1 + lanes], BLOCK)
        tables = np.zeros((2, lanes, columns), np.int32)
        tables[0] = (1 + np.arange(lanes * columns)).reshape(lanes, columns)
        tables[1, :, 0] = 1 + np.arange(lanes)
        prefill = jax.jit(lambda *a: qwen3_next.prefill_into_pages(
            *a, config, kernels))
        for lane, prompt in enumerate(prompts):
            tokens = np.zeros((1, 512), np.int32)
            tokens[0, :prompt] = np.random.default_rng(lane).integers(
                1, 4096, size=prompt)
            _, pages = prefill(params, tokens, tables[:, lane], pages,
                               prompt - 1)
        decode = jax.jit(lambda *a: qwen3_next.decode_step_paged(
            *a, config, kernels))
        rows = []
        for step in range(3):
            logits, pages, counters = decode(
                params, np.array([5, 6, 7, 8], np.int32) + step,
                np.asarray(prompts, np.int32) + step, tables, pages)
            rows.append(np.asarray(logits))
        return np.stack(rows), np.asarray(counters), np.asarray(pages[0][0])

    kernel, counted, state = run("pallas", pa.paged_attention_pallas)
    plain, plain_counted, plain_state = run("fused_xla", pa.paged_attention_xla)
    assert counted[3] == 4 and plain_counted[3] == 0
    assert counted[4] == plain_counted[4] == 3 * lanes
    assert np.isfinite(kernel).all() and np.abs(plain).max() > 1.0
    worst = float(np.abs(kernel - plain).max())
    assert worst <= 2.0 ** -4 * max(1.0, float(np.abs(plain).max())), worst
    assert np.abs(state - plain_state).max() <= 2.0 ** -6 * np.abs(
        plain_state).max()
    assert not state[0].any()


# ---------------------------------------------------------------------------
# jamba2_3b.reason8k_128: the selective scan's decode kernel and its prefill
# in chunks, the paged kernel at KV 1 / D 128, the decode step
# ---------------------------------------------------------------------------


def _scan_lanes(lanes=128, slots=129, seed=23):
    """A decode step's inputs of one Mamba layer at the published widths
    (5,120 channels of 16 states): steps of 0.001-0.2, ``A = -1 .. -16``,
    a pool of states of unit size whose slot 0 is zero, every fourth lane
    a batch bucket's padding. ``(u, delta, b, c, z, a, d_skip, slots,
    pool)``."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    channels, states = 5120, 16
    u = jax.random.normal(keys[0], (lanes, channels))
    z = jax.random.normal(keys[1], (lanes, channels))
    delta = jnp.exp(jax.random.uniform(
        keys[2], (lanes, channels), minval=np.log(1e-3), maxval=np.log(0.2)))
    b = jax.random.normal(keys[3], (lanes, states))
    c = jax.random.normal(keys[4], (lanes, states))
    a = -jnp.broadcast_to(
        jnp.arange(1.0, states + 1)[:, None], (states, channels))
    d_skip = 1 + 0.1 * jax.random.normal(keys[5], (channels,))
    pool = jax.random.normal(keys[6], (slots, states, channels)).at[0].set(0.0)
    order = 1 + np.random.default_rng(seed).permutation(slots - 1)[:lanes]
    order[::4] = 0
    return u, delta, b, c, z, a, d_skip, jnp.asarray(order, jnp.int32), pool


SCAN_STATE_BYTES = 16 * 5120 * 4


def test_selective_scan_step_compiled_matches_the_gather_and_scatter(device):
    """The scan's decode kernel compiled by Mosaic against the plain XLA
    form at the cell's shapes (128 lanes over a pool of 129 slots, a
    quarter of the lanes padding): float32 both ways, sums in another
    order and the chip's own exponential, so outputs and states agree to
    1e-5 of their size (a state in bf16 would be 4e-3 off); the slots of
    no lane are left bit for bit, the trash slot holds zeros. Prints us a
    live lane and the share of HBM's bandwidth of the states moved in and
    out."""
    import jax

    from client_tpu.models import selective_scan

    args = _scan_lanes()
    slots, pool = args[-2:]
    kernel = jax.jit(lambda *a: selective_scan.selective_scan_step(
        *a, kernel="pallas"))
    plain = jax.jit(lambda *a: selective_scan.selective_scan_step(
        *a, kernel="fused_xla"))
    out, new = kernel(*args)
    ref_out, ref_new = plain(*args)
    out, new, ref_out, ref_new = map(np.asarray, (out, new, ref_out, ref_new))
    assert np.isfinite(out).all() and np.abs(ref_out).max() > 0.1
    assert np.abs(out - ref_out).max() <= 1e-5 * np.abs(ref_out).max()
    assert np.abs(new - ref_new).max() <= 1e-5 * np.abs(ref_new).max()
    live = np.asarray(slots)[np.asarray(slots) != 0]
    untouched = np.setdiff1d(np.arange(1, len(new)), live)
    assert (new[untouched] == np.asarray(pool)[untouched]).all()
    assert not new[0].any() and not out[np.asarray(slots) == 0].any()
    moved = len(live) * 2 * SCAN_STATE_BYTES
    for name, fn in (("kernel", kernel), ("plain XLA", plain)):
        ms = _ms_a_call(fn, *args, calls=10)
        print(f"selective_scan_step, {len(live)} live of 128 lanes, {name} "
              f"(pool not donated: a copy of it rides along): {ms:.3f} ms "
              f"a call, {1e3 * ms / len(live):.2f} us a live lane, "
              f"{100 * moved / 819e9 / (ms / 1e3):.1f}% of HBM")


def test_selective_scan_step_in_place_is_timed_with_the_pool_donated(device):
    """The same call with its pool donated, as the decode program has it:
    no copy of the pool. Prints ms a call, us a lane and the states' share
    of HBM's bandwidth; a lone call of 0.14 ms of kernel is bound by its
    dispatch from the host (0.34 ms a call, my chip run, PR 40), so the
    kernel's own time is the cell's trace's (`ssm.step_roofline`)."""
    import time

    import jax

    from client_tpu.models import selective_scan

    *rows, slots, pool = _scan_lanes()
    live = int((np.asarray(slots) != 0).sum())
    step = jax.jit(
        lambda u, delta, b, c, z, a, d_skip, slots, pool:
        selective_scan.selective_scan_step(
            u, delta, b, c, z, a, d_skip, slots, pool, kernel="pallas"),
        donate_argnums=(8,))
    text = step.lower(*rows, slots, pool).compile().as_text()
    assert not [line for line in text.splitlines()
                if " copy(" in line and "f32[129,16,5120]" in line]
    out, pool = step(*rows, slots, pool)
    jax.block_until_ready(pool)
    best = float("inf")
    for _ in range(3):
        began = time.perf_counter()
        for _ in range(20):
            out, pool = step(*rows, slots, pool)
        jax.block_until_ready(pool)
        best = min(best, (time.perf_counter() - began) / 20)
    assert np.isfinite(np.asarray(out)).all()
    moved = live * 2 * SCAN_STATE_BYTES
    print(f"selective_scan_step in place, {live} live of 128 lanes: "
          f"{1e3 * best:.3f} ms a call, {1e6 * best / 128:.2f} us a lane, "
          f"{100 * moved / 819e9 / best:.1f}% of HBM")


def _associative_chunk(state, decay, drive, c):
    """A chunk by ``lax.associative_scan`` over its tokens: the form
    `selective_scan._scan_chunk` is timed against."""
    import jax

    def combine(left, right):
        return left[0] * right[0], right[0] * left[1] + right[1]

    carried, driven = jax.lax.associative_scan(combine, (decay, drive))
    h = driven + carried * state
    return h[-1], (h * c[:, :, None]).sum(axis=1)


@pytest.mark.parametrize("tokens", [512, 8192])
def test_the_chunked_scan_walks_a_chunk_faster_than_an_associative_scan(
        device, monkeypatch, tokens):
    """``chunked_selective_scan`` alone at the cell's shapes (a prompt of
    512 tokens, and the longest re-prefill's 8,192; 5,120 channels of 16
    states) with a chunk's tokens walked one after another, as the
    program has them, against the same function with
    ``lax.associative_scan`` inside a chunk: float32 both ways, so outputs
    and the state left agree to 1e-5 of their size, and the walk is the
    faster (0.54 against 1.25 ms at 512 tokens, 6.5 against 19.5 at
    8,192: my chip run, PR 40; the associative scan makes log2(64) passes
    over a chunk's `[64, 16, 5120]` arrays). Prints both times."""
    import jax

    from client_tpu.models import selective_scan

    u, delta, b, c, z, a, d_skip = _scan_lanes(lanes=tokens, slots=2)[:7]
    args = (u, delta, b, c, z, a, d_skip)
    walked = jax.jit(lambda *a: selective_scan.chunked_selective_scan(*a))
    out, state = walked(*args)
    walked_ms = _ms_a_call(walked, *args, calls=5)
    monkeypatch.setattr(selective_scan, "_scan_chunk", _associative_chunk)
    scanned = jax.jit(lambda *a: selective_scan.chunked_selective_scan(*a))
    ref_out, ref_state = scanned(*args)
    scanned_ms = _ms_a_call(scanned, *args, calls=5)
    assert np.isfinite(np.asarray(out)).all()
    assert np.abs(np.asarray(ref_state)).max() > 0.01
    scale = float(np.abs(np.asarray(ref_out)).max())
    assert np.abs(np.asarray(out) - np.asarray(ref_out)).max() <= 1e-5 * scale
    assert np.abs(np.asarray(state) - np.asarray(ref_state)).max() <= 1e-5
    print(f"chunked_selective_scan, {tokens} tokens, 5,120 channels of 16 "
          f"states: {walked_ms:.3f} ms a call with a chunk's tokens walked, "
          f"{scanned_ms:.3f} ms by associative_scan inside a chunk")
    assert walked_ms < scanned_ms


def test_compiled_pallas_at_the_jamba_cells_shapes(device):
    """The paged kernel at KV 1 / D 128 (tiles of 64 pages, 20 query rows
    a lane, no head mask), 128 lanes over contexts to 8,192, against
    plain XLA."""
    import jax

    from client_tpu.models import paged_attention as pa

    rng = np.random.default_rng(37)
    lanes, columns, kv, dim, heads = 128, 512, 1, 128, 20
    assert pa.pages_per_tile(BLOCK * kv, 1, dim, np.dtype("bfloat16"), 2) == 64
    keys = jax.random.split(jax.random.PRNGKey(37), 3)
    pools = [_device_normal(key, (1 + lanes * columns, BLOCK * kv, dim), 1.0)
             for key in keys[:2]]
    tables = (1 + np.arange(lanes * columns)).reshape(
        lanes, columns).astype(np.int32)
    positions = rng.integers(0, columns * BLOCK, size=(lanes, 1)).astype(
        np.int32)
    positions[0] = columns * BLOCK - 1
    live = positions // BLOCK + 1
    tables = np.where(np.arange(columns)[None] < live, tables, 0).astype(
        np.int32)
    q = _device_normal(keys[2], (lanes, 1, heads, dim), 1.0)
    kernel = jax.jit(lambda *a: pa.paged_attention_pallas(*a, kv_heads=kv))
    plain = jax.jit(lambda *a: pa.paged_attention_xla(*a, kv_heads=kv))
    args = (q, *pools, tables, positions)
    _assert_bf16_close(kernel(*args), plain(*args),
                       "jamba's multi-query attention")
    ms = _ms_a_call(kernel, *args)
    tokens = int((positions + 1).sum())
    print(f"jamba paged call, KV 1 / D 128, {tokens} cached tokens: "
          f"{ms:.3f} ms a call, "
          f"{100 * tokens * 512 / 819e9 / (ms / 1e3):.1f}% of HBM")


def test_jambas_decode_step_agrees_through_both_kernel_choices(device):
    """`jamba`'s whole decode step at the published widths (four layers:
    three Mamba layers and a multi-query attention layer) after a
    prefill, through the load-time choices ``pallas`` and ``fused_xla``:
    the logits agree to a few bf16 steps and the states the two leave
    agree in float32."""
    import jax

    from client_tpu.models import jamba, paged_attention as pa
    from client_tpu.models.engine_model import Kernels

    config = jamba.JambaConfig(
        vocab_size=4096, n_layers=4, attn_period=4, attn_offset=2)
    params = jamba.init_params(jax.random.PRNGKey(5), config)
    lanes, columns = 4, 128
    prompts = [17, 300, 64, 511]

    def run(name, attn):
        kernels = Kernels(name, attn)
        pages = jamba.init_pages(
            config, [1 + lanes * columns, 1 + lanes], BLOCK)
        tables = np.zeros((2, lanes, columns), np.int32)
        tables[0] = (1 + np.arange(lanes * columns)).reshape(lanes, columns)
        tables[1, :, 0] = 1 + np.arange(lanes)
        prefill = jax.jit(lambda *a: jamba.prefill_into_pages(
            *a, config, kernels))
        for lane, prompt in enumerate(prompts):
            tokens = np.zeros((1, 512), np.int32)
            tokens[0, :prompt] = np.random.default_rng(lane).integers(
                1, 4096, size=prompt)
            _, pages = prefill(params, tokens, tables[:, lane], pages,
                               prompt - 1)
        decode = jax.jit(lambda *a: jamba.decode_step_paged(
            *a, config, kernels))
        rows = []
        for step in range(3):
            logits, pages, counters = decode(
                params, np.array([5, 6, 7, 8], np.int32) + step,
                np.asarray(prompts, np.int32) + step, tables, pages)
            rows.append(np.asarray(logits))
        return np.stack(rows), np.asarray(counters), np.asarray(pages[0][0])

    kernel, counted, state = run("pallas", pa.paged_attention_pallas)
    plain, plain_counted, plain_state = run("fused_xla", pa.paged_attention_xla)
    assert counted[0] == plain_counted[0] == 3 * lanes
    assert np.isfinite(kernel).all() and np.abs(plain).max() > 1.0
    worst = float(np.abs(kernel - plain).max())
    assert worst <= 2.0 ** -4 * max(1.0, float(np.abs(plain).max())), worst
    assert np.abs(state - plain_state).max() <= 2.0 ** -6 * np.abs(
        plain_state).max()
    assert not state[0].any()


@pytest.mark.parametrize("window", [None, 512])
def test_compiled_pallas_at_the_phi4_cells_shapes(device, window):
    """The paged kernel as `phi4flash` calls it (``keys_per_value`` 2: 40
    query heads of 64 over 20 key heads of 64 and 10 value heads of 128,
    10 rows of 128 a token in either pool: pages of 40 KB, of which the
    budget's share of a slot holds 6.4, so tiles of 8 pages, the power of
    two nearest it), 64 lanes over contexts to 8,192, with and without
    the window of 512, against plain XLA. Prints ms a call, us a lane and
    the share of HBM's bandwidth of the 5,120 B a visible token."""
    import jax

    from client_tpu.models import paged_attention as pa

    rng = np.random.default_rng(41)
    lanes, columns, rows, dim, heads = 64, 512, 10, 128, 40
    assert pa.pages_per_tile(BLOCK, rows, dim, np.dtype("bfloat16"), 2) == 8
    keys = jax.random.split(jax.random.PRNGKey(41), 3)
    pools = [_device_normal(key, (1 + lanes * columns, BLOCK * rows, dim), 1.0)
             for key in keys[:2]]
    tables = (1 + np.arange(lanes * columns)).reshape(
        lanes, columns).astype(np.int32)
    positions = rng.integers(0, columns * BLOCK, size=(lanes, 1)).astype(
        np.int32)
    positions[0] = columns * BLOCK - 1
    live = positions // BLOCK + 1
    first = np.maximum(0, positions - (window or 1 << 30) + 1) // BLOCK
    column = np.arange(columns)[None]
    tables = np.where((column < live) & (column >= first), tables, 0).astype(
        np.int32)
    q = _device_normal(keys[2], (lanes, 1, heads, 64), 1.0)
    masking = dict(window=window, kv_heads=rows, keys_per_value=2)
    kernel = jax.jit(lambda *a: pa.paged_attention_pallas(*a, **masking))
    plain = jax.jit(lambda *a: pa.paged_attention_xla(*a, **masking))
    args = (q, *pools, tables, positions)
    _assert_bf16_close(kernel(*args), plain(*args),
                       "phi4flash's differential pairs")
    ms = _ms_a_call(kernel, *args)
    tokens = int(np.minimum(positions + 1, window or 1 << 30).sum())
    print(f"phi4flash paged call, 10 rows of 128, window {window}, {tokens} "
          f"visible tokens: {ms:.3f} ms a call, {1e3 * ms / lanes:.2f} us a "
          f"lane, {100 * tokens * 5120 / 819e9 / (ms / 1e3):.1f}% of HBM")


def test_phi4flashs_decode_step_agrees_through_both_kernel_choices(device):
    """`phi4flash`'s whole decode step at the published widths (twelve
    layers: four Mamba, three window, the full layer, two gated memory
    units and two cross layers over the one pool) after a prefill past
    the window, through the load-time choices ``pallas`` and
    ``fused_xla``: the logits agree to a few bf16 steps and the states
    the two leave agree in float32."""
    import jax

    from client_tpu.models import paged_attention as pa, phi4flash
    from client_tpu.llm.kv_cache import window_ring_blocks, window_tables
    from client_tpu.models.engine_model import Kernels

    config = phi4flash.Phi4FlashConfig(vocab_size=4096, n_layers=12)
    params = phi4flash.init_params(jax.random.PRNGKey(5), config)
    lanes, columns = 4, 128
    prompts = [17, 700, 64, 1023]
    ring = window_ring_blocks(config.window, BLOCK, 8)
    rings = (1 + np.arange(lanes * ring)).reshape(lanes, ring)

    def tables_at(positions):
        tables = np.zeros((3, lanes, columns), np.int32)
        tables[0] = (1 + np.arange(lanes * columns)).reshape(lanes, columns)
        tables[1] = window_tables(
            rings, [p // BLOCK for p in positions], columns)
        tables[2, :, 0] = 1 + np.arange(lanes)
        return tables

    def run(name, attn):
        kernels = Kernels(name, attn)
        pages = phi4flash.init_pages(
            config, [1 + lanes * columns, 1 + lanes * ring, 1 + lanes], BLOCK)
        prefill = jax.jit(lambda *a: phi4flash.prefill_into_pages(
            *a, config, kernels))
        for lane, prompt in enumerate(prompts):
            tokens = np.zeros((1, 1024), np.int32)
            tokens[0, :prompt] = np.random.default_rng(lane).integers(
                1, 4096, size=prompt)
            _, pages = prefill(
                params, tokens, tables_at([p - 1 for p in prompts])[:, lane],
                pages, prompt - 1)
        decode = jax.jit(lambda *a: phi4flash.decode_step_paged(
            *a, config, kernels))
        rows = []
        for step in range(3):
            positions = np.asarray(prompts, np.int32) + step
            logits, pages, counters = decode(
                params, np.array([5, 6, 7, 8], np.int32) + step, positions,
                tables_at(positions), pages)
            rows.append(np.asarray(logits))
        return np.stack(rows), np.asarray(counters), np.asarray(pages[0][0])

    kernel, counted, state = run("pallas", pa.paged_attention_pallas)
    plain, plain_counted, plain_state = run("fused_xla", pa.paged_attention_xla)
    assert list(counted) == list(plain_counted) == [
        4 * lanes, 3 * (sum(prompts) + 2 * lanes + lanes)]
    assert np.isfinite(kernel).all() and np.abs(plain).max() > 1.0
    worst = float(np.abs(kernel - plain).max())
    assert worst <= 2.0 ** -4 * max(1.0, float(np.abs(plain).max())), worst
    assert np.abs(state - plain_state).max() <= 2.0 ** -6 * np.abs(
        plain_state).max()
    assert not state[0].any()


# ---------------------------------------------------------------------------
# the serving path on the device
# ---------------------------------------------------------------------------


def test_client_server_infer_executes_on_device(device):
    """Full wire path (HTTP client -> server -> jitted model on the real
    platform -> response), with dynamic batching accounting visible."""
    import jax

    import client_tpu.http as httpclient
    from client_tpu.server.core import ServerCore
    from client_tpu.server.model_repository import Model, ModelRepository
    from client_tpu.testing import InProcessServer

    class _DeviceMatmul(Model):
        name = "tpu_matmul"
        max_batch_size = 8
        inputs = [{"name": "X", "datatype": "FP32", "shape": [16]}]
        outputs = [{"name": "Y", "datatype": "FP32", "shape": [16]}]

        def warmup(self):
            self._w = np.eye(16, dtype=np.float32) * 3.0
            self._fn = jax.jit(lambda x, w: x @ w)
            jax.block_until_ready(
                self._fn(np.zeros([1, 16], np.float32), self._w)
            )

        def execute(self, inputs, parameters):
            return {"Y": jax.device_get(self._fn(inputs["X"], self._w))}

    repository = ModelRepository()
    repository.add_model(_DeviceMatmul())
    core = ServerCore(repository)
    with InProcessServer(core=core, grpc=False, builtin_models=False) as server:
        client = httpclient.InferenceServerClient(server.http_url)
        try:
            data = np.arange(16, dtype=np.float32).reshape(1, 16)
            inp = httpclient.InferInput("X", [1, 16], "FP32")
            inp.set_data_from_numpy(data)

            async def burst():
                loop = asyncio.get_running_loop()
                return await asyncio.gather(
                    *[
                        loop.run_in_executor(
                            None,
                            lambda: httpclient.InferenceServerClient(
                                server.http_url
                            ).infer("tpu_matmul", [inp])
                        )
                        for _ in range(6)
                    ]
                )

            result = client.infer("tpu_matmul", [inp])
            np.testing.assert_allclose(result.as_numpy("Y"), data * 3.0)
            asyncio.run(burst())
            stats = client.get_inference_statistics("tpu_matmul")
            entry = stats["model_stats"][0]
            assert entry["inference_count"] >= 7
        finally:
            client.close()


def test_tpu_shm_staging_round_trip(device):
    """Device arrays -> one batched readback into the mapped pages ->
    zero-copy numpy view shows the same bytes."""
    import jax

    from client_tpu.utils import tpu_shared_memory as tpushm

    a = jax.device_put(np.arange(32, dtype=np.float32).reshape(4, 8))
    b = jax.device_put(np.ones([2, 2], np.int32) * 7)
    region = tpushm.create_shared_memory_region("tpu_tier_rt", 32 * 4 + 4 * 4)
    try:
        tpushm.set_shared_memory_region_from_jax(region, [a, b])
        got_a = tpushm.get_contents_as_numpy(region, np.float32, [4, 8])
        got_b = tpushm.get_contents_as_numpy(
            region, np.int32, [2, 2], offset=32 * 4
        )
        np.testing.assert_array_equal(got_a, np.asarray(a))
        np.testing.assert_array_equal(got_b, np.asarray(b))
    finally:
        tpushm.destroy_shared_memory_region(region)
