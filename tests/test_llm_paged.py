"""PR-14: ragged paged-attention kernels + copy-on-write prefix sharing.

Four tiers:

- COW allocator units (no jax): refcount accounting, chained content
  hashes, shared-block reclaim discipline, publish/unpublish;
- kernel parity (jax): every attention implementation (fused XLA,
  Pallas-interpret, stand-in) within 1e-5 of the stand-in's math on
  random ragged page layouts AND on full tiny-llama decode logits, plus
  suffix-prefill-vs-full-prefill parity;
- engine-level sharing on the float32 tiny llama: shared-prefix
  generations EXACTLY match the dense ``llama.generate`` oracle, blocks
  in use stay well below the no-sharing demand, shared blocks are never
  mutated while referenced, preempt-and-resume under sharing stays
  correct, refcount==0 reclaims within one iteration;
- sampled decoding determinism (stub, fake clock): seeded temperature /
  top-k streams reproduce per seed and replay identically across
  preemption, and the admission capacity math counts new blocks only.
"""

import asyncio

import numpy as np
import pytest

from client_tpu.llm import (
    BlockAllocator,
    CacheCapacityError,
    EngineConfig,
    LlmEngine,
)
from client_tpu.llm.engine import decode_fn_from_logits
from client_tpu.utils import InferenceServerException

pytestmark = pytest.mark.llm

MS = 1_000_000  # ns


# ---------------------------------------------------------------------------
# COW allocator units
# ---------------------------------------------------------------------------


def test_allocator_shared_refcounts_and_reclaim():
    alloc = BlockAllocator(num_blocks=17, block_size=4)
    prompt = list(range(12))  # 3 full blocks
    hashes = alloc.chain_hashes(prompt)
    assert len(hashes) == 3
    # same tokens -> same chain; different first block -> full divergence
    assert alloc.chain_hashes(prompt) == hashes
    other = alloc.chain_hashes([99] + prompt[1:])
    assert other[0] != hashes[0] and other[2] != hashes[2]

    a, matched = alloc.allocate_shared("a", 4, hashes)
    assert matched == 0  # nothing published yet
    assert alloc.publish("a", hashes) == 3
    assert alloc.match_count(hashes) == 3
    assert alloc.blocks_shared == 0  # published but single-referenced

    b, matched = alloc.allocate_shared("b", 4, hashes)
    assert matched == 3
    assert b[:3] == a[:3]  # physically the SAME blocks
    assert b[3] != a[3]
    assert alloc.blocks_shared == 3
    assert alloc.blocks_in_use == 5  # 4 + 4 - 3 shared
    assert alloc.prefix_hits == 3

    # freeing the publisher must NOT reclaim blocks b still references
    assert alloc.free("a") == 1  # only a's exclusive tail block
    assert alloc.blocks_shared == 0
    assert alloc.match_count(hashes) == 3  # still indexed (b holds them)
    for phys in b[:3]:
        assert alloc.refcount(phys) == 1
    # last reference: reclaimed AND unpublished
    assert alloc.free("b") == 4
    assert alloc.blocks_in_use == 0
    assert alloc.match_count(hashes) == 0
    assert alloc.free_blocks == alloc.capacity


def test_allocator_extend_never_returns_a_shared_block():
    alloc = BlockAllocator(num_blocks=9, block_size=4)
    hashes = alloc.chain_hashes(list(range(8)))
    a, _ = alloc.allocate_shared("a", 2, hashes)
    alloc.publish("a", hashes)
    b, matched = alloc.allocate_shared("b", 3, hashes)
    assert matched == 2
    grown = alloc.extend("b")
    assert grown not in a  # fresh, exclusively owned
    assert alloc.refcount(grown) == 1


def test_allocator_all_or_nothing_takes_no_references():
    alloc = BlockAllocator(num_blocks=5, block_size=4)  # capacity 4
    hashes = alloc.chain_hashes(list(range(8)))
    a, _ = alloc.allocate_shared("a", 3, hashes)
    alloc.publish("a", hashes)
    before = [alloc.refcount(p) for p in a]
    with pytest.raises(CacheCapacityError):
        # 2 matched + 4 fresh needed, only 1 free
        alloc.allocate_shared("b", 6, hashes)
    assert [alloc.refcount(p) for p in a] == before
    assert alloc.blocks_shared == 0


def test_allocator_publish_skips_already_indexed():
    alloc = BlockAllocator(num_blocks=9, block_size=4)
    prompt = list(range(8))
    hashes = alloc.chain_hashes(prompt)
    a, _ = alloc.allocate_shared("a", 2, hashes)
    assert alloc.publish("a", hashes) == 2
    # a second sequence that prefilled the same prompt itself (admitted
    # before the first published) publishes nothing new
    b = alloc.allocate("b", 2)
    assert alloc.publish("b", hashes) == 0
    assert alloc.match_count(hashes) == 2
    alloc.free("a")
    alloc.free("b")
    assert alloc.blocks_in_use == 0


# ---------------------------------------------------------------------------
# kernel parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_allocator_hands_whole_rings_back_as_runs(seed):
    """A pool that only ever hands out and takes back whole rings (a
    window group's) keeps every ring a run of consecutive blocks,
    whatever order they are claimed and returned in: the LIFO free list
    takes a ring back in reverse and so gives it out again in order."""
    ring, rings = 9, 5
    allocator = BlockAllocator(1 + ring * rings, 16)
    rng = np.random.default_rng(seed)
    held = {}
    for step in range(200):
        if held and (len(held) == rings or rng.random() < 0.5):
            allocator.free(held.pop(int(rng.choice(list(held)))))
        else:
            held[step] = step
            blocks = allocator.allocate(step, ring)
            assert blocks == list(range(blocks[0], blocks[0] + ring))
            assert (blocks[0] - 1) % ring == 0
    assert allocator.blocks_in_use == ring * len(held)


@pytest.fixture(scope="module")
def tiny_llama():
    import jax
    import jax.numpy as jnp

    from client_tpu.models import llama

    config = llama.LlamaConfig.tiny(max_seq_len=64, dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), config)
    return config, params


def _random_paged_state(rng, b, kv, d, bs, nb, num_blocks):
    """Random pages + a ragged set of page tables/positions."""
    k_pages = rng.normal(size=(num_blocks, bs, kv, d)).astype(np.float32)
    v_pages = rng.normal(size=(num_blocks, bs, kv, d)).astype(np.float32)
    tables = np.zeros((b, nb), dtype=np.int32)
    positions = np.zeros((b,), dtype=np.int32)
    free = list(range(1, num_blocks))
    for i in range(b):
        n_ctx = int(rng.integers(1, nb * bs))
        positions[i] = n_ctx - 1
        n_blocks = (n_ctx + bs - 1) // bs
        for j in range(n_blocks):
            tables[i, j] = free.pop()
    return k_pages, v_pages, tables, positions


@pytest.mark.parametrize("b,nb", [(1, 2), (3, 4), (8, 4)])
def test_attention_impls_agree_on_ragged_layouts(b, nb):
    """XLA and Pallas(interpret) within 1e-5 of the reference on random
    pages with ragged per-sequence fill."""
    from client_tpu.models import paged_attention as pa

    kv, g, d, bs = 2, 2, 16, 8
    h = kv * g
    rng = np.random.default_rng(b * 100 + nb)
    k_pages, v_pages, tables, positions = _random_paged_state(
        rng, b, kv, d, bs, nb, num_blocks=1 + b * nb
    )
    q = rng.normal(size=(b, 1, h, d)).astype(np.float32)
    args = (q, k_pages, v_pages, tables, positions[:, None])
    ref = np.asarray(pa.paged_attention_reference(*args))
    for name in ("fused_xla", "pallas_interpret"):
        _, attn = pa.resolve_decode_attention(name, "cpu")
        assert np.abs(np.asarray(attn(*args)) - ref).max() <= 1e-5, name


GARBAGE = 3.0e4  # large and finite: what a masked slot may hold


def _ragged_case(rng, kv, g, b, nb, t, bs=16, d=128, dv=None):
    """A float32 pool and the tables the engine would write for ``b``
    lanes of a ``nb``-column bucket, at real page shapes (so that
    ``pages_per_tile`` is what a served model gets). Lane lengths fall
    before, on and one past the first tile boundary, at 1, at the full
    table; the last lane of a batch of three or more is a padding lane
    (table all trash block, position 0). Every slot no query may see —
    past a lane's last position, and the whole trash block — holds
    ``GARBAGE``; table columns past a lane's last live block are 0.
    ``dv`` is V's row size where it is not K's.
    Returns (q, k_pages, v_pages, tables, positions[b, t])."""
    from client_tpu.models import paged_attention as pa

    dv = dv or d
    tile = min(pa.pages_per_tile(bs, kv, max(d, dv), np.float32), nb) * bs
    wanted = [tile + 1, tile - 1, tile, 1, nb * bs, 2 * tile + 1, tile + bs]
    lengths = [
        min(max(1, wanted[i % len(wanted)]), nb * bs) for i in range(b)
    ]
    if b > len(wanted):
        lengths[len(wanted):] = rng.integers(
            1, nb * bs + 1, size=b - len(wanted)
        ).tolist()
    k_pages = np.full((1 + b * nb, bs, kv, d), GARBAGE, dtype=np.float32)
    v_pages = np.full((1 + b * nb, bs, kv, dv), -GARBAGE, dtype=np.float32)
    tables = np.zeros((b, nb), dtype=np.int32)
    positions = np.zeros((b, t), dtype=np.int32)
    for i, length in enumerate(lengths):
        if b >= 3 and i == b - 1:
            continue  # the padding lane
        owned = (length + bs - 1) // bs
        blocks = 1 + i * nb + np.arange(owned)
        tables[i, :owned] = blocks
        live_k = rng.normal(size=(owned * bs, kv, d)).astype(np.float32)
        live_v = rng.normal(size=(owned * bs, kv, dv)).astype(np.float32)
        live_k[length:] = GARBAGE
        live_v[length:] = -GARBAGE
        k_pages[blocks] = live_k.reshape(owned, bs, kv, d)
        v_pages[blocks] = live_v.reshape(owned, bs, kv, dv)
        # verify rows: the last t positions of the context, clamped as
        # the engine clamps padding rows (and contexts shorter than t)
        first = max(0, length - t)
        positions[i] = first + np.minimum(np.arange(t), length - 1 - first)
    q = rng.normal(size=(b, t, kv * g, d)).astype(np.float32)
    return q, k_pages, v_pages, tables, positions


def _hide_behind_window(tables, k_pages, v_pages, positions, window):
    """In place, as the engine's window group hands a table over: the
    columns wholly behind every row's ``window`` name the trash block,
    and their pages, with the slots of the first visible block that lie
    behind the window of every row, hold garbage."""
    bs = k_pages.shape[1]
    for i in range(len(tables)):
        first = max(0, int(positions[i].min()) - window + 1)
        behind = tables[i, : first // bs].copy()
        tables[i, : first // bs] = 0
        k_pages[behind[behind > 0]] = GARBAGE
        v_pages[behind[behind > 0]] = -GARBAGE
        block = tables[i, first // bs]
        if block > 0:
            k_pages[block, : first % bs] = GARBAGE
            v_pages[block, : first % bs] = -GARBAGE


RAGGED_CASES = [
    # (kv, g, batch, nb, t): GQA 32/8 as the benchmark's cell serves it,
    # MHA (KV 32), a KV 2 tensor-parallel shard
    (8, 4, 16, 64, 1), (8, 4, 3, 24, 5), (8, 4, 1, 8, 1), (8, 4, 3, 8, 5),
    (8, 4, 3, 2, 1), (8, 4, 1, 1, 1), (8, 4, 3, 1, 5), (8, 4, 3, 6, 1),
    (32, 1, 3, 8, 1), (32, 1, 3, 24, 5), (32, 1, 1, 2, 1), (32, 1, 16, 8, 1),
    (2, 4, 3, 24, 1), (2, 4, 16, 64, 5), (2, 4, 1, 8, 5), (2, 4, 3, 2, 1),
]


@pytest.mark.parametrize(
    "kv,g,b,nb,t", RAGGED_CASES,
    ids=[f"kv{c[0]}-b{c[2]}-nb{c[3]}-t{c[4]}" for c in RAGGED_CASES],
)
def test_pallas_tiles_match_reference_on_ragged_lengths(kv, g, b, nb, t):
    """The Pallas kernel (interpreted) against the reference where its
    tiling shows: lengths around a tile boundary, one-tile and padding
    lanes, tables narrower than a tile and not a multiple of one, with
    garbage wherever the mask must hold."""
    from client_tpu.models import paged_attention as pa

    rng = np.random.default_rng(kv * 1000 + b * 100 + nb + t)
    q, k_pages, v_pages, tables, positions = _ragged_case(
        rng, kv, g, b, nb, t
    )
    args = (q, k_pages, v_pages, tables, positions)
    ref = np.asarray(pa.paged_attention_reference(*args))
    out = np.asarray(pa.paged_attention_pallas(*args, interpret=True))
    assert out.shape == ref.shape and np.isfinite(out).all()
    # a padding lane reads the trash block's one visible slot: GARBAGE
    scale = np.maximum(1.0, np.abs(ref).max(axis=-1, keepdims=True))
    assert (np.abs(out - ref) / scale).max() <= 1e-5


def test_the_kernel_reads_a_pair_at_its_offset_into_one_pool():
    """Ouro's call (`models/ouro.py`): multi-head, KV 16 with ONE query
    row a KV head, the lanes' tables offset into a pool that holds
    several (pass, layer) pairs one after another. The pair in the
    middle, read through ``tables + pair * NB``, gives what the pair
    alone gives through the plain tables, to the bit, and the oracle's
    rows; its neighbours hold garbage, and a padding lane's zeros name
    the pair's own trash block. A tile is 4 pages at the served bf16
    shapes (2 at this test's float32), and an offset keeps it whole."""
    import jax.numpy as jnp

    from client_tpu.models import paged_attention as pa

    assert pa.pages_per_tile(16, 16, 128, jnp.bfloat16) == 4
    rng = np.random.default_rng(47)
    q, k_pages, v_pages, tables, positions = _ragged_case(
        rng, 16, 1, 3, 24, 1)
    blocks = k_pages.shape[0]
    other = lambda pool, sign: np.full_like(pool, sign * GARBAGE)  # noqa: E731
    k_pool = np.concatenate([other(k_pages, 1), k_pages, other(k_pages, 1)])
    v_pool = np.concatenate([other(v_pages, -1), v_pages, other(v_pages, -1)])
    ref = np.asarray(pa.paged_attention_reference(
        q, k_pages, v_pages, tables, positions))
    alone = np.asarray(pa.paged_attention_pallas(
        q, k_pages, v_pages, tables, positions, interpret=True))
    offset = np.asarray(pa.paged_attention_pallas(
        q, k_pool, v_pool, tables + blocks, positions, interpret=True))
    assert np.array_equal(offset, alone)
    scale = np.maximum(1.0, np.abs(ref).max(axis=-1, keepdims=True))
    assert (np.abs(offset - ref) / scale).max() <= 1e-5
    assert (np.abs(np.asarray(pa.paged_attention_xla(
        q, k_pool, v_pool, tables + blocks, positions)) - ref)
        / scale).max() <= 1e-5
    # every live tile of a lane whose blocks lie side by side is whole
    # at either place, and the pool's end is the whole pool's
    first, lengths = pa.visible_slots(positions, None)
    whole = pa.whole_tiles(tables, first, lengths, 2, 16, blocks)
    moved = pa.whole_tiles(tables + blocks, first, lengths, 2, 16, 3 * blocks)
    assert (whole[:2] >= 0).sum() > 0
    assert np.array_equal(np.where(whole >= 0, whole + blocks, -1), moved)


MASKING_CASES = [
    # (kv, g, batch, nb, t, window, sink): the two cache groups of
    # mimo_v2_flash (K rows of 192, V rows of 128; 64 query heads over 4
    # and over 8 kv heads), each new argument alone and all together
    (4, 16, 3, 24, 1, None, False), (4, 16, 1, 8, 1, None, True),
    (8, 8, 16, 24, 1, 128, True), (8, 8, 3, 24, 1, 128, False),
    (8, 8, 3, 6, 1, 128, True), (8, 8, 1, 1, 1, 128, True),
    (8, 8, 3, 24, 5, 128, True), (8, 8, 3, 24, 1, 16, True),
    (8, 8, 3, 24, 1, 17, False), (4, 16, 3, 8, 5, 40, True),
]


@pytest.mark.parametrize(
    "kv,g,b,nb,t,window,sink", MASKING_CASES,
    ids=[f"kv{c[0]}-b{c[2]}-nb{c[3]}-t{c[4]}-w{c[5]}-s{int(c[6])}"
         for c in MASKING_CASES],
)
def test_pallas_window_sink_and_v_size_match_xla(
        kv, g, b, nb, t, window, sink):
    """The kernel's three new arguments against the XLA implementation on
    the ragged layouts: V rows narrower than K rows, a sliding window
    whose blocks wholly behind it are the trash block in the table (as
    the engine's window group hands them over) and hold garbage in the
    pool, and a per-head sink logit. A kernel that fetched a tile behind
    the window, or was one slot off at either edge of it, reads garbage
    and fails."""
    from client_tpu.models import paged_attention as pa

    rng = np.random.default_rng(kv * 1000 + b * 100 + nb + t + (window or 0))
    q, k_pages, v_pages, tables, positions = _ragged_case(
        rng, kv, g, b, nb, t, d=256, dv=128
    )
    # rows of 192 as the model pads them: zeros in the last 64 sizes of
    # q (so garbage there in a masked K row still multiplies to 0)
    q[..., 192:] = 0.0
    bs = k_pages.shape[1]
    if window is not None:
        _hide_behind_window(tables, k_pages, v_pages, positions, window)
    masking = {"window": window, "scale": 192 ** -0.5}
    if kv == 4:
        # the flat pools a model under 8 kv heads keeps
        k_pages = k_pages.reshape(len(k_pages), bs * kv, -1)
        v_pages = v_pages.reshape(len(v_pages), bs * kv, -1)
        masking["kv_heads"] = kv
    if sink:
        masking["sink"] = rng.normal(size=(kv * g,)).astype(np.float32) * 3
    args = (q, k_pages, v_pages, tables, positions)
    ref = np.asarray(pa.paged_attention_xla(*args, **masking))
    out = np.asarray(
        pa.paged_attention_pallas(*args, interpret=True, **masking)
    )
    assert out.shape == ref.shape and out.shape[-1] == 128
    assert np.isfinite(out).all()
    # float32 throughout; 3e-5 and not the 1e-5 of the test above: the
    # online softmax rescales a sink of a few units tile by tile, in
    # another order than XLA's one pass (1.5e-5 seen)
    scale = np.maximum(1.0, np.abs(ref).max(axis=-1, keepdims=True))
    assert (np.abs(out - ref) / scale).max() <= 3e-5
    if sink:
        # the sink is in the denominator: without it the rows differ
        bare = pa.paged_attention_xla(*args, **{**masking, "sink": None})
        assert np.abs(np.asarray(bare) - ref).max() > 1e-3


def _shared_value_oracle(q, k_pages, v_pages, tables, positions, window):
    """Differential attention's pairing in plain numpy, a lane and a head
    at a time: ``q`` [B, T, H, D], ``k_pages`` [N, bs, KV, 2 D] (a row the
    key heads ``2p, 2p + 1`` side by side), ``v_pages`` [N, bs, KV, Dv]
    (the pair's one value head); head ``h`` reads key head ``h // (H / (2
    KV))`` and sees slots ``<= position`` and, under ``window``, ``>
    position - window``."""
    b, t, h, d = q.shape
    bs, kv = k_pages.shape[1:3]
    out = np.zeros((b, t, h, v_pages.shape[-1]), np.float64)
    for lane in range(b):
        keys = k_pages[tables[lane]].reshape(-1, 2 * kv, d).astype(np.float64)
        values = v_pages[tables[lane]].reshape(
            -1, kv, v_pages.shape[-1]).astype(np.float64)
        slots = np.arange(len(keys))
        for row in range(t):
            seen = slots <= positions[lane, row]
            if window is not None:
                seen &= slots > positions[lane, row] - window
            for head in range(h):
                key_head = head // (h // (2 * kv))
                scores = keys[seen, key_head] @ q[lane, row, head] / d ** 0.5
                weights = np.exp(scores - scores.max())
                out[lane, row, head] = (
                    weights / weights.sum()) @ values[seen, key_head // 2]
    return out


SHARED_VALUE_CASES = [
    # (kv rows, query heads a key head, batch, nb, t, window): the cell's
    # 40 heads over 20 key heads of 64 and 10 value heads of 128, its
    # window of 512 (33 blocks: the ring's 40 in a table of 48), one lane,
    # a table narrower than a tile, verify rows, a toy's two rows
    (10, 2, 3, 24, 1, None), (10, 2, 3, 48, 1, 512), (10, 2, 1, 8, 1, None),
    (10, 2, 3, 1, 1, None), (10, 2, 3, 8, 3, 40), (2, 2, 3, 24, 1, 17),
    (2, 1, 7, 24, 1, None),
]


@pytest.mark.parametrize(
    "kv,g,b,nb,t,window", SHARED_VALUE_CASES,
    ids=[f"kv{c[0]}-g{c[1]}-b{c[2]}-nb{c[3]}-t{c[4]}-w{c[5]}"
         for c in SHARED_VALUE_CASES],
)
def test_value_heads_shared_by_key_pairs_match_the_oracle(
        kv, g, b, nb, t, window):
    """``keys_per_value = 2``: a key row of 128 holds two key heads of 64
    that share the row's value head of 128. The oracle
    (``paged_attention_reference``, which cuts the rows and repeats the
    values), plain XLA (which re-views the gathered rows) and the kernel
    (whose queries are widened with zeros to the key row, so that the
    kernel itself is unchanged) against a numpy loop over lanes and
    heads; ragged lengths around the tile of 4 pages that float32 rows
    give (3.2 of them a slot's share at KV 10, rounded to the nearer
    power of two; 2 at KV 2), garbage wherever the mask must hold, flat
    pools as the model keeps them."""
    from client_tpu.models import paged_attention as pa

    rng = np.random.default_rng(kv * 1000 + b * 100 + nb + t + (window or 0))
    q, k_pages, v_pages, tables, positions = _ragged_case(
        rng, kv, 2 * g, b, nb, t, d=128)
    q = q[..., :64]
    bs = k_pages.shape[1]
    if window is not None:
        _hide_behind_window(tables, k_pages, v_pages, positions, window)
    want = _shared_value_oracle(q, k_pages, v_pages, tables, positions, window)
    scale = np.maximum(1.0, np.abs(want).max(axis=-1, keepdims=True))
    args = (q, k_pages.reshape(len(k_pages), bs * kv, -1),
            v_pages.reshape(len(v_pages), bs * kv, -1), tables, positions)
    masking = dict(window=window, kv_heads=kv, keys_per_value=2)
    plain = np.asarray(pa.paged_attention_xla(*args, **masking))
    kernel = np.asarray(pa.paged_attention_pallas(
        *args, interpret=True, **masking))
    assert plain.shape == kernel.shape == want.shape == (b, t, 2 * g * kv, 128)
    assert np.isfinite(kernel).all()
    assert (np.abs(plain - want) / scale).max() <= 1e-5
    assert (np.abs(kernel - want) / scale).max() <= 1e-5
    if window is None:
        dumb = np.asarray(pa.paged_attention_reference(
            q, k_pages, v_pages, tables, positions, keys_per_value=2))
        assert (np.abs(dumb - want) / scale).max() <= 1e-5
    # the pairing shows: with the row's halves the other way round the
    # same call reads the other key head
    other = np.concatenate([k_pages[..., 64:], k_pages[..., :64]], axis=-1)
    swapped = np.asarray(pa.paged_attention_xla(
        args[0], other.reshape(args[1].shape), *args[2:], **masking))
    live = positions[:, 0] > 0
    assert np.abs(swapped - plain)[live].max() > 1e-2


def _rounded_up_case(rng, dtype, window, tile, b_lengths, nb, bs=16, kv=10):
    """Lanes of the given context lengths at ``keys_per_value`` 2 and 10
    rows of 128 a token, laid out as the engine would: without a window,
    every lane's blocks consecutive from a tile of the pool; under one,
    a ring a lane of ``window_ring_blocks(window, bs, tile)`` blocks
    (``kv_cache.window_tables``), holding the ``R`` newest columns, so a
    context longer than the ring has wrapped it. Every slot no row may
    see (past the position, behind the window, the trash block) holds
    ``GARBAGE``. Returns (q, k_pages, v_pages, tables, positions)."""
    from client_tpu.llm.kv_cache import window_ring_blocks, window_tables

    lanes = len(b_lengths)
    held = nb if window is None else window_ring_blocks(window, bs, tile)
    # a lane's blocks start on a tile of the pool: 1 + a multiple of it
    stride = -(-held // tile) * tile
    k_pages = np.full((1 + lanes * stride, bs, kv, 128), GARBAGE, np.float32)
    v_pages = np.full((1 + lanes * stride, bs, kv, 128), -GARBAGE, np.float32)
    blocks = (1 + np.arange(lanes * stride)).reshape(lanes, stride)[:, :held]
    positions = np.asarray(b_lengths, np.int32)[:, None] - 1
    if window is None:
        tables = np.where(
            np.arange(nb)[None] <= positions // bs, blocks, 0).astype(np.int32)
    else:
        tables = window_tables(blocks, (positions[:, 0] // bs).tolist(), nb)
    for lane, length in enumerate(b_lengths):
        first = 0 if window is None else max(0, length - window)
        for column in range(first // bs, (length - 1) // bs + 1):
            seen = np.arange(column * bs, (column + 1) * bs)
            seen = (seen >= first) & (seen < length)
            page = tables[lane, column]
            assert page > 0
            k_pages[page, seen] = rng.normal(size=(seen.sum(), kv, 128))
            v_pages[page, seen] = rng.normal(size=(seen.sum(), kv, 128))
    q = rng.normal(size=(lanes, 1, 40, 64)).astype(np.float32)
    return (q.astype(dtype), k_pages.astype(dtype), v_pages.astype(dtype),
            tables, positions)


@pytest.mark.parametrize("window", [None, 200])
@pytest.mark.parametrize("dtype,tile", [("float32", 4), ("bfloat16", 8)])
def test_a_tile_rounded_up_past_the_budget_matches_the_oracle(
        dtype, tile, window):
    """The rule's rounding UP, at the shipped budget: `phi4flash`'s call
    (``keys_per_value`` 2, 40 heads, 10 rows of 128 a token) has pages of
    which a slot's share holds 6.4 in bf16 and 3.2 in float32, so tiles
    of 8 and 4 where the largest power of two under the budget gave 4 and
    2. Contexts end inside, at the end of and one past a tile, near the
    start of the table and far enough in to have wrapped a window's ring
    (a window of 200: 14 blocks, a ring of 16, whole tiles, in a table of
    48 columns); whole tiles come by one copy a pool and bring what lies
    beside the live pages. The interpreted kernel against
    ``paged_attention_reference`` (which knows no window: under one, the
    numpy loop of the test above stands in) and plain XLA."""
    import jax.numpy as jnp

    from client_tpu.models import paged_attention as pa

    dtype = jnp.dtype(dtype)
    bs, kv, nb = 16, 10, 48
    assert pa._KV_VMEM_BUDGET == 1 << 20
    page_bytes = bs * kv * 128 * dtype.itemsize
    assert tile == pa.pages_per_tile(bs * kv, 1, 128, dtype, 2)
    # the floor of the pages a slot's share holds is under the tile
    assert tile > pa._KV_VMEM_BUDGET // 4 // page_bytes >= tile // 2
    slots = tile * bs
    lengths = [slots - 5, slots, slots + 1, 1,
               5 * slots - 7, 5 * slots, 5 * slots + 1, nb * bs]
    rng = np.random.default_rng(tile * 1000 + (window or 0))
    q, k_pages, v_pages, tables, positions = _rounded_up_case(
        rng, dtype, window, tile, lengths, nb)
    first_slots, seen = pa.visible_slots(positions, window)
    walked, whole = pa.count_tiles(
        tables, np.asarray(first_slots), np.asarray(seen), tile, bs,
        len(k_pages))
    assert walked == whole > len(lengths)
    flat = (q, k_pages.reshape(len(k_pages), bs * kv, -1),
            v_pages.reshape(len(v_pages), bs * kv, -1), tables, positions)
    masking = dict(window=window, kv_heads=kv, keys_per_value=2)
    kernel = np.asarray(pa.paged_attention_pallas(
        *flat, interpret=True, **masking), np.float32)
    plain = np.asarray(pa.paged_attention_xla(*flat, **masking), np.float32)
    if window is None:
        want = np.asarray(pa.paged_attention_reference(
            q, k_pages, v_pages, tables, positions, keys_per_value=2),
            np.float32)
    else:
        want = _shared_value_oracle(
            *(np.asarray(a, np.float32) for a in (q, k_pages, v_pages)),
            tables, positions, window)
    assert kernel.shape == want.shape == (len(lengths), 1, 40, 128)
    assert np.isfinite(kernel).all()
    scale = np.maximum(1.0, np.abs(want).max(axis=-1, keepdims=True))
    # float32 throughout, or bf16 operands and weights on the way to a
    # float32 sum and a bf16 result: a few of its steps of 2^-8
    limit = 1e-5 if dtype == jnp.float32 else 2.0 ** -6
    assert (np.abs(kernel - want) / scale).max() <= limit
    assert (np.abs(plain - want) / scale).max() <= limit


LANE_CASES = {
    # kv, query heads a key head, keys_per_value, T, table columns,
    # window, sink, a one-pool call's v_width: every shape of the masks a
    # grid step builds (the slot and kv head of a tile's columns, the kv
    # head of a row): one kv head (no head mask), the flat pools under
    # eight, differential attention's ten rows of pairs, verify rows,
    # rows that are no multiple of the 8 sublanes, tables under a tile
    "kv1-latent": (1, 8, 1, 1, 80, None, False, 32),
    "kv1-mqa-20-rows": (1, 20, 1, 1, 80, None, False, None),
    "kv1-verify-12-rows": (1, 4, 1, 3, 24, None, False, None),
    "kv4-window-sink": (4, 16, 1, 1, 24, 40, True, None),
    "kv4-verify-36-rows": (4, 3, 1, 3, 24, None, False, None),
    "kv8-narrow-table": (8, 4, 1, 1, 2, None, False, None),
    "kv8-verify-window-sink": (8, 4, 1, 3, 24, 72, True, None),
    "kv10-pairs": (10, 2, 2, 1, 24, None, False, None),
    "kv10-pairs-verify-window": (10, 2, 2, 3, 24, 40, False, None),
    "kv10-pairs-narrow-table": (10, 2, 2, 1, 1, None, False, None),
    "kv10-ten-rows-window": (10, 1, 1, 1, 24, 17, False, None),
}


def _lane_case(case):
    """(args, masking, the oracle's output) of one of :data:`LANE_CASES`
    on :func:`_ragged_case`'s four lanes (lengths around a tile's edge, a
    padding lane, garbage wherever a mask must hold), float32 throughout.
    The oracle is ``paged_attention_reference`` wherever it can say the
    case (it knows neither window nor sink), plain XLA elsewhere."""
    from client_tpu.models import paged_attention as pa

    kv, g, pairs, t, nb, window, sink, v_width = LANE_CASES[case]
    rng = np.random.default_rng(sum(map(ord, case)))
    q, k_pages, v_pages, tables, positions = _ragged_case(
        rng, kv, pairs * g, 4, nb, t)
    if pairs > 1:
        q = q[..., : q.shape[-1] // pairs]
    if window is not None:
        _hide_behind_window(tables, k_pages, v_pages, positions, window)
    masking = {"window": window, "keys_per_value": pairs}
    if sink:
        masking["sink"] = 3 * rng.normal(size=q.shape[2]).astype(np.float32)
    plainly = dict(keys_per_value=pairs)
    if v_width:
        v_pages, plainly["v_width"] = None, v_width
        masking.update(v_width=v_width)
    if window is None and not sink:
        want = pa.paged_attention_reference(
            q, k_pages, v_pages, tables, positions, **plainly)
    if kv < 8:
        # the flat pools a model under 8 kv heads keeps
        flat = lambda pool: pool.reshape(  # noqa: E731
            len(pool), -1, pool.shape[-1])
        k_pages, v_pages = flat(k_pages), v_pages if v_width else flat(v_pages)
        masking["kv_heads"] = kv
    args = (q, k_pages, v_pages, tables, positions)
    if window is not None or sink:
        want = pa.paged_attention_xla(*args, **masking)
    return args, masking, np.asarray(want)


@pytest.mark.parametrize("case", LANE_CASES)
def test_every_shape_of_a_lanes_masks_matches_the_oracle(case):
    """What a grid step does outside its walk, held to the oracle over
    every shape it takes: the head mask at KV 1 (none) / 4 / 8 / 10, key
    pairs, window and sink, the one-pool call, T = 1 and 3, row counts
    that are no multiple of the 8 sublanes, a table narrower than a
    tile; four lanes whose walks are one tile and several, so that the
    two tiles the kernel keeps in flight cross every kind of step from
    one lane to the next (a one-tile lane between two long ones, the
    padding lane last)."""
    from client_tpu.models import paged_attention as pa

    args, masking, want = _lane_case(case)
    out = np.asarray(
        pa.paged_attention_pallas(*args, interpret=True, **masking))
    assert out.shape == want.shape and np.isfinite(out).all()
    scale = np.maximum(1.0, np.abs(want).max(axis=-1, keepdims=True))
    assert (np.abs(out - want) / scale).max() <= 3e-5


# how a tile's pages lie in the pool (paged_attention.whole_tiles): the
# kinds are the kernel's arguments, the layouts what a table can look
# like to it
TILE_KINDS = {
    # kv, g, d, dv, rows, window, sink, flat pools
    "plain": (8, 4, 128, 128, 1, None, False, False),
    "window": (8, 4, 128, 128, 1, 72, False, False),
    "window-sink": (8, 4, 128, 128, 1, 72, True, False),
    "unequal-rows": (4, 4, 256, 128, 1, None, False, True),
    "flat-kv4": (4, 8, 128, 128, 1, 150, False, True),
    "verify-t4": (8, 4, 128, 128, 4, None, False, False),
}
TILE_LAYOUTS = (
    "consecutive", "shuffled", "prompt-then-fragments", "wrap-inside-tile",
    "trash-edges", "narrow", "off-pool",
)


def _placed_case(kind, layout):
    """Three lanes of float32 contents and the table ``layout`` puts
    them behind, for the kernel arguments of ``kind``; and the same
    contents behind a twin table that takes the other way into VMEM (a
    shuffled one, or the consecutive one for ``shuffled`` itself).
    Every pool slot no row may see holds ``GARBAGE``, the pages beside
    a lane's live ones included; columns wholly behind a window and past
    a lane's last block hold the trash block, as the engine writes them.
    Returns (q, positions, masking, pages, [(k, v, table), (k, v, table)])."""
    from client_tpu.models import paged_attention as pa

    kv, g, d, dv, t, window, sink, flat = TILE_KINDS[kind]
    bs, b = 16, 3
    rng = np.random.default_rng(
        len(kind) * 100 + TILE_LAYOUTS.index(layout))
    pages = pa.pages_per_tile(bs, kv, max(d, dv), np.float32)
    assert pages >= 2
    nb = max(1, pages - 1) if layout == "narrow" else 3 * pages - 1
    if layout == "trash-edges":
        # a last tile of one live column; under a window the first
        # visible slot is the last of its block too
        lengths = [(pages + 1) * bs - 15, 2 * pages * bs + 1,
                   min(window or nb * bs, nb * bs) + bs - t + 1]
    else:
        lengths = [nb * bs, min(nb * bs, pages * bs + bs + 5),
                   max(t, nb * bs - pages * bs - 3)]
    lengths = [max(t, min(length, nb * bs)) for length in lengths]
    room = nb + 2 * pages  # a lane's own stretch of the pool
    n = 1 + pages + b * room
    column = np.arange(nb)

    def placed(how):
        """[b, nb] pool pages of the lanes' logical blocks."""
        base = 1 + pages + room * np.arange(b)[:, None]
        if how == "shuffled":
            return 1 + rng.permutation(n - 1)[: b * nb].reshape(b, nb)
        if how == "prompt-then-fragments":
            # the first tile as allocated in one go, the rest backwards
            tail = base + nb - 1 - (column - pages)
            return np.where(column < pages, base + column, tail)
        if how == "wrap-inside-tile":
            # a ring's wrap at column P + 1, inside the second tile
            return base + (column + nb - pages - 1) % nb
        if how == "off-pool":
            # lane 0 from page 1 (a span from a first live column that
            # is not its tile's first starts under the pool), lane 2's
            # last blocks the pool's last pages
            pages_of = base + column
            pages_of[0] = 1 + column
            pages_of[2] = n - (lengths[2] + bs - 1) // bs + column
            return pages_of
        return base + column

    positions = np.stack([
        max(0, length - t) + np.minimum(
            np.arange(t), length - 1 - max(0, length - t))
        for length in lengths
    ]).astype(np.int32)
    first = np.maximum(positions.min(axis=1) - (window or 1 << 30) + 1, 0)
    live_k = rng.normal(size=(b, nb * bs, kv, d)).astype(np.float32)
    live_v = rng.normal(size=(b, nb * bs, kv, dv)).astype(np.float32)
    for lane, length in enumerate(lengths):
        live_k[lane, length:] = live_k[lane, : first[lane]] = GARBAGE
        live_v[lane, length:] = live_v[lane, : first[lane]] = -GARBAGE
    held = ((column[None] >= (first // bs)[:, None])
            & (column[None] <= ((np.asarray(lengths) - 1) // bs)[:, None]))
    layouts = []
    for how in (layout, "consecutive" if layout == "shuffled" else "shuffled"):
        where = placed(how)
        assert len(set(where[held])) == held.sum() and where[held].max() < n
        k_pages = np.full((n, bs, kv, d), GARBAGE, np.float32)
        v_pages = np.full((n, bs, kv, dv), -GARBAGE, np.float32)
        k_pages[where[held]] = live_k.reshape(b, nb, bs, kv, d)[held]
        v_pages[where[held]] = live_v.reshape(b, nb, bs, kv, dv)[held]
        if flat:
            k_pages = k_pages.reshape(n, bs * kv, d)
            v_pages = v_pages.reshape(n, bs * kv, dv)
        table = np.where(held, where, 0).astype(np.int32)
        layouts.append((k_pages, v_pages, table))
    q = rng.normal(size=(b, t, kv * g, d)).astype(np.float32)
    masking = {"window": window}
    if flat:
        masking["kv_heads"] = kv
    if d != dv:
        q[..., 192:] = 0.0
        masking["scale"] = 192 ** -0.5
    if sink:
        masking["sink"] = rng.normal(size=(kv * g,)).astype(np.float32) * 3
    return q, positions, masking, pages, layouts


@pytest.mark.parametrize("layout", TILE_LAYOUTS)
@pytest.mark.parametrize("kind", TILE_KINDS)
def test_pallas_fetches_whole_tiles_and_pages_to_the_same_bits(kind, layout):
    """A tile whose live pages lie side by side comes into VMEM by one
    copy a pool, any other page by page, and the arithmetic on a tile
    does not know which: the same contents behind ``layout``'s table and
    behind its twin give the same bits, and both XLA's result. The
    pages a whole tile's copy reads beside the live ones hold garbage
    (or another lane's rows), so a kernel that let a dead column's slot
    through the mask fails both ways."""
    from client_tpu.models import paged_attention as pa

    q, positions, masking, pages, layouts = _placed_case(kind, layout)
    bs = 16
    first, lengths = pa.visible_slots(positions, masking["window"])
    outs, wholes = [], []
    for k_pages, v_pages, table in layouts:
        args = (q, k_pages, v_pages, table, positions)
        out = np.asarray(
            pa.paged_attention_pallas(*args, interpret=True, **masking))
        ref = np.asarray(pa.paged_attention_xla(*args, **masking))
        assert out.shape == ref.shape and np.isfinite(out).all()
        scale = np.maximum(1.0, np.abs(ref).max(axis=-1, keepdims=True))
        assert (np.abs(out - ref) / scale).max() <= 3e-5
        outs.append(out)
        wholes.append(pa.count_tiles(
            table, first, lengths, pages, bs, len(k_pages)))
    assert (outs[0] == outs[1]).all()
    # the layout is what its name says: both ways in were taken
    (walked, whole), (_, twin_whole) = wholes
    if layout in ("consecutive", "trash-edges", "narrow"):
        assert whole == walked > twin_whole
    elif layout == "shuffled":
        assert whole < walked == twin_whole
    else:
        assert twin_whole <= whole < walked


ONE_POOL_CASES = {
    # table columns, lane lengths in slots, pool pages (None: a stretch
    # of its own a lane): lengths that end one slot before, on and one
    # past the long tile's edge (1,024 slots), past its second, at 1 and
    # at the table's end; a table narrower than one tile; a pool that
    # holds fewer pages than a tile (every tile page by page)
    "tile-edges": (160, (1023, 1024, 1025, 2049, 1, 2560), None),
    "narrow-table": (24, (384, 100, 1), None),
    "small-pool": (64, (208, 16, 200), 40),
}


@pytest.mark.parametrize("case", ONE_POOL_CASES)
def test_the_one_pool_call_folds_its_long_tile(case):
    """The one-pool call (values inside the key rows, KV 1) at the tile
    ``pages_per_tile`` gives one pool, 64 pages of 16 slots, against
    the oracle: one score block a stop whatever the tile's length, the
    dead slots a whole tile's copy brings masked (they hold garbage),
    and the same contents behind a shuffled table (the page-by-page
    path, one rolled loop over the tile's 64 pages) to the same bits."""
    from client_tpu.models import paged_attention as pa

    nb, lengths, n = ONE_POOL_CASES[case]
    bs, width, dv, heads = 16, 128, 32, 4
    pages = pa.pages_per_tile(bs, 1, width, np.float32, 1)
    assert pages == 64 and pages * bs == 1024
    lanes = len(lengths)
    owned = [-(-length // bs) for length in lengths]
    n = n or 1 + lanes * nb
    assert (nb < pages) == (case == "narrow-table")
    assert (n < min(pages, nb)) == (case == "small-pool")
    rng = np.random.default_rng(len(case))
    starts = 1 + np.concatenate([[0], np.cumsum(owned)[:-1]])
    moved = np.concatenate([[0], 1 + rng.permutation(n - 1)])
    consecutive = np.zeros((lanes, nb), np.int32)
    pool = np.full((n, bs, width), GARBAGE, np.float32)
    for lane, length in enumerate(lengths):
        blocks = starts[lane] + np.arange(owned[lane])
        consecutive[lane, :owned[lane]] = blocks
        rows = rng.normal(size=(owned[lane] * bs, width)).astype(np.float32)
        rows[length:] = GARBAGE
        pool[blocks] = rows.reshape(owned[lane], bs, width)
    shuffled = np.where(consecutive > 0, moved[consecutive], 0).astype(
        np.int32)
    shuffled_pool = pool[np.argsort(moved)]
    positions = (np.asarray(lengths, np.int32) - 1)[:, None]
    q = rng.normal(size=(lanes, 1, heads, width)).astype(np.float32)
    asked = dict(scale=0.2, kv_heads=1, v_width=dv)
    ref = np.asarray(pa.paged_attention_reference(
        q, pool[:, :, None], None, consecutive, positions,
        scale=0.2, v_width=dv))
    first, seen = pa.visible_slots(positions, None)
    outs = []
    for pool_, table in ((pool, consecutive), (shuffled_pool, shuffled)):
        out = np.asarray(pa.paged_attention_pallas(
            q, pool_, None, table, positions, interpret=True, **asked))
        assert out.shape == ref.shape == (lanes, 1, heads, dv)
        scale = np.maximum(1.0, np.abs(ref).max(axis=-1, keepdims=True))
        assert (np.abs(out - ref) / scale).max() <= 1e-5
        outs.append(out)
        walked, whole = pa.count_tiles(table, first, seen, pages, bs, n)
        assert walked == sum(-(-length // (min(pages, nb) * bs))
                             for length in lengths)
        if case == "small-pool":
            assert whole == 0  # no span of a tile's pages lies in it
        elif table is consecutive:
            # the lane whose span runs off the pool's end goes by page
            assert walked - 1 <= whole <= walked
        else:
            assert whole < walked
    assert (outs[0] == outs[1]).all()


def test_whole_tiles_rule_on_hand_made_tables():
    """:func:`paged_attention.whole_tiles`, the rule the kernel and the
    engine's counter share, case by case in numpy; and the same lines
    under ``jnp``."""
    import jax.numpy as jnp

    from client_tpu.models import paged_attention as pa

    bs, pool = 16, 64
    table = np.array([
        [1, 2, 3, 4, 9, 8, 7, 6, 10, 11, 0, 0],      # run, reversed, run
        [0, 0, 21, 22, 23, 24, 25, 26, 27, 0, 0, 0],  # a window's row
        [0, 0, 0, 1, 2, 3, 0, 0, 0, 0, 0, 0],  # span from under the pool
        [61, 62, 63, 0, 0, 0, 0, 0, 0, 0, 0, 0],   # span past the pool
        [60, 61, 62, 0, 0, 0, 0, 0, 0, 0, 0, 0],   # the pool's last span
        [5, 6, 50, 51, 7, 0, 0, 0, 0, 0, 0, 0],    # a break inside a tile
    ], dtype=np.int32)
    first = np.array([0, 2 * bs + 5, 3 * bs, 0, 0, 0])
    lengths = np.array([10 * bs, 9 * bs, 6 * bs, 3 * bs, 3 * bs - 15,
                        4 * bs + 1])
    expected = [
        [1, -1, 10],   # dead columns of the last tile are not compared
        [19, 23, 27],  # 21 sits at column 2: the span starts at 19
        [-1, 2, -1],   # 1 at column 3 would start at -2; tile 2 unwalked
        [-1, -1, -1],  # 61..64 runs off a pool of 64 pages
        [60, -1, -1],
        [-1, 7, -1],   # one live column is whole wherever it lies
    ]
    whole = pa.whole_tiles(table, first, lengths, 4, bs, pool)
    assert whole.tolist() == expected
    assert pa.count_tiles(table, first, lengths, 4, bs, pool) == (12, 8)
    traced = pa.whole_tiles(
        jnp.asarray(table), jnp.asarray(first), jnp.asarray(lengths),
        4, bs, pool)
    assert np.asarray(traced).tolist() == expected
    # a table narrower than a tile is one tile of its own width; one
    # not a whole number of tiles has spare columns nobody compares
    assert pa.whole_tiles(
        table[:1, :3], first[:1], lengths[:1] * 0 + 3 * bs, 4, bs, pool
    ).tolist() == [[1]]
    assert pa.whole_tiles(
        table[:1, :10], first[:1], lengths[:1], 4, bs, pool
    ).tolist() == [[1, -1, 10]]
    # the strictest pool: a span has to fit
    assert pa.whole_tiles(
        table[:1, :10], first[:1], lengths[:1], 4, bs, 13
    ).tolist() == [[1, -1, -1]]


def test_pages_per_tile_follows_the_shapes_alone():
    """The tile is a function of the pool's shapes and nothing else (no
    batch, table or length goes in); K and V, two slots each, take the
    power of two of pages nearest the VMEM budget: at most √2 of it, and
    no other power of two nearer."""
    import jax.numpy as jnp

    from client_tpu.models import paged_attention as pa

    assert pa._KV_VMEM_BUDGET == 1 << 20
    assert pa.pages_per_tile(16, 8, 128, jnp.bfloat16) == 8
    assert pa.pages_per_tile(16, 32, 128, jnp.bfloat16) == 2
    assert pa.pages_per_tile(16, 2, 128, jnp.bfloat16) == 32
    # KV 20 / D 64 with a value head of 128 a pair of key heads: 10 rows
    # of 128 a token in either pool, pages of 40 KB, 6.4 of them a slot:
    # 8 (1.31 MB in the four buffers), where the largest under gave 4
    assert pa.pages_per_tile(16, 10, 128, jnp.bfloat16) == 8
    # the same rows in float32, as the CPU parity tests hold them: 3.2
    assert pa.pages_per_tile(16, 10, 128, jnp.float32) == 4
    # the served models' two-pool tiles, as `LlmEngineModel` asks for
    # them (a page's rows flat, the wider pool's row): MiMo's full and
    # window groups (K rows of 256 at KV 4 and KV 8), Trinity's (KV 4)
    assert pa.pages_per_tile(16 * 4, 1, 256, jnp.bfloat16, 2) == 8
    assert pa.pages_per_tile(16 * 8, 1, 256, jnp.bfloat16, 2) == 4
    assert pa.pages_per_tile(16 * 4, 1, 128, jnp.bfloat16, 2) == 16
    # one pool (a latent cache's rows of 640 at KV 1): the budget alone
    # holds 25.6 pages, 512 columns of the score block at most; the tile
    # is lengthened to the 1,024 columns the budget gives K and V at KV
    # 8. Two pools of such rows (served by nothing): 12.8 a slot, 16
    assert pa.pages_per_tile(16, 1, 640, jnp.bfloat16, 1) == 64
    assert pa.pages_per_tile(16, 1, 640, jnp.bfloat16, 2) == 16
    for bs, kv, d, dtype in [(16, 1, 640, jnp.bfloat16),
                             (16, 1, 128, jnp.float32),
                             (32, 1, 640, jnp.bfloat16),
                             (16, 2, 256, jnp.bfloat16),
                             (16, 1, 4096, jnp.bfloat16)]:
        pages = pa.pages_per_tile(bs, kv, d, dtype, 1)
        scratch = 2 * pages * bs * kv * d * jnp.dtype(dtype).itemsize
        assert pages >= pa.pages_per_tile(bs, kv, d, dtype, 2)
        assert pages * bs * kv <= 1024
        assert scratch <= pa._ONE_POOL_VMEM_BUDGET
        # short of the columns only where the bytes stop it
        assert pages * bs * kv == 1024 or 2 * scratch > (
            pa._ONE_POOL_VMEM_BUDGET)
    for bs, kv, d, dtype in [(16, 32, 128, jnp.bfloat16),
                             (16, 32, 128, jnp.float32),
                             (32, 8, 128, jnp.bfloat16),
                             (16, 2, 64, jnp.bfloat16),
                             (16, 10, 128, jnp.bfloat16),
                             (16, 10, 128, jnp.float32),
                             (16, 1, 640, jnp.bfloat16),
                             (16, 3, 128, jnp.bfloat16),
                             (16, 5, 64, jnp.float32),
                             (8, 7, 384, jnp.bfloat16)]:
        pages = pa.pages_per_tile(bs, kv, d, dtype)
        scratch = 2 * 2 * pages * bs * kv * d * jnp.dtype(dtype).itemsize
        assert pages >= 1 and pages & (pages - 1) == 0
        # the four buffers hold at most √2 of the budget ..
        assert scratch ** 2 <= 2 * pa._KV_VMEM_BUDGET ** 2
        # .. and no power of two lies nearer it: half as many pages
        # fall short by more than these overshoot, twice as many
        # overshoot by more than these fall short
        assert 2 * scratch ** 2 >= pa._KV_VMEM_BUDGET ** 2
    # a page larger than a slot still moves, one at a time
    assert pa.pages_per_tile(64, 64, 256, jnp.float32) == 1
    assert pa.pages_per_tile(16, 64, 256, jnp.bfloat16) == 1


#: what `LlmEngineModel` asks `pages_per_tile` for each attending cache
#: group of the benchmark's eight cells (a page's rows flat, the wider
#: pool's row, bf16) and the tile it serves: (rows a page, row width,
#: pools, pages the budget's share of a slot holds, pages a tile)
SERVED_TILES = {
    "mistral7b.batch": (16 * 8, 128, 2, 8.0, 8),
    "mimo_v2_flash.reason-full": (16 * 4, 256, 2, 8.0, 8),
    "mimo_v2_flash.reason-window": (16 * 8, 256, 2, 4.0, 4),
    "trinity_mini.reason8k": (16 * 4, 128, 2, 16.0, 16),
    "gigachat3_702b.reason8k_128": (16, 640, 1, 25.6, 64),  # by columns
    "qwen3_next_80b.reason2k_128": (16 * 2, 256, 2, 16.0, 16),
    "jamba2_3b.reason8k_128": (16, 128, 2, 64.0, 64),
    "phi4_mini_flash.reason8k": (16 * 10, 128, 2, 6.4, 8),
    "ouro_2_6b.reason384_16": (16 * 16, 128, 2, 4.0, 4),
}


@pytest.mark.parametrize("cell", SERVED_TILES)
def test_every_cells_served_tile(cell):
    """The tile each cell's paged calls stop at. Every two-pool page but
    Phi-4-mini-flash's divides the budget, so those tiles are what the
    largest power of two under it gives as well; phi4's 40 KB pages (6.4
    a slot) take 8, 655,360 B a stop, where that gives 4."""
    import jax.numpy as jnp

    from client_tpu.models import paged_attention as pa

    rows, width, pools, held, pages = SERVED_TILES[cell]
    assert pa._KV_VMEM_BUDGET // (2 * pools) / (rows * width * 2) == held
    assert pa.pages_per_tile(rows, 1, width, jnp.bfloat16, pools) == pages


def test_phi4s_rings_and_runs_follow_the_tile():
    """`phi4_mini_flash.reason8k`'s engine sizes off its tile of 8 pages,
    as `LlmEngineModel` derives them: the allocators' runs, and the ring
    rounded up to the run. A window of 512 touches 33 blocks of 16: 40
    at tiles of 8 (36 at 4, 48 at 16)."""
    from client_tpu.llm.engine import EngineConfig
    from client_tpu.llm.kv_cache import window_ring_blocks
    from client_tpu.models import phi4flash

    tile = SERVED_TILES["phi4_mini_flash.reason8k"][-1]
    assert window_ring_blocks(512, 16, tile) == 40
    cell = EngineConfig(
        block_size=16, num_blocks=32769, max_active=64, max_seq_len=8192,
        prefix_sharing=False,
        cache_groups=phi4flash.cache_groups(phi4flash.Phi4FlashConfig()))
    assert cell.group_runs((tile, tile, 1)) == [8, 8, 1]
    assert cell.group_num_blocks((tile, tile, 1)) == [32769, 1 + 64 * 40, 65]
    # 4,096 runs of 8 in the full pool: 64 a lane at the longest
    assert (32769 - 1) // tile == 64 * (8192 // (16 * tile))


def test_decode_step_kernels_match_reference_on_tiny_llama(tiny_llama):
    """Full decode-step logits parity (<=1e-5) vs the reference, including
    at the engine's ragged (narrower) page-table width."""
    from client_tpu.models import llama
    from client_tpu.models import paged_attention as pa

    config, params = tiny_llama
    bs, max_blocks = 8, 8
    contexts = [[5, 9, 17, 3, 8], [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11], [7]]
    pages = llama.init_kv_pages(config, 33, bs)
    tables = np.zeros((len(contexts), max_blocks), dtype=np.int32)
    next_free = 1
    for i, ctx in enumerate(contexts):
        n_blocks = (len(ctx) + 1 + bs - 1) // bs
        tables[i, :n_blocks] = range(next_free, next_free + n_blocks)
        next_free += n_blocks
        toks = np.zeros([1, 16], dtype=np.int32)
        toks[0, : len(ctx)] = ctx
        _, pages = llama.prefill_into_pages(
            params, toks, tables[i], pages, len(ctx) - 1, config
        )
    tokens = np.array([11, 12, 13], dtype=np.int32)
    positions = np.array([len(c) for c in contexts], dtype=np.int32)
    ref, _ = llama.decode_step_paged_attn(
        params, tokens, positions, tables, pages, config,
        pa.paged_attention_reference,
    )
    ref = np.asarray(ref)
    for name in ("fused_xla", "pallas_interpret"):
        out, _ = llama.decode_step_paged_attn(
            params, tokens, positions, tables, pages, config,
            pa.resolve_decode_attention(name, "cpu")[1],
        )
        assert np.abs(np.asarray(out) - ref).max() <= 1e-5, name
    # ragged width: 2 blocks cover the longest context (11+1 tokens)
    out, _ = llama.decode_step_paged_attn(
        params, tokens, positions, tables[:, :2], pages, config,
        pa.paged_attention_xla,
    )
    assert np.abs(np.asarray(out) - ref).max() <= 1e-5


def test_suffix_prefill_matches_full_prefill(tiny_llama):
    """Prefilling only the unshared suffix against prefix pages must
    reproduce the full prefill's logits AND its written page content —
    including with an oversized (bucketed) static prefix width."""
    from client_tpu.models import llama

    config, params = tiny_llama
    bs = 8
    ctx = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8]  # 12 tokens, start at 8
    table = np.zeros([8], dtype=np.int32)
    table[:2] = [1, 2]
    toks = np.zeros([1, 16], dtype=np.int32)
    toks[0, :12] = ctx
    full_logits, full_pages = llama.prefill_into_pages(
        params, toks, table, llama.init_kv_pages(config, 33, bs), 11, config
    )
    prefix_toks = np.zeros([1, 8], dtype=np.int32)
    prefix_toks[0, :8] = ctx[:8]
    _, pages = llama.prefill_into_pages(
        params, prefix_toks, table, llama.init_kv_pages(config, 33, bs),
        7, config,
    )
    suffix = np.zeros([1, 8], dtype=np.int32)
    suffix[0, :4] = ctx[8:]
    for prefix_blocks in (1, 2):  # exact and bucket-padded static width
        logits, out_pages = llama.prefill_suffix_into_pages(
            params, suffix, table, pages, 3, 8, prefix_blocks, config
        )
        assert np.abs(
            np.asarray(logits) - np.asarray(full_logits)
        ).max() <= 1e-5
        for (fk, fv), (sk, sv) in zip(full_pages, out_pages):
            assert np.abs(np.asarray(fk[1:3]) - np.asarray(sk[1:3])).max() <= 1e-5
            assert np.abs(np.asarray(fv[1:3]) - np.asarray(sv[1:3])).max() <= 1e-5


# ---------------------------------------------------------------------------
# engine-level sharing on the tiny llama
# ---------------------------------------------------------------------------

PREFIX = [9, 3, 7, 1, 5, 2, 8, 4, 6, 1, 2, 3, 4, 5, 6, 7]  # 2 full blocks @ 8


@pytest.fixture(scope="module")
def shared_model(tiny_llama):
    """A warmed float32 tiny-llama engine model, prefix sharing ON."""
    from client_tpu.llm.serving import LlmEngineModel

    config, params = tiny_llama
    model = LlmEngineModel(
        config=config,
        params=params,
        engine_config=EngineConfig(
            block_size=8,
            num_blocks=1 + 8 * 8,
            max_active=8,
            max_queue=32,
            max_seq_len=64,
        ),
    )
    model.warmup()
    yield model
    model.shutdown()


def _dense_reference(model, prompt, max_tokens):
    from client_tpu.models import llama

    return np.asarray(
        llama.generate(
            model._params,
            np.array([prompt], dtype=np.int32),
            model._config,
            max_tokens,
        )
    )[0].tolist()


async def _model_generate(model, prompt, max_tokens, parameters=None):
    params = {"max_tokens": max_tokens}
    params.update(parameters or {})
    out = []
    async for response in model.execute_decoupled(
        {"INPUT_IDS": np.array(prompt, dtype=np.int32)}, params
    ):
        out.append(int(response["OUTPUT_IDS"][0]))
        if response["__final__"]:
            break
    return out


def test_warmup_selects_and_reports_kernel(shared_model):
    """Off-TPU the probe lands on fused_xla (or a forced override), and
    the choice rides the model config's parameters map."""
    from client_tpu.models import paged_attention

    assert shared_model.decode_kernel in paged_attention.KERNELS
    doc = shared_model.config()
    assert doc["parameters"]["decode_kernel"]["string_value"] == (
        shared_model.decode_kernel
    )
    assert doc["parameters"]["prefix_sharing"]["string_value"] == "cow"


def test_kernel_that_cannot_compile_is_a_load_failure(tiny_llama, monkeypatch):
    """No step down the kernel list: the compiled Pallas kernel cannot
    lower on the CPU backend, so forcing it makes the LOAD fail, with the
    kernel's name and the compiler's words in the index reason."""
    from client_tpu.llm.serving import LlmEngineModel
    from client_tpu.server.model_repository import ModelRepository

    config, params = tiny_llama
    monkeypatch.setenv("CLIENT_TPU_LLM_KERNEL", "pallas")
    model = LlmEngineModel(
        name="forced_pallas",
        config=config,
        params=params,
        engine_config=EngineConfig(
            block_size=8, num_blocks=9, max_active=1, max_seq_len=64
        ),
    )
    repository = ModelRepository()
    repository.add_model(model)
    (entry,) = repository.index()
    assert entry["state"] == "UNAVAILABLE"
    assert "decode_kernel='pallas'" in entry["reason"]
    assert "interpret" in entry["reason"].lower()  # the compiler's message
    assert model.decode_kernel is None and model.engine is None


@pytest.mark.parametrize(
    "family,config_type",
    [("llama", "LlamaConfig"), ("mimo_v2", "MimoV2Config")],
)
def test_unknown_kernel_name_is_a_load_failure(
        family, config_type, monkeypatch):
    """``CLIENT_TPU_LLM_KERNEL`` takes the three served names only: any
    other, the tests' reference among them, fails the LOAD of every
    model family, by the name asked for and with the choices listed."""
    import importlib

    from client_tpu.llm.serving import LlmEngineModel
    from client_tpu.server.model_repository import ModelRepository

    module = importlib.import_module(f"client_tpu.models.{family}")
    monkeypatch.setenv("CLIENT_TPU_LLM_KERNEL", "standin")
    model = LlmEngineModel(
        name=f"unknown_kernel_{family}",
        model=module.ENGINE_MODEL,
        config=getattr(module, config_type).tiny(),
        engine_config=EngineConfig(
            block_size=8, num_blocks=33, max_active=1, max_seq_len=64,
            prefix_sharing=False,
        ),
    )
    repository = ModelRepository()
    repository.add_model(model)
    (entry,) = repository.index()
    assert entry["state"] == "UNAVAILABLE"
    assert "'standin'" in entry["reason"]
    assert "pallas, pallas_interpret, fused_xla" in entry["reason"]
    assert model.decode_kernel is None and model.engine is None


def test_decode_and_verify_programs_hold_the_one_kernel(
        tiny_llama, monkeypatch):
    """The design, held: under the load-time choice ``pallas_interpret``
    the ``jit_llm_decode`` and the ``jit_llm_verify`` program of one
    model both contain the ``pallas_call`` named ``paged_attention``,
    the one function at T = 1 and at T = K+1. A decode step forked from
    the verify step's attention fails here, not in a review."""
    import jax

    from client_tpu.llm.serving import LlmEngineModel

    config, params = tiny_llama
    monkeypatch.setenv("CLIENT_TPU_LLM_KERNEL", "pallas_interpret")
    model = LlmEngineModel(
        name="one_kernel",
        config=config,
        params=params,
        engine_config=EngineConfig(
            block_size=8, num_blocks=9, max_active=1, max_seq_len=64
        ),
        speculation={"mode": "ngram", "k": 2},
    )
    model.warmup()
    try:
        assert model.decode_kernel == "pallas_interpret"
        _, decode, verify = model._device_fns
        pages = model.engine._pages
        one = np.zeros([1], dtype=np.int32)
        ids = np.zeros([model.engine_config.ids_width], dtype=np.int32)
        table = np.zeros([1, 1], dtype=np.int32)
        programs = {
            "llm_decode": jax.make_jaxpr(decode)(
                ids, one, one, one, table, pages),
            "llm_verify": jax.make_jaxpr(verify)(
                np.zeros([1, 3], dtype=np.int32),
                np.zeros([1, 3], dtype=np.int32), one, table, pages),
        }
        for name, jaxpr in programs.items():
            text = str(jaxpr)
            assert f"name={name}" in text
            assert "name=paged_attention" in text, name
    finally:
        model.shutdown()


def test_shared_prefix_generations_match_dense_and_share_blocks(shared_model):
    """The acceptance test: concurrent shared-prefix generations EXACTLY
    match the dense oracle, hit the prefix index, and keep peak
    blocks_in_use well below the no-sharing demand."""
    prompts = [PREFIX + [10 + i, 20 + i] for i in range(6)]
    refs = [_dense_reference(shared_model, p, 10) for p in prompts]
    engine = shared_model.engine
    hits_before = engine.allocator.prefix_hits

    async def run():
        peak = 0

        async def watch():
            nonlocal peak
            while True:
                peak = max(peak, engine.stats()["kv_blocks_in_use"])
                await asyncio.sleep(0)

        watcher = asyncio.ensure_future(watch())
        try:
            results = await asyncio.gather(
                *[_model_generate(shared_model, p, 10) for p in prompts]
            )
        finally:
            watcher.cancel()
        return results, peak

    results, peak = asyncio.run(run())
    for prompt, got, expected in zip(prompts, results, refs):
        assert got == expected, f"prompt {prompt} diverged"
    stats = engine.stats()
    assert stats["kv_blocks_in_use"] == 0
    # 5 of 6 requests match the 2-block prefix (the first publishes)
    assert engine.allocator.prefix_hits - hits_before >= 8
    # no-sharing demand: 6 sequences x blocks_for(18 + 10 + 1) = 4 -> 24;
    # sharing peaks at 2 shared + 6 exclusive tails + transient = ~10
    no_sharing_demand = 6 * engine.allocator.blocks_for(len(PREFIX) + 2 + 10 + 1)
    assert peak <= 0.6 * no_sharing_demand, (
        f"peak {peak} not well below no-sharing demand {no_sharing_demand}"
    )


def test_shared_blocks_never_mutated_while_referenced(shared_model):
    """COW invariant at the page level: the bytes of a shared prefix
    block must be bit-identical before and after another sharer's whole
    generation (which writes its own suffix and decode blocks)."""
    engine = shared_model.engine

    async def run():
        holder = engine.submit(PREFIX + [42, 43], max_tokens=20)
        token, final = await holder.__anext__()
        assert not final
        shared_phys = list(engine.allocator.owned(holder.seq_id))[:2]
        assert all(engine.allocator.refcount(p) == 1 for p in shared_phys)

        def snapshot():
            return [
                (
                    np.asarray(layer_pages[0][phys]).copy(),
                    np.asarray(layer_pages[1][phys]).copy(),
                )
                for layer_pages in engine._pages
                for phys in shared_phys
            ]

        before = snapshot()
        other = await _model_generate(shared_model, PREFIX + [77, 78], 12)
        assert len(other) == 12
        # the second sharer referenced (not copied) the prefix blocks
        assert engine.allocator.prefix_hits > 0
        after = snapshot()
        for (bk, bv), (ak, av) in zip(before, after):
            np.testing.assert_array_equal(bk, ak)
            np.testing.assert_array_equal(bv, av)
        engine.release(holder)
        for _ in range(100):
            if engine.stats()["kv_blocks_in_use"] == 0:
                break
            await asyncio.sleep(0)
        assert engine.stats()["kv_blocks_in_use"] == 0

    asyncio.run(run())


def test_sharing_survives_preemption_pressure(tiny_llama):
    """A pool far smaller than the gross working set: sharing + dry-pool
    preemption + resume must still reproduce the dense oracle exactly and
    reclaim every block."""
    from client_tpu.llm.serving import LlmEngineModel

    config, params = tiny_llama
    model = LlmEngineModel(
        config=config,
        params=params,
        engine_config=EngineConfig(
            block_size=8,
            num_blocks=9,  # 8 allocatable blocks << the gross working set
            max_active=8,
            max_queue=16,
            max_seq_len=64,
        ),
    )
    model.warmup()
    try:
        prompts = [PREFIX + [30 + i] for i in range(4)]
        refs = [_dense_reference(model, p, 14) for p in prompts]

        async def run():
            results = await asyncio.gather(
                *[_model_generate(model, p, 14) for p in prompts]
            )
            for prompt, got, expected in zip(prompts, results, refs):
                assert got == expected, f"prompt {prompt} diverged"
            stats = model.engine.stats()
            assert stats["preemptions"] > 0
            assert stats["prefix_cache_hits"] > 0
            assert stats["kv_blocks_in_use"] == 0

        asyncio.run(run())
    finally:
        model.shutdown()


def test_sampled_generation_through_model_is_seed_deterministic(shared_model):
    """Temperature sampling through the real model: same seed -> same
    stream, different seed -> (with overwhelming probability on 10
    draws) a different stream; greedy default unchanged."""
    prompt = PREFIX + [11, 13]

    async def run():
        sampled1 = await _model_generate(
            shared_model, prompt, 10,
            {"temperature": 1.0, "seed": 7, "top_k": 16},
        )
        sampled2 = await _model_generate(
            shared_model, prompt, 10,
            {"temperature": 1.0, "seed": 7, "top_k": 16},
        )
        sampled3 = await _model_generate(
            shared_model, prompt, 10,
            {"temperature": 1.0, "seed": 8, "top_k": 16},
        )
        greedy = await _model_generate(shared_model, prompt, 10)
        return sampled1, sampled2, sampled3, greedy

    s1, s2, s3, greedy = asyncio.run(run())
    assert s1 == s2
    assert s1 != s3
    assert greedy == _dense_reference(shared_model, prompt, 10)
    with pytest.raises(InferenceServerException, match="temperature"):
        shared_model.engine.submit(
            [1, 2], max_tokens=2, parameters={"temperature": "hot"}
        )
    with pytest.raises(InferenceServerException, match="temperature"):
        shared_model.engine.submit(
            [1, 2], max_tokens=2, parameters={"temperature": -0.5}
        )
    with pytest.raises(InferenceServerException, match="top_k"):
        shared_model.engine.submit(
            [1, 2], max_tokens=2, parameters={"top_k": -3}
        )
    # a negative seed would crash np.random.default_rng inside the step
    # loop (engine-fatal) — it must be a submit-time 400 instead
    with pytest.raises(InferenceServerException, match="seed"):
        shared_model.engine.submit(
            [1, 2], max_tokens=2,
            parameters={"temperature": 1.0, "seed": -4},
        )


# ---------------------------------------------------------------------------
# scheduler-level sharing + sampling units (stub model, fake clock)
# ---------------------------------------------------------------------------

VOCAB = 32


class _FakeClock:
    def __init__(self):
        self.now = 0

    def __call__(self):
        return self.now


def _consistent_stub_engine(clock, **overrides):
    """Stub whose prefill and decode agree: the logits for the token at
    absolute position p with value t are one-hot at (t + p) % VOCAB (plus
    a small spread so temperature sampling has real choices). Prefill
    receives only the suffix, so it reconstructs (t, p) from last_index
    and the absolute start — exactly the sharing contract."""

    def logits_row(token, position):
        row = np.linspace(0.0, 1.0, VOCAB, dtype=np.float32)
        row[(int(token) + int(position)) % VOCAB] = 3.0
        return row

    def prefill(tokens, page_table, pages, last_index, start):
        row = logits_row(tokens[0, last_index], start + last_index)
        return row[None, :], pages

    def decode(tokens, positions, page_tables, pages):
        n = tokens.shape[0]
        out = np.zeros([n, VOCAB], dtype=np.float32)
        for i in range(n):
            out[i] = logits_row(tokens[i], positions[i])
        return out, pages

    defaults = dict(
        block_size=4, num_blocks=33, max_active=4, max_queue=8,
        max_seq_len=128,
    )
    defaults.update(overrides)
    return LlmEngine(
        prefill,
        decode_fn_from_logits(decode),
        pages=object(),
        engine_config=EngineConfig(**defaults),
        model_name="stub",
        clock_ns=clock,
    )


async def _collect(seq):
    out = []
    async for token, final in seq:
        out.append(token)
        if final:
            break
    return out


def test_sampled_stream_replays_across_preemption():
    """The per-token PRNG chain (seed, n) makes a preempted-and-resumed
    sampled generation identical to an unpressured one."""
    prompt = [1, 2, 3]
    params = {"temperature": 1.0, "seed": 42, "top_k": 8}

    def run_with(num_blocks):
        clock = _FakeClock()

        async def go():
            engine = _consistent_stub_engine(
                clock, num_blocks=num_blocks, max_seq_len=32
            )
            seqs = [
                engine.submit(prompt, max_tokens=10, parameters=params),
                engine.submit([4, 5, 6], max_tokens=10,
                              parameters={"temperature": 1.0, "seed": 9}),
            ]
            results = await asyncio.gather(*[_collect(s) for s in seqs])
            stats = engine.stats()
            assert stats["kv_blocks_in_use"] == 0
            engine.close()
            return results, stats["preemptions"]

        return asyncio.run(go())

    roomy, preempt_roomy = run_with(num_blocks=33)
    tight, preempt_tight = run_with(num_blocks=5)  # 4 blocks: forced preemption
    assert preempt_roomy == 0
    assert preempt_tight > 0
    assert roomy == tight
    # distinct seeds diverged (spread logits: near-uniform draws)
    assert roomy[0] != roomy[1]


def test_admission_counts_new_blocks_only():
    """The capacity-check satellite: with a live shared prefix, waiting
    sequences admit against their POST-MATCH demand — the same workload
    without sharing admits strictly fewer concurrently."""
    prefix = list(range(32))  # 8 full blocks @ block_size 4

    def run(prefix_sharing):
        clock = _FakeClock()

        async def go():
            # capacity 16: one sharer owns 8 prefix + ~2 blocks; each
            # additional sharer needs only ~2 fresh blocks when sharing
            engine = _consistent_stub_engine(
                clock, num_blocks=17, max_active=6, max_queue=16,
                prefix_sharing=prefix_sharing,
            )
            seqs = [
                engine.submit(prefix + [100 + i, 200 + i], max_tokens=6)
                for i in range(4)
            ]
            peak_active = 0

            async def watch():
                nonlocal peak_active
                while True:
                    peak_active = max(
                        peak_active, engine.stats()["active_sequences"]
                    )
                    await asyncio.sleep(0)

            watcher = asyncio.ensure_future(watch())
            try:
                results = await asyncio.gather(*[_collect(s) for s in seqs])
            finally:
                watcher.cancel()
            assert all(len(r) == 6 for r in results)
            assert engine.stats()["kv_blocks_in_use"] == 0
            engine.close()
            return peak_active

        return asyncio.run(go())

    # gross demand per sequence: blocks_for(34 + 6 + 1) = 11 of 16 -> at
    # most ONE admitted at a time without sharing; with sharing all four
    # fit concurrently (8 shared + 4 x ~3 exclusive)
    assert run(prefix_sharing=False) <= 1
    assert run(prefix_sharing=True) >= 3


def test_sharers_admitted_while_the_first_is_pending_match_its_blocks():
    """Four requests with one 8-block prefix arrive together: all four
    prefills are dispatched in one admission pass, the first's blocks
    published when it was dispatched, so the three behind it reference
    them while it is still pending: 24 hits of a demand of 32, as when
    each prefill was waited for before the next."""
    prefix = list(range(32))  # 8 full blocks @ block_size 4
    clock = _FakeClock()

    async def go():
        engine = _consistent_stub_engine(
            clock, num_blocks=33, max_active=6, max_queue=16)
        reference = _consistent_stub_engine(
            clock, num_blocks=33, max_active=6, max_queue=16,
            prefix_sharing=False)
        prompts = [prefix + [100 + i, 200 + i] for i in range(4)]
        seqs = [engine.submit(p, max_tokens=6) for p in prompts]
        tasks = [asyncio.ensure_future(_collect(s)) for s in seqs]
        while not engine._admitting:
            await asyncio.sleep(0)
        assert [p.seq for p in engine._admitting] == seqs
        assert [s.shared_blocks for s in seqs] == [0, 8, 8, 8]
        pending = engine.stats()
        assert pending["active_sequences"] == pending["waiting_sequences"] == 0
        assert pending["kv_blocks_shared"] == 8
        assert pending["prefix_cache_hits"] == 24
        results = await asyncio.gather(*tasks)
        unshared = await asyncio.gather(*[
            _collect(reference.submit(p, max_tokens=6)) for p in prompts])
        stats = engine.stats()
        engine.close()
        reference.close()
        return results, unshared, stats

    results, unshared, stats = asyncio.run(go())
    assert results == unshared and all(len(r) == 6 for r in results)
    assert stats["prefix_cache_hits"] == 24
    assert stats["prefix_block_demand"] == 32
    assert stats["prefills"] == 4 and stats["prefills_behind"] == 0
    assert stats["kv_blocks_in_use"] == 0


def test_submit_accepts_post_match_demand_and_fails_cleanly_when_gone():
    """submit() recomputes the capacity fast-fail against post-match
    demand (a prompt mostly covered by a live shared prefix is not
    rejected for its gross block count); if the sharers vanish before
    admission, the engine fails the request with a clean
    RESOURCE_EXHAUSTED instead of wedging the admission queue."""
    clock = _FakeClock()

    async def go():
        # capacity 8 blocks @ 4 tokens
        engine = _consistent_stub_engine(
            clock, num_blocks=9, max_active=4, max_queue=8, max_seq_len=128
        )
        prefix = list(range(24))  # 6 full blocks
        holder = engine.submit(prefix, max_tokens=8)
        await holder.__anext__()  # admitted: 6 prefix blocks published
        # gross demand 40 tokens -> 10 blocks > capacity 8, but 5 blocks
        # ride the live shared prefix: post-match demand 5 <= 8
        big = engine.submit(prefix + list(range(50, 58)), max_tokens=8)
        # without the fix this submit raises InferenceServerException
        assert big is not None
        # now release the holder BEFORE big is admitted (its blocks are
        # reclaimed and unpublished) -> big's residual demand exceeds
        # the whole pool -> clean async capacity failure, queue unwedged
        engine.release(holder)
        with pytest.raises(CacheCapacityError):
            await _collect(big)
        # engine still serves fresh work
        fresh = await _collect(engine.submit([1, 2, 3], max_tokens=2))
        assert len(fresh) == 2
        assert engine.stats()["kv_blocks_in_use"] == 0
        engine.close()

    asyncio.run(go())


# ---------------------------------------------------------------------------
# genai-perf shared-prefix workload inputs
# ---------------------------------------------------------------------------


def test_create_llm_inputs_shared_prefix_and_routing_key(tmp_path):
    from client_tpu.genai_perf.inputs import create_llm_inputs

    doc = create_llm_inputs(
        str(tmp_path / "inputs.json"),
        num_prompts=6,
        input_tokens_mean=8,
        output_tokens_mean=4,
        shared_prefix_tokens=32,
    )
    entries = doc["data"]
    assert len(entries) == 6
    first_ids = entries[0]["INPUT_IDS"]["content"]
    keys = set()
    for entry in entries:
        ids = entry["INPUT_IDS"]["content"]
        assert ids[:32] == first_ids[:32]  # token-exact shared prefix
        assert len(ids) > 32
        assert entry["parameters"]["routing_key"].startswith("prefix-")
        assert entry["parameters"]["max_tokens"] >= 1  # merged, not clobbered
        keys.add(entry["parameters"]["routing_key"])
    assert len(keys) == 1  # one affinity key per shared prefix
    # distinct prefixes produce distinct routing keys
    other = create_llm_inputs(
        "", num_prompts=1, input_tokens_mean=8, output_tokens_mean=4,
        shared_prefix_tokens=16,
    )
    assert other["data"][0]["parameters"]["routing_key"] not in keys
    # no prefix -> no routing key stamped
    plain = create_llm_inputs(
        "", num_prompts=1, input_tokens_mean=8, output_tokens_mean=4
    )
    assert "routing_key" not in plain["data"][0].get("parameters", {})
