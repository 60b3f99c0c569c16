"""Ragged paged attention: one contract, three functions, one chooser.

The continuous-batching engine stores every live sequence's KV cache as a
page table over ONE physical block pool (``models/llama.py``
``init_kv_pages``). A step's attention must therefore read a *ragged*
set of pages per sequence: each sequence attends over however many
blocks it has actually earned ("Ragged Paged Attention", PAPERS.md arxiv
2604.15464, is the blueprint). Every function here does that read under
the one contract::

    attn(q[B, T, H, D], k_pages[N, bs, KV, D], v_pages[N, bs, KV, Dv],
         page_tables[B, NB], positions[B, T], **masking) -> out[B, T, H, Dv]

Query row ``t`` of sequence ``b`` sits at absolute position
``positions[b, t]`` and sees slot ``block*bs + offset`` iff it is ``<=
positions[b, t]`` (a freshly scattered token attends to itself);
physical block 0 is the trash block, whose slots that rule always
masks. A decode step is the ``T = 1`` case (``q[:, None]``,
``positions[:, None]``, ``out[:, 0]``); the verify step of speculative
decoding asks for K+1 positions a sequence in ONE call, and the
per-position mask is the whole verification trick: row ``t`` sees
exactly its own speculative prefix (rows ``0..t`` were scattered at
``positions[b, 0..t]`` before the read), never the draft tokens after
it, so the K+1 logits rows are what K+1 sequential decode steps would
have produced. Padding lanes and padding rows produce garbage the
caller discards. ``masking`` is ``window``, ``sink``, ``scale`` and
``kv_heads`` (:func:`paged_attention_pallas`).

A model whose value heads each serve SEVERAL key heads (differential
attention: a value head of 128 shared by the pair of key heads of 64
whose two softmaxes are subtracted, ``models/phi4flash.py``) passes
``keys_per_value = r``: a key row then holds, side by side, the ``r``
key heads of ``D`` columns that share the row's value head
(``k_pages[N, bs, KV, r * D]``: key head ``g`` is columns ``(g % r) *
D`` of row ``g // r``, value head ``g // r`` serves it), ``q`` is ``[B,
T, H, D]`` and head ``h`` reads key head ``h // (H / (r * KV))``, plain
grouped-query order over the ``r * KV`` key heads. A token's row is
stored once and each live row is read once a call; one softmax a query
head, ``out[B, T, H, Dv]``.

A model whose values lie INSIDE its key rows (a latent cache: one row a
token for all heads, the values its leading columns) passes ONE pool,
``v_pages=None`` and ``v_width``, the width of the values: every
function then reads each row once and takes ``v`` as the row's leading
``v_width`` columns (``out[B, T, H, v_width]``). The arguments of the
call pick the path, never a model's name.

- :func:`paged_attention_pallas`: the flash-style Pallas kernel, one
  grid step per sequence. The page table and each sequence's length ride
  scalar prefetch; the pools stay in HBM and the kernel copies a TILE of
  pages (the power of two of them whose bytes lie nearest a fixed VMEM
  budget at the pools' shapes: 8 at KV 8 / D 128 / bf16, whose pages
  divide it, and 8 at 10 rows of 128 a token, whose 40 KB pages do not;
  a one-pool call's as many as give its score block the columns that
  tile has, 64 at rows of 640: :func:`pages_per_tile`) into one of three
  VMEM slots itself, the next two tiles in flight while this one is
  folded into the online softmax, and stops at the sequence's own last
  tile:
  no ``[B, S]`` gather ever materializes and the padding of the table
  to its bucket is never read. A tile whose live pages lie side by side
  in the pool (a window group's ring, a prompt allocated in one go)
  comes by ONE copy of the tile's pages from each pool, any other page
  by page; which, is read off the table itself a tile at a time
  (:func:`whole_tiles`), and the arithmetic on a tile does not know how
  it came. What a TPU serves.
  ``interpret=True`` runs the same kernel under the Pallas interpreter,
  for CPU tests and rehearsals.
- :func:`paged_attention_xla`: one fused XLA computation over the
  gathered pages, the grouped-query einsum with no head repeat, at
  whatever (bucketed) table width the caller passes. What every other
  platform serves, and the XLA side of every on-chip comparison.
- :func:`paged_attention_reference`: gather, materialized head repeat,
  full-width masked softmax, no ``masking``. The tests' oracle, kept as
  dumb as possible and served by nothing.

:func:`resolve_decode_attention` is the one place the choice is made,
once, at model warmup (``llm/serving.py``): by platform (TPU hosts take
the Pallas kernel, everything else plain XLA), or as
``CLIENT_TPU_LLM_KERNEL`` says, over the three names of :data:`KERNELS`.
The choice is reported in the model's config parameters.
"""

import functools
import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: names accepted by :func:`resolve_decode_attention`, best first
KERNELS = ("pallas", "pallas_interpret", "fused_xla")


# ---------------------------------------------------------------------------
# the tests' oracle: gather + repeat_kv + full-width masked softmax
# ---------------------------------------------------------------------------


def paged_attention_reference(q, k_pages, v_pages, page_tables, positions,
                              *, v_width=None, scale=None, keys_per_value=1):
    """Gather + repeat_kv + a ``[B, T, S]`` mask.

    The oracle the XLA and Pallas functions are pinned against: kept as
    dumb as possible (materialized head repeat, full-width softmax) and
    served by nothing. With ``v_pages=None`` the values are the leading
    ``v_width`` columns of ``k_pages``' rows. With ``keys_per_value`` a
    key row is cut into its key heads and each value head repeated for
    the key heads it serves."""
    b, t, h, d = q.shape
    _, bs, kv, _ = k_pages.shape
    kv *= keys_per_value
    n_rep = h // kv
    s = page_tables.shape[1] * bs
    k_ctx = k_pages[page_tables].reshape(b, s, kv, d)
    if v_pages is None:
        v_ctx = k_ctx[..., :v_width]
    else:
        v_ctx = jnp.repeat(
            v_pages[page_tables].reshape(b, s, kv // keys_per_value, -1),
            keys_per_value, axis=2)
    k_rep = jnp.broadcast_to(
        k_ctx[:, :, :, None, :], (b, s, kv, n_rep, d)
    ).reshape(b, s, h, d)
    v_rep = jnp.broadcast_to(
        v_ctx[:, :, :, None, :], (b, s, kv, n_rep, v_ctx.shape[-1])
    ).reshape(b, s, h, -1)
    qh = q.transpose(0, 2, 1, 3)  # [B, H, T, D]
    kh = k_rep.transpose(0, 2, 1, 3)  # [B, H, S, D]
    vh = v_rep.transpose(0, 2, 1, 3)
    scores = jnp.einsum(
        "bhtd,bhkd->bhtk", qh, kh, preferred_element_type=jnp.float32
    )
    scores = scores * scale if scale else scores / (d ** 0.5)
    # per-position validity: query row t sees slot s iff s <= pos[b, t]
    valid = jnp.arange(s)[None, None, :] <= positions[:, :, None]  # [B, T, S]
    scores = jnp.where(valid[:, None, :, :], scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhtk,bhkd->bhtd", weights, vh.astype(weights.dtype))
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B, T, H, D]


# ---------------------------------------------------------------------------
# plain XLA: grouped-query einsum, no repeat materialization
# ---------------------------------------------------------------------------


def _pool_shape(k_pages, v_pages, kv_heads, v_width=None):
    """(block size, kv heads, V row size) of pools ``[N, bs, KV, D]`` or,
    with ``kv_heads`` given, ``[N, bs*KV, D]`` (row ``t*KV + h``). The
    flat form is for models whose KV is under the 8 sublanes of a tile:
    ``[..., 4, D]`` is padded to 8 rows in HBM, twice the bytes, and
    re-viewing it flat is then a copy of the pool. One pool
    (``v_pages=None``) holds its values in the leading ``v_width``
    columns of its rows."""
    if (v_pages is None) != (v_width is not None):
        raise ValueError(
            "one pool whose values lie inside its rows takes v_pages=None "
            "and v_width; two pools take neither")
    dv = v_width if v_pages is None else v_pages.shape[-1]
    if kv_heads is None:
        return k_pages.shape[1], k_pages.shape[2], dv
    return k_pages.shape[1] // kv_heads, kv_heads, dv


def _window_valid(valid, slots, positions, window):
    """``valid`` and, under a sliding ``window``, ``slot > position -
    window``: a query sees its own position and the window - 1 before."""
    if window is None:
        return valid
    return valid & (slots > positions - window)


def _softmax_with_sink(scores, sink):
    """Softmax over the last axis; ``sink`` (broadcastable to
    ``scores[..., 0]``) is one more logit a row that adds to the
    denominator and has no value row."""
    if sink is None:
        return jax.nn.softmax(scores, axis=-1)
    sink = sink.astype(jnp.float32)[..., None]
    m = jnp.maximum(scores.max(axis=-1, keepdims=True), sink)
    p = jnp.exp(scores - m)
    return p / (p.sum(axis=-1, keepdims=True) + jnp.exp(sink - m))


def paged_attention_xla(q, k_pages, v_pages, page_tables, positions,
                        *, window=None, sink=None, scale=None,
                        kv_heads=None, v_width=None, keys_per_value=1):
    """One fused XLA computation over the gathered pages.

    Head layout matches ``_repeat_kv`` (head ``k*g + r`` reads kv head
    ``k``), so ``q.reshape(b, t, kv, g, d)`` lines queries up with their
    kv group and the score/weighted-sum einsums contract directly against
    the un-repeated context: the ``[B, S, H, D]`` repeat never exists,
    and S is whatever (bucketed) width the caller's page table has. The
    gathered context is transposed to ``[B, KV, S, D]`` up front: both
    contractions then run as plain batched matmuls over adjacent
    (batch, kv) dims, which measures ~25% faster than contracting the
    ``[B, S, KV, D]`` gather layout in place (PERF.md PR-14). The
    query-position axis ``T`` rides along both einsums, so one call
    scores all K+1 verify positions against the same gathered pages
    instead of gathering K+1 times. One pool (``v_pages=None``) is
    gathered once, and the values are the context's leading ``v_width``
    columns. With ``keys_per_value = r`` the gathered key rows are
    re-viewed as ``r * KV`` key heads for the scores, and the weights of
    the ``r`` key heads of a row meet the row's one value head."""
    b, t, h, d = q.shape
    bs, kv, dv = _pool_shape(k_pages, v_pages, kv_heads, v_width)
    keys = kv * keys_per_value
    g = h // keys
    s = page_tables.shape[1] * bs
    k_ctx = k_pages[page_tables].reshape(b, s, keys, d).transpose(0, 2, 1, 3)
    if v_pages is None:
        v_ctx = k_ctx[..., :dv]
    else:
        v_ctx = v_pages[page_tables].reshape(b, s, kv, dv).transpose(
            0, 2, 1, 3)
    qg = q.reshape(b, t, keys, g, d)
    scores = jnp.einsum(
        "btkgd,bksd->bkgts", qg, k_ctx, preferred_element_type=jnp.float32
    ) * (scale or d ** -0.5)
    slots = jnp.arange(s)[None, None, :]
    valid = _window_valid(
        slots <= positions[:, :, None], slots, positions[:, :, None], window
    )  # [B, T, S]
    scores = jnp.where(valid[:, None, None, :, :], scores, NEG_INF)
    weights = _softmax_with_sink(
        scores, None if sink is None else sink.reshape(keys, g, 1)
    )
    if keys_per_value > 1:
        # the key heads of a row side by side: one value head's group
        weights = weights.reshape(b, kv, h // kv, t, s)
    out = jnp.einsum(
        "bkgts,bksd->btkgd", weights, v_ctx.astype(weights.dtype)
    )
    return out.reshape(b, t, h, dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel: tiles of pages, double-buffered by hand, ragged trip count
# ---------------------------------------------------------------------------

#: VMEM that sets a tile's length: what two slots of one tile of each
#: pool aim at; a tile is a power of two of pages, so two slots hold
#: this to within a factor of √2 (the kernel keeps :data:`_SLOTS`)
_KV_VMEM_BUDGET = 1 << 20
#: the (token, kv head) row the budget was set for, D 128 in bf16: two
#: slots of K and V tiles of such rows hold 1,024 of them, which is the
#: score block's width there
_BUDGET_ROW_BYTES = 128 * 2
#: the most a ONE-POOL call's two slots may take of the 16 MB of VMEM
#: Mosaic gives a kernel, where the rule below lengthens its tile
_ONE_POOL_VMEM_BUDGET = 4 << 20
#: VMEM slots a pool: the tile being folded and TWO in flight behind it.
#: The budgets above size a tile as if there were two (they set the
#: tile's length, which this does not change); the third makes the
#: buffers 1.5 times that, at most 2.1 MB for two pools and 6 MB for one
_SLOTS = 3


def pages_per_tile(block_size: int, kv_heads: int, head_dim: int,
                   dtype, pools: int = 2) -> int:
    """Pages the kernel brings into VMEM per tile: the power of two whose
    bytes lie NEAREST (by ratio) what :data:`_KV_VMEM_BUDGET` gives one
    slot of one of ``pools`` pools (K and V, or one whose values lie
    inside its key rows), two slots each. The budget is what a tile aims
    at, not a ceiling: two slots hold between 1/√2 and √2 of it (the
    kernel's three, :data:`_SLOTS`, at most 2.12 MB of the 16 MB Mosaic
    gives a kernel), and a page larger
    than a slot still moves, one at a time. A function of the pools'
    shapes alone, so one program serves every batch, and every model
    finds its own tile: 8 pages (128 tokens) at KV 8 / D 128 / bf16, 2
    at KV 32, 32 for a KV 2 tensor-parallel shard, 64 at KV 1 / D 128 (a
    multi-query model's 4 KB pages), all of which divide the budget; 8
    at KV 20 / D 64 with a value head of 128 a PAIR of key heads (10 rows
    of 128 a token in either pool, pages of 40 KB of which a slot's
    share holds 6.4: ``keys_per_value`` 2). Nearest and not the largest
    UNDER the budget, which would give that call 4 pages: 62% of the
    bytes and of the score-block columns a stop is meant to carry, and
    what a stop costs beyond its bytes twice as often (PERF.md, PR 45:
    4 / 8 / 16 pages on the chip).

    A ONE-POOL call gets a longer tile than those bytes hold. What a
    tile stop costs beyond its bytes (the copy's start and its latency
    with one copy in flight, the flag, the wait, the rescale of the
    accumulator) is paid once a score block and so spread over the
    block's columns, the tile's rows (pages x rows a page). One pool
    holds a token's row for ALL heads, so under the same bytes its tile
    has a quarter of the columns a two-pool tile at KV 8 has (256
    against 1,024 at rows of 640), and its stop's fixed part is as long
    as its bytes. Its tile is therefore lengthened to the columns the
    budget gives the shapes it was set for (1,024 of
    :data:`_BUDGET_ROW_BYTES`), two slots of it staying under
    :data:`_ONE_POOL_VMEM_BUDGET`: 64 pages (1,024 tokens, 1.25 MB a
    slot) for one pool of 640-wide bf16 rows at KV 1, where the budget
    alone gives 16. Past those columns the fold itself, not the copy,
    sets a stop's time and the dead slots behind a lane's last token
    grow (PERF.md, PR 37: 32 / 64 / 128 pages on the chip)."""
    page_bytes = block_size * kv_heads * head_dim * jnp.dtype(dtype).itemsize
    share = _KV_VMEM_BUDGET // (2 * pools)
    pages = 1 << max(0, round(math.log2(share / page_bytes)))
    if pools == 1:
        columns = _KV_VMEM_BUDGET // (2 * 2) // _BUDGET_ROW_BYTES
        longer = min(columns // (block_size * kv_heads),
                     _ONE_POOL_VMEM_BUDGET // 2 // page_bytes)
        if longer > pages:
            pages = 1 << (longer.bit_length() - 1)
    return pages


def visible_slots(positions, window):
    """(first, length) a sequence: the slots ``[first, length)`` of its
    context that the query rows at ``positions[B, T]`` see between them,
    each its own position and, under a sliding ``window``, the ``window
    - 1`` before. Where the kernel's walk starts and ends, and what
    :func:`whole_tiles` calls live."""
    lengths = positions.max(axis=1) + 1
    if window is None:
        return lengths * 0, lengths
    first = positions.min(axis=1) - window + 1
    return first * (first > 0), lengths


def whole_tiles(page_tables, first_slots, lengths, pages: int,
                block_size: int, num_pages: int):
    """``[B, cdiv(NB, P)]``: the first pool page of every WHOLE tile of
    ``page_tables[B, NB]``, -1 for a tile that is not (``P`` is
    ``min(pages, NB)``, a tile the table columns ``[t*P, (t+1)*P)``).

    A tile is whole when its LIVE columns hold consecutive pool pages
    (column ``j`` page ``page0 + j % P``) and the ``P`` pages from
    ``page0`` lie inside a pool of ``num_pages``. A column is live when
    it holds a slot some query row of the sequence can see: from the
    block of ``first_slots[b]`` to the block of slot ``lengths[b] - 1``
    (:func:`visible_slots`). What a whole tile's
    dead columns name is never read: the kernel reads the pages beside
    the live ones in their place, and masks them by slot as it masks the
    trash block. A tile with no live column is never walked, and reads
    -1.

    The one rule the kernel (which fetches a whole tile with one copy a
    pool) and the engine's ``attn_tiles_whole`` share: plain array
    operators only, so that ``jnp`` arrays under a trace and the numpy
    tables the engine builds go through the same lines."""
    nb = page_tables.shape[-1]
    pages = min(pages, nb)
    n_tiles = -(-nb // pages)
    column = np.arange(n_tiles * pages, dtype=np.int32)
    if nb % pages:
        # a table narrower than a whole number of tiles: the spare
        # columns of its last tile are nobody's live column
        page_tables = page_tables[:, np.minimum(column, nb - 1)]
    column = column.reshape(n_tiles, pages)
    dead = ~(
        (column >= (first_slots // block_size)[:, None, None])
        & (column <= ((lengths - 1) // block_size)[:, None, None])
        & (column < nb)
    )
    # the span's first page as each column has it; a dead column's is
    # put out of every live one's reach, either side
    page0 = page_tables.reshape(-1, n_tiles, pages) - column % pages
    far = dead * np.int32(num_pages + pages)
    low = (page0 + far).min(axis=-1)
    high = (page0 - far).max(axis=-1)
    whole = (low == high) & (low >= 0) & (low + pages <= num_pages)
    return whole * (low + 1) - 1


def count_tiles(page_tables, first_slots, lengths, pages: int,
                block_size: int, num_pages: int) -> Tuple[int, int]:
    """(tiles the kernel walks, those of them that are whole) for numpy
    tables: what ``LlmEngine.stats()`` books as ``attn_tiles_walked``
    and ``attn_tiles_whole``. The walk is the kernel's: from the tile of
    a sequence's first visible slot to the tile of its last."""
    tile_slots = min(pages, page_tables.shape[-1]) * block_size
    walked = (lengths - 1) // tile_slots - first_slots // tile_slots + 1
    whole = whole_tiles(
        page_tables, first_slots, lengths, pages, block_size, num_pages)
    return int(walked.sum()), int((whole >= 0).sum())


def _rpa_kernel(kv, scale, window, has_sink, v_width, *refs):
    """Grid step ``b``: fold sequence ``b``'s live pages, one tile of
    ``P`` pages at a time, into the online softmax of all its query rows.

    The pools stay in HBM, viewed ``[N, bs*KV, D]`` (pool row ``t*KV +
    h`` is token ``t``, kv head ``h``; K rows are ``Dk`` wide and V rows
    ``Dv``, which need not be equal). A tile comes into slot ``s`` of
    ``k_buf`` / ``v_buf`` (``[3, P, bs*KV, D]``) on ``sems[0|1, s]`` in
    one of two ways, as ``whole_ref[b, tile]`` (:func:`whole_tiles`,
    scalar-prefetched beside the table) says: a WHOLE tile, whose live
    pages lie side by side in the pool, by one copy of ``P`` pages from
    each pool (the entry is the first of them); any other (-1) by ``P``
    page copies a pool, each with its table read. The wait is built
    from the flag of the tile it waits for, read again by (sequence,
    tile): a DMA semaphore counts in the copy's own size.

    The call's walk is one sequence of stops, every sequence's tiles in
    order, and TWO tiles are in flight behind the one being folded
    (:data:`_SLOTS` slots a pool, taken in turn): a stop starts the
    copies of the stop two after it (``stop_after``, which steps from a
    sequence's last tile to the first of the next; for a sequence of
    one tile, two sequences on) into the slot the stop before it was
    folded in, then waits for its own. With one tile in flight the
    memory idled from the landing of one copy to the start of the next,
    once a stop; with two a copy is queued behind the one that runs
    (PERF.md, PR 46: 8-24% of a lone call on the chip). The first two stops of the
    call are started by its first grid step, and only the very first is
    waited for with nothing to do. ``slot_ref`` (SMEM) carries the slot
    across grid steps. The trip count is the
    sequence's own ``cdiv(len, P*bs)``: table columns past it are never
    read. Under a sliding ``window`` a third scalar-prefetched vector
    gives each sequence's first visible position: the walk starts at the
    tile that holds it, tiles wholly behind the window are never
    fetched, and a row sees ``slot > position - window`` only.

    Queries arrive as rows ``[KV*M, D]`` (row ``h*M + m``: kv head
    ``h``, ``M`` = query positions x group size). Both contractions are
    plain matmuls against the tile as it lies in VMEM, ``[P*bs*KV, D]``:
    every row meets every (token, kv head) column and the columns of the
    other kv heads are masked with the slots past the row's position, so
    their weights are exact zeros in the second matmul. The MXU loads
    the same K and V tiles it would for per-head dots; no page is
    transposed and no head repeated. ``pos_ref`` (``[1, KV*M, 1]``
    int32, VMEM) carries each row's validity threshold as a vector:
    SMEM, where the scalar-prefetched table and lengths live, only
    serves scalar loads. With ``has_sink`` a ``[KV*M, 1]`` float32
    column holds each row's sink logit: it seeds the running maximum and
    a denominator of one, a key with no value row.

    The masks (``slot_in_tile``, ``own_head``) are built by every grid
    step from two iotas: their divisions run over the few vector
    registers Mosaic keeps of an iota's one varying dimension (15 at
    ``[40, 1280]``), and handing them in ready made, or leaving them
    out, read the same on the chip (PERF.md, PR 46).

    With ``v_width`` there is ONE pool, whose rows hold their values in
    their leading ``v_width`` columns: one HBM ref, one buffer of three
    slots, one copy a tile (or a page), and the second matmul runs
    against the leading columns of the tile the first one read."""
    refs = list(refs)
    tbl_ref, whole_ref, len_ref = refs[:3]
    del refs[:3]
    first_ref = refs.pop(0) if window is not None else None
    q_ref, pos_ref = refs[:2]
    del refs[:2]
    sink_ref = refs.pop(0) if has_sink else None
    n_pools = 1 if v_width else 2
    hbm, o_ref, bufs = refs[:n_pools], refs[n_pools], refs[n_pools + 1:-2]
    sems, slot_ref = refs[-2:]
    k_hbm, k_buf = hbm[0], bufs[0]
    dv = v_width or bufs[1].shape[-1]
    b = pl.program_id(0)
    n_seqs = pl.num_programs(0)
    nb = tbl_ref.shape[1]
    n_slots, pages, page_rows, _ = k_buf.shape
    tile_rows = pages * page_rows
    tile_slots = tile_rows // kv
    rows = q_ref.shape[1]

    def tiles_of(seq):
        """[first, end) of the tiles ``seq`` walks: at least one,
        whatever the length says: the sequences before it have its
        first tile in flight, and somebody has to wait for it."""
        first = 0 if first_ref is None else first_ref[seq] // tile_slots
        return first, jnp.maximum(
            first + 1, (len_ref[seq] + tile_slots - 1) // tile_slots)

    def stop_after(seq, tile):
        """The walk's next stop: ``seq``'s next tile, or the first tile
        of the sequence after it (``n_seqs``: the walk is over)."""
        _, end = tiles_of(jnp.minimum(seq, n_seqs - 1))
        first_after, _ = tiles_of(jnp.minimum(seq + 1, n_seqs - 1))
        last = tile + 1 >= end
        return (jnp.where(last, seq + 1, seq),
                jnp.where(last, first_after, tile + 1))

    def slot_after(slot, by):
        slot = slot + by
        return jnp.where(slot >= n_slots, slot - n_slots, slot)

    tile0, n_tiles = tiles_of(b)

    def page_copies(slot, j, page):
        return [
            pltpu.make_async_copy(
                pool.at[page], buf.at[slot, j], sems.at[index, slot]
            )
            for index, (pool, buf) in enumerate(zip(hbm, bufs))
        ]

    def tile_copies(slot, page0):
        return [
            pltpu.make_async_copy(
                pool.at[pl.ds(page0, pages)], buf.at[slot],
                sems.at[index, slot]
            )
            for index, (pool, buf) in enumerate(zip(hbm, bufs))
        ]

    # the page loops are rolled: a copy traced once per site, not once
    # per page, keeps the program's trace (paid at every server start,
    # once per layer) as short as the kernel it replaces

    def either_way(seq, tile, whole, by_page):
        if k_hbm.shape[0] < pages:
            return by_page()  # a pool that holds no span of P pages
        page0 = whole_ref[seq, tile]
        pl.when(page0 >= 0)(lambda: whole(page0))
        pl.when(page0 < 0)(by_page)

    def start_tile(seq, tile, slot):
        def whole(page0):
            for copy in tile_copies(slot, page0):
                copy.start()

        def by_page():
            @pl.loop(0, pages)
            def _start(j):
                # a table narrower than a whole number of tiles: the
                # last tile's spare pages re-read the last column,
                # masked as slots past every position
                column = jnp.minimum(tile * pages + j, nb - 1)
                for copy in page_copies(slot, j, tbl_ref[seq, column]):
                    copy.start()

        either_way(seq, tile, whole, by_page)

    def wait_tile(seq, tile, slot):
        # a wait takes its size from the copy, not its source
        def whole(_):
            for copy in tile_copies(slot, 0):
                copy.wait()

        def by_page():
            @pl.loop(0, pages)
            def _wait(j):
                for copy in page_copies(slot, j, 0):
                    copy.wait()

        either_way(seq, tile, whole, by_page)

    def start_stop(seq, tile, slot):
        pl.when(seq < n_seqs)(lambda: start_tile(
            jnp.minimum(seq, n_seqs - 1), tile, slot))

    @pl.when(b == 0)
    def _first_tiles():
        slot_ref[0] = 0
        stop = 0, tiles_of(0)[0]
        for slot in range(n_slots - 1):
            start_stop(*stop, slot)
            stop = stop_after(*stop)

    first_slot = slot_ref[0]
    q = q_ref[0]  # [rows, Dk]
    pos = pos_ref[0]  # [rows, 1]
    column = jax.lax.broadcasted_iota(jnp.int32, (rows, tile_rows), 1)
    row_head = jax.lax.broadcasted_iota(
        jnp.int32, (rows, tile_rows), 0
    ) // (rows // kv)
    # at one kv head every column is the row's own
    own_head = True if kv == 1 else column % kv == row_head
    slot_in_tile = column // kv

    def fold(i, carry):
        slot, m_prev, l_prev, acc = carry
        # the slot the tile before this one was folded in is free: the
        # stop ``n_slots - 1`` ahead of this one goes there
        ahead = b, i
        for _ in range(n_slots - 1):
            ahead = stop_after(*ahead)
        start_stop(*ahead, slot_after(slot, n_slots - 1))
        wait_tile(b, i, slot)
        k = k_buf[slot].reshape(tile_rows, -1)
        if v_width:
            v = k[:, :v_width]
        else:
            v = bufs[1][slot].reshape(tile_rows, -1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [rows, tile_rows]
        # per-row slot validity: absolute slot index <= this ROW's
        # position (covers ragged tails, padding lanes, and the trash
        # block alike), in the row's own kv head
        valid = own_head & (slot_in_tile <= pos - i * tile_slots)
        if window is not None:
            valid &= slot_in_tile > pos - window - i * tile_slots
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(axis=1, keepdims=True)
        # weights ride the MXU in the page dtype (f32 accumulate), the
        # same operand precision XLA's default gives the XLA function
        acc = acc * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        return slot_after(slot, 1), m_new, l_new, acc

    if sink_ref is None:
        m0 = jnp.full((rows, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((rows, 1), jnp.float32)
    else:
        m0 = sink_ref[...]
        l0 = jnp.ones((rows, 1), jnp.float32)
    slot_ref[0], _, l, acc = jax.lax.fori_loop(
        tile0, n_tiles, fold,
        (first_slot, m0, l0, jnp.zeros((rows, dv), jnp.float32)),
    )
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def _widened_to_key_rows(q, kv: int, r: int):
    """``q`` [B, T, H, D] -> [B, T, H, r * D]: head ``h`` (which reads
    key head ``h // (H / (r * kv))``, the ``(... % r)``-th of its key
    row) holds its ``D`` columns where that key head lies in the row,
    zeros beside them."""
    b, t, h, d = q.shape
    place = jnp.eye(r, dtype=q.dtype)[(jnp.arange(h) // (h // (r * kv))) % r]
    return (q[:, :, :, None, :] * place[:, :, None]).reshape(b, t, h, r * d)


@functools.partial(
    jax.jit,
    static_argnames=("interpret", "window", "scale", "kv_heads", "v_width",
                     "keys_per_value"),
)
def paged_attention_pallas(q, k_pages, v_pages, page_tables, positions,
                           *, interpret: bool = False, window=None,
                           sink=None, scale=None, kv_heads=None,
                           v_width=None, keys_per_value: int = 1):
    """Flash-style ragged paged attention as a Pallas kernel.

    One grid step per sequence. ``page_tables``, :func:`whole_tiles` of
    it and each sequence's length (its largest query position + 1) are
    scalar-prefetched; the kernel copies pages ``page_tables[b, j]``
    from the pools in HBM into VMEM itself, :func:`pages_per_tile` at a
    time (a whole tile by one copy a pool, which also brings, and masks,
    what lies beside its live pages) and TWO tiles ahead of the
    arithmetic (three VMEM slots a pool; across the step from one
    sequence to the next as well), and stops at the sequence's own last
    tile — no row of
    sequence ``b`` sees a slot it does not own, the padding of the table
    to its bucket costs nothing, no contiguous per-sequence view is ever
    materialized in HBM, and the T verify rows of a sequence share each
    tile (the pages cross HBM->VMEM once for all K+1 positions). Queries
    are regrouped ``[B, T, H, D] -> [B, KV*T*G, D]`` outside the kernel
    (head ``k*G + g`` reads kv head ``k``, matching ``_repeat_kv``) — a
    copy of the queries only; the pools are only re-viewed
    ``[N, bs*KV, D]``.

    ``v_pages`` may hold rows of another size than ``k_pages`` (the
    output has V's); both are whole numbers of 128 lanes, which is what
    Mosaic copies, so a model with K rows of 192 pads them (and its
    queries) with zeros to 256 and gives its own ``scale`` (default
    ``D ** -0.5``). With ``kv_heads`` the pools come flat,
    ``[N, bs*KV, D]`` (:func:`_pool_shape`). ``window`` (static) makes each row see only the
    ``window`` slots up to its position, the walk starting at the tile
    of the sequence's earliest visible slot; ``sink`` (``[H]``) is one
    more logit a head in the softmax's denominator. With neither, the
    program is the one it was without them. ``v_pages=None`` with
    ``v_width`` (static) is the one-pool call of the module docstring: a
    latent model's rows of 576 (512 of them the values) are stored 640
    wide, at KV 1, and all its query heads are rows of one matmul
    against the tile. With ``keys_per_value = r`` (static; the module
    docstring's shared value heads) the kernel is the one it is without:
    each query head is widened with zeros to the key row, its ``D``
    columns where its key head lies in the row, so that its product with
    the whole row is its product with its own key head and the ``r *
    G`` heads of a row are one kv head's group. The row is read once;
    the score matmul is ``r`` times as wide, on an MXU that waits for
    the copies.

    Jitted, so that the layers of a model, which all call it with the
    same shapes, share one trace and one lowering of the kernel: a
    server traces every decode program anew at each start, whatever the
    compile cache holds."""
    b, t, h, d = q.shape
    n = k_pages.shape[0]
    bs, kv, dv = _pool_shape(k_pages, v_pages, kv_heads, v_width)
    pools = [k_pages] if v_pages is None else [k_pages, v_pages]
    if keys_per_value > 1:
        scale = scale or d ** -0.5
        q = _widened_to_key_rows(q, kv, keys_per_value)
        d *= keys_per_value
    g = h // kv
    rows = kv * t * g
    nb = page_tables.shape[1]
    pages = min(
        pages_per_tile(bs, kv, max(d, dv), k_pages.dtype, len(pools)), nb)
    q_rows = (
        q.reshape(b, t, kv, g, d).transpose(0, 2, 1, 3, 4).reshape(b, rows, d)
    )
    positions = positions.astype(jnp.int32)
    row_positions = jnp.tile(jnp.repeat(positions, g, axis=1), (1, kv))
    page_tables = page_tables.astype(jnp.int32)
    first_slots, lengths = visible_slots(positions, window)
    prefetch = [
        page_tables,
        whole_tiles(
            page_tables, first_slots, lengths, pages, bs, n
        ).astype(jnp.int32),
        lengths,
    ]
    if window is not None:
        prefetch.append(first_slots)
    row_block = lambda width: pl.BlockSpec(  # noqa: E731
        (1, rows, width), lambda i, *_: (i, 0, 0))
    inputs = [q_rows, row_positions[:, :, None]]
    in_specs = [row_block(d), row_block(1)]
    if sink is not None:
        # row k*(T*G) + t*G + gi is head k*G + gi
        sink_rows = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(kv, 1, g), (kv, t, g)
        ).reshape(rows, 1)
        inputs.append(sink_rows)
        in_specs.append(pl.BlockSpec((rows, 1), lambda i, *_: (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b,),
        in_specs=in_specs + [
            pl.BlockSpec(memory_space=pl.ANY) for _ in pools
        ],
        out_specs=row_block(dv),
        scratch_shapes=[
            pltpu.VMEM((_SLOTS, pages, bs * kv, pool.shape[-1]), pool.dtype)
            for pool in pools
        ] + [
            pltpu.SemaphoreType.DMA((len(pools), _SLOTS)),
            pltpu.SMEM((1,), jnp.int32),  # the slot the next tile is in
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _rpa_kernel, kv, scale or d ** -0.5, window, sink is not None,
            v_width,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, dv), q.dtype),
        # sequence b prefetches sequence b+1's first tile: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="paged_attention",
    )(
        *prefetch, *inputs,
        *(pool.reshape(n, bs * kv, pool.shape[-1]) for pool in pools),
    )
    return (
        out.reshape(b, kv, t, g, dv).transpose(0, 2, 1, 3, 4)
        .reshape(b, t, h, dv)
    )


# ---------------------------------------------------------------------------
# selection
# ---------------------------------------------------------------------------


def make_tp_attention(attn: Callable, mesh, tp_axis: str = "tp") -> Callable:
    """Wrap an attention impl so it runs per-shard under a ``tp`` mesh.

    Tensor-parallel paged decode shards BOTH q (on the query-head axis)
    and the K/V page pools (on the kv-head axis) over ``tp_axis``. Every
    impl's math is already self-contained per kv-head group — the group
    size ``n_rep = H/KV`` is preserved under an even head split — so the
    per-shard call needs no collectives at all: shard ``i`` computes the
    attention output for its own heads against its own page shard, and
    the output stays head-sharded for the downstream (row-sharded) wo
    projection.

    The wrap exists because GSPMD cannot partition a ``pallas_call`` (it
    would replicate the whole pool per device); ``shard_map`` hands each
    device its local block, which also pins the XLA function to the
    no-communication partitioning instead of trusting sharding
    propagation to find it. Page tables and positions are replicated
    (they index POOL ROWS, which are not sharded — the head axis is).
    ``check_vma=False``: the impls are opaque to the varying-axes checker.
    """
    from jax.sharding import PartitionSpec

    heads_spec = PartitionSpec(None, None, tp_axis, None)  # q, pools, out
    replicated = PartitionSpec()
    return jax.shard_map(
        attn,
        mesh=mesh,
        in_specs=(heads_spec, heads_spec, heads_spec, replicated, replicated),
        out_specs=heads_spec,
        check_vma=False,
    )


def resolve_decode_attention(
    requested: Optional[str], platform: str
) -> Tuple[str, Callable]:
    """Pick the paged attention for ``platform`` (a
    ``jax.default_backend()`` string): the name the model reports and
    the function every program of the model calls.

    ``requested`` (the ``CLIENT_TPU_LLM_KERNEL`` env override) forces
    one of :data:`KERNELS`; otherwise TPU hosts get the Pallas kernel
    and everything else plain XLA. The choice is final: a kernel that
    fails to compile at warmup is a load failure carrying the compiler's
    message, never a silent step down to another implementation."""
    name = requested or ("pallas" if platform == "tpu" else "fused_xla")
    if name == "pallas":
        return name, paged_attention_pallas
    if name == "pallas_interpret":
        return name, functools.partial(paged_attention_pallas, interpret=True)
    if name == "fused_xla":
        return name, paged_attention_xla
    raise ValueError(
        f"unknown paged-attention kernel '{name}' "
        f"(choose from {', '.join(KERNELS)})"
    )
