"""Ragged paged-attention decode kernels (the compute half of ROADMAP item 2).

The continuous-batching engine stores every live sequence's KV cache as a
page table over ONE physical block pool (``models/llama.py``
``init_kv_pages``).  The decode step's attention must therefore read a
*ragged* set of pages per sequence — each sequence attends over however
many blocks it has actually earned.  This module provides the three
implementations of that read, in ascending order of fusion ("Ragged Paged
Attention", PAPERS.md arxiv 2604.15464, is the blueprint):

- ``standin``: the PR-9 XLA gather/scatter stand-in — gathers every
  sequence's pages into a contiguous ``[B, S, KV, D]`` view, materializes
  the grouped-query head repeat, and runs a validity-masked softmax over
  the FULL padded width.  Kept as the bench baseline.
- ``fused_xla``: one fused XLA call that skips the ``repeat_kv``
  materialization entirely (grouped-query einsum over the gathered pages)
  and works on whatever page-table width the caller passes — the engine
  buckets that width to the live batch's longest sequence, so compute
  scales with actual context instead of ``max_seq_len``.  This is the
  implementation off-TPU.
- ``pallas``: a flash-style Pallas kernel.  The grid walks
  ``(sequence, block)``; the page table rides scalar prefetch so each
  grid step's BlockSpec ``index_map`` streams exactly ONE physical block
  from the pool into VMEM — no ``[B, S]`` gather ever materializes.
  Online-softmax scratch (running max / denominator / accumulator)
  carries across the block axis.  ``pallas_interpret`` runs the same
  kernel under the Pallas interpreter for CPU parity tests.

Selection happens once at model warmup (``llm/serving.py``), by
platform: TPU hosts take the Pallas kernel, everything else
``fused_xla``, and the choice is reported in the model's config
parameters.  All implementations share one contract::

    attn(q[B, H, D], k_pages[N, bs, KV, D], v_pages[N, bs, KV, D],
         page_tables[B, NB], positions[B]) -> out[B, H, D]

with slot validity ``block*bs + offset <= positions[b]`` (the freshly
scattered token attends to itself) and physical block 0 reserved as the
trash block whose slots are always masked by that rule.

Speculative decoding (PR-15) adds a MULTI-QUERY variant of the same
contract: the verify step of draft-propose/paged-verify asks the target
model for logits at K+1 positions per sequence in ONE call, so each
implementation grows an ``*_mq`` twin::

    attn_mq(q[B, T, H, D], k_pages[N, bs, KV, D], v_pages[N, bs, KV, D],
            page_tables[B, NB], positions[B, T]) -> out[B, T, H, D]

where query row ``t`` of sequence ``b`` sits at absolute position
``positions[b, t]`` and slot validity generalizes PER POSITION:
``block*bs + offset <= positions[b, t]``.  That one mask is the whole
verification trick — row ``t`` sees exactly its own speculative prefix
(rows ``0..t`` were scattered at ``positions[b, 0..t]`` before the
read), never the draft tokens after it, so the K+1 logits rows are
bit-for-bit what K+1 sequential decode steps would have produced.
Padding rows (``t`` beyond a lane's draft length) produce garbage the
caller discards, exactly like padding lanes do in the single-query
contract.
"""

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: names accepted by :func:`resolve_decode_attention`, best first
KERNELS = ("pallas", "pallas_interpret", "fused_xla", "standin")


# ---------------------------------------------------------------------------
# stand-in (PR-9 baseline): gather + repeat_kv + full-width masked softmax
# ---------------------------------------------------------------------------


def paged_attention_standin(q, k_pages, v_pages, page_tables, positions):
    """The gather/scatter stand-in, lifted to the shared attention
    contract (numerically identical to the inline attention of
    ``llama.decode_step_paged``)."""
    b, h, d = q.shape
    _, bs, kv, _ = k_pages.shape
    n_rep = h // kv
    s = page_tables.shape[1] * bs
    k_ctx = k_pages[page_tables].reshape(b, s, kv, d)
    v_ctx = v_pages[page_tables].reshape(b, s, kv, d)
    # the materialized head repeat the fused variants avoid
    k_rep = jnp.broadcast_to(
        k_ctx[:, :, :, None, :], (b, s, kv, n_rep, d)
    ).reshape(b, s, h, d)
    v_rep = jnp.broadcast_to(
        v_ctx[:, :, :, None, :], (b, s, kv, n_rep, d)
    ).reshape(b, s, h, d)
    qh = q[:, None, :, :].transpose(0, 2, 1, 3)  # [B, H, 1, D]
    kh = k_rep.transpose(0, 2, 1, 3)  # [B, H, S, D]
    vh = v_rep.transpose(0, 2, 1, 3)
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", qh, kh, preferred_element_type=jnp.float32
    ) / (d ** 0.5)
    valid = jnp.arange(s)[None, :] <= positions[:, None]  # [B, S]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", weights, vh.astype(weights.dtype))
    return out[:, :, 0, :].astype(q.dtype)  # [B, H, D]


def paged_attention_standin_mq(q, k_pages, v_pages, page_tables, positions):
    """Multi-query stand-in: gather + repeat_kv + a ``[B, T, S]`` mask.

    The oracle the fused/Pallas mq variants are pinned against — kept as
    dumb as possible (materialized head repeat, full-width softmax)."""
    b, t, h, d = q.shape
    _, bs, kv, _ = k_pages.shape
    n_rep = h // kv
    s = page_tables.shape[1] * bs
    k_ctx = k_pages[page_tables].reshape(b, s, kv, d)
    v_ctx = v_pages[page_tables].reshape(b, s, kv, d)
    k_rep = jnp.broadcast_to(
        k_ctx[:, :, :, None, :], (b, s, kv, n_rep, d)
    ).reshape(b, s, h, d)
    v_rep = jnp.broadcast_to(
        v_ctx[:, :, :, None, :], (b, s, kv, n_rep, d)
    ).reshape(b, s, h, d)
    qh = q.transpose(0, 2, 1, 3)  # [B, H, T, D]
    kh = k_rep.transpose(0, 2, 1, 3)  # [B, H, S, D]
    vh = v_rep.transpose(0, 2, 1, 3)
    scores = jnp.einsum(
        "bhtd,bhkd->bhtk", qh, kh, preferred_element_type=jnp.float32
    ) / (d ** 0.5)
    # per-position validity: query row t sees slot s iff s <= pos[b, t]
    valid = jnp.arange(s)[None, None, :] <= positions[:, :, None]  # [B, T, S]
    scores = jnp.where(valid[:, None, :, :], scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhtk,bhkd->bhtd", weights, vh.astype(weights.dtype))
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B, T, H, D]


# ---------------------------------------------------------------------------
# fused XLA variant: grouped-query einsum, no repeat materialization
# ---------------------------------------------------------------------------


def paged_attention_fused_xla(q, k_pages, v_pages, page_tables, positions):
    """One fused XLA computation over the gathered pages.

    Head layout matches ``_repeat_kv`` (head ``k*g + r`` reads kv head
    ``k``), so ``q.reshape(b, kv, g, d)`` lines queries up with their kv
    group and the score/weighted-sum einsums contract directly against
    the un-repeated context — the ``[B, S, H, D]`` repeat never exists,
    and S is whatever (bucketed) width the caller's page table has. The
    gathered context is transposed to ``[B, KV, S, D]`` up front: both
    contractions then run as plain batched matmuls over adjacent
    (batch, kv) dims, which measures ~25% faster than contracting the
    ``[B, S, KV, D]`` gather layout in place (PERF.md PR-14)."""
    b, h, d = q.shape
    _, bs, kv, _ = k_pages.shape
    g = h // kv
    s = page_tables.shape[1] * bs
    k_ctx = k_pages[page_tables].reshape(b, s, kv, d).transpose(0, 2, 1, 3)
    v_ctx = v_pages[page_tables].reshape(b, s, kv, d).transpose(0, 2, 1, 3)
    qg = q.reshape(b, kv, g, d)
    scores = jnp.einsum(
        "bkgd,bksd->bkgs", qg, k_ctx, preferred_element_type=jnp.float32
    ) / (d ** 0.5)
    valid = jnp.arange(s)[None, :] <= positions[:, None]  # [B, S]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", weights, v_ctx.astype(weights.dtype))
    return out.reshape(b, h, d).astype(q.dtype)


def paged_attention_fused_xla_mq(q, k_pages, v_pages, page_tables, positions):
    """Multi-query fused XLA variant (the verify-step workhorse off-TPU).

    Same layout choices as :func:`paged_attention_fused_xla` — gathered
    context transposed to ``[B, KV, S, D]``, queries regrouped to their
    kv head — with the query-position axis ``T`` riding along both
    einsums, so one call scores all K+1 verify positions against the
    same gathered pages instead of gathering K+1 times."""
    b, t, h, d = q.shape
    _, bs, kv, _ = k_pages.shape
    g = h // kv
    s = page_tables.shape[1] * bs
    k_ctx = k_pages[page_tables].reshape(b, s, kv, d).transpose(0, 2, 1, 3)
    v_ctx = v_pages[page_tables].reshape(b, s, kv, d).transpose(0, 2, 1, 3)
    qg = q.reshape(b, t, kv, g, d)
    scores = jnp.einsum(
        "btkgd,bksd->bkgts", qg, k_ctx, preferred_element_type=jnp.float32
    ) / (d ** 0.5)
    valid = jnp.arange(s)[None, None, :] <= positions[:, :, None]  # [B, T, S]
    scores = jnp.where(valid[:, None, None, :, :], scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgts,bksd->btkgd", weights, v_ctx.astype(weights.dtype)
    )
    return out.reshape(b, t, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel: per-block streaming + online softmax
# ---------------------------------------------------------------------------

#: lane width of a TPU vector register: the running max/denominator
#: scratch is kept lane-aligned (each value broadcast across one vreg
#: row) rather than as a 1-wide column
_LANES = 128


def _rpa_kernel(block_size, scale,
                tbl_ref, q_ref, pos_ref, k_ref, v_ref, o_ref,
                m_ref, l_ref, acc_ref):
    """Grid step (b, j): fold physical block ``tbl[b, j]`` of sequence
    ``b`` into the online-softmax state of all its query rows.

    Queries arrive grouped by kv head (``q_ref`` block ``[1, KV, M, D]``,
    ``M`` = query positions x group size), so both contractions are
    batched matmuls with the kv head LEADING — the only batched form
    Mosaic lowers — and grouped-query heads share their kv head's block
    with no in-kernel repeat. ``pos_ref`` (``[1, M, 1]`` int32, VMEM)
    carries each row's validity threshold as a vector: SMEM, where the
    scalar-prefetched page table lives, only serves scalar loads.
    Scratch (running max ``m``, denominator ``l``, accumulator ``acc``)
    persists across the block axis; the first block initializes it, the
    last normalizes out."""
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]  # [KV, M, D]
    k = jnp.swapaxes(k_ref[0], 0, 1)  # [bs, KV, D] -> [KV, bs, D]
    v = jnp.swapaxes(v_ref[0], 0, 1)
    s = jnp.einsum(
        "kmd,ktd->kmt", q, k, preferred_element_type=jnp.float32
    ) * scale  # [KV, M, bs]
    # per-row slot validity: absolute slot index <= this ROW's position
    # (covers ragged tails, padding lanes, and the trash block alike)
    slot = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1
    )
    valid = (slot <= pos_ref[0])[None]  # [1, M, bs]
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[...]  # [KV, M, LANES]
    m_new = jnp.maximum(m_prev, s.max(axis=2, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new[..., :1]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=2, keepdims=True)
    # weights ride the MXU in the page dtype (f32 accumulate), the same
    # operand precision XLA's default gives the fused variant on TPU
    acc_ref[...] = acc_ref[...] * alpha[..., :1] + jnp.einsum(
        "kmt,ktd->kmd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(j == nb - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / l_ref[..., :1]).astype(o_ref.dtype)


def paged_attention_pallas_mq(q, k_pages, v_pages, page_tables, positions,
                              *, interpret: bool = False):
    """Flash-style multi-query ragged paged attention as a Pallas kernel.

    ``page_tables`` is scalar-prefetched so the BlockSpec index maps can
    stream block ``page_tables[b, j]`` (ONE physical block,
    ``[bs, KV, D]``) into VMEM per grid step — sequence ``b`` never
    touches pages it does not own, no contiguous per-sequence view is
    ever materialized in HBM, and the T verify rows of a sequence share
    each streamed block (the pages cross HBM->VMEM once for all K+1
    positions). Queries are regrouped ``[B, T, H, D] -> [B, KV, T*G, D]``
    outside the kernel (head ``k*G + g`` reads kv head ``k``, matching
    ``_repeat_kv``) — a copy of the queries only, never of the pages."""
    b, t, h, d = q.shape
    _, bs, kv, _ = k_pages.shape
    g = h // kv
    m = t * g
    nb = page_tables.shape[1]
    q_rows = (
        q.reshape(b, t, kv, g, d).transpose(0, 2, 1, 3, 4).reshape(b, kv, m, d)
    )
    row_positions = jnp.repeat(positions.astype(jnp.int32), g, axis=1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1, kv, m, d), lambda i, j, tbl: (i, 0, 0, 0)),
            pl.BlockSpec((1, m, 1), lambda i, j, tbl: (i, 0, 0)),
            pl.BlockSpec(
                (1, bs, kv, d), lambda i, j, tbl: (tbl[i, j], 0, 0, 0)
            ),
            pl.BlockSpec(
                (1, bs, kv, d), lambda i, j, tbl: (tbl[i, j], 0, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, kv, m, d), lambda i, j, tbl: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv, m, _LANES), jnp.float32),  # running max
            pltpu.VMEM((kv, m, _LANES), jnp.float32),  # running denominator
            pltpu.VMEM((kv, m, d), jnp.float32),  # weighted-value accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_rpa_kernel, bs, 1.0 / (d ** 0.5)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_rows.shape, q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(page_tables, q_rows, row_positions[:, :, None], k_pages, v_pages)
    return (
        out.reshape(b, kv, t, g, d).transpose(0, 2, 1, 3, 4).reshape(b, t, h, d)
    )


def paged_attention_pallas(q, k_pages, v_pages, page_tables, positions,
                           *, interpret: bool = False):
    """Single-query decode: the T=1 case of
    :func:`paged_attention_pallas_mq`."""
    return paged_attention_pallas_mq(
        q[:, None], k_pages, v_pages, page_tables, positions[:, None],
        interpret=interpret,
    )[:, 0]


def paged_attention_pallas_interpret(q, k_pages, v_pages, page_tables,
                                     positions):
    """The Pallas kernel under the interpreter — CPU-runnable for parity
    tests and for forcing the kernel path off-TPU."""
    return paged_attention_pallas(
        q, k_pages, v_pages, page_tables, positions, interpret=True
    )


def paged_attention_pallas_interpret_mq(q, k_pages, v_pages, page_tables,
                                        positions):
    """The multi-query Pallas kernel under the interpreter."""
    return paged_attention_pallas_mq(
        q, k_pages, v_pages, page_tables, positions, interpret=True
    )


# ---------------------------------------------------------------------------
# selection
# ---------------------------------------------------------------------------

_IMPLS = {
    "standin": paged_attention_standin,
    "fused_xla": paged_attention_fused_xla,
    "pallas": paged_attention_pallas,
    "pallas_interpret": paged_attention_pallas_interpret,
}

# every kernel name has a multi-query twin so the speculative verify
# path rides whatever implementation warmup selected for plain decode
_IMPLS_MQ = {
    "standin": paged_attention_standin_mq,
    "fused_xla": paged_attention_fused_xla_mq,
    "pallas": paged_attention_pallas_mq,
    "pallas_interpret": paged_attention_pallas_interpret_mq,
}


def get_attention_impl(name: str) -> Callable:
    try:
        return _IMPLS[name]
    except KeyError:
        raise ValueError(
            f"unknown paged-attention kernel '{name}' "
            f"(choose from {', '.join(KERNELS)})"
        ) from None


def get_attention_impl_mq(name: str) -> Callable:
    """The multi-query (speculative verify) twin of ``name``."""
    try:
        return _IMPLS_MQ[name]
    except KeyError:
        raise ValueError(
            f"unknown paged-attention kernel '{name}' "
            f"(choose from {', '.join(KERNELS)})"
        ) from None


def make_tp_attention(
    attn: Callable, mesh, tp_axis: str = "tp", multi_query: bool = False
) -> Callable:
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
    device its local block, which also pins the XLA variants to the
    no-communication partitioning instead of trusting sharding
    propagation to find it. Page tables and positions are replicated
    (they index POOL ROWS, which are not sharded — the head axis is).
    ``check_vma=False``: the impls are opaque to the varying-axes checker.
    """
    from jax.sharding import PartitionSpec

    q_spec = (
        PartitionSpec(None, None, tp_axis, None)
        if multi_query
        else PartitionSpec(None, tp_axis, None)
    )
    pages_spec = PartitionSpec(None, None, tp_axis, None)
    replicated = PartitionSpec()
    return jax.shard_map(
        attn,
        mesh=mesh,
        in_specs=(q_spec, pages_spec, pages_spec, replicated, replicated),
        out_specs=q_spec,
        check_vma=False,
    )


def resolve_decode_attention(
    requested: Optional[str], platform: str
) -> Tuple[str, Callable]:
    """Pick the decode attention for ``platform`` (a
    ``jax.default_backend()`` string).

    ``requested`` (the ``CLIENT_TPU_LLM_KERNEL`` env override) forces a
    specific implementation; otherwise TPU hosts get the Pallas kernel
    and everything else the fused XLA variant. The choice is final: a
    kernel that fails to compile at warmup is a load failure carrying
    the compiler's message, never a silent step down to another
    implementation."""
    if requested:
        return requested, get_attention_impl(requested)
    if platform == "tpu":
        return "pallas", paged_attention_pallas
    return "fused_xla", paged_attention_fused_xla
