"""Ring attention: exact attention over a sequence-parallel device axis.

Long-context prefill support for the serving runtime: Q/K/V are sharded
along the sequence dimension across the ``sp`` mesh axis; each device
computes flash-style online-softmax partial attention against its local K/V
block, then rotates K/V around the ring with ``ppermute`` until every query
block has seen every key block. Communication rides the ICI ring and
overlaps with the per-block matmuls that XLA schedules on the MXU.

This is the TPU-native answer to the long-context requirement the reference
delegates to its server (SURVEY.md §5 "long-context / sequence
parallelism"): blockwise ring attention (Liu et al., 2023) expressed with
``shard_map`` + XLA collectives rather than NCCL kernels.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _local_ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str,
    mesh_axis_names,
    causal: bool,
    scale: float,
):
    """Per-shard body: q/k/v are the local blocks [B, H, L_blk, D]."""
    axis_size = jax.lax.psum(1, axis_name)
    axis_index = jax.lax.axis_index(axis_name)
    batch, heads, q_len, head_dim = q.shape
    k_len = k.shape[2]

    q_positions = axis_index * q_len + jnp.arange(q_len)  # global positions

    def step(carry, i):
        o_acc, m_acc, l_acc, k_cur, v_cur = carry
        # Which global block currently sits on this device: blocks rotate
        # "backwards" around the ring, so after i hops we hold the block
        # that started (axis_index - i) mod axis_size.
        src_block = (axis_index - i) % axis_size

        scores = jnp.einsum(
            "bhqd,bhkd->bhqk", q, k_cur, preferred_element_type=jnp.float32
        ) * scale
        if causal:
            k_positions = src_block * k_len + jnp.arange(k_len)
            mask = q_positions[:, None] >= k_positions[None, :]
            scores = jnp.where(mask[None, None, :, :], scores, NEG_INF)

        m_blk = jnp.max(scores, axis=-1)
        m_new = jnp.maximum(m_acc, m_blk)
        p = jnp.exp(scores - m_new[..., None])
        correction = jnp.exp(m_acc - m_new)
        l_new = l_acc * correction + jnp.sum(p, axis=-1)
        o_new = o_acc * correction[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_cur.astype(p.dtype)
        )

        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return (o_new, m_new, l_new, k_next, v_next), None

    # shard_map typing: scan carries must match the varying-axes type of
    # the body's outputs, so the constant initial state is cast to varying
    o0, m0, l0 = jax.lax.pcast(
        (
            jnp.zeros((batch, heads, q_len, head_dim), dtype=jnp.float32),
            jnp.full((batch, heads, q_len), NEG_INF, dtype=jnp.float32),
            jnp.zeros((batch, heads, q_len), dtype=jnp.float32),
        ),
        tuple(mesh_axis_names),
        to="varying",
    )
    (o_final, _, l_final, _, _), _ = jax.lax.scan(
        step, (o0, m0, l0, k, v), jnp.arange(axis_size)
    )
    # Fully-masked rows (can't happen with causal self-attention, but guard
    # division) and normalization.
    denom = jnp.where(l_final == 0.0, 1.0, l_final)
    return (o_final / denom[..., None]).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    dp_axis: str = "dp",
    tp_axis: str = "tp",
    sp_axis: str = "sp",
):
    """Exact multi-head attention with sequence-parallel ring communication.

    Args
    ----
    q, k, v:
        [batch, heads, seq, head_dim] arrays; ``seq`` is (logically) sharded
        over ``sp_axis``, batch over ``dp_axis``, heads over ``tp_axis``.
    mesh:
        The device mesh holding those axes.
    causal:
        Apply a causal mask using *global* sequence positions.

    Returns [batch, heads, seq, head_dim] with the same sharding as ``q``.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    spec = P(dp_axis, tp_axis, sp_axis, None)
    body = functools.partial(
        _local_ring_attention,
        axis_name=sp_axis,
        mesh_axis_names=mesh.axis_names,
        causal=causal,
        scale=scale,
    )
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )(q, k, v)


def reference_attention(q, k, v, causal: bool = True, scale=None):
    """Single-device exact attention for testing ring_attention."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        q_len, k_len = q.shape[2], k.shape[2]
        mask = jnp.arange(q_len)[:, None] >= jnp.arange(k_len)[None, :]
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v.astype(weights.dtype)).astype(
        q.dtype
    )
