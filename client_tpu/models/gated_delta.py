"""The gated delta rule of a linear-attention layer (Gated DeltaNet,
``models/qwen3_next.py``): a head keeps a state ``S`` [Dk, Dv] in float32
and a token turns it by::

    S <- exp(g) S;   u = beta (v - S^T k);   S <- S + k u^T;   o = S^T q

with ``q`` and ``k`` L2-normalised (``q`` scaled by ``Dk ** -0.5``) by the
caller, ``g <= 0`` the head's log decay and ``beta`` in (0, 1). Key head
``j // rep`` serves value head ``j``. Three forms of the one mathematics:

- :func:`recurrent_step`, the rule as written, a token at a time: what a
  decode step does under the ``fused_xla`` choice (a gather of the lanes'
  states by slot, the rule, a scatter back) and what the other two are
  tested against.
- :func:`gated_delta_step`, the decode step over a POOL of states
  ``[slots, Hv, Dk, Dv]`` of which lane ``b`` owns ``slots[b]``: under the
  Pallas choices one kernel, named ``gated_delta_step`` in a device trace,
  a grid step a lane and a block of heads; the lane's slot rides scalar
  prefetch and picks the state block, which is copied HBM -> VMEM, turned
  in float32 and copied back to the same place (the pool is aliased input
  to output): a step moves each live lane's state in and out once and
  never a whole pool. Lanes that name slot 0 (the padding rows of a batch
  bucket, the warm-up probes) write zeros there and read out zeros.
- :func:`chunked_gated_delta`, a whole prompt in chunks of 64 tokens
  (the WY form of the published implementation's chunked rule): inside a
  chunk the ``u`` of all its tokens come from the chunk's unit-lower-
  triangular system, applied as its inverse by block recursion
  (:func:`_solve_unit_lower`: batched matmuls, no row-by-row solve),
  across chunks the state is carried by a scan. Plain ``jax.numpy`` under
  every kernel choice, float32 at ``highest`` matmul precision: the state
  a prefill leaves is the one the recurrence would have left.

Nothing is stored narrower than float32: a state in bf16 is a different
result (``tests/test_qwen3_next.py`` holds that it fails the comparison).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST
#: tokens of a prefill chunk (the published implementation's)
CHUNK = 64
#: value heads of one grid step's state block: 16 heads of [128, 128]
#: float32 are 1 MB, in and out and twice buffered 4 MB of VMEM
_HEAD_BLOCK = 16
_VMEM_LIMIT = 32 << 20
#: the slot that belongs to no sequence
TRASH_SLOT = 0


def recurrent_step(state, q, k, v, g, beta):
    """One token of the rule for any leading dims: ``state`` [..., H, Dk,
    Dv], ``q`` / ``k`` [..., H, Dk] (a row a VALUE head), ``v`` [..., H,
    Dv], ``g`` / ``beta`` [..., H], all float32. Sums, not matmuls, so
    float32 is float32 on every backend. Returns ``(o [..., H, Dv],
    state)``."""
    state = state * jnp.exp(g)[..., None, None]
    seen = (state * k[..., None]).sum(axis=-2)
    u = beta[..., None] * (v - seen)
    state = state + k[..., None] * u[..., None, :]
    return (state * q[..., None]).sum(axis=-2), state


def _expand(x, heads: int):
    """[..., Hk, D] -> [..., heads, D]: key head ``j // rep`` for value
    head ``j``."""
    return jnp.repeat(x, heads // x.shape[-2], axis=-2)


def _step_kernel(rep, slots_ref, q_ref, k_ref, bv_ref, d_ref, bd_ref, kq_ref,
                 s_ref, o_ref, s_out_ref):
    """Grid step (lane, head block). With ``d = exp(g)`` and the OLD state
    ``S``: ``u = beta v - beta d S^T k``, ``S' = d S + k u^T``, ``o = d S^T
    q + (k . q) u``, which is the rule with both read-outs taken from the
    one pass over ``S``. Everything a head needs as a row [1, Dv] comes
    ready from XLA (``bv = beta v``, ``d``, ``bd = beta d``, ``kq = k .
    q``, each broadcast over Dv); ``k`` and ``q`` are turned to columns
    here."""
    live = slots_ref[pl.program_id(0)] != TRASH_SLOT
    q_cols, k_cols = q_ref[0].T, k_ref[0].T  # [Dk, key heads of the block]
    for head in range(s_ref.shape[1]):
        state = s_ref[0, head]  # [Dk, Dv]
        at = head // rep
        k_col, q_col = k_cols[:, at:at + 1], q_cols[:, at:at + 1]
        decay = d_ref[0, head:head + 1]
        s_k = (state * k_col).sum(axis=0, keepdims=True)
        s_q = (state * q_col).sum(axis=0, keepdims=True)
        u = bv_ref[0, head:head + 1] - bd_ref[0, head:head + 1] * s_k
        s_out_ref[0, head] = jnp.where(live, decay * state + k_col * u, 0.0)
        o_ref[0, head:head + 1] = jnp.where(
            live, decay * s_q + kq_ref[0, head:head + 1] * u, 0.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_pallas(q, k, bv, d, bd, kq, slots, state_pool, *, interpret):
    lanes, key_heads, dk = q.shape
    heads, dv = bv.shape[1:]
    rep = heads // key_heads
    block = min(_HEAD_BLOCK, heads)
    if heads % block or block % rep:
        raise ValueError(
            f"{heads} value heads over {key_heads} key heads do not fall "
            f"into blocks of {block}")

    def lane_rows(width, per):
        return pl.BlockSpec((1, per, width), lambda b, j, slots: (b, j, 0))

    state = pl.BlockSpec((1, block, dk, dv),
                         lambda b, j, slots: (slots[b], j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(lanes, heads // block),
        in_specs=[lane_rows(dk, block // rep), lane_rows(dk, block // rep),
                  lane_rows(dv, block), lane_rows(dv, block),
                  lane_rows(dv, block), lane_rows(dv, block), state],
        out_specs=[lane_rows(dv, block), state],
    )
    return pl.pallas_call(
        functools.partial(_step_kernel, rep),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((lanes, heads, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state_pool.shape, state_pool.dtype)],
        # operand 7 (slots come first) is the pool, output 1 the same memory
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="gated_delta_step",
    )(slots, q, k, bv, d, bd, kq, state_pool)


def gated_delta_step(q, k, v, g, beta, slots, state_pool, *, kernel: str):
    """One decode step of ``B`` lanes over the pool. ``q`` / ``k`` [B, Hk,
    Dk] (normalised, ``q`` scaled), ``v`` [B, Hv, Dv], ``g`` / ``beta``
    [B, Hv], float32; ``slots`` [B] int32; ``state_pool`` [slots, Hv, Dk,
    Dv] float32. ``kernel`` is the load-time choice's name
    (``engine_model.Kernels.name``): ``pallas`` / ``pallas_interpret``
    the kernel, anything else the gather, :func:`recurrent_step` and a
    scatter. Returns ``(o [B, Hv, Dv] float32, state_pool)``."""
    heads = v.shape[1]
    slots = slots.astype(jnp.int32)
    if kernel in ("pallas", "pallas_interpret"):
        decay = jnp.exp(g)
        rows = lambda x: jnp.broadcast_to(x[..., None], v.shape)
        kq = _expand((k * q).sum(axis=-1, keepdims=True), heads)
        out, state_pool = _step_pallas(
            q, k, beta[..., None] * v, rows(decay), rows(beta * decay),
            jnp.broadcast_to(kq, v.shape), slots, state_pool,
            interpret=kernel == "pallas_interpret")
        return out, state_pool
    out, state = recurrent_step(
        state_pool[slots], _expand(q, heads), _expand(k, heads), v, g, beta)
    live = (slots != TRASH_SLOT)[:, None, None]
    state = jnp.where(live[..., None], state, 0.0)
    return jnp.where(live, out, 0.0), state_pool.at[slots].set(state)


def _solve_unit_lower(lower, rhs):
    """``lower^-1 @ rhs`` for unit-lower-triangular ``lower`` [..., C, C],
    ``C`` a power of two, and ``rhs`` [..., C, N]: the inverse is built by
    block recursion and applied with one product, so nothing walks the
    rows one after another. ``[[A, 0], [X, B]]`` has the inverse
    ``[[A^-1, 0], [-B^-1 X A^-1, B^-1]]``. ``inv`` holds the inverses of
    the diagonal blocks of one size and zeros elsewhere (a lone row's is
    1), and a level doubles that size with two products over the whole
    matrix, all its blocks at once: ``cross`` is what ``lower`` holds
    below the diagonal blocks inside the blocks of twice the size, so
    ``inv @ cross @ inv`` is ``B^-1 X A^-1`` in each such place and zero
    in every other. Exact in exact arithmetic, and no power of the
    strictly lower part is ever formed: on a chunk of nearly collinear
    keys those grow like binomial coefficients and cancel, which is what
    rules out the product form ``(I - N)(I + N^2)(I + N^4)...``
    (``tests/test_qwen3_next.py``)."""
    size = lower.shape[-1]
    if size & (size - 1):
        raise ValueError(f"a chunk of {size} tokens is no power of two")
    row, col = jnp.arange(size)[:, None], jnp.arange(size)[None, :]

    def cross(block):
        return jnp.where((row // (2 * block) == col // (2 * block))
                         & (row // block > col // block), lower, 0.0)

    inv = jnp.eye(size, dtype=lower.dtype) - cross(1)
    block = 2
    while block < size:
        inv = inv - jnp.matmul(
            jnp.matmul(inv, cross(block), precision=HIGHEST), inv,
            precision=HIGHEST)
        block *= 2
    return jnp.matmul(inv, rhs, precision=HIGHEST)


def chunked_gated_delta(q, k, v, g, beta, chunk: int = CHUNK):
    """A whole sequence from a zero state. ``q`` / ``k`` [L, Hk, Dk]
    (normalised, ``q`` scaled), ``v`` [L, Hv, Dv], ``g`` / ``beta`` [L,
    Hv], float32; a token with ``g = 0`` and ``beta = 0`` (the padding of
    a prompt to its bucket, and of ``L`` to whole chunks here) leaves the
    state as it found it. Returns ``(o [L, Hv, Dv], state [Hv, Dk, Dv])``,
    the state after the last token."""
    length, heads = v.shape[:2]
    pad = -length % chunk
    n = (length + pad) // chunk

    def chunks(x):  # [L, H, ...] -> [H, n, C, ...]
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        x = x.reshape((n, chunk) + x.shape[1:])
        return jnp.moveaxis(x, 2, 0)

    q, k = chunks(_expand(q, heads)), chunks(_expand(k, heads))
    v, g, beta = chunks(v), chunks(g), chunks(beta)
    dv = v.shape[-1]
    summed = jnp.cumsum(g, axis=-1)  # [H, n, C], within a chunk
    row, col = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
    # decay from token j to token i >= j of a chunk, 0 above the diagonal
    decay = jnp.exp(jnp.where(
        row >= col, summed[..., :, None] - summed[..., None, :], -jnp.inf))
    k_beta, v_beta = k * beta[..., None], v * beta[..., None]
    inner = jnp.einsum("hnid,hnjd->hnij", k_beta, k, precision=HIGHEST)
    lower = jnp.where(row > col, inner * decay, 0.0) + jnp.eye(chunk)
    solved = _solve_unit_lower(lower, jnp.concatenate(
        [v_beta, k_beta * jnp.exp(summed)[..., None]], axis=-1))
    own, carried = solved[..., :dv], solved[..., dv:]
    among = jnp.einsum("hnid,hnjd->hnij", q, k, precision=HIGHEST) * decay

    def one(state, xs):
        q_i, k_i, own_i, carried_i, among_i, summed_i = xs
        u = own_i - jnp.einsum(
            "hck,hkv->hcv", carried_i, state, precision=HIGHEST)
        out = jnp.einsum(
            "hck,hkv->hcv", q_i * jnp.exp(summed_i)[..., None], state,
            precision=HIGHEST,
        ) + jnp.einsum("hij,hjv->hiv", among_i, u, precision=HIGHEST)
        last = summed_i[:, -1:]
        state = state * jnp.exp(last)[..., None] + jnp.einsum(
            "hck,hcv->hkv", k_i * jnp.exp(last - summed_i)[..., None], u,
            precision=HIGHEST)
        return state, out

    per_chunk = [jnp.moveaxis(x, 1, 0)
                 for x in (q, k, own, carried, among, summed)]
    state, out = jax.lax.scan(
        one, jnp.zeros((heads, q.shape[-1], dv), jnp.float32), per_chunk)
    out = jnp.moveaxis(out, 1, 0).reshape(heads, n * chunk, dv)
    return jnp.moveaxis(out, 0, 1)[:length], state
