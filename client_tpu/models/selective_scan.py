"""The selective scan of a Mamba-1 layer (``models/jamba.py``): a channel
``d`` of the layer keeps a state ``h[n, d]`` of ``d_state`` numbers in
float32 and a token turns it by::

    h[n, d] <- exp(delta[d] A[n, d]) h[n, d] + delta[d] B[n] u[d]
    y[d]     = (sum_n h[n, d] C[n] + D[d] u[d]) silu(z[d])

with ``u`` the convolved input, ``delta > 0`` the token's step a channel,
``B`` and ``C`` the token's input and output vectors, ``A < 0`` and ``D``
the layer's own, ``z`` the gate. Every form takes ``z = None`` for the
sum UNGATED (``sum_n h[n, d] C[n] + D[d] u[d]``, the memory that
``models/phi4flash.py``'s gated memory units read of one layer, and what
its own gate is then applied to outside). The transition is diagonal and differs
for every (state, channel) pair, so nothing in it is a matmul and nothing
factors over heads: multiply-adds and exponentials over the ``[d_state,
d_inner]`` block. The state lies channels-last (``[16, 5120]`` at
Jamba2-3B's sizes: the channels on the 128 lanes of a vector register,
the states on its sublanes). Three forms of the one mathematics:

- :func:`recurrent_step`, the rule as written, a token at a time: what a
  decode step does under the ``fused_xla`` choice (a gather of the lanes'
  states by slot, the rule, a scatter back) and what the other two are
  tested against.
- :func:`selective_scan_step`, the decode step over a POOL of states
  ``[slots, d_state, d_inner]`` of which lane ``b`` owns ``slots[b]``:
  under the Pallas choices one kernel, named ``selective_scan_step`` in a
  device trace, a grid step a lane; the lane's slot rides scalar prefetch
  and picks the state block, which is copied HBM -> VMEM, turned in
  float32 and copied back to the same place (the pool is aliased input to
  output), ``A`` and ``D`` resident: a step moves each live lane's state
  in and out once and never a whole pool. Lanes that name slot 0 (the
  padding rows of a batch bucket, the warm-up probes) write zeros there
  and read out zeros.
- :func:`chunked_selective_scan`, a whole prompt from a zero state in
  chunks of :data:`CHUNK` tokens: a chunk's decays and drives ``[C,
  d_state, d_inner]`` are made at once and the rule walks its tokens, a
  ``lax.scan`` carries the state across chunks, so no ``[L, d_state,
  d_inner]`` tensor (2.7 GB at 8,192 tokens) is ever built. Plain
  ``jax.numpy`` under every kernel choice, float32 sums and products (no
  matmul, so no matmul precision to ask for): the state a prefill leaves
  is the one the recurrence would have left.

Nothing is stored narrower than float32: a state in bf16 is a different
result (``tests/test_jamba.py`` holds that it fails the comparison).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: tokens of a prefill chunk: the decays and drives of one are 2 x 21 MB
#: at [64, 16, 5120] float32
CHUNK = 64
#: the slot that belongs to no sequence
TRASH_SLOT = 0
#: lanes whose rows one block of the decode kernel's ``u``, ``delta``,
#: ``z`` and ``y`` holds: the eight sublanes of a float32 tile
_LANE_ROWS = 8


def recurrent_step(state, u, delta, b, c, z, a, d_skip):
    """One token of the rule for any leading dims: ``state`` [..., N, D],
    ``u`` / ``delta`` / ``z`` [..., D], ``b`` / ``c`` [..., N], ``a`` [N,
    D], ``d_skip`` [D], all float32; ``z = None``: no gate. Returns ``(y
    [..., D], state)``."""
    state = (jnp.exp(delta[..., None, :] * a) * state
             + (delta * u)[..., None, :] * b[..., :, None])
    y = (state * c[..., :, None]).sum(axis=-2) + d_skip * u
    return (y if z is None else y * jax.nn.silu(z)), state


def _step_kernel(slots_ref, u_ref, delta_ref, *refs):
    """Grid step a lane. ``u_ref`` / ``delta_ref`` / ``z_ref`` / ``y_ref``
    [R, D] hold the rows of ``R`` lanes as they lie in HBM (a block is
    fetched, and ``y``'s written back, once for its ``R`` steps; this
    lane's row is ``lane % R``), ``bc_ref`` [1, N, 2] the lane's ``B`` and
    ``C`` as columns, ``a_ref`` [N, D] and ``d_ref`` [1, D] the layer's
    own (the same block every step, so fetched once), ``s_ref`` [1, N, D]
    the lane's slot of the pool. An ungated call has no ``z_ref``."""
    *gate, bc_ref, a_ref, d_ref, s_ref, y_ref, s_out_ref = refs
    z_ref = gate[0] if gate else None
    lane = pl.program_id(0)
    live = slots_ref[lane] != TRASH_SLOT
    row = pl.ds(lane % u_ref.shape[0], 1)
    u, delta = u_ref[row, :], delta_ref[row, :]
    if z_ref is not None:
        z = z_ref[row, :]
    b_col, c_col = bc_ref[0, :, 0:1], bc_ref[0, :, 1:2]
    state = (jnp.exp(delta * a_ref[...]) * s_ref[0]
             + (delta * u) * b_col)
    y = (state * c_col).sum(axis=0, keepdims=True) + d_ref[...] * u
    s_out_ref[0] = jnp.where(live, state, 0.0)
    if z_ref is not None:
        y = y * jax.nn.silu(z)
    y_ref[row, :] = jnp.where(live, y, 0.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_pallas(u, delta, z, bc, a, d_skip, slots, state_pool, *, interpret):
    gates = [] if z is None else [z]
    lanes, channels = u.shape
    states = bc.shape[1]
    # the lanes' rows come eight to a block, as they are tiled in HBM (no
    # copy of them into a layout of a row a block); a batch bucket under
    # eight lanes is one block
    rows = _LANE_ROWS if lanes % _LANE_ROWS == 0 else lanes
    lane_rows = pl.BlockSpec((rows, channels),
                             lambda b, slots: (b // rows, 0))
    state = pl.BlockSpec((1, states, channels),
                         lambda b, slots: (slots[b], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(lanes,),
        in_specs=[lane_rows] * (2 + len(gates)) + [
            pl.BlockSpec((1, states, 2), lambda b, slots: (b, 0, 0)),
            pl.BlockSpec((states, channels), lambda b, slots: (0, 0)),
            pl.BlockSpec((1, channels), lambda b, slots: (0, 0)),
            state,
        ],
        out_specs=[lane_rows, state],
    )
    return pl.pallas_call(
        _step_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((lanes, channels), jnp.float32),
                   jax.ShapeDtypeStruct(state_pool.shape, state_pool.dtype)],
        # the last operand (slots come first) is the pool, output 1 the
        # same memory
        input_output_aliases={6 + len(gates): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="selective_scan_step",
    )(slots, u, delta, *gates, bc, a, d_skip[None], state_pool)


def selective_scan_step(u, delta, b, c, z, a, d_skip, slots, state_pool, *,
                        kernel: str):
    """One decode step of ``B`` lanes over the pool. ``u`` / ``delta`` /
    ``z`` [B, D], ``b`` / ``c`` [B, N], ``a`` [N, D], ``d_skip`` [D],
    float32 (``z = None``: the sum ungated); ``slots`` [B] int32;
    ``state_pool`` [slots, N, D] float32. ``kernel`` is the load-time choice's name
    (``engine_model.Kernels.name``): ``pallas`` / ``pallas_interpret``
    the kernel, anything else the gather, :func:`recurrent_step` and a
    scatter. Returns ``(y [B, D] float32, state_pool)``."""
    slots = slots.astype(jnp.int32)
    if kernel in ("pallas", "pallas_interpret"):
        return _step_pallas(
            u, delta, z, jnp.stack([b, c], axis=-1), a, d_skip, slots,
            state_pool, interpret=kernel == "pallas_interpret")
    y, state = recurrent_step(state_pool[slots], u, delta, b, c, z, a, d_skip)
    live = (slots != TRASH_SLOT)[:, None]
    state = jnp.where(live[..., None], state, 0.0)
    return jnp.where(live, y, 0.0), state_pool.at[slots].set(state)


def _scan_chunk(state, decay, drive, c):
    """A chunk's tokens by the rule, one after another: ``state`` [N, D]
    before the chunk, ``decay`` / ``drive`` [C, N, D], ``c`` [C, N] ->
    (the state after it, ``sum_n h_t[n, d] c_t[n]`` [C, D])."""
    def token(h, xs):
        decay_t, drive_t, c_t = xs
        h = decay_t * h + drive_t
        return h, (h * c_t[:, None]).sum(axis=0)

    return jax.lax.scan(token, state, (decay, drive, c), unroll=8)


def chunked_selective_scan(u, delta, b, c, z, a, d_skip, chunk: int = CHUNK):
    """A whole sequence from a zero state. ``u`` / ``delta`` / ``z`` [L,
    D], ``b`` / ``c`` [L, N], ``a`` [N, D], ``d_skip`` [D], float32 (``z
    = None``: the sums ungated); a token with ``delta = 0`` (the padding of a prompt to its bucket, and
    of ``L`` to whole chunks here) leaves the state as it found it.
    Returns ``(y [L, D], state [N, D])``, the state after the last
    token."""
    length, channels = u.shape
    pad = -length % chunk
    n = (length + pad) // chunk

    def chunks(x):  # [L, ...] -> [n, C, ...]
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((n, chunk) + x.shape[1:])

    def one(state, xs):
        u_i, delta_i, b_i, c_i = xs
        decay = jnp.exp(delta_i[:, None, :] * a)
        drive = (delta_i * u_i)[:, None, :] * b_i[:, :, None]
        return _scan_chunk(state, decay, drive, c_i)

    state, read = jax.lax.scan(
        one, jnp.zeros((b.shape[1], channels), jnp.float32),
        (chunks(u), chunks(delta), chunks(b), chunks(c)))
    y = read.reshape(n * chunk, channels)[:length] + d_skip * u
    return (y if z is None else y * jax.nn.silu(z)), state
