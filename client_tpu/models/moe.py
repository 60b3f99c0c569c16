"""A sparse-expert layer told which experts it holds.

Expert parallelism gives each chip a contiguous share ``held = (first,
count)`` of a layer's ``n_experts``. The layer here routes every token
over ALL experts (the router keeps its published width and its experts
per token), computes the part of the result that the held experts give,
and puts nothing in place of the absent ones: what they would have added
is another chip's, and on one chip the exchange that would bring it is
simply not there. The shares' outputs add up to the whole layer's
(``tests/test_mimo_v2.py``).

The held experts' matmuls are grouped by expert: the (token, expert)
pairs that land on a held expert are sorted by expert, each expert's
rows padded to a whole number of row tiles, and one Pallas kernel
(``name="moe_experts"``, the name a device trace shows) walks the tiles.
The tile's expert rides scalar prefetch and picks the weight blocks, so
an expert no token chose is never read, consecutive tiles of one expert
reuse its block, and the tiles past the last used one re-name the last
block fetched and do nothing. Which path runs is the load-time kernel
choice's (``engine_model.Kernels.name``), never this module's: the
kernel compiled, the kernel interpreted, or the plain XLA path
(``jax.lax.ragged_dot`` over the same sorted pairs). There is no dense
pass over all held experts in any of them: in a 2,048-token prefill that
would be 16 times the routed FLOPs.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: columns of an expert's hidden width one grid step brings in: gate, up
#: and down blocks of 512 are 12 MB at d 4096 in bf16, long enough DMAs
#: that the step's fixed cost is a few per cent of them
_F_TILE = 512
#: the three weight blocks twice (one in flight), rows, accumulator
_VMEM_LIMIT = 48 << 20

COUNTERS = ("moe_pairs", "moe_experts_touched", "moe_load_max")


def route(h, router, bias, top_k: int):
    """``noaux_tc`` routing with sigmoid scores, one group: scores ``s =
    sigmoid(h @ router)`` in float32, the ``top_k`` of ``s + bias`` (the
    correction bias selects and does not weigh), weights ``s_e`` over
    their sum. ``h`` [T, d] -> (ids [T, K] int32, weights [T, K] f32)."""
    scores = jax.nn.sigmoid(jnp.dot(
        h.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ))
    _, ids = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    return ids.astype(jnp.int32), picked / picked.sum(axis=-1, keepdims=True)


def row_tile(tokens: int, top_k: int) -> int:
    """Rows of a tile: 16 (a bf16 sublane tile) for a decode batch,
    where a held expert sees a handful of tokens, 128 for a prefill,
    where one tile should hold all of an expert's rows so its weights
    are read once."""
    return 16 if tokens * top_k <= 1024 else 128


def _held_pairs(ids, held):
    """Which (token, expert) pairs land on this chip: ``on`` [T, K],
    ``flat`` [T*K] (a pair's expert among the held ones, ``count`` for a
    pair on none) and ``counts`` [count], the held experts' pairs."""
    first, count = held
    local = ids - first
    on = (local >= 0) & (local < count)
    flat = jnp.where(on, local, count).reshape(-1)
    counts = jnp.zeros(count + 1, jnp.int32).at[flat].add(1)[:count]
    return on, flat, counts


def _plan(flat, counts, shape, tm: int):
    """Where each pair's row lies once the held pairs (:func:`_held_pairs`
    of ids of ``shape`` [T, K]) are grouped by expert. Returns
    ``row_token`` [R] (the token a row holds; 0 for padding rows),
    ``pair_row`` [T, K] (the row of a pair; junk for a pair on no held
    expert), ``tile_expert`` [tiles] (local expert of a tile; the last
    used tile's for the tiles past it) and ``used`` (tiles that hold a
    row)."""
    tokens, top_k = shape
    count = counts.shape[0]
    n = tokens * top_k
    tiles = -(-tokens * min(top_k, count) // tm) + count
    order = jnp.argsort(flat, stable=True)
    sorted_expert = flat[order]
    padded = -(-counts // tm) * tm
    ends = jnp.cumsum(padded)
    group_start = jnp.concatenate([ends - padded, jnp.zeros(1, jnp.int32)])
    start = jnp.concatenate(
        [jnp.cumsum(counts) - counts, jnp.zeros(1, jnp.int32)])
    rank = jnp.arange(n, dtype=jnp.int32) - start[sorted_expert]
    rows = tiles * tm
    dest = jnp.where(sorted_expert < count,
                     group_start[sorted_expert] + rank, rows)
    row_token = jnp.zeros(rows, jnp.int32).at[dest].set(
        (order // top_k).astype(jnp.int32), mode="drop")
    pair_row = jnp.zeros(n, jnp.int32).at[order].set(
        jnp.minimum(dest, rows - 1)).reshape(tokens, top_k)
    used = ends[-1] // tm
    tile = jnp.minimum(jnp.arange(tiles, dtype=jnp.int32),
                       jnp.maximum(used - 1, 0))
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, tile * tm, side="right"), count - 1
    ).astype(jnp.int32)
    return row_token, pair_row, tile_expert, used.astype(jnp.int32)


def _experts_kernel(n_f, expert_ref, used_ref, x_ref, gate_ref, up_ref,
                    down_ref, o_ref, acc_ref):
    """Grid step (tile, f): rows of one expert through one block of its
    hidden width, accumulated over the blocks in float32."""
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(i < used_ref[0])
    def _tile():
        @pl.when(j == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]
        gate = jnp.dot(x, gate_ref[0], preferred_element_type=jnp.float32)
        up = jnp.dot(x, up_ref[0], preferred_element_type=jnp.float32)
        mid = (jax.nn.silu(gate) * up).astype(x.dtype)
        acc_ref[...] += jnp.dot(mid, down_ref[0],
                                preferred_element_type=jnp.float32)

        @pl.when(j == n_f - 1)
        def _store():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def grouped_experts(x_rows, tile_expert, used, w_gate, w_up, w_down, *,
                    tm: int, interpret: bool = False):
    """``x_rows`` [R, d], grouped by expert in tiles of ``tm`` rows;
    ``w_gate`` / ``w_up`` [E, d, f], ``w_down`` [E, f, d]. Row tile
    ``i`` goes through expert ``tile_expert[i]``'s SwiGLU; tiles from
    ``used`` on are left as they are in memory (nothing reads them).
    Jitted, so a model's layers share one lowering."""
    rows, d = x_rows.shape
    f = w_gate.shape[-1]
    tf = min(_F_TILE, f)
    n_f = f // tf
    tiles = rows // tm

    def row_map(i, j, expert, used):
        return (jnp.minimum(i, jnp.maximum(used[0] - 1, 0)), 0)

    def f_index(i, j, used):
        # a tile past the last used one names the block already there
        return jnp.where(i < used[0], j, n_f - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(tiles, n_f),
        in_specs=[
            pl.BlockSpec((tm, d), row_map),
            pl.BlockSpec((1, d, tf), lambda i, j, expert, used:
                         (expert[i], 0, f_index(i, j, used))),
            pl.BlockSpec((1, d, tf), lambda i, j, expert, used:
                         (expert[i], 0, f_index(i, j, used))),
            pl.BlockSpec((1, tf, d), lambda i, j, expert, used:
                         (expert[i], f_index(i, j, used), 0)),
        ],
        out_specs=pl.BlockSpec((tm, d), row_map),
        scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_experts_kernel, n_f),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, d), x_rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="moe_experts",
    )(tile_expert, used.reshape(1), x_rows, w_gate, w_up, w_down)


def _experts_xla(h, flat, counts, experts, shape):
    """The plain path: the pairs (:func:`_held_pairs`) sorted by expert,
    those on no held expert last, go through three ``ragged_dot`` s over
    the groups; rows past the held pairs come out zero. Returns each
    pair's row ``[T, K, d]`` float32."""
    order = jnp.argsort(flat, stable=True)
    rows = h[order // shape[1]]

    def grouped(x, w):
        return jax.lax.ragged_dot(
            x, w, counts, preferred_element_type=jnp.float32)

    mid = jax.nn.silu(grouped(rows, experts["w_gate"])) * grouped(
        rows, experts["w_up"])
    out_rows = grouped(mid.astype(h.dtype), experts["w_down"])
    return out_rows[jnp.argsort(order)].reshape(*shape, -1)


def expert_layer(h, ids, weights, experts, held, *, kernel: str):
    """The held experts' part of the layer's output.

    ``h`` [T, d] (the normed input), ``ids`` / ``weights`` [T, K] from
    :func:`route` over all experts, ``experts`` the held experts' stacked
    weights (``w_gate`` / ``w_up`` [count, d, f], ``w_down`` [count, f,
    d]), ``held = (first, count)``. ``kernel`` is the load-time choice:
    ``pallas`` the ``moe_experts`` kernel, ``pallas_interpret`` the same
    under the interpreter, anything else the plain XLA path. Returns
    ``(out [T, d] float32, counters [3] int32)``: :data:`COUNTERS` for
    this call, which are the pairs on held experts, the held experts
    some token chose, and the most pairs on one of them."""
    on, flat, counts = _held_pairs(ids, held)
    if kernel in ("pallas", "pallas_interpret"):
        tm = row_tile(*ids.shape)
        row_token, pair_row, tile_expert, used = _plan(
            flat, counts, ids.shape, tm)
        out_rows = grouped_experts(
            h[row_token], tile_expert, used,
            experts["w_gate"], experts["w_up"], experts["w_down"],
            tm=tm, interpret=kernel == "pallas_interpret",
        )
        picked = out_rows[pair_row].astype(jnp.float32)
    else:
        picked = _experts_xla(h, flat, counts, experts, ids.shape)
    # a gather by pair, not a scatter by row; `where`, not a product with
    # a zero weight: the rows no pair names hold whatever was in memory
    out = (jnp.where(on[..., None], picked, 0.0)
           * weights[..., None]).sum(axis=1)
    counters = jnp.stack(
        [on.sum(), (counts > 0).sum(), counts.max()]).astype(jnp.int32)
    return out, counters
