"""A sparse-expert layer told which experts it holds.

Expert parallelism gives each chip a contiguous share ``held = (first,
count)`` of a layer's ``n_experts``. The layer here routes every token
over ALL experts (the router keeps its published width and its experts
per token), computes the part of the result that the held experts give,
and puts nothing in place of the absent ones: what they would have added
is another chip's, and on one chip the exchange that would bring it is
simply not there. The shares' outputs add up to the whole layer's
(``tests/test_mimo_v2.py``). A model with a *shared* expert (one every
token goes through, ``models/afmoe.py``) hands it to the layer too: every
chip of the deployment computes it whole, so it is in each share's
output and is counted once when the shares are summed
(``tests/test_afmoe.py``).

Two Pallas kernels share the name ``moe_experts`` (what a device trace
shows), and the call's row count alone picks one (:data:`_RESIDENT_ROWS`):

- a decode batch (few rows) is not planned at all: every row stays
  resident in VMEM, the kernel walks the held experts some row chose and
  adds each expert's SwiGLU output at the row's routing weight for it
  (zero where row and expert are no pair) into one float32 ``[T, d]``
  (:func:`resident_experts`). What XLA prepares is a handful of
  element-wise operations: no sort, no row gather, no pair gather.
- a prefill's rows are grouped by expert: the (token, expert) pairs that
  land on a held expert are sorted by expert, each expert's rows padded
  to a whole number of row tiles, and the kernel walks the tiles
  (:func:`grouped_experts`). A dense pass over all held experts would be
  16 times the routed FLOPs in a 2,048-token prefill.

In both the expert of a grid step rides scalar prefetch and picks the
weight blocks, so an expert no token chose is never read, and a step
with nothing to do re-names the block already there and costs no DMA.
Whether a kernel runs compiled or interpreted, or the plain XLA path
does (``jax.lax.ragged_dot`` over the sorted pairs), is the load-time
kernel choice's (``engine_model.Kernels.name``), never this module's.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: columns of an expert's hidden width one grid step brings in: gate, up
#: and down blocks of 512 are 12 MB in bf16 at ``mimo_v2``'s d 4096 (four
#: grid steps an expert of width 2,048) and 6 MB at ``afmoe``'s d 2048 (two
#: an expert of width 1,024), long enough DMAs that the step's fixed cost
#: is a few per cent of them
_F_TILE = 512
#: what the three weight blocks may hold twice over (one in flight): 512
#: columns at d 4096 in bf16. A wider model's tile narrows to fit it
#: (:func:`_f_tile`): 256 columns at ``deepseek_v3``'s d 7168, blocks of
#: 3.7 MB, eight grid steps an expert of width 2,048
_WEIGHT_VMEM = 24 << 20
#: the three weight blocks twice (one in flight), rows, accumulator
_VMEM_LIMIT = 48 << 20

#: the most rows an expert-layer call may have to keep them all resident:
#: the rows a weight tile takes in one pass of the MXU. Up to here an
#: expert's matmuls are bound by bringing its weights in, not by the
#: rows streamed past them, so the rows no pair names ride free.
#: Measured on a v5e at ``mimo_v2_flash.reason``'s sizes (d 4096, width
#: 2,048, 16 held; PR 30; the whole layer, router's outputs in, ms a
#: call, resident / planned in row tiles of 16 / a dense pass): 64 rows
#: 1.13 / 1.20 / 1.30, 128 rows 1.20 / 1.36 / 1.38, the kernel itself
#: level (1.006 against 1.012 at 64 rows);
#: ``tests/test_tpu_platform.py`` asserts the order
_RESIDENT_ROWS = 128
#: rows of a planned tile: one tile should hold all of an expert's rows
#: in a prefill, so its weights are read once
_ROW_TILE = 128

COUNTERS = ("moe_pairs", "moe_experts_touched", "moe_load_max",
            "moe_resident_calls")


def route(h, router, bias, top_k: int, scale: float = 1.0,
          n_group: int = 1, topk_group: int = 1, eps: float = 0.0,
          score: str = "sigmoid"):
    """Routing over all experts in float32. ``score`` (static) names the
    scores: ``"sigmoid"`` is ``noaux_tc`` routing, ``s = sigmoid(h @
    router)``, the ``top_k`` of ``c = s + bias`` (the correction bias
    selects and does not weigh); ``"softmax"`` (``qwen3_next``) is ``s =
    softmax(h @ router)`` over ALL the experts, and a model without a
    correction bias passes ``bias=None`` (``c = s``). Either way the
    weights are ``s_e`` over the chosen ones' sum (plus ``eps``), which
    for softmax scores is ``norm_topk_prob``, times the model's
    ``scale`` (``afmoe``'s ``route_scale``, ``deepseek_v3``'s
    ``routed_scaling_factor``). Group-limited (``n_group`` > 1): the
    experts fall into ``n_group`` groups of consecutive ones, a group
    scores the sum of its two largest ``c``, and the ``top_k`` are taken
    among the ``topk_group`` best groups' experts (``c`` reads 0
    elsewhere). At one group, a scale of 1, no ``eps`` and sigmoid
    scores the program is the one it was without them. ``h`` [T, d] ->
    (ids [T, K] int32, weights [T, K] f32)."""
    if score not in ("sigmoid", "softmax"):
        raise ValueError(f"score={score!r}: 'sigmoid' or 'softmax'")
    logits = jnp.dot(
        h.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    if score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(logits)
    biased = scores if bias is None else scores + bias.astype(jnp.float32)
    if n_group > 1:
        tokens, experts = biased.shape
        grouped = biased.reshape(tokens, n_group, experts // n_group)
        best_two = jax.lax.top_k(grouped, 2)[0].sum(axis=-1)
        _, kept = jax.lax.top_k(best_two, topk_group)
        keep = (kept[:, :, None] == jnp.arange(n_group)).any(axis=1)
        biased = jnp.where(keep[:, :, None], grouped, 0.0).reshape(
            tokens, experts)
    _, ids = jax.lax.top_k(biased, top_k)
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    total = picked.sum(axis=-1, keepdims=True)
    weights = picked / (total + eps if eps else total)
    if scale != 1.0:
        weights = weights * scale
    return ids.astype(jnp.int32), weights


def _f_tile(d: int, f: int, dtype) -> int:
    """Columns of an expert's hidden width ``f`` a grid step brings in:
    :data:`_F_TILE`, or fewer where a model is so wide (``d``) that gate,
    up and down blocks of that many columns, twice buffered, pass
    :data:`_WEIGHT_VMEM`: then the most 128-lane columns that fit and
    divide ``f``."""
    fit = _WEIGHT_VMEM // (6 * d * jnp.dtype(dtype).itemsize)
    tile = min(_F_TILE, f)
    if fit >= tile:
        return tile
    fit = max(128, fit // 128 * 128)
    while fit > 128 and f % fit:
        fit -= 128
    return fit


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
    tf = _f_tile(d, f, w_gate.dtype)
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


def _walk(counts):
    """What the resident kernel's step ``e`` does, one int32 a held
    expert, for scalar prefetch: ``count + e`` where some row chose
    expert ``e`` (compute it); else ``count + p`` for the last chosen
    expert ``p`` before ``e``; else ``count - 1 - n`` for the first
    chosen expert ``n`` after it (0 where no row chose any). One masked
    maximum, so one device operation; :func:`_walk_block` reads it."""
    count = counts.shape[0]
    there, here = np.arange(count)[None, :], np.arange(count)[:, None]
    # a table of numpy's, so the program holds it as a literal
    named = np.where(there <= here, count + there, count - 1 - there)
    return jnp.where(counts[None, :] > 0, named.astype(np.int32), 0).max(
        axis=1)


def _walk_block(walk, e, j, n_f: int):
    """(expert block, f block) that step ``(e, j)`` names: its own where
    expert ``e`` is chosen, else the block already there, which is the
    last one of the chosen expert before it, or the first one of the
    chosen expert after it: an expert no row chose costs no DMA."""
    count = walk.shape[0]
    k = walk[e]
    chosen, after = k == count + e, k >= count
    return (jnp.where(after, k - count, count - 1 - k),
            jnp.where(chosen, j, jnp.where(after, n_f - 1, 0)))


def _resident_kernel(walk_ref, x_ref, w_ref, gate_ref, up_ref, down_ref,
                     o_ref):
    """Grid step (expert, f): every row through one block of the expert's
    hidden width, added into the output block (resident: its index never
    changes, so it leaves VMEM once, after the last step) at each row's
    weight for this expert."""
    e, j = pl.program_id(0), pl.program_id(1)

    @pl.when((e == 0) & (j == 0))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(walk_ref[e] == walk_ref.shape[0] + e)
    def _expert():
        x = x_ref[...]
        gate = jnp.dot(x, gate_ref[0], preferred_element_type=jnp.float32)
        up = jnp.dot(x, up_ref[0], preferred_element_type=jnp.float32)
        mid = (jax.nn.silu(gate) * up).astype(x.dtype)
        down = jnp.dot(mid, down_ref[0], preferred_element_type=jnp.float32)
        w = w_ref[...]
        mine = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1) == e
        o_ref[...] += down * jnp.where(mine, w, 0.0).sum(
            axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def resident_experts(x, w, counts, w_gate, w_up, w_down, *,
                     interpret: bool = False):
    """``x`` [T, d] with T a whole number of bf16 sublane tiles (16 rows)
    and at most :data:`_RESIDENT_ROWS`; ``w`` [T, count] float32, row
    ``t``'s routing weight for held expert ``e`` and zero where they are
    no pair; ``counts`` [count], the pairs on each held expert. Returns
    ``sum_e w[:, e] * swiglu_e(x)`` over the experts with a pair, [T, d]
    float32. Jitted, so a model's layers share one lowering."""
    rows, d = x.shape
    count, _, f = w_gate.shape
    tf = _f_tile(d, f, w_gate.dtype)
    n_f = f // tf

    def resident(e, j, walk):
        return (0, 0)

    def wide(e, j, walk):
        block, f_block = _walk_block(walk, e, j, n_f)
        return (block, 0, f_block)

    def tall(e, j, walk):
        block, f_block = _walk_block(walk, e, j, n_f)
        return (block, f_block, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(count, n_f),
        in_specs=[
            pl.BlockSpec((rows, d), resident),
            pl.BlockSpec((rows, count), resident),
            pl.BlockSpec((1, d, tf), wide),
            pl.BlockSpec((1, d, tf), wide),
            pl.BlockSpec((1, tf, d), tall),
        ],
        out_specs=pl.BlockSpec((rows, d), resident),
    )
    return pl.pallas_call(
        _resident_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="moe_experts",
    )(_walk(counts), x, w, w_gate, w_up, w_down)


def _resident_layer(h, ids, weights, experts, held, interpret: bool):
    """What XLA prepares for :func:`resident_experts` and its call: the
    dense weight ``w[T, count] = sum_k where(ids[:, k] == first + e,
    weights[:, k], 0)`` and the held experts' pairs, a compare and two
    sums. Returns ``(out [T, d] float32, counts [count])``."""
    tokens = h.shape[0]
    first, count = held
    hit = ids[..., None] == first + jnp.arange(count, dtype=ids.dtype)
    counts = hit.sum(axis=(0, 1), dtype=jnp.int32)
    w = jnp.where(hit, weights[..., None], 0.0).sum(axis=1)
    # a batch bucket under a bf16 sublane tile is padded to one
    pad = ((0, -tokens % 16), (0, 0))
    out = resident_experts(
        jnp.pad(h, pad), jnp.pad(w, pad), counts,
        experts["w_gate"], experts["w_up"], experts["w_down"],
        interpret=interpret)
    return out[:tokens], counts


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


def shared_expert(h, shared):
    """The expert every token goes through: ``h`` [T, d] through one
    SwiGLU (``w_gate`` / ``w_up`` [d, f], ``w_down`` [f, d]) with the
    kernels' arithmetic (operands as stored, float32 accumulation, the
    hidden row rounded to the operands' type). Plain XLA under every
    kernel choice: at decode size it is bound by its weights' bytes,
    which XLA streams as it does a dense MLP's. A shared expert with a
    gate of its own (``w_sg`` [d], ``qwen3_next``) is weighed a token by
    ``sigmoid(h @ w_sg)`` in float32. Returns [T, d] float32."""
    gate = jnp.dot(h, shared["w_gate"], preferred_element_type=jnp.float32)
    up = jnp.dot(h, shared["w_up"], preferred_element_type=jnp.float32)
    mid = (jax.nn.silu(gate) * up).astype(h.dtype)
    out = jnp.dot(mid, shared["w_down"], preferred_element_type=jnp.float32)
    if "w_sg" in shared:
        out = out * jax.nn.sigmoid(jnp.dot(
            h, shared["w_sg"][:, None], preferred_element_type=jnp.float32))
    return out


def expert_layer(h, ids, weights, experts, held, *, kernel: str,
                 shared=None):
    """The held experts' part of the layer's output, and with ``shared``
    (:func:`shared_expert`'s weights, its scalar gate ``w_sg`` among
    them where the model has one) the shared expert's whole output
    added to it.

    ``h`` [T, d] (the normed input), ``ids`` / ``weights`` [T, K] from
    :func:`route` over all experts, ``experts`` the held experts' stacked
    weights (``w_gate`` / ``w_up`` [count, d, f], ``w_down`` [count, f,
    d]), ``held = (first, count)``. ``kernel`` is the load-time choice:
    ``pallas`` the ``moe_experts`` kernels, ``pallas_interpret`` the same
    under the interpreter, anything else the plain XLA path. Under the
    first two a call of at most :data:`_RESIDENT_ROWS` rows keeps its
    rows resident and plans nothing; a longer one is planned. Returns
    ``(out [T, d] float32, counters [4] int32)``: :data:`COUNTERS` for
    this call, which are the pairs on held experts, the held experts
    some token chose, the most pairs on one of them, and 1 if the call
    took the resident path."""
    pallas = kernel in ("pallas", "pallas_interpret")
    resident = pallas and h.shape[0] <= _RESIDENT_ROWS
    if resident:
        out, counts = _resident_layer(
            h, ids, weights, experts, held, kernel == "pallas_interpret")
    else:
        on, flat, counts = _held_pairs(ids, held)
        if pallas:
            row_token, pair_row, tile_expert, used = _plan(
                flat, counts, ids.shape, _ROW_TILE)
            out_rows = grouped_experts(
                h[row_token], tile_expert, used,
                experts["w_gate"], experts["w_up"], experts["w_down"],
                tm=_ROW_TILE, interpret=kernel == "pallas_interpret",
            )
            picked = out_rows[pair_row].astype(jnp.float32)
        else:
            picked = _experts_xla(h, flat, counts, experts, ids.shape)
        # a gather by pair, not a scatter by row; `where`, not a product
        # with a zero weight: the rows no pair names hold whatever was in
        # memory
        out = (jnp.where(on[..., None], picked, 0.0)
               * weights[..., None]).sum(axis=1)
    if shared is not None:
        out = out + shared_expert(h, shared)
    counters = jnp.stack(
        [counts.sum(), (counts > 0).sum(), counts.max(), jnp.int32(resident)]
    ).astype(jnp.int32)
    return out, counters
