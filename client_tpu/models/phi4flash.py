"""Phi-4-mini-flash-reasoning's decoder (``model_type: phi4flash``, the
SambaY decoder-hybrid-decoder of arXiv:2507.06607) on the paged engine:
a self-decoder of Mamba-1 layers and differential attention under a
sliding window, ONE full-attention layer, and a cross-decoder that keeps
no cache of its own, whose attention layers read that one layer's K/V
pool and whose gated memory units read one Mamba layer's scan output.

From the model's public ``config.json`` and, for what it does not spell
out, the published ``modeling_phi4flash.py`` as recalled (each such item
is under ``assumed`` in ``benchmark/configs/phi4_mini_flash/config.json``).
With ``x`` the residual stream and ``LN(x) = w (x - mean x) / sqrt(var x
+ eps) + b`` in float32, a layer is ``h = x + Mixer(LN1 x)``, ``y = h +
MLP(LN2 h)``, ``MLP(m) = (silu(m W_g) * m W_u) W_d``; the logits are
``LN_f(y) @ embed^T``; no rotary and no other position signal. With ``N``
layers, the mixer of layer ``i`` (:attr:`Phi4FlashConfig.layer_kinds`)::

    i <= N/2, even          Mamba-1                      a ``state`` slot
    i <  N/2, odd           attention, window            own K/V, a ring
    i == N/2                Mamba-1, the MEMORY layer    a ``state`` slot
    i == N/2 + 1            attention, full              own K/V: THE pool
    i >  N/2 + 1, even      gated memory unit            none
    i >  N/2 + 1, odd       cross-attention              none: THE pool

Mamba (``models/selective_scan.py``; Jamba's mixer without its three
inner norms)::

    [u | z]       = a @ w_in
    u             = silu(causal depthwise conv of 4 taps, WITH bias)
    [dl | B | C]  = u @ w_x
    delta         = softplus(dl @ w_dt + b_dt)
    h[n, d]      <- exp(delta[d] A[n, d]) h[n, d] + delta[d] B[n] u[d]
    m[d]          = sum_n h[n, d] C[n] + D[d] u[d]
    out           = (m * silu(z)) @ w_out

The memory layer's ``m``, BEFORE its gate, is what every gated memory
unit reads at the same position: ``out = (m * silu(a @ w_in)) @ w_out``.

Differential attention (``H`` heads of ``D`` over ``KV`` key heads; the
heads pair up: ``q1, q2`` query heads ``2j, 2j + 1``, ``k1, k2`` key heads
``2p, 2p + 1``, one value head ``[v_2p | v_2p+1]`` of ``2 D`` a key pair,
query pair ``j`` over key pair ``j // (H / KV)``)::

    A_s    = softmax(q_s k_s^T / sqrt(D)) V            # s = 1, 2; causal
    lam    = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init(i)
    o_j    = (1 - lam_init(i)) * w_sub * rms(A_1 - lam A_2)
    out    = [o_0 | .. ] @ wo + bo

with ``lam_init(i) = 0.8 - 0.6 exp(-0.3 i)``. A self layer projects ``q,
k, v`` (with biases), a cross layer ``q`` alone. Both softmaxes of all
pairs are ONE call of the paged attention: a token's key row of a pair
``[k_2p | k_2p+1]`` and its value row ``[v_2p | v_2p+1]`` are the
projections as they come, ``KV / 2`` rows of ``2 D`` in either pool, and
``keys_per_value = 2`` says that the row's two key heads share its value
head (``models/paged_attention.py``); the query heads are put in
grouped-query order over the key heads on the way in
(:func:`_in_key_order`) and the subtraction is plain XLA after the call.
At the published widths a token takes 10 rows of 128 in either pool, a
page of 16 tokens 40,960 B: the kernel's tile is 8 pages (128 tokens,
``paged_attention.pages_per_tile``: the power of two nearest the 6.4
its budget holds), and a window of 512 keeps a ring of 40 blocks, five
tiles.

Three cache groups (``models/engine_model.py``), in this order: the full
group of the ONE full layer, the window group of the window layers, the
``state`` group of the Mamba layers (pools as ``models/jamba.py``'s). The
cross-decoder's layers store nothing: their entry of ``init_pages`` is
empty, and the cross layers attend over the full layer's pools with the
full group's table, in place: the pool is never copied.

A prefill runs the self-decoder, the memory layer and the full layer's
K/V projection over the whole prompt, and everything after that on the
prompt's LAST position alone (YOCO's prefill): the engine's ``prefill``
returns that position's logits and nothing else of the later positions
is ever read.
"""

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from client_tpu.models import paged_attention, selective_scan
from client_tpu.models.engine_model import (
    FULL, STATE, WINDOW, CacheGroup, EngineModel, Kernels,
)
from client_tpu.models.jamba import _convolved, step_draw
from client_tpu.models.llama import _mlp_block
from client_tpu.models.mimo_v2 import _prefill_attention, _write

MAMBA, WINDOWED, FULL_ATTN, GMU, CROSS = (
    "mamba", "window", "full", "gmu", "cross")
#: the index of a storing layer's cache group (and of its row of the
#: tables), in the order of :func:`cache_groups`
GROUP_OF = {FULL_ATTN: 0, WINDOWED: 1, MAMBA: 2}

#: the model's own per-step counters: (lane, Mamba layer) pairs whose
#: state a decode step read and wrote; rows of the full group's pool the
#: step's attention read (over the live lanes, context x the layers that
#: read the pool)
COUNTERS = ("ssm_state_updates", "shared_kv_rows_read")


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    d_model: int = 2560
    n_layers: int = 32
    n_heads: int = 40
    n_kv_heads: int = 20
    d_ff: int = 10240
    window: int = 512
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 160
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if (self.d_model % self.n_heads or self.n_heads % self.n_kv_heads
                or self.n_kv_heads % 2):
            raise ValueError(
                f"{self.n_heads} heads over {self.n_kv_heads} KV heads do "
                f"not pair up over a hidden size of {self.d_model}")
        if self.n_layers % 4:
            raise ValueError(
                f"{self.n_layers} layers: the memory layer, layer N/2, "
                "is a Mamba layer only where N is a multiple of 4")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def kv_pairs(self) -> int:
        """Rows a token has in a K or a V pool: a pair of key heads, and
        the value head they share, a row."""
        return self.n_kv_heads // 2

    @property
    def memory_layer(self) -> int:
        return self.n_layers // 2

    @property
    def full_layer(self) -> int:
        return self.n_layers // 2 + 1

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """The module docstring's table."""
        def kind(i):
            if i <= self.memory_layer:
                return WINDOWED if i % 2 else MAMBA
            if i == self.full_layer:
                return FULL_ATTN
            return CROSS if i % 2 else GMU

        return tuple(kind(i) for i in range(self.n_layers))

    @property
    def shared_readers(self) -> int:
        """Layers that read the full group's pool: the one that writes
        it and the cross-attention layers."""
        return 1 + self.layer_kinds.count(CROSS)

    def lambda_init(self, index: int) -> float:
        return 0.8 - 0.6 * float(np.exp(-0.3 * index))

    @staticmethod
    def tiny(**overrides) -> "Phi4FlashConfig":
        """A toy of the same shape for CPU tests."""
        base = dict(
            vocab_size=256, d_model=64, n_layers=12, n_heads=8,
            n_kv_heads=4, d_ff=128, window=16, d_state=16, dt_rank=8,
            max_seq_len=128, dtype=jnp.float32,
        )
        base.update(overrides)
        return Phi4FlashConfig(**base)


# -- parameters ---------------------------------------------------------------


def init_params(key, config: Phi4FlashConfig) -> Dict[str, Any]:
    """Seeded weights in the pytree the functions below take: the norms'
    scales 0.1 N(0,1) around 1 and their biases 0.1 N(0,1), the
    projection biases at a size that shows, the four lambda vectors 0.1
    N(0,1) as published, the Mamba draws as ``models/jamba.py``'s."""
    d, h, kv, dh = (config.d_model, config.n_heads, config.n_kv_heads,
                    config.head_dim)
    di, n, r, f = config.d_inner, config.d_state, config.dt_rank, config.d_ff
    keys = jax.random.split(key, config.n_layers + 1)
    s = 1.0 / np.sqrt(d)

    def normal(k, shape, scale, dtype=config.dtype, around=0.0):
        return (around + jax.random.normal(k, shape, jnp.float32) * scale
                ).astype(dtype)

    layers = []
    for index, kind in enumerate(config.layer_kinds):
        k = jax.random.split(keys[index], 24)
        layer = {"ln1_w": normal(k[0], (d,), 0.1, around=1.0),
                 "ln1_b": normal(k[1], (d,), 0.1),
                 "ln2_w": normal(k[2], (d,), 0.1, around=1.0),
                 "ln2_b": normal(k[3], (d,), 0.1),
                 "w_gate": normal(k[4], (d, f), s),
                 "w_up": normal(k[5], (d, f), s),
                 "w_down": normal(k[6], (f, d), 1.0 / np.sqrt(f))}
        if kind == MAMBA:
            a_log, b_dt = step_draw(k[7], config)
            layer.update(
                w_in=normal(k[8], (d, 2 * di), s),
                conv_w=normal(k[9], (config.d_conv, di), 0.5),
                conv_b=normal(k[10], (di,), 0.5),
                w_x=normal(k[11], (di, r + 2 * n), 1.0 / np.sqrt(di)),
                w_dt=normal(k[12], (r, di), 0.35 / np.sqrt(r)),
                b_dt=b_dt, A_log=a_log,
                D=normal(k[13], (di,), 0.1, jnp.float32, around=1.0),
                w_out=normal(k[14], (di, d), 1.0 / np.sqrt(di)),
            )
        elif kind == GMU:
            layer.update(
                w_in=normal(k[7], (d, di), s),
                w_out=normal(k[8], (di, d), 1.0 / np.sqrt(di)),
            )
        else:
            layer.update(
                wq=normal(k[7], (d, h, dh), s),
                bq=normal(k[8], (h, dh), 0.3),
                wo=normal(k[9], (h // 2, 2 * dh, d), 1.0 / np.sqrt(d)),
                bo=normal(k[10], (d,), 0.1),
                sub_norm=normal(k[11], (2 * dh,), 0.1, around=1.0),
                lambdas=normal(k[12], (4, dh), 0.1, jnp.float32),
            )
            if kind != CROSS:
                layer.update(
                    wk=normal(k[13], (d, kv, dh), s),
                    bk=normal(k[14], (kv, dh), 0.3),
                    wv=normal(k[15], (d, kv, dh), s),
                    bv=normal(k[16], (kv, dh), 0.3),
                )
        layers.append(layer)
    k = jax.random.split(keys[-1], 3)
    return {
        # of size 1 / sqrt(d), so that the tied head's logits are of unit
        # size; every layer's first norm takes the size out again
        "embed": normal(k[0], (config.vocab_size, d), s),
        "final_w": normal(k[1], (d,), 0.1, around=1.0),
        "final_b": normal(k[2], (d,), 0.1),
        "layers": layers,
    }


# -- cache groups and pools ---------------------------------------------------


def cache_groups(config: Phi4FlashConfig):
    """[full, window, state], each the layers that STORE there: the full
    group is the one full layer, whose pool the cross layers read too; a
    gated memory unit and a cross layer are in no group."""
    kinds = config.layer_kinds

    def of(kind):
        return tuple(i for i, k in enumerate(kinds) if k == kind)

    return [CacheGroup(FULL, of(FULL_ATTN)),
            CacheGroup(WINDOW, of(WINDOWED), window=config.window),
            CacheGroup(STATE, of(MAMBA))]


def init_pages(config: Phi4FlashConfig, num_blocks, block_size: int):
    """In layer order: a self-attention layer's flat ``(k_pages, v_pages)``
    of its group's blocks (``KV / 2`` rows of ``2 D`` a token in either),
    a Mamba layer's ``(state_pool, conv_pool)`` of ``num_blocks[2]``
    SLOTS, and NOTHING for a gated memory unit or a cross layer."""
    rows = block_size * config.kv_pairs
    pages = []
    for kind in config.layer_kinds:
        if kind == MAMBA:
            pages.append((
                jnp.zeros((num_blocks[2], config.d_state, config.d_inner),
                          jnp.float32),
                jnp.zeros((num_blocks[2],
                           (config.d_conv - 1) * config.d_inner),
                          config.dtype),
            ))
        elif kind in (WINDOWED, FULL_ATTN):
            pages.append(tuple(
                jnp.zeros((num_blocks[GROUP_OF[kind]], rows,
                           2 * config.head_dim), config.dtype)
                for _ in "kv"))
        else:
            pages.append(())
    return pages


def kv_row_bytes(config: Phi4FlashConfig):
    """[(stored, counted)]: a cached token's K and V in the full layer and
    in one window layer (the same row), and ONE SLOT of one Mamba layer."""
    itemsize = jnp.dtype(config.dtype).itemsize
    token = 2 * config.n_kv_heads * config.head_dim * itemsize
    slot = (config.d_state * config.d_inner * 4
            + (config.d_conv - 1) * config.d_inner * itemsize)
    return [(token, token), (token, token), (slot, slot)]


# -- building blocks ----------------------------------------------------------


def layer_norm(x, weight, bias, eps):
    """``LN``: mean-centred, with bias, float32 inside."""
    x32 = x.astype(jnp.float32)
    centred = x32 - x32.mean(axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(centred), axis=-1, keepdims=True)
    return (centred * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _projected(layer, normed, name):
    """``normed`` [T, d] -> [T, heads, D] by ``w<name>`` and ``b<name>``."""
    return (jnp.einsum("td,dhk->thk", normed, layer["w" + name])
            + layer["b" + name])


def _in_key_order(q, config: Phi4FlashConfig):
    """``q`` [T, H, D], published order (head ``2j + s`` is ``q_{s+1}`` of
    pair ``j``) -> grouped-query order over the key heads: key pair
    ``p``'s heads are ``q_1`` of its pairs, then ``q_2`` of them."""
    t, h, d = q.shape
    pairs = config.n_heads // config.n_kv_heads
    return q.reshape(t, config.kv_pairs, pairs, 2, d).swapaxes(2, 3).reshape(
        t, h, d)


def _pair_rows(x, config: Phi4FlashConfig):
    """K or V [T, KV, D] -> the pools' rows [T, KV / 2, 2 D]."""
    return x.reshape(x.shape[0], config.kv_pairs, -1)


def _subtracted(attended, layer, index: int, config: Phi4FlashConfig):
    """``attended`` [T, H, 2 D] in :func:`_in_key_order`'s order -> the
    mixer's output [T, d]: ``A_1 - lam A_2`` a pair, its norm, ``W_o``."""
    t = attended.shape[0]
    pairs = config.n_heads // config.n_kv_heads
    a = attended.astype(jnp.float32).reshape(
        t, config.kv_pairs, 2, pairs, -1)
    lq1, lk1, lq2, lk2 = layer["lambdas"]
    init = config.lambda_init(index)
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + init
    diff = (a[:, :, 0] - lam * a[:, :, 1]).reshape(t, config.n_heads // 2, -1)
    unit = diff * jax.lax.rsqrt(
        jnp.mean(jnp.square(diff), axis=-1, keepdims=True) + config.norm_eps)
    out = ((1.0 - init) * unit * layer["sub_norm"].astype(jnp.float32)
           ).astype(attended.dtype)
    return jnp.einsum("tpk,pkd->td", out, layer["wo"]) + layer["bo"]


def _last_row_attention(q, k_rows, v_rows, last_index, config):
    """One query position over a prompt: ``q`` [1, H, D] in key order,
    ``k_rows`` / ``v_rows`` [L, KV / 2, 2 D] -> [1, H, 2 D]; it sees the
    keys up to ``last_index``."""
    length = k_rows.shape[0]
    kv, dh = config.n_kv_heads, config.head_dim
    scores = jnp.einsum(
        "kgd,skd->kgs", q[0].reshape(kv, -1, dh),
        k_rows.reshape(length, kv, dh),
        preferred_element_type=jnp.float32) * dh ** -0.5
    scores = jnp.where(jnp.arange(length) <= last_index, scores,
                       paged_attention.NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1).reshape(
        config.kv_pairs, -1, length)
    out = jnp.einsum("pgs,spd->pgd", weights.astype(v_rows.dtype), v_rows)
    return out.reshape(1, config.n_heads, -1)


def _scan_inputs(layer, conv, config: Phi4FlashConfig):
    """The convolution's output [T, Di] -> what the scan takes of it, all
    float32: ``u`` [T, Di], ``delta`` [T, Di], ``B`` and ``C`` [T, N]. No
    norm on ``dl``, ``B`` or ``C``: those are Jamba's."""
    r, n = config.dt_rank, config.d_state
    mixed = jnp.dot(conv, layer["w_x"], preferred_element_type=jnp.float32)
    delta = jax.nn.softplus(
        jnp.dot(mixed[:, :r].astype(conv.dtype), layer["w_dt"],
                preferred_element_type=jnp.float32) + layer["b_dt"])
    return conv.astype(jnp.float32), delta, mixed[:, r:r + n], mixed[:, r + n:]


def _gated(memory, gate, w_out, dtype):
    """``(m * silu(z)) @ w_out``: a Mamba mixer's way out and a gated
    memory unit's. ``memory`` float32, ``gate`` as projected."""
    return jnp.dot(
        (memory * jax.nn.silu(gate.astype(jnp.float32))).astype(dtype), w_out)


def _ffn(layer, x, config: Phi4FlashConfig):
    normed = layer_norm(x, layer["ln2_w"], layer["ln2_b"], config.norm_eps)
    return x + _mlp_block(layer, normed[None])[0]


def _head(params, x, config: Phi4FlashConfig):
    """The tied head: the final ``LN``, then the embedding transposed."""
    x = layer_norm(x, params["final_w"], params["final_b"], config.norm_eps)
    return jnp.einsum("td,vd->tv", x, params["embed"]).astype(jnp.float32)


# -- the engine's programs ----------------------------------------------------


def prefill_into_pages(params, tokens, page_tables, pages, last_index,
                       config: Phi4FlashConfig, kernels: Kernels):
    """Prefill one prompt. ``tokens`` [1, L] (padded to its bucket),
    ``page_tables`` [3, max_blocks]: row 0 the full group's blocks, row 1
    the window group's (positions past ``last_index`` and blocks behind
    the window go to the trash block), row 2 the sequence's slot in
    column 0. Layers up to the full layer's K/V projection run over the
    whole prompt (a Mamba layer as ``models/jamba.py``'s, a window layer
    on the prompt in plain XLA); the full layer attends for the last
    position alone and every layer after it runs on that one row.
    Returns (logits of the last token [1, V], pages)."""
    del kernels  # a prompt runs in plain XLA under every choice
    length = tokens.shape[1]
    rows, di, taps = config.kv_pairs, config.d_inner, config.d_conv
    positions = jnp.arange(length)
    real = positions <= last_index
    slot = page_tables[2, 0]
    kept = slot != selective_scan.TRASH_SLOT
    scale = config.head_dim ** -0.5
    x = params["embed"][tokens[0]]
    memory = shared = None
    new_pages = []
    for index, (layer, pools, kind) in enumerate(zip(
            params["layers"], pages, config.layer_kinds)):
        normed = layer_norm(x, layer["ln1_w"], layer["ln1_b"],
                            config.norm_eps)
        if kind == MAMBA:
            state_pool, conv_pool = pools
            mixed = jnp.dot(normed, layer["w_in"])
            padded = jnp.pad(mixed[:, :di], ((taps - 1, 0), (0, 0)))
            conv = _convolved(
                layer, [padded[j:j + length] for j in range(taps)], x.dtype)
            u, delta, b, c = _scan_inputs(layer, conv, config)
            read, state = selective_scan.chunked_selective_scan(
                u, jnp.where(real[:, None], delta, 0.0), b, c, None,
                -jnp.exp(layer["A_log"]), layer["D"])
            last_inputs = jax.lax.dynamic_slice_in_dim(
                padded, last_index + 1, taps - 1).reshape(-1)
            pools = (
                state_pool.at[slot].set(jnp.where(kept, state, 0.0)),
                conv_pool.at[slot].set(
                    jnp.where(kept, last_inputs, 0).astype(conv_pool.dtype)),
            )
            if index == config.memory_layer:
                memory = jax.lax.dynamic_slice_in_dim(read, last_index, 1)
            out = _gated(read, mixed[:, di:], layer["w_out"], x.dtype)
        elif kind == GMU:
            out = _gated(memory, jnp.dot(normed, layer["w_in"]),
                         layer["w_out"], x.dtype)
        else:
            if kind != CROSS:
                k_pages, v_pages = pools
                block_size = k_pages.shape[1] // rows
                table = page_tables[GROUP_OF[kind]]
                phys = jnp.where(real, table[positions // block_size], 0)
                off = jnp.where(real, positions % block_size, 0)
                k = _pair_rows(_projected(layer, normed, "k"), config)
                v = _pair_rows(_projected(layer, normed, "v"), config)
                pools = (_write(k_pages, phys, off, k, rows),
                         _write(v_pages, phys, off, v, rows))
            if kind == FULL_ATTN:
                # from here on the last position alone
                shared = (k, v)
                normed = jax.lax.dynamic_slice_in_dim(normed, last_index, 1)
                x = jax.lax.dynamic_slice_in_dim(x, last_index, 1)
            q = _in_key_order(_projected(layer, normed, "q"), config)
            if kind == WINDOWED:
                wide = paged_attention._widened_to_key_rows(
                    q[None], rows, 2)[0]
                attended = _prefill_attention(
                    wide, k, v, config.window, None, scale).astype(x.dtype)
            else:
                attended = _last_row_attention(
                    q, *shared, last_index, config).astype(x.dtype)
            out = _subtracted(attended, layer, index, config)
        new_pages.append(pools)
        x = _ffn(layer, x + out.astype(x.dtype), config)
    return _head(params, x, config), new_pages


def decode_step_paged(params, tokens, positions, page_tables, pages,
                      config: Phi4FlashConfig, kernels: Kernels):
    """One decode step for ``B`` lanes. ``page_tables`` [3, B, NB]: row 0
    the full group's, row 1 the window group's rings, row 2 each lane's
    slot in column 0 (a padding lane the trash slot). A self-attention
    layer writes the token's K/V rows and attends through
    ``kernels.attn``; a cross layer attends over the full layer's pools,
    as that layer left them this step, with the full group's table; a
    Mamba layer turns the lane's state in its slot and the memory layer
    hands its ungated sums to the gated memory units. Returns (logits [B,
    V], pages, counters int32: :data:`COUNTERS`)."""
    lanes = tokens.shape[0]
    rows, di = config.kv_pairs, config.d_inner
    slots = page_tables[2, :, 0]
    live = slots != selective_scan.TRASH_SLOT
    x = params["embed"][tokens]
    memory = shared = None
    updates = jnp.int32(0)
    new_pages = []
    for index, (layer, pools, kind) in enumerate(zip(
            params["layers"], pages, config.layer_kinds)):
        normed = layer_norm(x, layer["ln1_w"], layer["ln1_b"],
                            config.norm_eps)
        if kind == MAMBA:
            state_pool, conv_pool = pools
            mixed = jnp.dot(normed, layer["w_in"])
            window = jnp.concatenate(
                [conv_pool[slots], mixed[:, :di]], axis=1)  # [B, taps * Di]
            conv = _convolved(
                layer, [window[:, j * di:(j + 1) * di]
                        for j in range(config.d_conv)], x.dtype)
            conv_pool = conv_pool.at[slots].set(
                jnp.where(live[:, None], window[:, di:], 0))
            u, delta, b, c = _scan_inputs(layer, conv, config)
            read, state_pool = selective_scan.selective_scan_step(
                u, delta, b, c, None, -jnp.exp(layer["A_log"]), layer["D"],
                slots, state_pool, kernel=kernels.name)
            pools = (state_pool, conv_pool)
            if index == config.memory_layer:
                memory = read
            out = _gated(read, mixed[:, di:], layer["w_out"], x.dtype)
            updates = updates + live.sum(dtype=jnp.int32)
        elif kind == GMU:
            out = _gated(memory, jnp.dot(normed, layer["w_in"]),
                         layer["w_out"], x.dtype)
        else:
            group = GROUP_OF.get(kind, 0)
            if kind != CROSS:
                k_pages, v_pages = pools
                block_size = k_pages.shape[1] // rows
                phys = page_tables[
                    group, jnp.arange(lanes), positions // block_size]
                off = positions % block_size
                # scatter this step's K/V, THEN attend: the current
                # position's entry must be visible to its own attention
                pools = (
                    _write(k_pages, phys, off, _pair_rows(
                        _projected(layer, normed, "k"), config), rows),
                    _write(v_pages, phys, off, _pair_rows(
                        _projected(layer, normed, "v"), config), rows))
            if kind == FULL_ATTN:
                shared = pools
            q = _in_key_order(_projected(layer, normed, "q"), config)
            attended = kernels.attn(
                q[:, None], *(pools if kind == WINDOWED else shared),
                page_tables[group], positions[:, None],
                window=config.window if kind == WINDOWED else None,
                kv_heads=rows, keys_per_value=2)[:, 0]
            out = _subtracted(attended, layer, index, config)
        new_pages.append(pools)
        x = _ffn(layer, x + out.astype(x.dtype), config)
    rows_read = jnp.where(live, positions + 1, 0).sum(
        dtype=jnp.int32) * config.shared_readers
    return (_head(params, x, config), new_pages,
            jnp.stack([updates, rows_read]))


ENGINE_MODEL = EngineModel(
    name="phi4flash",
    init_params=init_params,
    cache_groups=cache_groups,
    init_pages=init_pages,
    prefill=prefill_into_pages,
    decode=decode_step_paged,
    heads=lambda config: (config.n_heads, config.n_kv_heads),
    kv_row_bytes=kv_row_bytes,
    step_counters=COUNTERS,
)
