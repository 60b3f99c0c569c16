"""Jamba's decoder (``model_type: jamba``; AI21-Jamba2-3B) on the paged
engine: Mamba-1 layers whose state is one slot a sequence, beside a few
multi-query attention layers whose K/V is paged, a dense SwiGLU after
every mixer, the head tied to the embedding, no positional encoding.

From the model's public ``config.json`` (ai21labs/AI21-Jamba2-3B) and,
for what it does not spell out, the published ``modeling_jamba.py`` as
recalled (each such item is under ``assumed`` in
``benchmark/configs/jamba2_3b/config.json``). With ``x`` the residual
stream, ``n(x) = x / sqrt(mean(x^2) + eps)`` in float32 and every norm
``w n(x)`` (``llama.rms_norm``), a layer is ``h = x + Mixer(w1 n(x))``,
``y = h + MLP(w2 n(h))``, ``MLP(m) = (silu(m W_g) * m W_u) W_d``; layer
``i`` is attention where ``i % attn_period == attn_offset``, else Mamba;
the logits are ``w_f n(y) @ embed^T``.

Attention (20 heads of 128 over 1 KV head), no rotary and no other
position signal::

    q, k, v = a @ wq, a @ wk, a @ wv
    out     = softmax(q k^T / sqrt(D)) v @ wo                  # causal

Mamba (``d_inner`` channels, ``d_state`` states a channel, ``dt_rank``)::

    [u | z]         = a @ w_in
    u               = silu(causal depthwise conv of 4 taps, WITH bias)
    [dl | B | C]    = u @ w_x;   dl, B, C = w n(dl), w n(B), w n(C)
    delta           = softplus(dl @ w_dt + b_dt)
    h[n, d]        <- exp(delta[d] A[n, d]) h[n, d] + delta[d] B[n] u[d]
    y[d]            = sum_n h[n, d] C[n] + D[d] u[d]
    out             = (y * silu(z)) @ w_out

``A = -exp(A_log)``; the recurrence is ``models/selective_scan.py``'s, in
float32 on a float32 state.

Two cache groups (``models/engine_model.py``): the full group of the
attention layers (flat K and V pools, ``[N, bs*KV, D]``) and the ``state``
group of the Mamba layers, whose pools are ``(state [slots, d_state,
d_inner] float32, conv [slots, (taps - 1) * d_inner])``, the channels
last and a slot's three convolution inputs side by side in ONE row (a
``[slots, 3, d_inner]`` pool lies in HBM with its slots second to last,
so that every gather and scatter by slot is a copy of the whole pool
before it and after): a sequence holds one slot whatever its length,
``tables[1][..., 0]``. A prefill writes the slot whole (the scan's final
state; the last three convolution inputs up to ``last_index``), a decode
step turns it in place. Slot 0 is the trash slot and holds zeros.
"""

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from client_tpu.models import selective_scan
from client_tpu.models.engine_model import (
    FULL, STATE, CacheGroup, EngineModel, Kernels,
)
from client_tpu.models.llama import _mlp_block, rms_norm
from client_tpu.models.mimo_v2 import _prefill_attention, _write

#: the model's own per-step counter: (lane, Mamba layer) pairs whose state
#: a decode step read and wrote
COUNTERS = ("ssm_state_updates",)


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    d_model: int = 2560
    n_layers: int = 28
    attn_period: int = 14
    attn_offset: int = 7
    n_heads: int = 20
    n_kv_heads: int = 1
    d_ff: int = 8192
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 160
    norm_eps: float = 1e-6
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.d_model % self.n_heads or self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"{self.n_heads} heads over {self.n_kv_heads} KV heads do "
                f"not divide a hidden size of {self.d_model}")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def layer_kinds(self) -> Tuple[int, ...]:
        """0 an attention layer, 1 a Mamba layer: a layer's tables are
        ``tables[kind]``."""
        return tuple(int(i % self.attn_period != self.attn_offset)
                     for i in range(self.n_layers))

    @staticmethod
    def tiny(**overrides) -> "JambaConfig":
        """A toy of the same shape for CPU tests."""
        base = dict(
            vocab_size=256, d_model=64, n_layers=4, attn_period=4,
            attn_offset=2, n_heads=4, n_kv_heads=1, d_ff=128, d_state=16,
            dt_rank=8, max_seq_len=128, dtype=jnp.float32,
        )
        base.update(overrides)
        return JambaConfig(**base)


# -- parameters ---------------------------------------------------------------


def step_draw(key, config: JambaConfig):
    """``(A_log [N, Di], b_dt [Di])`` float32. ``A_log = log(1..N)`` a
    channel and ``b_dt`` the inverse softplus of a log-uniform draw on
    0.001-0.1, both as published; with ``w_dt`` drawn so that ``dl @
    w_dt`` is of size 0.35, ``delta`` stays in about 0.001-0.2 and a
    channel's decay a token ``exp(delta A)`` spreads over about
    0.04-0.999."""
    step = jnp.exp(jax.random.uniform(
        key, (config.d_inner,), jnp.float32, np.log(1e-3), np.log(1e-1)))
    a_log = jnp.log(jnp.arange(1, config.d_state + 1, dtype=jnp.float32))
    return (jnp.broadcast_to(a_log[:, None],
                             (config.d_state, config.d_inner)),
            step + jnp.log(-jnp.expm1(-step)))


def init_params(key, config: JambaConfig) -> Dict[str, Any]:
    """Seeded weights in the pytree the functions below take. The norm
    scales lie 0.1 N(0,1) around 1, ``D`` around 1, the convolution's taps
    and bias at a size that shows."""
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
        k = jax.random.split(keys[index], 16)
        layer = {"mixer_norm": normal(k[0], (d,), 0.1, around=1.0),
                 "mlp_norm": normal(k[1], (d,), 0.1, around=1.0),
                 "w_gate": normal(k[2], (d, f), s),
                 "w_up": normal(k[3], (d, f), s),
                 "w_down": normal(k[4], (f, d), 1.0 / np.sqrt(f))}
        if kind:
            a_log, b_dt = step_draw(k[5], config)
            layer.update(
                w_in=normal(k[6], (d, 2 * di), s),
                conv_w=normal(k[7], (config.d_conv, di), 0.5),
                conv_b=normal(k[8], (di,), 0.5),
                w_x=normal(k[9], (di, r + 2 * n), 1.0 / np.sqrt(di)),
                dt_norm=normal(k[10], (r,), 0.1, around=1.0),
                b_norm=normal(k[11], (n,), 0.1, around=1.0),
                c_norm=normal(k[12], (n,), 0.1, around=1.0),
                w_dt=normal(k[13], (r, di), 0.35 / np.sqrt(r)),
                b_dt=b_dt, A_log=a_log,
                D=normal(k[14], (di,), 0.1, jnp.float32, around=1.0),
                w_out=normal(k[15], (di, d), 1.0 / np.sqrt(di)),
            )
        else:
            layer.update(
                wq=normal(k[5], (d, h, dh), s),
                wk=normal(k[6], (d, kv, dh), s),
                wv=normal(k[7], (d, kv, dh), s),
                wo=normal(k[8], (h, dh, d), 1.0 / np.sqrt(h * dh)),
            )
        layers.append(layer)
    k = jax.random.split(keys[-1], 2)
    return {
        # of size 1 / sqrt(d), so that the tied head's logits are of unit
        # size; every layer's first norm takes the size out again
        "embed": normal(k[0], (config.vocab_size, d), s),
        "final_norm": normal(k[1], (d,), 0.1, around=1.0),
        "layers": layers,
    }


# -- cache groups and pools ---------------------------------------------------


def cache_groups(config: JambaConfig):
    """[full group, state group]: group ``g`` holds the layers of kind
    ``g``, so a layer's tables are ``tables[config.layer_kinds[layer]]``."""
    kinds = config.layer_kinds
    return [
        CacheGroup(FULL, tuple(i for i, k in enumerate(kinds) if k == 0)),
        CacheGroup(STATE, tuple(i for i, k in enumerate(kinds) if k == 1)),
    ]


def init_pages(config: JambaConfig, num_blocks, block_size: int):
    """In layer order: an attention layer's flat ``(k_pages, v_pages)`` of
    ``num_blocks[0]`` blocks, a Mamba layer's ``(state_pool, conv_pool)``
    of ``num_blocks[1]`` SLOTS, the state in float32, the channels last,
    a slot's convolution inputs one row."""
    rows = block_size * config.n_kv_heads
    pages = []
    for kind in config.layer_kinds:
        if kind:
            pages.append((
                jnp.zeros((num_blocks[1], config.d_state, config.d_inner),
                          jnp.float32),
                jnp.zeros((num_blocks[1],
                           (config.d_conv - 1) * config.d_inner),
                          config.dtype),
            ))
        else:
            pages.append(tuple(
                jnp.zeros((num_blocks[0], rows, config.head_dim),
                          config.dtype) for _ in "kv"))
    return pages


def kv_row_bytes(config: JambaConfig):
    """[(stored, counted)]: a cached token's K and V in one attention
    layer, and ONE SLOT of one Mamba layer (its float32 state and its
    convolution inputs), which is what a sequence holds there whatever
    its length."""
    itemsize = jnp.dtype(config.dtype).itemsize
    token = 2 * config.n_kv_heads * config.head_dim * itemsize
    slot = (config.d_state * config.d_inner * 4
            + (config.d_conv - 1) * config.d_inner * itemsize)
    return [(token, token), (slot, slot)]


# -- building blocks ----------------------------------------------------------


def _qkv(layer, normed):
    """``normed`` [T, d] -> q [T, H, D], k and v [T, KV, D]: no rotary."""
    return tuple(jnp.einsum("td,dhk->thk", normed, layer[name])
                 for name in ("wq", "wk", "wv"))


def _convolved(layer, taps, dtype):
    """``taps``: the convolution's ``kernel`` inputs [T, channels] each, tap
    ``j`` the input ``kernel - 1 - j`` tokens back: silu of the depthwise
    sum and the bias, float32 inside."""
    weights = layer["conv_w"].astype(jnp.float32)
    summed = sum(tap.astype(jnp.float32) * weights[j]
                 for j, tap in enumerate(taps))
    return jax.nn.silu(summed + layer["conv_b"].astype(jnp.float32)
                       ).astype(dtype)


def _scan_inputs(layer, conv, config: JambaConfig):
    """The convolution's output [T, Di] -> what the scan takes of it, all
    float32: ``u`` [T, Di], ``delta`` [T, Di], ``B`` and ``C`` [T, N]."""
    r, n, eps = config.dt_rank, config.d_state, config.norm_eps
    mixed = jnp.dot(conv, layer["w_x"], preferred_element_type=jnp.float32)
    step = rms_norm(mixed[:, :r], layer["dt_norm"], eps)
    b = rms_norm(mixed[:, r:r + n], layer["b_norm"], eps)
    c = rms_norm(mixed[:, r + n:], layer["c_norm"], eps)
    delta = jax.nn.softplus(
        jnp.dot(step.astype(conv.dtype), layer["w_dt"],
                preferred_element_type=jnp.float32) + layer["b_dt"])
    return conv.astype(jnp.float32), delta, b, c


def _ffn(layer, x, config: JambaConfig):
    normed = rms_norm(x, layer["mlp_norm"], config.norm_eps)
    return x + _mlp_block(layer, normed[None])[0]


def _head(params, x, config: JambaConfig):
    """The tied head: the final norm, then the embedding transposed."""
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    return jnp.einsum("td,vd->tv", x, params["embed"]).astype(jnp.float32)


# -- the engine's programs ----------------------------------------------------


def prefill_into_pages(params, tokens, page_tables, pages, last_index,
                       config: JambaConfig, kernels: Kernels):
    """Prefill one prompt. ``tokens`` [1, L] (padded to its bucket),
    ``page_tables`` [2, max_blocks]: row 0 the full group's blocks
    (positions past ``last_index`` go to the trash block), row 1 the
    sequence's slot in column 0. An attention layer scatters its K/V and
    attends on the prompt in plain XLA; a Mamba layer runs the scan in
    chunks with the padding masked (``delta = 0`` past ``last_index``)
    and writes the final state and the last convolution inputs up to
    ``last_index`` WHOLE into the slot (zeros into the trash slot), under
    every kernel choice. Returns (logits of the last token [1, V],
    pages)."""
    del kernels  # a prompt runs in plain XLA under every choice
    length = tokens.shape[1]
    kv, di, taps = config.n_kv_heads, config.d_inner, config.d_conv
    positions = jnp.arange(length)
    real = positions <= last_index
    slot = page_tables[1, 0]
    kept = slot != selective_scan.TRASH_SLOT
    x = params["embed"][tokens[0]]
    new_pages = []
    for layer, pools, kind in zip(params["layers"], pages,
                                  config.layer_kinds):
        normed = rms_norm(x, layer["mixer_norm"], config.norm_eps)
        if kind:
            state_pool, conv_pool = pools
            mixed = jnp.dot(normed, layer["w_in"])
            padded = jnp.pad(mixed[:, :di], ((taps - 1, 0), (0, 0)))
            conv = _convolved(
                layer, [padded[j:j + length] for j in range(taps)], x.dtype)
            u, delta, b, c = _scan_inputs(layer, conv, config)
            out, state = selective_scan.chunked_selective_scan(
                u, jnp.where(real[:, None], delta, 0.0), b, c,
                mixed[:, di:].astype(jnp.float32),
                -jnp.exp(layer["A_log"]), layer["D"])
            last_inputs = jax.lax.dynamic_slice_in_dim(
                padded, last_index + 1, taps - 1).reshape(-1)
            new_pages.append((
                state_pool.at[slot].set(jnp.where(kept, state, 0.0)),
                conv_pool.at[slot].set(
                    jnp.where(kept, last_inputs, 0).astype(conv_pool.dtype)),
            ))
            x = x + jnp.dot(out.astype(x.dtype), layer["w_out"])
        else:
            k_pages, v_pages = pools
            block_size = k_pages.shape[1] // kv
            phys = jnp.where(real, page_tables[0, positions // block_size], 0)
            off = jnp.where(real, positions % block_size, 0)
            q, k, v = _qkv(layer, normed)
            new_pages.append((_write(k_pages, phys, off, k, kv),
                              _write(v_pages, phys, off, v, kv)))
            out = _prefill_attention(
                q, k, v, None, None, config.head_dim ** -0.5)
            x = x + jnp.einsum("thk,hkd->td", out.astype(x.dtype),
                               layer["wo"])
        x = _ffn(layer, x, config)
    last = jax.lax.dynamic_slice_in_dim(x, last_index, 1)
    return _head(params, last, config), new_pages


def decode_step_paged(params, tokens, positions, page_tables, pages,
                      config: JambaConfig, kernels: Kernels):
    """One decode step for ``B`` lanes. ``page_tables`` [2, B, NB]: row 0
    the full group's, row 1 each lane's slot in column 0 (a padding lane
    the trash slot). An attention layer writes the token's K/V and
    attends through ``kernels.attn``; a Mamba layer shifts the lane's
    convolution inputs and turns the lane's state in its slot
    (``selective_scan.selective_scan_step``, the kernel or the gather and
    scatter as ``kernels.name`` says). Returns (logits [B, V], pages,
    counters int32: :data:`COUNTERS`)."""
    lanes = tokens.shape[0]
    kv, di = config.n_kv_heads, config.d_inner
    slots = page_tables[1, :, 0]
    live = slots != selective_scan.TRASH_SLOT
    x = params["embed"][tokens]
    updates = jnp.int32(0)
    new_pages = []
    for layer, pools, kind in zip(params["layers"], pages,
                                  config.layer_kinds):
        normed = rms_norm(x, layer["mixer_norm"], config.norm_eps)
        if kind:
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
            out, state_pool = selective_scan.selective_scan_step(
                u, delta, b, c, mixed[:, di:].astype(jnp.float32),
                -jnp.exp(layer["A_log"]), layer["D"], slots, state_pool,
                kernel=kernels.name)
            new_pages.append((state_pool, conv_pool))
            x = x + jnp.dot(out.astype(x.dtype), layer["w_out"])
            updates = updates + live.sum(dtype=jnp.int32)
        else:
            k_pages, v_pages = pools
            block_size = k_pages.shape[1] // kv
            phys = page_tables[0, jnp.arange(lanes), positions // block_size]
            off = positions % block_size
            q, k, v = _qkv(layer, normed)
            # scatter this step's K/V, THEN attend: the current position's
            # entry must be visible to its own attention
            k_pages = _write(k_pages, phys, off, k, kv)
            v_pages = _write(v_pages, phys, off, v, kv)
            new_pages.append((k_pages, v_pages))
            out = kernels.attn(
                q[:, None], k_pages, v_pages, page_tables[0],
                positions[:, None], kv_heads=kv)[:, 0]
            x = x + jnp.einsum("thk,hkd->td", out, layer["wo"])
        x = _ffn(layer, x, config)
    return _head(params, x, config), new_pages, updates[None]


ENGINE_MODEL = EngineModel(
    name="jamba",
    init_params=init_params,
    cache_groups=cache_groups,
    init_pages=init_pages,
    prefill=prefill_into_pages,
    decode=decode_step_paged,
    heads=lambda config: (config.n_heads, config.n_kv_heads),
    kv_row_bytes=kv_row_bytes,
    step_counters=COUNTERS,
)
