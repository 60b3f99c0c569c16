"""Qwen3-Next's decoder (``model_type: qwen3_next``) on the paged engine:
three Gated DeltaNet layers to one gated full-attention layer, many small
softmax-routed experts with a gated shared one, of which this chip holds
a share.

From the model's public ``config.json`` (Qwen/Qwen3-Next-80B-A3B-Instruct)
and, for what it does not spell out, the published
``modeling_qwen3_next.py`` as recalled (each such item is under
``assumed`` in ``benchmark/configs/qwen3_next_80b/config.json``). With
``x`` the residual stream, ``n(x) = x / sqrt(mean(x^2) + eps)`` in float32
and ``N(x) = n(x) (1 + w)`` (residual stream, ``q`` / ``k`` heads, final
norm), a layer is ``h = x + Mixer(N1 x)``, ``y = h + MoE(N2 h)``; layer
``i`` is full attention where ``(i + 1) % full_interval == 0``.

Gated full attention (16 heads of 256 over 2 KV heads)::

    [q | gate] = a @ wq            # a head's 512 split 256 / 256
    k, v       = a @ wk, a @ wv;   q, k = N_q(q), N_k(k)       # a head
    q, k       = rope on the first rotary_dim of head_dim sizes
    o          = softmax(q k^T / sqrt(D)) v                    # causal
    out        = (o * sigmoid(gate)) @ wo

Gated DeltaNet (16 key heads and 32 value heads of 128)::

    [q|k|v|z]  = a @ w_qkvz;   [b | a'] = a @ w_ba
    [q|k|v]    = silu(causal depthwise conv of 4 taps, no bias)
    beta       = sigmoid(b);   g = -exp(A_log) softplus(a' + dt_bias)
    q, k       = q / sqrt(sum q^2 + 1e-6) * Dk^-0.5,  k / sqrt(sum k^2 + 1e-6)
    o          = the gated delta rule (``models/gated_delta.py``), float32
    out        = (w * n(o) * silu(z)) a head, joined, @ w_out

Experts: ``p = softmax(m @ router)`` over ALL experts in float32, the
``top_k`` largest, weights ``p_e`` over their sum; each a SwiGLU; the
shared expert a SwiGLU times ``sigmoid(m @ w_sg)`` (``models/moe.py``).

Two cache groups (``models/engine_model.py``): the full group of the
gated-attention layers (flat K and V pools, ``[N, bs*KV, D]``) and the
``state`` group of the DeltaNet layers, whose pools are ``(state [slots,
Hv, Dk, Dv] float32, conv [slots, taps - 1, heads, lanes])``, a slot's
three convolution inputs oldest first, each folded into the rows of its
q, k and v heads (``Qwen3NextConfig.conv_lanes``), so that a slot owns
whole tiles of the pool as the TPU stores it and a gather or a scatter
by slot moves those and nothing else (a ``[slots, 3, channels]`` pool
lies in HBM with its slots second to last and is copied whole before
every gather and after every scatter; one row of ``3 * channels`` a slot
shares each tile among 16 slots, and a scatter by slot then writes a
sixteenth of every tile it touches): a sequence
holds one slot whatever its length, ``tables[1][..., 0]``. A prefill
writes the slot whole (the chunked rule's final state; the last three
convolution inputs up to ``last_index``), a decode step turns it in
place. Slot 0 is the trash slot and holds zeros. Rotary pairs are (2i,
2i+1), the program's layout throughout (``llama._rope``).
"""

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from client_tpu.models import gated_delta, moe
from client_tpu.models.engine_model import (
    FULL, STATE, CacheGroup, EngineModel, Kernels,
)
from client_tpu.models.llama import _rope
from client_tpu.models.mimo_v2 import _prefill_attention, _write

#: the model's own per-step counters, after ``moe.COUNTERS``: (lane,
#: DeltaNet layer) pairs whose state a decode step read and wrote
COUNTERS = moe.COUNTERS + ("gdn_state_updates",)
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    d_model: int = 2048
    n_layers: int = 48
    full_interval: int = 4
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    rotary_dim: int = 64
    lin_key_heads: int = 16
    lin_value_heads: int = 32
    lin_key_dim: int = 128
    lin_value_dim: int = 128
    conv_kernel: int = 4
    d_expert: int = 512
    d_shared: int = 512
    n_experts: int = 512
    top_k: int = 10
    held: Tuple[int, int] = (0, 512)
    rope_theta: float = 1e7
    norm_eps: float = 1e-6
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.n_experts):
            raise ValueError(
                f"held={self.held} is not a share of {self.n_experts} experts")
        if self.lin_value_heads % self.lin_key_heads:
            raise ValueError(
                f"{self.lin_value_heads} value heads over "
                f"{self.lin_key_heads} key heads")

    @property
    def layer_kinds(self) -> Tuple[int, ...]:
        """0 a gated full-attention layer, 1 a Gated DeltaNet layer: a
        layer's tables are ``tables[kind]``."""
        return tuple(int((i + 1) % self.full_interval != 0)
                     for i in range(self.n_layers))

    @property
    def conv_dim(self) -> int:
        return (2 * self.lin_key_heads * self.lin_key_dim
                + self.lin_value_heads * self.lin_value_dim)

    @property
    def conv_lanes(self) -> int:
        """The width the convolution's channels are folded by where they
        are stored and convolved, ``[.., conv_dim // lanes, lanes]``: the
        widest that leaves every q, k and v head whole rows (128 at the
        published sizes, a head a row)."""
        return math.gcd(self.lin_key_dim, self.lin_value_dim)

    @property
    def value_dim(self) -> int:
        return self.lin_value_heads * self.lin_value_dim

    @staticmethod
    def tiny(**overrides) -> "Qwen3NextConfig":
        """A toy of the same shape for CPU tests."""
        base = dict(
            vocab_size=256, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2,
            head_dim=16, rotary_dim=4, lin_key_heads=2, lin_value_heads=4,
            lin_key_dim=16, lin_value_dim=16, d_expert=32, d_shared=32,
            n_experts=16, top_k=4, held=(0, 16), rope_theta=100.0,
            max_seq_len=128, dtype=jnp.float32,
        )
        base.update(overrides)
        return Qwen3NextConfig(**base)


# -- parameters ---------------------------------------------------------------


def decay_draw(key, heads: int):
    """``(A_log, dt_bias)`` [heads] float32 such that a head's decay a
    token, ``exp(-exp(A_log) softplus(a + dt_bias))``, spreads over about
    0.9 to 0.999 (half-lives of 7 to 700 tokens) for ``a`` of unit size:
    the published initial draw (``A`` uniform on 0-16) forgets within one
    token, and a state that is never carried would pass every comparison.
    ``-log(decay)`` is drawn log-uniform on 1e-3..1e-1 and put into
    ``A_log`` at ``softplus(dt_bias) = 0.5``."""
    rate = jnp.exp(jax.random.uniform(
        key, (heads,), jnp.float32, np.log(1e-3), np.log(1e-1)))
    dt_bias = jnp.full((heads,), np.log(np.expm1(0.5)), jnp.float32)
    return jnp.log(rate / 0.5), dt_bias


def init_params(key, config: Qwen3NextConfig) -> Dict[str, Any]:
    """Seeded weights in the pytree the functions below take. The norm
    scales lie 0.1 N(0,1) around their neutral value (0 for the ``1 + w``
    norms, 1 for the DeltaNet's output norm): left out, each shows."""
    d, h, kv, dh = (config.d_model, config.n_heads, config.n_kv_heads,
                    config.head_dim)
    keys = jax.random.split(key, config.n_layers + 2)
    s = 1.0 / np.sqrt(d)

    def normal(k, shape, scale, dtype=config.dtype):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    def swiglu(k, f):
        k = jax.random.split(k, 3)
        return {"w_gate": normal(k[0], (d, f), s),
                "w_up": normal(k[1], (d, f), s),
                "w_down": normal(k[2], (f, d), 1.0 / np.sqrt(f))}

    layers = []
    for index, kind in enumerate(config.layer_kinds):
        k = jax.random.split(keys[index], 16)
        layer = {"mixer_norm": normal(k[0], (d,), 0.1),
                 "mlp_norm": normal(k[1], (d,), 0.1)}
        if kind:
            a_log, dt_bias = decay_draw(k[2], config.lin_value_heads)
            layer.update(
                w_qkvz=normal(k[3], (d, config.conv_dim + config.value_dim),
                              s),
                w_ba=normal(k[4], (d, 2 * config.lin_value_heads), s),
                conv_w=normal(k[5], (config.conv_kernel, config.conv_dim),
                              0.5),
                A_log=a_log, dt_bias=dt_bias,
                gdn_norm=1.0 + normal(k[6], (config.lin_value_dim,), 0.1,
                                      jnp.float32).astype(config.dtype),
                w_out=normal(k[7], (config.value_dim, d),
                             1.0 / np.sqrt(config.value_dim)),
            )
        else:
            layer.update(
                q_norm=normal(k[2], (dh,), 0.1),
                k_norm=normal(k[3], (dh,), 0.1),
                wq=normal(k[4], (d, h, 2 * dh), s),
                wk=normal(k[5], (d, kv, dh), s),
                wv=normal(k[6], (d, kv, dh), s),
                wo=normal(k[7], (h, dh, d), 1.0 / np.sqrt(h * dh)),
            )
        f, count = config.d_expert, config.held[1]
        layer["router"] = normal(k[8], (d, config.n_experts), s)
        layer["experts"] = {
            "w_gate": normal(k[9], (count, d, f), s),
            "w_up": normal(k[10], (count, d, f), s),
            "w_down": normal(k[11], (count, f, d), 1.0 / np.sqrt(f)),
        }
        layer["shared"] = dict(swiglu(k[12], config.d_shared),
                               w_sg=normal(k[13], (d,), s))
        layers.append(layer)
    return {
        "embed": normal(keys[-2], (config.vocab_size, d), 1.0),
        "final_norm": normal(jax.random.fold_in(keys[-2], 1), (d,), 0.1),
        "lm_head": normal(keys[-1], (d, config.vocab_size), s),
        "layers": layers,
    }


# -- cache groups and pools ---------------------------------------------------


def cache_groups(config: Qwen3NextConfig):
    """[full group, state group]: group ``g`` holds the layers of kind
    ``g``, so a layer's tables are ``tables[config.layer_kinds[layer]]``."""
    kinds = config.layer_kinds
    return [
        CacheGroup(FULL, tuple(i for i, k in enumerate(kinds) if k == 0)),
        CacheGroup(STATE, tuple(i for i, k in enumerate(kinds) if k == 1)),
    ]


def init_pages(config: Qwen3NextConfig, num_blocks, block_size: int):
    """In layer order: a full layer's flat ``(k_pages, v_pages)`` of
    ``num_blocks[0]`` blocks, a DeltaNet layer's ``(state_pool,
    conv_pool)`` of ``num_blocks[1]`` SLOTS, the state in float32, a
    slot's convolution inputs ``[conv_kernel - 1, conv_dim // lanes,
    lanes]``, oldest first, each folded by ``config.conv_lanes``."""
    rows = block_size * config.n_kv_heads
    pages = []
    for kind in config.layer_kinds:
        if kind:
            pages.append((
                jnp.zeros((num_blocks[1], config.lin_value_heads,
                           config.lin_key_dim, config.lin_value_dim),
                          jnp.float32),
                jnp.zeros((num_blocks[1], config.conv_kernel - 1,
                           config.conv_dim // config.conv_lanes,
                           config.conv_lanes), config.dtype),
            ))
        else:
            pages.append(tuple(
                jnp.zeros((num_blocks[0], rows, config.head_dim),
                          config.dtype) for _ in "kv"))
    return pages


def kv_row_bytes(config: Qwen3NextConfig):
    """[(stored, counted)]: a cached token's K and V in one full layer,
    and ONE SLOT of one DeltaNet layer (its float32 state and its
    convolution inputs), which is what a sequence holds there whatever
    its length."""
    itemsize = jnp.dtype(config.dtype).itemsize
    token = 2 * config.n_kv_heads * config.head_dim * itemsize
    slot = (config.lin_value_heads * config.lin_key_dim
            * config.lin_value_dim * 4
            + (config.conv_kernel - 1) * config.conv_dim * itemsize)
    return [(token, token), (slot, slot)]


# -- building blocks ----------------------------------------------------------


def _unit(x, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps)


def _norm(x, w, eps):
    """``n(x) (1 + w)`` in float32, in ``x``'s type."""
    return (_unit(x, eps) * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _partial_rope(x, positions, config: Qwen3NextConfig):
    rot = config.rotary_dim
    return jnp.concatenate(
        [_rope(x[..., :rot], positions, config.rope_theta), x[..., rot:]],
        axis=-1)


def _attention_inputs(layer, normed, positions, config: Qwen3NextConfig):
    """``normed`` [T, d] at ``positions`` [T] -> q [T, H, D], k and v [T,
    KV, D] (q and k normed a head, then turned on their first
    ``rotary_dim`` sizes), and the output gate [T, H, D] in float32."""
    dh = config.head_dim
    both = jnp.einsum("td,dhk->thk", normed, layer["wq"])
    q, gate = both[..., :dh], both[..., dh:]
    k = jnp.einsum("td,dhk->thk", normed, layer["wk"])
    v = jnp.einsum("td,dhk->thk", normed, layer["wv"])
    q = _partial_rope(_norm(q, layer["q_norm"], config.norm_eps),
                      positions, config)
    k = _partial_rope(_norm(k, layer["k_norm"], config.norm_eps),
                      positions, config)
    return q, k, v, jax.nn.sigmoid(gate.astype(jnp.float32))


def _join_attention(layer, out, gate):
    out = (out.astype(jnp.float32) * gate).astype(out.dtype)
    return jnp.einsum("thk,hkd->td", out, layer["wo"])


def _delta_inputs(layer, normed, config: Qwen3NextConfig):
    """``normed`` [T, d] -> the convolution's input [T, channels //
    lanes, lanes] (q, k and v before it, folded by ``config.conv_lanes``),
    z [T, Hv, Dv], beta and g [T, Hv] in float32."""
    heads = config.lin_value_heads
    mixed = jnp.dot(normed, layer["w_qkvz"])
    ba = jnp.dot(normed, layer["w_ba"],
                 preferred_element_type=jnp.float32)
    z = mixed[:, config.conv_dim:].reshape(-1, heads, config.lin_value_dim)
    beta = jax.nn.sigmoid(ba[:, :heads])
    g = -jnp.exp(layer["A_log"]) * jax.nn.softplus(
        ba[:, heads:] + layer["dt_bias"])
    inputs = mixed[:, :config.conv_dim].reshape(
        -1, config.conv_dim // config.conv_lanes, config.conv_lanes)
    return inputs, z, beta, g


def _convolved(layer, taps, dtype):
    """``taps``: the convolution's ``kernel`` inputs [T, channels // lanes,
    lanes] each, tap ``j`` the input ``kernel - 1 - j`` tokens back: silu
    of the depthwise sum, oldest first, float32 inside."""
    weights = layer["conv_w"].astype(jnp.float32).reshape(
        -1, *taps[0].shape[1:])
    summed = sum(tap.astype(jnp.float32) * weights[j]
                 for j, tap in enumerate(taps))
    return jax.nn.silu(summed).astype(dtype)


def _delta_heads(conv, config: Qwen3NextConfig):
    """The convolution's output [T, channels // lanes, lanes] -> q and k
    [T, Hk, Dk] L2-normalised (q scaled by ``Dk ** -0.5``) and v [T, Hv,
    Dv], all float32: whole rows each, and where ``lanes`` is the head
    size a head a row."""
    hk, dk = config.lin_key_heads, config.lin_key_dim
    rows = hk * dk // config.conv_lanes
    conv = conv.astype(jnp.float32)
    q = conv[:, :rows].reshape(-1, hk, dk)
    k = conv[:, rows:2 * rows].reshape(-1, hk, dk)
    v = conv[:, 2 * rows:].reshape(
        -1, config.lin_value_heads, config.lin_value_dim)
    unit = lambda x: x * jax.lax.rsqrt(
        jnp.square(x).sum(axis=-1, keepdims=True) + L2_EPS)
    return unit(q) * dk ** -0.5, unit(k), v


def _join_delta(layer, out, z, config: Qwen3NextConfig):
    """The rule's output [T, Hv, Dv] float32 through its gated norm ``w
    n(o) silu(z)`` a head and ``w_out``."""
    gated = (_unit(out, config.norm_eps)
             * layer["gdn_norm"].astype(jnp.float32)
             * jax.nn.silu(z.astype(jnp.float32)))
    return jnp.dot(gated.reshape(out.shape[0], -1).astype(z.dtype),
                   layer["w_out"])


def _ffn(layer, x, config: Qwen3NextConfig, kernel: str):
    """x [T, d] -> (x + MoE(N(x)), the expert layer's counters).
    ``kernel``: the load-time choice's name."""
    normed = _norm(x, layer["mlp_norm"], config.norm_eps)
    ids, weights = moe.route(
        normed, layer["router"], None, config.top_k, score="softmax")
    out, counters = moe.expert_layer(
        normed, ids, weights, layer["experts"], config.held,
        kernel=kernel, shared=layer["shared"])
    return x + out.astype(x.dtype), counters


def _head(params, x, config: Qwen3NextConfig):
    x = _norm(x, params["final_norm"], config.norm_eps)
    return jnp.einsum("td,dv->tv", x, params["lm_head"]).astype(jnp.float32)


# -- the engine's programs ----------------------------------------------------


def prefill_into_pages(params, tokens, page_tables, pages, last_index,
                       config: Qwen3NextConfig, kernels: Kernels):
    """Prefill one prompt. ``tokens`` [1, L] (padded to its bucket),
    ``page_tables`` [2, max_blocks]: row 0 the full group's blocks
    (positions past ``last_index`` go to the trash block), row 1 the
    sequence's slot in column 0. A full layer scatters its K/V and
    attends on the prompt in plain XLA; a DeltaNet layer runs the chunked
    rule with the padding masked (``beta = 0``, ``g = 0`` past
    ``last_index``) and writes the final state and the last convolution
    inputs up to ``last_index`` (oldest first, zeros before the prompt's
    start) WHOLE into the slot (zeros into the trash slot), under every
    kernel choice. Returns (logits of the last token
    [1, V], pages)."""
    length = tokens.shape[1]
    kv = config.n_kv_heads
    taps = config.conv_kernel
    positions = jnp.arange(length)
    real = positions <= last_index
    slot = page_tables[1, 0]
    kept = slot != gated_delta.TRASH_SLOT
    x = params["embed"][tokens[0]]
    new_pages = []
    for layer, pools, kind in zip(params["layers"], pages,
                                  config.layer_kinds):
        normed = _norm(x, layer["mixer_norm"], config.norm_eps)
        if kind:
            state_pool, conv_pool = pools
            inputs, z, beta, g = _delta_inputs(layer, normed, config)
            padded = jnp.pad(inputs, ((taps - 1, 0), (0, 0), (0, 0)))
            conv = _convolved(
                layer, [padded[j:j + length] for j in range(taps)], x.dtype)
            q, k, v = _delta_heads(conv, config)
            out, state = gated_delta.chunked_gated_delta(
                q, k, v, jnp.where(real[:, None], g, 0.0),
                jnp.where(real[:, None], beta, 0.0))
            last_inputs = jax.lax.dynamic_slice_in_dim(
                padded, last_index + 1, taps - 1)
            new_pages.append((
                state_pool.at[slot].set(jnp.where(kept, state, 0.0)),
                conv_pool.at[slot].set(
                    jnp.where(kept, last_inputs, 0).astype(conv_pool.dtype)),
            ))
            x = x + _join_delta(layer, out, z, config)
        else:
            k_pages, v_pages = pools
            block_size = k_pages.shape[1] // kv
            phys = jnp.where(real, page_tables[0, positions // block_size], 0)
            off = jnp.where(real, positions % block_size, 0)
            q, k, v, gate = _attention_inputs(layer, normed, positions, config)
            new_pages.append((_write(k_pages, phys, off, k, kv),
                              _write(v_pages, phys, off, v, kv)))
            out = _prefill_attention(
                q, k, v, None, None, config.head_dim ** -0.5)
            x = x + _join_attention(layer, out, gate)
        x, _ = _ffn(layer, x, config, kernels.name)
    last = jax.lax.dynamic_slice_in_dim(x, last_index, 1)
    return _head(params, last, config), new_pages


def decode_step_paged(params, tokens, positions, page_tables, pages,
                      config: Qwen3NextConfig, kernels: Kernels):
    """One decode step for ``B`` lanes. ``page_tables`` [2, B, NB]: row 0
    the full group's, row 1 each lane's slot in column 0 (a padding lane
    the trash slot). A full layer writes the token's K/V and attends
    through ``kernels.attn``; a DeltaNet layer shifts the lane's
    convolution inputs by one input (the taps are the slot's three and
    the new one), and turns the lane's state in its slot
    (``gated_delta.gated_delta_step``, the kernel or the gather and
    scatter as ``kernels.name`` says). Returns (logits [B, V], pages,
    counters int32: :data:`COUNTERS`)."""
    lanes = tokens.shape[0]
    kv = config.n_kv_heads
    slots = page_tables[1, :, 0]
    live = slots != gated_delta.TRASH_SLOT
    x = params["embed"][tokens]
    counters = jnp.zeros(len(moe.COUNTERS), jnp.int32)
    updates = jnp.int32(0)
    new_pages = []
    for layer, pools, kind in zip(params["layers"], pages,
                                  config.layer_kinds):
        normed = _norm(x, layer["mixer_norm"], config.norm_eps)
        if kind:
            state_pool, conv_pool = pools
            inputs, z, beta, g = _delta_inputs(layer, normed, config)
            window = jnp.concatenate(
                [conv_pool[slots], inputs[:, None]], axis=1)  # [B, taps, ..]
            conv = _convolved(
                layer, [window[:, j] for j in range(config.conv_kernel)],
                x.dtype)
            conv_pool = conv_pool.at[slots].set(
                jnp.where(live[:, None, None, None], window[:, 1:], 0))
            q, k, v = _delta_heads(conv, config)
            out, state_pool = gated_delta.gated_delta_step(
                q, k, v, g, beta, slots, state_pool, kernel=kernels.name)
            new_pages.append((state_pool, conv_pool))
            x = x + _join_delta(layer, out, z, config)
            updates = updates + live.sum(dtype=jnp.int32)
        else:
            k_pages, v_pages = pools
            block_size = k_pages.shape[1] // kv
            phys = page_tables[0, jnp.arange(lanes), positions // block_size]
            off = positions % block_size
            q, k, v, gate = _attention_inputs(layer, normed, positions, config)
            # scatter this step's K/V, THEN attend: the current position's
            # entry must be visible to its own attention
            k_pages = _write(k_pages, phys, off, k, kv)
            v_pages = _write(v_pages, phys, off, v, kv)
            new_pages.append((k_pages, v_pages))
            out = kernels.attn(
                q[:, None], k_pages, v_pages, page_tables[0],
                positions[:, None], kv_heads=kv)[:, 0]
            x = x + _join_attention(layer, out, gate)
        x, counted = _ffn(layer, x, config, kernels.name)
        counters = counters + counted
    counters = jnp.concatenate([counters, updates[None]])
    return _head(params, x, config), new_pages, counters


ENGINE_MODEL = EngineModel(
    name="qwen3_next",
    init_params=init_params,
    cache_groups=cache_groups,
    init_pages=init_pages,
    prefill=prefill_into_pages,
    decode=decode_step_paged,
    heads=lambda config: (config.n_heads, config.n_kv_heads),
    kv_row_bytes=kv_row_bytes,
    step_counters=COUNTERS,
)
