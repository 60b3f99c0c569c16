"""Trinity-Mini's decoder (``model_type: afmoe``) on the paged engine: a
2,048-token window beside full attention, gated QK-normed heads, many
small routed experts with a shared one, of which this chip holds a share.

From the model's public ``config.json`` (arcee-ai/Trinity-Mini) and, for
what it does not spell out, the published ``modeling_afmoe.py`` as
recalled (each such item is under ``assumed`` in
``benchmark/configs/trinity_mini/config.json``). With ``x`` the residual
stream and ``N`` an RMSNorm with a learned scale, a layer is::

    a     = N_in(x)
    q,k,v = a@wq, a@wk, a@wv;   q = N_q(q), k = N_k(k)    # per head
    q,k   = rope(q, k) in a window layer; a full layer turns nothing
    o     = softmax(q k^T / sqrt(D)) v                    # causal; window
    o     = o * sigmoid(a @ wg)                           # per element
    x     = x + N_post_attn(o @ wo)
    m     = N_pre_mlp(x)
    f     = SwiGLU(m)                          in the leading dense layers
    f     = SwiGLU_shared(m) + sum_held w_e SwiGLU_e(m)   after them
    x     = x + N_post_mlp(f)

and the embedding is scaled by ``sqrt(d_model)`` (``mup_enabled``). It
differs from ``models/mimo_v2.py``'s block in arithmetic, not numbers:
the two head norms, rope in window layers only, the output gate, four
norms a layer (a sublayer's output is normed before it joins the
residual), the embedding's scale, several leading dense layers, a
shared expert and ``route_scale`` (``models/moe.py``).

Two cache groups (``models/engine_model.py``), full then window
(``mimo_v2.cache_groups``, which reads ``layer_kinds`` and ``window``
alone), so a layer's tables are ``tables[config.layer_kinds[layer]]``.
Pools are flat, ``[N, bs*KV, D]`` (``paged_attention._pool_shape``): at
4 KV heads a ``[.., 4, D]`` pool would be padded to 8 sublanes in HBM.
Rotary pairs are (2i, 2i+1), the program's layout throughout
(``llama._rope``).
"""

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from client_tpu.models import moe
from client_tpu.models.engine_model import EngineModel, Kernels
from client_tpu.models.llama import _mlp_block, _rope, rms_norm
from client_tpu.models.mimo_v2 import (
    _prefill_attention, _write, cache_groups,
)


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    d_model: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    layer_kinds: Tuple[int, ...] = (1, 1, 1, 0)  # 1 = window
    n_dense_layers: int = 2
    d_ff: int = 6144
    d_expert: int = 1024
    n_experts: int = 128
    top_k: int = 8
    held: Tuple[int, int] = (0, 128)
    n_shared_experts: int = 1
    route_scale: float = 2.826
    window: int = 2048
    rope_theta: float = 1e4
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.n_experts):
            raise ValueError(
                f"held={self.held} is not a share of {self.n_experts} experts")
        if not 0 <= self.n_dense_layers <= len(self.layer_kinds):
            raise ValueError(
                f"n_dense_layers={self.n_dense_layers} of "
                f"{len(self.layer_kinds)} layers")

    @property
    def n_layers(self) -> int:
        return len(self.layer_kinds)

    @property
    def embed_scale(self) -> float:
        return float(np.sqrt(self.d_model))

    @staticmethod
    def tiny(**overrides) -> "AfmoeConfig":
        """A toy of the same shape for CPU tests: a window of 24 over
        blocks of 8 wraps its ring within 40 tokens."""
        base = dict(
            vocab_size=256, d_model=64, n_heads=8, n_kv_heads=2,
            head_dim=16, layer_kinds=(1, 1, 1, 0, 1, 0), n_dense_layers=2,
            d_ff=128, d_expert=32, n_experts=16, top_k=4, held=(0, 16),
            window=24, max_seq_len=128, dtype=jnp.float32,
        )
        base.update(overrides)
        return AfmoeConfig(**base)


# -- parameters ---------------------------------------------------------------


def init_params(key, config: AfmoeConfig) -> Dict[str, Any]:
    """Seeded weights in the pytree the functions below take. The norm
    scales and the router's bias are of a size that shows: left out,
    each changes the logits or the experts chosen."""
    d, h, kv, dh = (config.d_model, config.n_heads, config.n_kv_heads,
                    config.head_dim)
    keys = jax.random.split(key, config.n_layers + 2)

    def normal(k, shape, scale, dtype=config.dtype):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    def scale_of(k, size):
        return (1.0 + normal(k, (size,), 0.1, jnp.float32)).astype(config.dtype)

    def swiglu(k, f):
        k = jax.random.split(k, 3)
        return {"w_gate": normal(k[0], (d, f), s),
                "w_up": normal(k[1], (d, f), s),
                "w_down": normal(k[2], (f, d), 1.0 / np.sqrt(f))}

    s = 1.0 / np.sqrt(d)
    layers = []
    for index in range(config.n_layers):
        k = jax.random.split(keys[index], 16)
        layer = {
            "attn_norm": scale_of(k[0], d),
            "post_attn_norm": scale_of(k[1], d),
            "mlp_norm": scale_of(k[2], d),
            "post_mlp_norm": scale_of(k[3], d),
            "q_norm": scale_of(k[4], dh),
            "k_norm": scale_of(k[5], dh),
            "wq": normal(k[6], (d, h, dh), s),
            "wk": normal(k[7], (d, kv, dh), s),
            "wv": normal(k[8], (d, kv, dh), s),
            "wg": normal(k[9], (d, h, dh), s),
            "wo": normal(k[10], (h, dh, d), s),
        }
        if index < config.n_dense_layers:
            layer.update(swiglu(k[11], config.d_ff))
        else:
            f, count = config.d_expert, config.held[1]
            layer["router"] = normal(k[11], (d, config.n_experts), s)
            layer["router_bias"] = normal(
                k[12], (config.n_experts,), 0.02, jnp.float32)
            layer["experts"] = {
                "w_gate": normal(k[13], (count, d, f), s),
                "w_up": normal(k[14], (count, d, f), s),
                "w_down": normal(k[15], (count, f, d), 1.0 / np.sqrt(f)),
            }
            layer["shared"] = swiglu(
                jax.random.fold_in(k[11], 1), f * config.n_shared_experts)
        layers.append(layer)
    return {
        "embed": normal(keys[-2], (config.vocab_size, d), s),
        "final_norm": scale_of(jax.random.fold_in(keys[-2], 1), d),
        "lm_head": normal(keys[-1], (d, config.vocab_size), s),
        "layers": layers,
    }


# -- cache groups and pools ---------------------------------------------------


def init_pages(config: AfmoeConfig, num_blocks, block_size: int):
    """One flat (k_pages, v_pages) pair a layer, in layer order, each in
    its group's pool size ``num_blocks[kind]``."""
    rows = block_size * config.n_kv_heads
    return [
        tuple(jnp.zeros((num_blocks[kind], rows, config.head_dim),
                        config.dtype) for _ in "kv")
        for kind in config.layer_kinds
    ]


# -- building blocks ----------------------------------------------------------


def _attention_inputs(layer, normed, positions, config: AfmoeConfig,
                      index: int):
    """``normed`` [T, d] at ``positions`` [T] -> q [T, H, D], k and v [T,
    KV, D] (q and k normed per head, then turned in a window layer
    only), and the output gate [T, H, D] in float32."""
    q = jnp.einsum("td,dhk->thk", normed, layer["wq"])
    k = jnp.einsum("td,dhk->thk", normed, layer["wk"])
    v = jnp.einsum("td,dhk->thk", normed, layer["wv"])
    q = rms_norm(q, layer["q_norm"], config.norm_eps)
    k = rms_norm(k, layer["k_norm"], config.norm_eps)
    if config.layer_kinds[index]:
        q = _rope(q, positions, config.rope_theta)
        k = _rope(k, positions, config.rope_theta)
    gate = jax.nn.sigmoid(jnp.einsum(
        "td,dhk->thk", normed, layer["wg"],
        preferred_element_type=jnp.float32))
    return q, k, v, gate


def _join_attention(layer, x, out, gate, config: AfmoeConfig):
    """The heads' output [T, H, D] through its gate and ``wo``, normed,
    onto the residual stream."""
    out = (out.astype(jnp.float32) * gate).astype(x.dtype)
    out = jnp.einsum("thk,hkd->td", out, layer["wo"])
    return x + rms_norm(out, layer["post_attn_norm"], config.norm_eps)


def _ffn(layer, x, config: AfmoeConfig, index: int, kernel: str):
    """x [T, d] -> (x + N_post(FFN(N_pre(x))), the expert layer's
    counters or None). ``kernel``: the load-time choice's name."""
    normed = rms_norm(x, layer["mlp_norm"], config.norm_eps)
    if index < config.n_dense_layers:
        out, counters = _mlp_block(layer, normed[None])[0], None
    else:
        ids, weights = moe.route(
            normed, layer["router"], layer["router_bias"], config.top_k,
            scale=config.route_scale)
        out, counters = moe.expert_layer(
            normed, ids, weights, layer["experts"], config.held,
            kernel=kernel, shared=layer["shared"])
    out = rms_norm(out, layer["post_mlp_norm"], config.norm_eps)
    return x + out.astype(x.dtype), counters


def _embed(params, tokens, config: AfmoeConfig):
    return (params["embed"][tokens].astype(jnp.float32)
            * config.embed_scale).astype(config.dtype)


def _head(params, x, config: AfmoeConfig):
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    return jnp.einsum("td,dv->tv", x, params["lm_head"]).astype(jnp.float32)


# -- the engine's programs ----------------------------------------------------


def prefill_into_pages(params, tokens, page_tables, pages, last_index,
                       config: AfmoeConfig, kernels: Kernels):
    """Prefill one prompt, scattering each layer's K/V through its
    group's table. ``tokens`` [1, L] (padded to its bucket),
    ``page_tables`` [2, max_blocks] (positions past ``last_index`` and a
    window group's blocks behind the window go to the trash block);
    ``kernels`` the load-time choice (its name picks the expert layer's
    path; the prompt's attention on itself is plain XLA under every
    choice, a chunk of queries at a time). Returns (logits of the last
    token [1, V], pages)."""
    length = tokens.shape[1]
    kv = config.n_kv_heads
    block_size = pages[0][0].shape[1] // kv
    positions = jnp.arange(length)
    real = positions <= last_index
    phys = jnp.where(real[None], page_tables[:, positions // block_size], 0)
    off = jnp.where(real, positions % block_size, 0)
    x = _embed(params, tokens[0], config)
    new_pages = []
    for index, (layer, (k_pages, v_pages)) in enumerate(
            zip(params["layers"], pages)):
        kind = config.layer_kinds[index]
        normed = rms_norm(x, layer["attn_norm"], config.norm_eps)
        q, k, v, gate = _attention_inputs(
            layer, normed, positions, config, index)
        new_pages.append((_write(k_pages, phys[kind], off, k, kv),
                          _write(v_pages, phys[kind], off, v, kv)))
        out = _prefill_attention(
            q, k, v, config.window if kind else None, None,
            config.head_dim ** -0.5)
        x = _join_attention(layer, x, out, gate, config)
        x, _ = _ffn(layer, x, config, index, kernels.name)
    last = jax.lax.dynamic_slice_in_dim(x, last_index, 1)
    return _head(params, last, config), new_pages


def decode_step_paged(params, tokens, positions, page_tables, pages,
                      config: AfmoeConfig, kernels: Kernels):
    """One decode step for ``B`` lanes. ``page_tables`` [2, B, NB]: row
    0 the full group's, row 1 the window group's. Writes each token's
    K/V into its sequence's current block of each group, then attends
    through ``kernels.attn`` (its ``T = 1`` case) and runs the experts
    on the path ``kernels.name`` says. Returns (logits [B, V], pages,
    counters int32: ``moe.COUNTERS`` summed over the expert layers)."""
    lanes = tokens.shape[0]
    kv = config.n_kv_heads
    block_size = pages[0][0].shape[1] // kv
    phys = page_tables[:, jnp.arange(lanes), positions // block_size]
    off = positions % block_size
    x = _embed(params, tokens, config)
    counters = jnp.zeros(len(moe.COUNTERS), jnp.int32)
    new_pages = []
    for index, (layer, (k_pages, v_pages)) in enumerate(
            zip(params["layers"], pages)):
        kind = config.layer_kinds[index]
        normed = rms_norm(x, layer["attn_norm"], config.norm_eps)
        q, k, v, gate = _attention_inputs(
            layer, normed, positions, config, index)
        # scatter this step's K/V, THEN attend: the current position's
        # entry must be visible to its own attention
        k_pages = _write(k_pages, phys[kind], off, k, kv)
        v_pages = _write(v_pages, phys[kind], off, v, kv)
        new_pages.append((k_pages, v_pages))
        out = kernels.attn(
            q[:, None], k_pages, v_pages, page_tables[kind],
            positions[:, None],
            window=config.window if kind else None, kv_heads=kv)[:, 0]
        x = _join_attention(layer, x, out, gate, config)
        x, counted = _ffn(layer, x, config, index, kernels.name)
        if counted is not None:
            counters = counters + counted
    return _head(params, x, config), new_pages, counters


ENGINE_MODEL = EngineModel(
    name="afmoe",
    init_params=init_params,
    cache_groups=cache_groups,
    init_pages=init_pages,
    prefill=prefill_into_pages,
    decode=decode_step_paged,
    step_counters=moe.COUNTERS,
)
