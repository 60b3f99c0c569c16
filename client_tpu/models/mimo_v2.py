"""MiMo-V2-Flash's decoder on the paged engine: window and full attention
layers side by side, sparse experts of which this chip holds a share.

From the model's public ``config.json`` (XiaomiMiMo/MiMo-V2-Flash):
pre-norm residual blocks, RMSNorm, untied head, no biases. A layer's
attention is *full* (``hybrid_layer_pattern`` 0: ``n_kv_heads`` KV
heads, rope theta ``rope_theta``) or *window* (1: ``swa_n_kv_heads`` KV
heads, theta ``swa_rope_theta``, a query sees the ``window`` positions
up to its own, and one learned sink logit a query head joins the
softmax's denominator). Query/key heads are ``head_dim`` wide with rope
on the first ``rotary_dim`` sizes only; value heads ``v_head_dim``; the
heads' output is scaled by ``value_scale`` before ``wo``. A layer's FFN
is a dense SwiGLU (``moe_layers`` 0) or ``n_experts`` routed experts,
``top_k`` a token (``models/moe.py``), of which ``held = (first,
count)`` live here.

Two cache groups (``models/engine_model.py``): the full layers' pools
and the window layers' pools have their own shapes and their own page
tables; the engine hands a window layer a table in which every block
wholly behind the window is the trash block. Pools are flat, ``[N,
bs*KV, D]`` (``paged_attention._pool_shape``), and K rows are padded
with zeros from ``head_dim`` to a whole number of 128 lanes (192 ->
256), which is what Mosaic can copy; the softmax's scale stays
``head_dim ** -0.5``.

Rotary pairs are (2i, 2i+1), the program's layout throughout
(``llama._rope``), a permutation of HF's columns. The three
multi-token-prediction layers of the release are not in ``config.json``
and not here.
"""

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from client_tpu.models import moe, paged_attention
from client_tpu.models.engine_model import (
    FULL, WINDOW, CacheGroup, EngineModel, Kernels,
)
from client_tpu.models.llama import _mlp_block, _rope, rms_norm

#: queries a prefill attends with at once: bounds the scores it holds
#: live to [heads, 256, keys] whatever the prompt's length
_PREFILL_CHUNK = 256


@dataclasses.dataclass(frozen=True)
class MimoV2Config:
    vocab_size: int = 152576
    d_model: int = 4096
    n_heads: int = 64
    n_kv_heads: int = 4
    swa_n_kv_heads: int = 8
    head_dim: int = 192
    v_head_dim: int = 128
    rotary_dim: int = 64
    layer_kinds: Tuple[int, ...] = (0, 1, 1, 1, 1, 0)  # 1 = window
    moe_layers: Tuple[int, ...] = (0, 1, 1, 1, 1, 1)   # 1 = experts
    d_ff: int = 16384
    d_expert: int = 2048
    n_experts: int = 256
    top_k: int = 8
    held: Tuple[int, int] = (0, 256)
    window: int = 128
    rope_theta: float = 5e6
    swa_rope_theta: float = 1e4
    value_scale: float = 0.707
    norm_eps: float = 1e-5
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        first, count = self.held
        if len(self.layer_kinds) != len(self.moe_layers):
            raise ValueError("layer_kinds and moe_layers differ in length")
        if not (0 <= first and count >= 1
                and first + count <= self.n_experts):
            raise ValueError(
                f"held={self.held} is not a share of {self.n_experts} experts")

    @property
    def n_layers(self) -> int:
        return len(self.layer_kinds)

    @property
    def k_row(self) -> int:
        """A K row in the pool: ``head_dim`` padded to 128 lanes."""
        return -(-self.head_dim // 128) * 128

    def kv_heads(self, layer: int) -> int:
        return self.swa_n_kv_heads if self.layer_kinds[layer] else self.n_kv_heads

    @staticmethod
    def tiny(**overrides) -> "MimoV2Config":
        """A toy of the same shape for CPU tests: a window of 24 over
        blocks of 8 wraps its ring within 40 tokens."""
        base = dict(
            vocab_size=256, d_model=64, n_heads=8, n_kv_heads=2,
            swa_n_kv_heads=4, head_dim=24, v_head_dim=16, rotary_dim=8,
            layer_kinds=(0, 1, 1, 0), moe_layers=(0, 1, 1, 1), d_ff=128,
            d_expert=32, n_experts=16, top_k=4, held=(0, 16), window=24,
            max_seq_len=128, dtype=jnp.float32,
        )
        base.update(overrides)
        return MimoV2Config(**base)


# -- parameters ---------------------------------------------------------------


def init_params(key, config: MimoV2Config) -> Dict[str, Any]:
    """Seeded weights in the pytree the functions below take. The router
    bias and the sinks are of a size that shows: left out, either changes
    the experts chosen or the logits."""
    d, h, dk, dv = (config.d_model, config.n_heads, config.head_dim,
                    config.v_head_dim)
    keys = jax.random.split(key, config.n_layers + 2)

    def normal(k, shape, scale, dtype=config.dtype):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    s = 1.0 / np.sqrt(d)
    layers = []
    for index in range(config.n_layers):
        k = jax.random.split(keys[index], 12)
        kv = config.kv_heads(index)
        layer = {
            "attn_norm": jnp.ones((d,), config.dtype),
            "mlp_norm": jnp.ones((d,), config.dtype),
            "wq": normal(k[0], (d, h, dk), s),
            "wk": normal(k[1], (d, kv, dk), s),
            "wv": normal(k[2], (d, kv, dv), s),
            "wo": normal(k[3], (h, dv, d), s / np.sqrt(2 * config.n_layers)),
        }
        if config.layer_kinds[index]:
            layer["sink"] = normal(k[4], (h,), 2.0, jnp.float32)
        if config.moe_layers[index]:
            f, count = config.d_expert, config.held[1]
            layer["router"] = normal(k[5], (d, config.n_experts), s)
            layer["router_bias"] = normal(
                k[6], (config.n_experts,), 0.02, jnp.float32)
            layer["experts"] = {
                "w_gate": normal(k[7], (count, d, f), s),
                "w_up": normal(k[8], (count, d, f), s),
                "w_down": normal(k[9], (count, f, d), 1.0 / np.sqrt(f)),
            }
        else:
            f = config.d_ff
            layer.update(
                w_gate=normal(k[7], (d, f), s), w_up=normal(k[8], (d, f), s),
                w_down=normal(k[9], (f, d), 1.0 / np.sqrt(f)))
        layers.append(layer)
    return {
        "embed": normal(keys[-2], (config.vocab_size, d), 1.0),
        "final_norm": jnp.ones((d,), config.dtype),
        "lm_head": normal(keys[-1], (d, config.vocab_size), s),
        "layers": layers,
    }


# -- cache groups and pools ---------------------------------------------------


def cache_groups(config: MimoV2Config):
    """[full group, window group]: group ``g`` holds the layers of kind
    ``g``, so a layer's tables are ``tables[config.layer_kinds[layer]]``."""
    by_kind = [
        tuple(i for i, kind in enumerate(config.layer_kinds) if kind == g)
        for g in (0, 1)
    ]
    return [CacheGroup(FULL, by_kind[0]),
            CacheGroup(WINDOW, by_kind[1], window=config.window)]


def init_pages(config: MimoV2Config, num_blocks, block_size: int):
    """One flat (k_pages, v_pages) pair a layer, in layer order, each in
    its group's pool size ``num_blocks[kind]``."""
    pages = []
    for index, kind in enumerate(config.layer_kinds):
        rows = block_size * config.kv_heads(index)
        pages.append((
            jnp.zeros((num_blocks[kind], rows, config.k_row), config.dtype),
            jnp.zeros((num_blocks[kind], rows, config.v_head_dim),
                      config.dtype),
        ))
    return pages


def kv_row_bytes(config: MimoV2Config):
    """(stored, counted) bytes a cached token takes in a layer of each
    group: K rows are stored ``k_row`` wide and hold ``head_dim``."""
    size = jnp.dtype(config.dtype).itemsize
    out = []
    for kind in (0, 1):
        index = next((i for i, k in enumerate(config.layer_kinds)
                      if k == kind), 0)
        kv = config.kv_heads(index)
        out.append((kv * (config.k_row + config.v_head_dim) * size,
                    kv * (config.head_dim + config.v_head_dim) * size))
    return out


# -- building blocks ----------------------------------------------------------


def _qkv(layer, normed, positions, config: MimoV2Config, index: int):
    """``normed`` [T, d] at ``positions`` [T] -> q [T, H, k_row], k [T,
    KV, k_row] (rope on the first ``rotary_dim`` sizes, zeros past
    ``head_dim``), v [T, KV, v_head_dim]."""
    theta = (config.swa_rope_theta if config.layer_kinds[index]
             else config.rope_theta)
    rot, pad = config.rotary_dim, config.k_row - config.head_dim

    def rotated(x):
        turned = _rope(x[..., :rot], positions, theta)
        return jnp.pad(jnp.concatenate([turned, x[..., rot:]], axis=-1),
                       ((0, 0), (0, 0), (0, pad)))

    q = jnp.einsum("td,dhk->thk", normed, layer["wq"])
    k = jnp.einsum("td,dhk->thk", normed, layer["wk"])
    v = jnp.einsum("td,dhk->thk", normed, layer["wv"])
    return rotated(q), rotated(k), v


def _write(pool, phys, off, rows, kv: int):
    """Rows [T, KV, D] of T tokens into a flat pool at (block ``phys``,
    slot ``off``): pool row ``off * KV + head``."""
    at = off[:, None] * kv + jnp.arange(kv)[None, :]
    return pool.at[phys[:, None], at].set(rows)


def _ffn(layer, x, config: MimoV2Config, index: int, kernel: str):
    """x [T, d] -> (x + FFN(norm(x)), the expert layer's counters or
    None). ``kernel``: the load-time choice's name."""
    normed = rms_norm(x, layer["mlp_norm"], config.norm_eps)
    if not config.moe_layers[index]:
        return x + _mlp_block(layer, normed[None])[0], None
    ids, weights = moe.route(
        normed, layer["router"], layer["router_bias"], config.top_k)
    out, counters = moe.expert_layer(
        normed, ids, weights, layer["experts"], config.held, kernel=kernel)
    return x + out.astype(x.dtype), counters


def _head(params, x, config: MimoV2Config):
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    return jnp.einsum("td,dv->tv", x, params["lm_head"]).astype(jnp.float32)


def _prefill_attention(q, k, v, window, sink, scale):
    """Causal attention of a prompt on itself, a chunk of queries at a
    time: q [L, H, D], k [L, KV, D], v [L, KV, Dv] -> [L, H, Dv]. A
    window layer's chunk meets only the keys its window reaches."""
    length, heads, _ = q.shape
    kv = k.shape[1]
    chunk = min(length, _PREFILL_CHUNK)
    qg = q.reshape(length // chunk, chunk, kv, heads // kv, -1)
    if window is None:
        reach, keys, values = 0, k, v
    else:
        # the keys before a chunk that its first query's window reaches
        reach = min(-(-(window - 1) // 8) * 8, length)
        keys = jnp.pad(k, ((reach, 0), (0, 0), (0, 0)))
        values = jnp.pad(v, ((reach, 0), (0, 0), (0, 0)))
    sink = None if sink is None else sink.reshape(kv, heads // kv, 1)

    def one(args):
        index, q_chunk = args
        q_pos = index * chunk + jnp.arange(chunk)
        if window is None:
            k_chunk, v_chunk, k_pos = keys, values, jnp.arange(length)
        else:
            start = index * chunk  # in the padded keys: position - reach
            k_chunk = jax.lax.dynamic_slice_in_dim(keys, start, chunk + reach)
            v_chunk = jax.lax.dynamic_slice_in_dim(values, start, chunk + reach)
            k_pos = start - reach + jnp.arange(chunk + reach)
        scores = jnp.einsum("ckgd,skd->kgcs", q_chunk, k_chunk,
                            preferred_element_type=jnp.float32) * scale
        seen = (k_pos[None, :] <= q_pos[:, None]) & (k_pos[None, :] >= 0)
        if window is not None:
            seen &= k_pos[None, :] > q_pos[:, None] - window
        scores = jnp.where(seen[None, None], scores, paged_attention.NEG_INF)
        weights = paged_attention._softmax_with_sink(scores, sink)
        return jnp.einsum("kgcs,skd->ckgd", weights.astype(v.dtype), v_chunk)

    out = jax.lax.map(one, (jnp.arange(length // chunk), qg))
    return out.reshape(length, heads, -1)


# -- the engine's programs ----------------------------------------------------


def prefill_into_pages(params, tokens, page_tables, pages, last_index,
                       config: MimoV2Config, kernels: Kernels):
    """Prefill one prompt, scattering each layer's K/V through its
    group's table. ``tokens`` [1, L] (padded to its bucket),
    ``page_tables`` [2, max_blocks] (positions past ``last_index`` and a
    window group's blocks behind the window go to the trash block);
    ``kernels`` the load-time choice (its name picks the expert layer's
    path; the prompt's attention on itself is plain XLA under every
    choice). Returns (logits of the last token [1, V], pages)."""
    length = tokens.shape[1]
    block_size = pages[0][0].shape[1] // config.kv_heads(0)
    positions = jnp.arange(length)
    real = positions <= last_index
    phys = jnp.where(real[None], page_tables[:, positions // block_size], 0)
    off = jnp.where(real, positions % block_size, 0)
    x = params["embed"][tokens[0]].astype(config.dtype)
    new_pages = []
    for index, (layer, (k_pages, v_pages)) in enumerate(
            zip(params["layers"], pages)):
        kind, kv = config.layer_kinds[index], config.kv_heads(index)
        normed = rms_norm(x, layer["attn_norm"], config.norm_eps)
        q, k, v = _qkv(layer, normed, positions, config, index)
        new_pages.append((_write(k_pages, phys[kind], off, k, kv),
                          _write(v_pages, phys[kind], off, v, kv)))
        out = _prefill_attention(
            q, k, v, config.window if kind else None, layer.get("sink"),
            config.head_dim ** -0.5)
        out = (out * config.value_scale).astype(x.dtype)
        x = x + jnp.einsum("thk,hkd->td", out, layer["wo"])
        x, _ = _ffn(layer, x, config, index, kernels.name)
    last = jax.lax.dynamic_slice_in_dim(x, last_index, 1)
    return _head(params, last, config), new_pages


def decode_step_paged(params, tokens, positions, page_tables, pages,
                      config: MimoV2Config, kernels: Kernels):
    """One decode step for ``B`` lanes. ``page_tables`` [2, B, NB]: row
    0 the full group's, row 1 the window group's. Writes each token's
    K/V into its sequence's current block of each group, then attends
    through ``kernels.attn`` (its ``T = 1`` case) and runs the experts on the path
    ``kernels.name`` says. Returns (logits [B, V], pages, counters [3]
    int32: ``moe.COUNTERS`` summed over the expert layers)."""
    lanes = tokens.shape[0]
    block_size = pages[0][0].shape[1] // config.kv_heads(0)
    phys = page_tables[:, jnp.arange(lanes), positions // block_size]
    off = positions % block_size
    x = params["embed"][tokens].astype(config.dtype)
    counters = jnp.zeros(len(moe.COUNTERS), jnp.int32)
    new_pages = []
    for index, (layer, (k_pages, v_pages)) in enumerate(
            zip(params["layers"], pages)):
        kind, kv = config.layer_kinds[index], config.kv_heads(index)
        normed = rms_norm(x, layer["attn_norm"], config.norm_eps)
        q, k, v = _qkv(layer, normed, positions, config, index)
        # scatter this step's K/V, THEN attend: the current position's
        # entry must be visible to its own attention
        k_pages = _write(k_pages, phys[kind], off, k, kv)
        v_pages = _write(v_pages, phys[kind], off, v, kv)
        new_pages.append((k_pages, v_pages))
        out = kernels.attn(
            q[:, None], k_pages, v_pages, page_tables[kind],
            positions[:, None],
            window=config.window if kind else None, sink=layer.get("sink"),
            scale=config.head_dim ** -0.5, kv_heads=kv)[:, 0]
        out = (out * config.value_scale).astype(x.dtype)
        x = x + jnp.einsum("bhk,hkd->bd", out, layer["wo"])
        x, counted = _ffn(layer, x, config, index, kernels.name)
        if counted is not None:
            counters = counters + counted
    return _head(params, x, config), new_pages, counters


ENGINE_MODEL = EngineModel(
    name="mimo_v2",
    init_params=init_params,
    cache_groups=cache_groups,
    init_pages=init_pages,
    prefill=prefill_into_pages,
    decode=decode_step_paged,
    kv_row_bytes=kv_row_bytes,
    step_counters=moe.COUNTERS,
)
