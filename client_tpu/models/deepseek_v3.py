"""A ``deepseek_v3`` decoder (GigaChat3.1-702B-A36B) on the paged engine:
multi-head latent attention over one compressed cache row a token,
group-limited routing over many experts with a shared one, of which
this chip holds a share.

From the model's public ``config.json`` and, for what it does not spell
out, the published ``modeling_deepseek.py`` as recalled (each such item
is under ``assumed`` in ``benchmark/configs/gigachat3_702b/config.json``).
With ``x`` the residual stream and ``N`` an RMSNorm with a learned scale
(pre-norm blocks), a layer is::

    a      = N_in(x)
    c_q    = N_q(a @ w_dq)                              # [T, q_lora_rank]
    q      = c_q @ w_uq -> [T, H, nope + rope];  q_rope = rope(q_rope)
    [c|r]  = a @ w_dkv;  c_kv = N_kv(c);  k_rope = rope(r)   # ONE k_rope
    CACHE  : [c_kv | k_rope], one row a token a layer for all heads
    k_nope = c_kv @ w_uk [T, H, nope];  v = c_kv @ w_uv [T, H, v]
    o      = softmax(scale * [q_nope|q_rope].[k_nope|k_rope]^T) . v
    x      = x + o @ w_o
    m      = N_mlp(x)
    f      = SwiGLU(m)                       in the leading dense layers
    f      = SwiGLU_shared(m) + sum_held w_e SwiGLU_e(m)     after them
    x      = x + f

(``w_uk`` and ``w_uv`` are the published ``kv_b_proj``'s columns, a head
at a time; stored apart because decode uses each alone.) Rope is YaRN's
(:func:`yarn_inv_freq`), and the softmax scale carries its ``mscale``
squared (:attr:`DeepseekV3Config.softmax_scale`). Routing is
``moe.route``'s group-limited ``noaux_tc``.

**Two attention paths over one cache.** What is stored is what is read:
the normed latent and the roped key, 576 numbers a token a layer (rows
of :attr:`DeepseekV3Config.row_width`, the next whole number of 128
lanes, zeros behind). Prefill expands the prompt's own ``c_kv`` through
``w_uk`` / ``w_uv`` and attends in the plain form above. Decode never
expands the cache: it absorbs ``w_uk`` into the query and ``w_uv`` into
the output::

    q_lat[h] = q_nope[h] @ w_uk[h]^T            # [kv_lora_rank]
    score    = scale * (q_lat[h].c_kv_j + q_rope[h].k_rope_j)
    o_lat[h] = sum_j p_j c_kv_j;   o[h] = o_lat[h] @ w_uv[h]

so every head's query is a row ``[q_lat | q_rope]`` against the SAME
cache row, whose leading ``kv_lora_rank`` columns are also its values:
the one-pool call of ``models/paged_attention.py`` at one kv head.

One cache group, full (``models/engine_model.py``): a layer's pages are
one pool ``[N, bs, row_width]``. Rotary pairs are (2i, 2i+1), the
program's layout throughout (``llama._rope``).
"""

import dataclasses
import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from client_tpu.models import moe
from client_tpu.models.engine_model import FULL, CacheGroup, EngineModel, Kernels
from client_tpu.models.llama import _mlp_block, rms_norm
from client_tpu.models.mimo_v2 import _prefill_attention

#: rows of a prompt that go through a feed-forward layer at once: a
#: dense MLP's gate and up rows of 18,432, and the planned expert
#: layer's gathered rows and per-pair outputs at d 7,168, are 2 GB and
#: more for 8,192 tokens taken whole
_FFN_CHUNK = 2048

COUNTERS = moe.COUNTERS + ("moe_lanes_here",)


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 128256
    d_model: int = 7168
    n_layers: int = 64
    n_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 192
    n_dense_layers: int = 3
    d_ff: int = 18432
    d_expert: int = 2048
    n_experts: int = 256
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    held: Tuple[int, int] = (0, 256)
    n_shared_experts: int = 1
    route_scale: float = 2.5
    rope_theta: float = 1e5
    rope_factor: float = 64.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    norm_eps: float = 1e-6
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.n_experts):
            raise ValueError(
                f"held={self.held} is not a share of {self.n_experts} experts")
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError(
                f"n_dense_layers={self.n_dense_layers} of "
                f"{self.n_layers} layers")
        if self.n_experts % self.n_group or not (
                1 <= self.topk_group <= self.n_group):
            raise ValueError(
                f"n_group={self.n_group}, topk_group={self.topk_group} over "
                f"{self.n_experts} experts")

    @property
    def row(self) -> int:
        """What a cached token holds a layer: the latent and the roped key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def row_width(self) -> int:
        """A cache row as stored: whole 128-lane groups, which is what
        Mosaic copies."""
        return -(-self.row // 128) * 128

    @property
    def softmax_scale(self) -> float:
        """``(nope + rope) ** -0.5``, times YaRN's ``mscale`` over all
        sizes, squared (q and k each carry it)."""
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        return scale * _yarn_mscale(
            self.rope_factor, self.rope_mscale_all_dim) ** 2

    @staticmethod
    def tiny(**overrides) -> "DeepseekV3Config":
        """A toy of the same shape for CPU tests: 16 experts in 4 groups
        of which 2 are kept, rows of 40 stored 128 wide, a YaRN ramp
        over all four rotary pairs."""
        base = dict(
            vocab_size=256, d_model=64, n_layers=3, n_heads=4,
            q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=24, n_dense_layers=1, d_ff=128,
            d_expert=32, n_experts=16, top_k=4, n_group=4, topk_group=2,
            held=(0, 16), rope_theta=100.0, rope_original_max=512,
            rope_factor=8.0, max_seq_len=128, dtype=jnp.float32,
        )
        base.update(overrides)
        return DeepseekV3Config(**base)


# -- rope ---------------------------------------------------------------------


def _yarn_mscale(factor: float, mscale: float) -> float:
    if factor <= 1 or not mscale:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


@functools.lru_cache(maxsize=None)
def yarn_inv_freq(config: DeepseekV3Config) -> np.ndarray:
    """YaRN's frequencies of the ``qk_rope_head_dim / 2`` rotary pairs:
    pair ``i`` turns at ``f_i = theta ** (-2i / dim)`` where it makes
    more than ``beta_fast`` turns over the original context, at ``f_i /
    factor`` where fewer than ``beta_slow``, and on a linear ramp between
    the two pair indices those bounds fall on."""
    dim = config.qk_rope_head_dim
    base = config.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def pair_of(turns):
        return (dim * math.log(config.rope_original_max
                               / (turns * 2 * math.pi))
                / (2 * math.log(config.rope_theta)))

    low = max(math.floor(pair_of(config.rope_beta_fast)), 0)
    high = min(math.ceil(pair_of(config.rope_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (base * (1 - ramp) + base / config.rope_factor * ramp).astype(
        np.float32)


def _rope(x, positions, config: DeepseekV3Config):
    """``x`` [T, ..., rope] at ``positions`` [T], pairs (2i, 2i+1), YaRN's
    frequencies; cos and sin carry ``mscale / mscale_all_dim`` (1 here)."""
    angles = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(config)
    angles = angles.reshape(
        angles.shape[:1] + (1,) * (x.ndim - 2) + angles.shape[1:])
    size = (_yarn_mscale(config.rope_factor, config.rope_mscale)
            / _yarn_mscale(config.rope_factor, config.rope_mscale_all_dim))
    cos, sin = jnp.cos(angles) * size, jnp.sin(angles) * size
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# -- parameters ---------------------------------------------------------------


def init_params(key, config: DeepseekV3Config) -> Dict[str, Any]:
    """Seeded weights in the pytree the functions below take. The norm
    scales and the router's bias are of a size that shows: left out,
    each changes the logits or the experts chosen."""
    d, h = config.d_model, config.n_heads
    nope, rope, dv = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                      config.v_head_dim)
    rq, rkv = config.q_lora_rank, config.kv_lora_rank
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
            "mlp_norm": scale_of(k[1], d),
            "q_norm": scale_of(k[2], rq),
            "kv_norm": scale_of(k[3], rkv),
            "w_dq": normal(k[4], (d, rq), s),
            "w_uq": normal(k[5], (rq, h, nope + rope), 1.0 / np.sqrt(rq)),
            "w_dkv": normal(k[6], (d, rkv + rope), s),
            "w_uk": normal(k[7], (rkv, h, nope), 1.0 / np.sqrt(rkv)),
            "w_uv": normal(k[8], (rkv, h, dv), 1.0 / np.sqrt(rkv)),
            "w_o": normal(k[9], (h, dv, d), 1.0 / np.sqrt(h * dv)),
        }
        if index < config.n_dense_layers:
            layer.update(swiglu(k[10], config.d_ff))
        else:
            f, count = config.d_expert, config.held[1]
            layer["router"] = normal(k[10], (d, config.n_experts), s)
            layer["router_bias"] = normal(
                k[11], (config.n_experts,), 0.02, jnp.float32)
            layer["experts"] = {
                "w_gate": normal(k[12], (count, d, f), s),
                "w_up": normal(k[13], (count, d, f), s),
                "w_down": normal(k[14], (count, f, d), 1.0 / np.sqrt(f)),
            }
            layer["shared"] = swiglu(k[15], f * config.n_shared_experts)
        layers.append(layer)
    return {
        "embed": normal(keys[-2], (config.vocab_size, d), 1.0),
        "final_norm": scale_of(jax.random.fold_in(keys[-2], 1), d),
        "lm_head": normal(keys[-1], (d, config.vocab_size), s),
        "layers": layers,
    }


# -- cache groups and pools ---------------------------------------------------


def cache_groups(config: DeepseekV3Config):
    return [CacheGroup(FULL, tuple(range(config.n_layers)))]


def init_pages(config: DeepseekV3Config, num_blocks, block_size: int):
    """One pool a layer, ``[N, bs, row_width]``: a token's row is
    ``[c_kv | k_rope | zeros]``."""
    return [jnp.zeros((num_blocks[0], block_size, config.row_width),
                      config.dtype) for _ in range(config.n_layers)]


def kv_row_bytes(config: DeepseekV3Config):
    """(stored, counted) bytes a cached token takes in a layer, a group."""
    size = jnp.dtype(config.dtype).itemsize
    return [(config.row_width * size, config.row * size)]


# -- building blocks ----------------------------------------------------------


def _latents(layer, normed, positions, config: DeepseekV3Config):
    """``normed`` [T, d] at ``positions`` [T] -> the queries' two parts
    (``q_nope`` [T, H, nope], ``q_rope`` [T, H, rope], turned) and the
    token's cache row without its padding ([T, row]: the normed latent
    and the roped key)."""
    nope, rkv = config.qk_nope_head_dim, config.kv_lora_rank
    c_q = rms_norm(normed @ layer["w_dq"], layer["q_norm"], config.norm_eps)
    q = jnp.einsum("tr,rhk->thk", c_q, layer["w_uq"])
    q_rope = _rope(q[..., nope:], positions, config)
    down = normed @ layer["w_dkv"]
    c_kv = rms_norm(down[:, :rkv], layer["kv_norm"], config.norm_eps)
    k_rope = _rope(down[:, rkv:], positions, config)
    return q[..., :nope], q_rope, jnp.concatenate([c_kv, k_rope], axis=-1)


def _stored(row, config: DeepseekV3Config):
    return jnp.pad(row, ((0, 0), (0, config.row_width - config.row)))


def _attend_plain(layer, q_nope, q_rope, row, config: DeepseekV3Config):
    """The plain form over a prompt's own rows: expand every token's
    latent to per-head keys and values, attend causally. [T, H, v]."""
    rkv, heads = config.kv_lora_rank, config.n_heads
    c_kv, k_rope = row[:, :rkv], row[:, rkv:]
    k_nope = jnp.einsum("tc,chn->thn", c_kv, layer["w_uk"])
    v = jnp.einsum("tc,chv->thv", c_kv, layer["w_uv"])
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, None], (len(row), heads, k_rope.shape[-1]))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    return _prefill_attention(q, k, v, None, None, config.softmax_scale)


def _attend_absorbed(layer, q_nope, q_rope, pool, tables, positions,
                     config: DeepseekV3Config, attn):
    """The absorbed form over the cache: every head's ``[q_lat | q_rope |
    zeros]`` against the pool's rows, values read out of the rows'
    leading columns, and ``w_uv`` on the way out. [B, H, v]."""
    q_lat = jnp.einsum("bhn,chn->bhc", q_nope, layer["w_uk"])
    q = jnp.pad(jnp.concatenate([q_lat, q_rope], axis=-1),
                ((0, 0), (0, 0), (0, config.row_width - config.row)))
    o_lat = attn(
        q[:, None], pool, None, tables, positions[:, None],
        scale=config.softmax_scale, kv_heads=1,
        v_width=config.kv_lora_rank)[:, 0]
    return jnp.einsum("bhc,chv->bhv", o_lat, layer["w_uv"])


def _route(layer, normed, config: DeepseekV3Config):
    return moe.route(
        normed, layer["router"], layer["router_bias"], config.top_k,
        scale=config.route_scale, n_group=config.n_group,
        topk_group=config.topk_group, eps=1e-20)


def _ffn(layer, x, config: DeepseekV3Config, index: int, kernel: str):
    """x [T, d] -> (x + FFN(N_mlp(x)), the layer's :data:`COUNTERS` or
    None for a dense layer). ``kernel``: the load-time choice's name."""
    normed = rms_norm(x, layer["mlp_norm"], config.norm_eps)
    if index < config.n_dense_layers:
        return x + _mlp_block(layer, normed[None])[0], None
    ids, weights = _route(layer, normed, config)
    out, counters = moe.expert_layer(
        normed, ids, weights, layer["experts"], config.held,
        kernel=kernel, shared=layer["shared"])
    here = moe._held_pairs(ids, config.held)[0].any(axis=1).sum()
    counters = jnp.concatenate([counters, here.astype(jnp.int32)[None]])
    return x + out.astype(x.dtype), counters


def _ffn_prompt(layer, x, config: DeepseekV3Config, index: int, kernel: str):
    """:func:`_ffn` over a prompt's rows, :data:`_FFN_CHUNK` at a time."""
    length = x.shape[0]
    if length <= _FFN_CHUNK:
        return _ffn(layer, x, config, index, kernel)[0]
    chunks = x.reshape(length // _FFN_CHUNK, _FFN_CHUNK, -1)
    return jax.lax.map(
        lambda rows: _ffn(layer, rows, config, index, kernel)[0], chunks
    ).reshape(x.shape)


def _head(params, x, config: DeepseekV3Config):
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    return jnp.einsum("td,dv->tv", x, params["lm_head"]).astype(jnp.float32)


# -- the engine's programs ----------------------------------------------------


def prefill_into_pages(params, tokens, page_table, pages, last_index,
                       config: DeepseekV3Config, kernels: Kernels):
    """Prefill one prompt, scattering each layer's cache rows through the
    table. ``tokens`` [1, L] (padded to its bucket), ``page_table``
    [max_blocks] (positions past ``last_index`` go to the trash block);
    ``kernels`` the load-time choice (its name picks the expert layer's
    path; the prompt's attention on itself is the plain form in plain
    XLA under every choice, a chunk of queries at a time). Returns
    (logits of the last token [1, V], pages)."""
    length = tokens.shape[1]
    block_size = pages[0].shape[1]
    positions = jnp.arange(length)
    real = positions <= last_index
    phys = jnp.where(real, page_table[positions // block_size], 0)
    off = jnp.where(real, positions % block_size, 0)
    x = params["embed"][tokens[0]].astype(config.dtype)
    new_pages = []
    for index, (layer, pool) in enumerate(zip(params["layers"], pages)):
        normed = rms_norm(x, layer["attn_norm"], config.norm_eps)
        q_nope, q_rope, row = _latents(layer, normed, positions, config)
        new_pages.append(pool.at[phys, off].set(_stored(row, config)))
        out = _attend_plain(layer, q_nope, q_rope, row, config)
        x = x + jnp.einsum("thv,hvd->td", out.astype(x.dtype), layer["w_o"])
        x = _ffn_prompt(layer, x, config, index, kernels.name)
    last = jax.lax.dynamic_slice_in_dim(x, last_index, 1)
    return _head(params, last, config), new_pages


def decode_step_paged(params, tokens, positions, page_tables, pages,
                      config: DeepseekV3Config, kernels: Kernels):
    """One decode step for ``B`` lanes, ``page_tables`` [B, NB]. Writes
    each token's row into its sequence's current block, then attends in
    the absorbed form through ``kernels.attn`` (the one-pool call, ``T =
    1``) and runs the experts on the path ``kernels.name`` says. Returns
    (logits [B, V], pages, counters int32: :data:`COUNTERS` summed over
    the expert layers)."""
    lanes = tokens.shape[0]
    block_size = pages[0].shape[1]
    phys = page_tables[jnp.arange(lanes), positions // block_size]
    off = positions % block_size
    x = params["embed"][tokens].astype(config.dtype)
    counters = jnp.zeros(len(COUNTERS), jnp.int32)
    new_pages = []
    for index, (layer, pool) in enumerate(zip(params["layers"], pages)):
        normed = rms_norm(x, layer["attn_norm"], config.norm_eps)
        q_nope, q_rope, row = _latents(layer, normed, positions, config)
        # scatter this step's row, THEN attend: the current position's
        # entry must be visible to its own attention
        pool = pool.at[phys, off].set(_stored(row, config))
        new_pages.append(pool)
        out = _attend_absorbed(layer, q_nope, q_rope, pool, page_tables,
                               positions, config, kernels.attn)
        x = x + jnp.einsum("bhv,hvd->bd", out.astype(x.dtype), layer["w_o"])
        x, counted = _ffn(layer, x, config, index, kernels.name)
        if counted is not None:
            counters = counters + counted
    return _head(params, x, config), new_pages, counters


ENGINE_MODEL = EngineModel(
    name="deepseek_v3",
    init_params=init_params,
    cache_groups=cache_groups,
    init_pages=init_pages,
    prefill=prefill_into_pages,
    decode=decode_step_paged,
    kv_row_bytes=kv_row_bytes,
    step_counters=COUNTERS,
)
