"""Ouro's looped decoder (``model_type: ouro``; ByteDance/Ouro-2.6B, the
looped language model of arXiv:2510.25741) on the paged engine: a dense
multi-head decoder whose layer stack runs ``total_ut_steps`` times a
token over the SAME weights, each pass with K/V of its own.

From the model's public ``config.json`` and, for what it does not spell
out, the published ``modeling_ouro.py`` as recalled (each such item is
under ``assumed`` in ``benchmark/configs/ouro_2_6b/config.json``). With
``h`` the residual stream and every norm ``w n(x)``, ``n(x) = x /
sqrt(mean(x^2) + eps)`` in float32 (``llama.rms_norm``)::

    h = E[token]
    for u in 0 .. total_ut_steps - 1:              # the same weights a pass
        for l in 0 .. n_layers - 1:
            a = RMS1_l(h)
            q, k, v = a Wq_l, a Wk_l, a Wv_l;  q, k = rope(q), rope(k)
            cache[u, l] <- k, v                    # a pass's K/V is its own
            o = softmax(q K[u, l]^T / sqrt(D)) V[u, l]           # causal
            h = h + RMS2_l(o Wo_l)                 # a norm on the OUTPUT
            m = RMS3_l(h)
            h = h + RMS4_l((silu(m Wg_l) * m Wu_l) Wd_l)
        h = RMS_f(h)                               # closes EVERY pass
    logits = h W_head                              # untied

The exit gate (``sigmoid(h w_exit + b_exit)`` after every pass) is
carried in the parameters and not computed: at ``early_exit_threshold``
1, the published setting and the only one served, the last pass's state
is always the one read and the gate moves no logit.

**The program is as long as one layer, not as the loop.** Every
per-layer weight is stacked ``[n_layers, ...]``; ``prefill`` and
``decode`` are a ``lax.fori_loop`` over the passes around a ``lax.scan``
over the layers, so their jaxprs hold one layer body (and ``decode`` one
paged-attention call) whatever the pass count and the depth.

**One pool pair for the whole model.** A cache "layer" is a (pass,
layer) pair, ``pass * n_layers + layer`` (the published cache's index),
and all ``total_ut_steps * n_layers`` of them lie in ONE ``k_pool`` and
ONE ``v_pool`` of ``[pairs * NB, block, KV, D]``: pair ``p``'s block
``b`` is pool row ``p * NB + b``. The scan's body adds ``p * NB`` to the
lane's page table and to the scatter of the new rows, and carries the
two pools; the kernel fetches pages by block id, and an offset id is a
block id. Block 0 of every pair is that pair's trash block (the padding
rows of a batch bucket and the padded tail of a prompt name block 0).
To the engine (``models/engine_model.py``) this is one ``full`` group of
ONE storing layer, layer 0, whose cached token takes ``pairs`` rows:
``init_pages`` returns the pair as entry 0 and ``()`` for every other
layer, ``kv_row_bytes`` is the bytes over all pairs.
"""

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from client_tpu.models.engine_model import (
    FULL, CacheGroup, EngineModel, Kernels,
)
from client_tpu.models.llama import _mlp_block, _rope, rms_norm
from client_tpu.models.mimo_v2 import _prefill_attention

#: the model's own per-step counters: layer bodies a decode step ran
#: (passes x layers: what an early exit would move) and the (token, pair)
#: rows of K and V its attention read for the live lanes
COUNTERS = ("loop_layer_passes", "loop_kv_rows_read")


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    vocab_size: int = 49152
    d_model: int = 2048
    n_layers: int = 48
    n_heads: int = 16
    n_kv_heads: int = 16
    head_dim: int = 128
    d_ff: int = 5632
    ut_steps: int = 4
    max_seq_len: int = 65536
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads or self.ut_steps < 1:
            raise ValueError(
                f"{self.n_heads} heads over {self.n_kv_heads} KV heads, "
                f"{self.ut_steps} passes")

    @property
    def cache_pairs(self) -> int:
        """(pass, layer) pairs, each with K/V of its own."""
        return self.ut_steps * self.n_layers

    @staticmethod
    def tiny(**overrides) -> "OuroConfig":
        """A toy of the same shape for CPU tests."""
        base = dict(
            vocab_size=256, d_model=64, n_layers=3, n_heads=4, n_kv_heads=4,
            head_dim=16, d_ff=128, ut_steps=4, max_seq_len=128,
            dtype=jnp.float32,
        )
        base.update(overrides)
        return OuroConfig(**base)


# -- parameters ---------------------------------------------------------------


def init_params(key, config: OuroConfig) -> Dict[str, Any]:
    """Seeded weights in the pytree the functions below take: every
    per-layer tensor stacked ``[n_layers, ...]``, the norms' scales 0.1
    N(0,1) around 1 so that each weighs, the exit gate's weights carried."""
    d, h, kv, dh, f, n = (config.d_model, config.n_heads, config.n_kv_heads,
                          config.head_dim, config.d_ff, config.n_layers)
    k = jax.random.split(key, 16)
    s = 1.0 / np.sqrt(d)

    def normal(key, shape, scale, around=0.0):
        return (around + jax.random.normal(key, shape, jnp.float32) * scale
                ).astype(config.dtype)

    return {
        # of unit size, as the stream is after every pass's closing norm
        "embed": normal(k[0], (config.vocab_size, d), 1.0),
        "final_norm": normal(k[1], (d,), 0.1, 1.0),
        "lm_head": normal(k[2], (d, config.vocab_size), s),
        "exit_w": normal(k[3], (d,), s),
        "exit_b": normal(k[4], (), 1.0),
        "layers": {
            "attn_norm": normal(k[5], (n, d), 0.1, 1.0),
            "attn_out_norm": normal(k[6], (n, d), 0.1, 1.0),
            "mlp_norm": normal(k[7], (n, d), 0.1, 1.0),
            "mlp_out_norm": normal(k[8], (n, d), 0.1, 1.0),
            # q, k and v projections are held [out, in]: stacked [in, out]
            # (or [in, H, D]) the TPU compiler asks for the other order and
            # copies all layers' three weights, 1.2 GB, every step
            "wq": normal(k[9], (n, h * dh, d), s),
            "wk": normal(k[10], (n, kv * dh, d), s),
            "wv": normal(k[11], (n, kv * dh, d), s),
            "wo": normal(k[12], (n, h * dh, d), 1.0 / np.sqrt(h * dh)),
            "w_gate": normal(k[13], (n, d, f), s),
            "w_up": normal(k[14], (n, d, f), s),
            "w_down": normal(k[15], (n, f, d), 1.0 / np.sqrt(f)),
        },
    }


def cache_groups(config: OuroConfig):
    """One full group of ONE storing layer: layer 0's entry is the pool
    pair of every (pass, layer) pair (the module docstring)."""
    return [CacheGroup(FULL, (0,))]


def init_pages(config: OuroConfig, num_blocks, block_size: int):
    """``(k_pool, v_pool)`` of ``[pairs * num_blocks, block, KV, D]`` as
    layer 0's entry, ``()`` for every other layer."""
    shape = (config.cache_pairs * num_blocks[0], block_size,
             config.n_kv_heads, config.head_dim)
    pools = (jnp.zeros(shape, config.dtype), jnp.zeros(shape, config.dtype))
    return [pools] + [()] * (config.n_layers - 1)


def kv_row_bytes(config: OuroConfig):
    """A cached token's bytes over ALL (pass, layer) pairs, as stored
    and as read (whole lanes at any head size served): the one storing
    layer's row."""
    row = (config.cache_pairs * 2 * config.n_kv_heads * config.head_dim
           * jnp.dtype(config.dtype).itemsize)
    return [(row, row)]


# -- the rolled loops ---------------------------------------------------------


def _pair_base(step, index, config: OuroConfig, stride: int):
    """First pool row of pass ``step``'s layer ``index``: the pair's
    blocks lie ``stride`` rows a pair, in the published cache's order."""
    return (step * config.n_layers + index) * stride


def _looped(params, x, pools, positions, attend, config: OuroConfig):
    """The passes around the layers, both rolled: ``x`` [B, L, d] through
    ``ut_steps`` passes of the stacked ``params["layers"]`` and each
    pass's closing norm. ``attend(q, k, v, pools, base) -> (out, pools)``
    writes the pair's new rows and reads its cache, ``base`` the pair's
    first pool row (``pair * NB``)."""
    eps, theta, dh = config.norm_eps, config.rope_theta, config.head_dim
    stride = pools[0].shape[0] // config.cache_pairs

    def layer(carry, xs):
        x, pools = carry
        w, base = xs
        a = rms_norm(x, w["attn_norm"], eps)
        q, k, v = (jnp.einsum("bld,fd->blf", a, w[name]).reshape(
            *a.shape[:2], -1, dh) for name in ("wq", "wk", "wv"))
        out, pools = attend(_rope(q, positions, theta),
                            _rope(k, positions, theta), v, pools, base)
        out = out.astype(x.dtype).reshape(*a.shape[:2], -1)
        x = x + rms_norm(jnp.dot(out, w["wo"]), w["attn_out_norm"], eps)
        m = rms_norm(x, w["mlp_norm"], eps)
        x = x + rms_norm(_mlp_block(w, m), w["mlp_out_norm"], eps)
        return (x, pools), None

    def one_pass(step, carry):
        bases = _pair_base(step, jnp.arange(config.n_layers), config, stride)
        (x, pools), _ = jax.lax.scan(layer, carry, (params["layers"], bases))
        return rms_norm(x, params["final_norm"], eps), pools

    return jax.lax.fori_loop(0, config.ut_steps, one_pass, (x, pools))


def _head(params, x):
    return jnp.einsum("td,dv->tv", x, params["lm_head"]).astype(jnp.float32)


# -- the engine's programs ----------------------------------------------------


def prefill_into_pages(params, tokens, page_table, pages, last_index,
                       config: OuroConfig, kernels: Kernels):
    """Prefill one prompt: ``tokens`` [1, L] (padded to its bucket),
    ``page_table`` [max_blocks]. Every (pass, layer) pair scatters its
    K/V through the table at its offset (positions past ``last_index`` go
    to the pair's trash block) and attends on the prompt in plain XLA.
    Returns (logits of the last token [1, V], pages)."""
    del kernels  # a prompt runs in plain XLA under every choice
    length = tokens.shape[1]
    block_size = pages[0][0].shape[1]
    positions = jnp.arange(length)
    real = positions <= last_index
    phys = jnp.where(real, page_table[positions // block_size], 0)
    off = jnp.where(real, positions % block_size, 0)

    def attend(q, k, v, pools, base):
        k_pool, v_pool = pools
        pools = (k_pool.at[base + phys, off].set(k[0]),
                 v_pool.at[base + phys, off].set(v[0]))
        out = _prefill_attention(
            q[0], k[0], v[0], None, None, config.head_dim ** -0.5)
        return out[None], pools

    x, pools = _looped(params, params["embed"][tokens], pages[0],
                       positions[None], attend, config)
    last = jax.lax.dynamic_slice_in_dim(x[0], last_index, 1)
    return _head(params, last), [pools] + list(pages[1:])


def decode_step_paged(params, tokens, positions, page_tables, pages,
                      config: OuroConfig, kernels: Kernels):
    """One decode step for ``B`` lanes, ``page_tables`` [B, NB]: every
    (pass, layer) pair writes the token's K/V into the lane's block at
    the pair's offset and attends through ``kernels.attn`` over the
    table at that offset (a padding lane, whose table is all zeros,
    names the pair's trash block). Returns (logits [B, V], pages,
    counters int32: :data:`COUNTERS`)."""
    lanes = tokens.shape[0]
    if lanes == 1:
        # a lone lane's scatter is one dynamic-update-slice, for which the
        # TPU compiler re-lays the pools it carries through the loops (a
        # copy of each, padded to 34 GB): a padding lane beside it keeps
        # the write a scatter and the program the two-lane bucket's
        beside = lambda a: jnp.concatenate([a, jnp.zeros_like(a)])  # noqa: E731
        logits, pages, counters = decode_step_paged(
            params, beside(tokens), beside(positions), beside(page_tables),
            pages, config, kernels)
        return logits[:1], pages, counters
    block_size = pages[0][0].shape[1]
    phys = page_tables[jnp.arange(lanes), positions // block_size]
    off = positions % block_size
    pos2 = positions[:, None]

    def attend(q, k, v, pools, base):
        # scatter this step's K/V, THEN attend: the current position's
        # entry must be visible to its own attention
        k_pool = pools[0].at[base + phys, off].set(k[:, 0])
        v_pool = pools[1].at[base + phys, off].set(v[:, 0])
        out = kernels.attn(q, k_pool, v_pool, page_tables + base, pos2)
        return out, (k_pool, v_pool)

    x, pools = _looped(params, params["embed"][tokens][:, None], pages[0],
                       pos2, attend, config)
    # block 0 is never a sequence's: a live lane's first column is not 0
    live = page_tables[:, 0] != 0
    counters = jnp.stack([
        jnp.int32(config.cache_pairs),
        config.cache_pairs * jnp.sum(
            jnp.where(live, positions + 1, 0), dtype=jnp.int32)])
    return _head(params, x[:, 0]), [pools] + list(pages[1:]), counters


ENGINE_MODEL = EngineModel(
    name="ouro",
    init_params=init_params,
    cache_groups=cache_groups,
    init_pages=init_pages,
    prefill=prefill_into_pages,
    decode=decode_step_paged,
    heads=lambda config: (config.n_heads, config.n_kv_heads),
    kv_row_bytes=kv_row_bytes,
    step_counters=COUNTERS,
)
