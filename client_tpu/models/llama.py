"""Flagship decoder-only transformer (Llama-family architecture) in pure JAX.

Design (TPU-first, not a torch port):

- parameters are a plain pytree with an explicit ``PartitionSpec`` twin
  (``param_specs``) — Megatron-style tensor parallelism: attention heads and
  MLP hidden sharded over ``tp``, embeddings sharded over the vocab;
- ``forward`` is a single jitted function; under a mesh, `jax.jit` with
  sharding-annotated inputs lets XLA insert the tp collectives (psum over
  the contracted axes materializes as all-reduce on ICI);
- long-context prefill can route attention through
  :func:`client_tpu.parallel.ring_attention` when the mesh has an ``sp``
  axis (sequence sharded);
- decode keeps a KV cache pytree and generates with ``lax.scan`` — no
  Python loop inside jit (XLA semantics: static shapes, traced once);
- bfloat16 activations/params with float32 attention softmax and optimizer
  state, the standard TPU recipe.

Role in the framework: the "Llama-7B streaming" benchmark config of
BASELINE.json (served via client_tpu.models.serving.LlmDecodeModel) and the
flagship entry for the driver's __graft_entry__.
"""

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from client_tpu.models.engine_model import FULL, CacheGroup, EngineModel
from client_tpu.parallel import DP_AXIS, SP_AXIS, TP_AXIS
from client_tpu.parallel.ring_attention import reference_attention, ring_attention


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    d_ff: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @staticmethod
    def tiny(**overrides) -> "LlamaConfig":
        """A tiny config for tests/dryruns (compiles in seconds)."""
        base = dict(
            vocab_size=256,
            d_model=64,
            n_layers=2,
            n_heads=4,
            n_kv_heads=4,
            d_ff=128,
            max_seq_len=128,
        )
        base.update(overrides)
        return LlamaConfig(**base)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_params(key, config: LlamaConfig) -> Dict[str, Any]:
    """Initialize a parameter pytree (He/scaled-normal init)."""
    d, h, hd, f = (
        config.d_model,
        config.n_heads,
        config.head_dim,
        config.d_ff,
    )
    kv = config.n_kv_heads
    keys = jax.random.split(key, config.n_layers + 2)

    def normal(k, shape, scale):
        return (jax.random.normal(k, shape, dtype=jnp.float32) * scale).astype(
            config.dtype
        )

    layers = []
    for i in range(config.n_layers):
        lk = jax.random.split(keys[i], 7)
        scale = 1.0 / np.sqrt(d)
        layers.append(
            {
                "wq": normal(lk[0], (d, h, hd), scale),
                "wk": normal(lk[1], (d, kv, hd), scale),
                "wv": normal(lk[2], (d, kv, hd), scale),
                "wo": normal(lk[3], (h, hd, d), scale / np.sqrt(2 * config.n_layers)),
                "w_gate": normal(lk[4], (d, f), scale),
                "w_up": normal(lk[5], (d, f), scale),
                "w_down": normal(lk[6], (f, d), 1.0 / np.sqrt(f)),
                "attn_norm": jnp.ones((d,), dtype=config.dtype),
                "mlp_norm": jnp.ones((d,), dtype=config.dtype),
            }
        )
    return {
        "embed": normal(keys[-2], (config.vocab_size, d), 1.0),
        "final_norm": jnp.ones((d,), dtype=config.dtype),
        "lm_head": normal(keys[-1], (d, config.vocab_size), 1.0 / np.sqrt(d)),
        "layers": layers,
    }


def param_specs(config: LlamaConfig) -> Dict[str, Any]:
    """PartitionSpec pytree twin of init_params (tp = tensor parallel)."""
    layer = {
        "wq": P(None, TP_AXIS, None),
        "wk": P(None, TP_AXIS, None),
        "wv": P(None, TP_AXIS, None),
        "wo": P(TP_AXIS, None, None),
        "w_gate": P(None, TP_AXIS),
        "w_up": P(None, TP_AXIS),
        "w_down": P(TP_AXIS, None),
        "attn_norm": P(),
        "mlp_norm": P(),
    }
    return {
        "embed": P(TP_AXIS, None),
        "final_norm": P(),
        "lm_head": P(None, TP_AXIS),
        "layers": [layer] * config.n_layers,
    }


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------


def rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight


def _rope(x, positions, theta):
    """Rotary position embedding; x: [..., L, H, D]."""
    head_dim = x.shape[-1]
    freqs = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., L, D/2]
    angles = angles[..., None, :]  # broadcast over heads
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    out = jnp.stack([out1, out2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def _repeat_kv(x, n_rep: int):
    """[B, L, KV, D] -> [B, L, KV*n_rep, D] (grouped-query attention)."""
    if n_rep == 1:
        return x
    b, l, kv, d = x.shape
    return jnp.broadcast_to(
        x[:, :, :, None, :], (b, l, kv, n_rep, d)
    ).reshape(b, l, kv * n_rep, d)


def _attention_block(
    layer, x, positions, config: LlamaConfig, mesh: Optional[Mesh], kv_cache=None
):
    """Self-attention; returns (output, new_kv) — new_kv None when caching
    is off."""
    b, l, d = x.shape
    n_rep = config.n_heads // config.n_kv_heads
    q = jnp.einsum("bld,dhk->blhk", x, layer["wq"])
    k = jnp.einsum("bld,dhk->blhk", x, layer["wk"])
    v = jnp.einsum("bld,dhk->blhk", x, layer["wv"])
    q = _rope(q, positions, config.rope_theta)
    k = _rope(k, positions, config.rope_theta)

    if kv_cache is not None:
        # decode: append this step's K/V at index `positions` in the cache
        cache_k, cache_v = kv_cache  # [B, S, KV, D]
        idx = positions[0, 0]  # same step index across batch (scalar)
        cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k, idx, axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v, idx, axis=1)
        k_full = _repeat_kv(cache_k, n_rep)
        v_full = _repeat_kv(cache_v, n_rep)
        qh = q.transpose(0, 2, 1, 3)  # [B, H, 1, D]
        kh = k_full.transpose(0, 2, 1, 3)  # [B, H, S, D]
        vh = v_full.transpose(0, 2, 1, 3)
        scores = jnp.einsum(
            "bhqd,bhkd->bhqk", qh, kh, preferred_element_type=jnp.float32
        ) / np.sqrt(config.head_dim)
        # mask out cache slots beyond the current position
        valid = jnp.arange(kh.shape[2]) <= idx
        scores = jnp.where(valid[None, None, None, :], scores, -1e30)
        weights = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", weights, vh.astype(weights.dtype))
        out = out.astype(x.dtype).transpose(0, 2, 1, 3)  # [B, 1, H, D]
        new_kv = (cache_k, cache_v)
    else:
        k_full = _repeat_kv(k, n_rep)
        v_full = _repeat_kv(v, n_rep)
        qh = q.transpose(0, 2, 1, 3)
        kh = k_full.transpose(0, 2, 1, 3)
        vh = v_full.transpose(0, 2, 1, 3)
        if mesh is not None and SP_AXIS in mesh.axis_names and mesh.shape[SP_AXIS] > 1:
            out = ring_attention(qh, kh, vh, mesh, causal=True)
        else:
            out = reference_attention(qh, kh, vh, causal=True)
        out = out.transpose(0, 2, 1, 3)
        new_kv = None

    out = jnp.einsum("blhk,hkd->bld", out, layer["wo"])
    return out, new_kv


def _mlp_block(layer, x):
    gate = jax.nn.silu(jnp.einsum("bld,df->blf", x, layer["w_gate"]))
    up = jnp.einsum("bld,df->blf", x, layer["w_up"])
    return jnp.einsum("blf,fd->bld", gate * up, layer["w_down"])


# ---------------------------------------------------------------------------
# forward / loss / train
# ---------------------------------------------------------------------------


def forward(
    params,
    tokens: jnp.ndarray,
    config: LlamaConfig,
    mesh: Optional[Mesh] = None,
    positions: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Full-sequence forward (prefill): tokens [B, L] -> logits [B, L, V]."""
    if positions is None:
        positions = jnp.arange(tokens.shape[1])[None, :]
    x = params["embed"][tokens].astype(config.dtype)
    for layer in params["layers"]:
        h, _ = _attention_block(
            layer, rms_norm(x, layer["attn_norm"], config.norm_eps), positions,
            config, mesh,
        )
        x = x + h
        x = x + _mlp_block(
            layer, rms_norm(x, layer["mlp_norm"], config.norm_eps)
        )
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    return jnp.einsum("bld,dv->blv", x, params["lm_head"]).astype(jnp.float32)


def loss_fn(params, tokens, config: LlamaConfig, mesh=None):
    """Next-token cross-entropy over tokens [B, L].

    Runs forward on the full sequence and shifts the logits (keeps the
    sequence length divisible by the sp mesh axis; the last position's
    logits are simply unused).
    """
    logits = forward(params, tokens, config, mesh)[:, :-1]
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


def make_train_step(config: LlamaConfig, mesh: Optional[Mesh], learning_rate=1e-3):
    """Build a jitted (params, opt_state, tokens) -> (params, opt_state,
    loss) training step, sharded over the mesh when given."""
    import optax

    optimizer = optax.adamw(learning_rate)

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, config, mesh)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    if mesh is None:
        return jax.jit(train_step), optimizer
    specs = param_specs(config)
    param_shardings = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    token_sharding = NamedSharding(mesh, P(DP_AXIS, None))
    jitted = jax.jit(
        train_step,
        in_shardings=(param_shardings, None, token_sharding),
        out_shardings=(param_shardings, None, None),
    )
    return jitted, optimizer


# ---------------------------------------------------------------------------
# KV-cache decode
# ---------------------------------------------------------------------------


def init_kv_cache(config: LlamaConfig, batch: int, max_len: Optional[int] = None):
    """Zeroed KV cache pytree: one (k, v) pair per layer."""
    max_len = max_len or config.max_seq_len
    shape = (batch, max_len, config.n_kv_heads, config.head_dim)
    return [
        (
            jnp.zeros(shape, dtype=config.dtype),
            jnp.zeros(shape, dtype=config.dtype),
        )
        for _ in range(config.n_layers)
    ]


def prefill_with_cache(
    params, tokens, cache, config: LlamaConfig, mesh=None, last_index=None
):
    """Run the prompt through the model, filling the cache.

    Returns (logits_of_last_token [B, V], cache). ``last_index`` (traced
    scalar) selects which position's logits to return — callers that pad
    prompts to bucket lengths pass the real last-token index so padding
    does not change the result (causal attention guarantees positions
    <= last_index never attend to the padded tail, and decode overwrites
    padded cache slots before its validity mask ever exposes them).
    """
    b, l = tokens.shape
    positions = jnp.arange(l)[None, :].repeat(b, axis=0)
    x = params["embed"][tokens].astype(config.dtype)
    new_cache = []
    for layer, kv in zip(params["layers"], cache):
        normed = rms_norm(x, layer["attn_norm"], config.norm_eps)
        q = jnp.einsum("bld,dhk->blhk", normed, layer["wq"])
        k = jnp.einsum("bld,dhk->blhk", normed, layer["wk"])
        v = jnp.einsum("bld,dhk->blhk", normed, layer["wv"])
        q = _rope(q, positions, config.rope_theta)
        k = _rope(k, positions, config.rope_theta)
        cache_k = jax.lax.dynamic_update_slice_in_dim(kv[0], k, 0, axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(kv[1], v, 0, axis=1)
        new_cache.append((cache_k, cache_v))
        n_rep = config.n_heads // config.n_kv_heads
        qh = q.transpose(0, 2, 1, 3)
        kh = _repeat_kv(k, n_rep).transpose(0, 2, 1, 3)
        vh = _repeat_kv(v, n_rep).transpose(0, 2, 1, 3)
        out = reference_attention(qh, kh, vh, causal=True).transpose(0, 2, 1, 3)
        x = x + jnp.einsum("blhk,hkd->bld", out, layer["wo"])
        x = x + _mlp_block(layer, rms_norm(x, layer["mlp_norm"], config.norm_eps))
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    if last_index is None:
        last = x[:, -1]
    else:
        last = jnp.take_along_axis(
            x, jnp.full((b, 1, 1), last_index, dtype=jnp.int32).repeat(
                x.shape[-1], axis=-1
            ), axis=1,
        )[:, 0]
    logits = jnp.einsum("bd,dv->bv", last, params["lm_head"])
    return logits.astype(jnp.float32), new_cache


def decode_step(params, token, position, cache, config: LlamaConfig):
    """One decode step: token [B], position scalar -> (logits [B, V], cache)."""
    b = token.shape[0]
    positions = jnp.full((b, 1), position, dtype=jnp.int32)
    x = params["embed"][token][:, None, :].astype(config.dtype)
    new_cache = []
    for layer, kv in zip(params["layers"], cache):
        h, new_kv = _attention_block(
            layer,
            rms_norm(x, layer["attn_norm"], config.norm_eps),
            positions,
            config,
            mesh=None,
            kv_cache=kv,
        )
        new_cache.append(new_kv)
        x = x + h
        x = x + _mlp_block(layer, rms_norm(x, layer["mlp_norm"], config.norm_eps))
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    logits = jnp.einsum("bd,dv->bv", x[:, 0], params["lm_head"])
    return logits.astype(jnp.float32), new_cache


# ---------------------------------------------------------------------------
# paged KV cache (block-pool layout for the continuous-batching engine)
# ---------------------------------------------------------------------------
#
# "Ragged Paged Attention" (PAPERS.md, arxiv 2604.15464) reproduced at the
# cache-manager level: instead of one dense [B, max_seq, KV, D] cache per
# request, every layer owns ONE physical pool of fixed-size token blocks
# shared by all live sequences:
#
#     k_pages, v_pages : [num_blocks, block_size, n_kv_heads, head_dim]
#
# A sequence's logical view is its *page table* — a row of physical block
# ids, one per ``block_size`` tokens of context. Decode scatters the step's
# K/V into (page_table[pos // bs], pos % bs) and reads the sequence's
# pages through the paged attention chosen at load
# (``models/paged_attention.py``); the manager semantics —
# allocate-on-demand, free-on-completion, shared pool — are the same
# under every choice.
#
# Physical block 0 is reserved as the TRASH block: padding lanes of a
# bucketed decode batch and padded prompt-tail positions point their
# writes at it, so they can never clobber a live sequence's cache, and
# unallocated page-table entries are 0 — masked out by the per-sequence
# validity mask before they influence attention.


def init_kv_pages(config: LlamaConfig, num_blocks: int, block_size: int):
    """Zeroed block pool: one (k_pages, v_pages) pair per layer."""
    shape = (num_blocks, block_size, config.n_kv_heads, config.head_dim)
    return [
        (
            jnp.zeros(shape, dtype=config.dtype),
            jnp.zeros(shape, dtype=config.dtype),
        )
        for _ in range(config.n_layers)
    ]


def prefill_into_pages(
    params, tokens, page_table, pages, last_index, config: LlamaConfig
):
    """Prefill one prompt and scatter its K/V into the block pool.

    ``tokens`` [1, L] (L = padded bucket length), ``page_table``
    [max_blocks] physical block ids (0 = unallocated/trash),
    ``last_index`` the real last-token index (traced scalar). Runs the
    prompt through :func:`prefill_with_cache` on a dense scratch cache of
    the bucket length, then writes positions ``0..last_index`` into the
    pages (padded tail positions write to the trash block). Returns
    (logits_of_last_token [1, V], new_pages).
    """
    b, l = tokens.shape
    block_size = pages[0][0].shape[1]
    scratch = init_kv_cache(config, b, l)
    logits, dense = prefill_with_cache(
        params, tokens, scratch, config, last_index=last_index
    )
    pos = jnp.arange(l)
    valid = pos <= last_index
    phys = jnp.where(valid, page_table[pos // block_size], 0)
    off = jnp.where(valid, pos % block_size, 0)
    new_pages = []
    for (k_pages, v_pages), (dense_k, dense_v) in zip(pages, dense):
        new_pages.append(
            (
                k_pages.at[phys, off].set(dense_k[0]),
                v_pages.at[phys, off].set(dense_v[0]),
            )
        )
    return logits, new_pages


def decode_step_paged_attn(
    params, tokens, positions, page_tables, pages, config: LlamaConfig, attn
):
    """One continuous-batching decode step over the block pool.

    ``tokens`` [B] (each sequence's most recent token), ``positions`` [B]
    (that token's context position — PER SEQUENCE, unlike
    :func:`decode_step`'s shared scalar), ``page_tables`` [B, NB]
    physical block ids, at any width ``NB`` the caller chooses: the
    engine slices it to a bucket of the live batch's longest sequence,
    so attention cost follows actual context instead of ``max_seq_len``.
    Writes each token's K/V into its sequence's current block, then
    reads the ragged pages through ``attn``, the load-time choice of
    ``models/paged_attention.py`` (its ``T = 1`` case: one query row a
    sequence, valid slots ``<= position``). Padding lanes (page table
    all zeros, position 0) write to the trash block and produce garbage
    logits the caller discards. Returns (logits [B, V], new_pages)."""
    b = tokens.shape[0]
    block_size = pages[0][0].shape[1]
    pos2 = positions[:, None]  # [B, 1]
    phys = page_tables[jnp.arange(b), positions // block_size]  # [B]
    off = positions % block_size
    x = params["embed"][tokens][:, None, :].astype(config.dtype)
    new_pages = []
    for layer, (k_pages, v_pages) in zip(params["layers"], pages):
        normed = rms_norm(x, layer["attn_norm"], config.norm_eps)
        q = jnp.einsum("bld,dhk->blhk", normed, layer["wq"])
        k = jnp.einsum("bld,dhk->blhk", normed, layer["wk"])
        v = jnp.einsum("bld,dhk->blhk", normed, layer["wv"])
        q = _rope(q, pos2, config.rope_theta)
        k = _rope(k, pos2, config.rope_theta)
        # scatter this step's K/V, THEN attend: the current position's
        # entry must be visible to its own attention
        k_pages = k_pages.at[phys, off].set(k[:, 0])
        v_pages = v_pages.at[phys, off].set(v[:, 0])
        new_pages.append((k_pages, v_pages))
        out = attn(q, k_pages, v_pages, page_tables, pos2)[:, 0]
        x = x + jnp.einsum("bhk,hkd->bd", out, layer["wo"])[:, None, :]
        x = x + _mlp_block(layer, rms_norm(x, layer["mlp_norm"], config.norm_eps))
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    logits = jnp.einsum("bd,dv->bv", x[:, 0], params["lm_head"])
    return logits.astype(jnp.float32), new_pages


def decode_step_paged_multi(
    params, tokens, positions, lengths, page_tables, pages,
    config: LlamaConfig, attn
):
    """Speculative-verify decode step: K+1 query positions per sequence
    in ONE ragged paged-attention call (the batched-verify half of
    draft-propose speculative decoding).

    ``tokens`` [B, T] (row 0 = each sequence's last real token, rows
    ``1..`` its draft candidates), ``positions`` [B, T] the absolute
    context position of every row, ``lengths`` [B] how many leading rows
    of each lane are real — rows at index >= ``lengths[b]`` are padding:
    their K/V writes are redirected to the trash block and their logits
    are garbage the caller discards.  All T rows' K/V are scattered
    BEFORE the attention read, and ``attn``'s per-position validity mask
    (``slot <= positions[b, t]``) is what gives row ``t`` exactly its
    own speculative prefix — so the T logits rows equal T sequential
    :func:`decode_step_paged_attn` calls feeding the draft tokens one at
    a time.  Returns (logits [B, T, V], new_pages).
    """
    b, t = tokens.shape
    block_size = pages[0][0].shape[1]
    row_valid = jnp.arange(t)[None, :] < lengths[:, None]  # [B, T]
    phys = jnp.where(
        row_valid,
        jnp.take_along_axis(
            page_tables, positions // block_size, axis=1
        ),
        0,
    )  # [B, T]
    off = jnp.where(row_valid, positions % block_size, 0)
    x = params["embed"][tokens].astype(config.dtype)  # [B, T, D]
    new_pages = []
    for layer, (k_pages, v_pages) in zip(params["layers"], pages):
        normed = rms_norm(x, layer["attn_norm"], config.norm_eps)
        q = jnp.einsum("btd,dhk->bthk", normed, layer["wq"])
        k = jnp.einsum("btd,dhk->bthk", normed, layer["wk"])
        v = jnp.einsum("btd,dhk->bthk", normed, layer["wv"])
        q = _rope(q, positions, config.rope_theta)
        k = _rope(k, positions, config.rope_theta)
        # scatter every verify row's K/V, THEN attend: row t's prefix
        # rows 0..t-1 must be visible to its attention (the per-position
        # validity mask keeps rows t+1.. invisible)
        k_pages = k_pages.at[phys, off].set(k)
        v_pages = v_pages.at[phys, off].set(v)
        new_pages.append((k_pages, v_pages))
        out = attn(q, k_pages, v_pages, page_tables, positions)
        x = x + jnp.einsum("bthk,hkd->btd", out, layer["wo"])
        x = x + _mlp_block(layer, rms_norm(x, layer["mlp_norm"], config.norm_eps))
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    logits = jnp.einsum("btd,dv->btv", x, params["lm_head"])
    return logits.astype(jnp.float32), new_pages


def prefill_suffix_into_pages(
    params, tokens, page_table, pages, last_index, start_index,
    prefix_blocks: int, config: LlamaConfig
):
    """Prefill ONLY a prompt's unshared suffix, attending to its shared
    prefix through the block pool (the compute half of copy-on-write
    prefix sharing: matched blocks are read, never recomputed, never
    written).

    ``tokens`` [1, L] holds the suffix (``context[start_index:]``) padded
    to the bucket length L; ``last_index`` is the suffix-LOCAL index of
    the real last token and ``start_index`` the absolute position of
    ``tokens[0, 0]`` (both traced scalars; ``start_index`` is always
    block-aligned — prefix matches are whole blocks).  ``prefix_blocks``
    is STATIC (a power-of-two bucket >= ``start_index // block_size``):
    it fixes the gather width for the shared-prefix context, and slack
    blocks in the bucket are masked by absolute position, so gathering a
    slot the suffix scatter just wrote (or the trash block) can never
    leak into attention.  Returns (logits_of_last_token [1, V],
    new_pages); only blocks at index >= ``start_index // block_size``
    are written — shared blocks stay untouched, which is the engine's
    COW invariant."""
    b, l = tokens.shape
    block_size = pages[0][0].shape[1]
    kv_heads = config.n_kv_heads
    pos = jnp.arange(l)
    abs_pos = start_index + pos  # [L] absolute positions of the suffix
    valid_w = pos <= last_index
    phys_w = jnp.where(valid_w, page_table[abs_pos // block_size], 0)
    off_w = jnp.where(valid_w, abs_pos % block_size, 0)
    s0 = prefix_blocks * block_size
    # key-validity masks: prefix slot s is real iff s < start_index
    # (bucket slack and trash land above it); suffix key j needs
    # causality within the suffix and j <= last_index (padding tail)
    prefix_valid = (jnp.arange(s0) < start_index)[None, :]  # [1, s0]
    suffix_valid = (pos[:, None] >= pos[None, :]) & (
        pos[None, :] <= last_index
    )  # [L, L]
    mask = jnp.concatenate(
        [jnp.broadcast_to(prefix_valid, (l, s0)), suffix_valid], axis=1
    )  # [L, s0+L]
    x = params["embed"][tokens].astype(config.dtype)
    new_pages = []
    g = config.n_heads // kv_heads
    for layer, (k_pages, v_pages) in zip(params["layers"], pages):
        normed = rms_norm(x, layer["attn_norm"], config.norm_eps)
        q = jnp.einsum("bld,dhk->blhk", normed, layer["wq"])
        k = jnp.einsum("bld,dhk->blhk", normed, layer["wk"])
        v = jnp.einsum("bld,dhk->blhk", normed, layer["wv"])
        q = _rope(q, abs_pos[None, :], config.rope_theta)
        k = _rope(k, abs_pos[None, :], config.rope_theta)
        k_pages = k_pages.at[phys_w, off_w].set(k[0])
        v_pages = v_pages.at[phys_w, off_w].set(v[0])
        new_pages.append((k_pages, v_pages))
        k_pref = k_pages[page_table[:prefix_blocks]].reshape(
            1, s0, kv_heads, config.head_dim
        )
        v_pref = v_pages[page_table[:prefix_blocks]].reshape(
            1, s0, kv_heads, config.head_dim
        )
        k_all = jnp.concatenate([k_pref.astype(k.dtype), k], axis=1)
        v_all = jnp.concatenate([v_pref.astype(v.dtype), v], axis=1)
        qg = q.reshape(b, l, kv_heads, g, config.head_dim)
        scores = jnp.einsum(
            "blkgd,bskd->bkgls", qg, k_all,
            preferred_element_type=jnp.float32,
        ) / np.sqrt(config.head_dim)
        scores = jnp.where(mask[None, None, None], scores, -1e30)
        weights = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum(
            "bkgls,bskd->blkgd", weights, v_all.astype(weights.dtype)
        ).reshape(b, l, config.n_heads, config.head_dim).astype(x.dtype)
        x = x + jnp.einsum("blhk,hkd->bld", out, layer["wo"])
        x = x + _mlp_block(layer, rms_norm(x, layer["mlp_norm"], config.norm_eps))
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    last = jnp.take_along_axis(
        x, jnp.full((b, 1, 1), last_index, dtype=jnp.int32).repeat(
            x.shape[-1], axis=-1
        ), axis=1,
    )[:, 0]
    logits = jnp.einsum("bd,dv->bv", last, params["lm_head"])
    return logits.astype(jnp.float32), new_pages


def generate(
    params,
    prompt_tokens: jnp.ndarray,
    config: LlamaConfig,
    max_new_tokens: int,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
):
    """Greedy/temperature generation with lax.scan (no Python decode loop).

    Returns [B, max_new_tokens] generated token ids.
    """
    b, prompt_len = prompt_tokens.shape
    cache = init_kv_cache(config, b, prompt_len + max_new_tokens)
    logits, cache = prefill_with_cache(params, prompt_tokens, cache, config)
    if rng is None:
        rng = jax.random.PRNGKey(0)

    def sample(logits, key):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / temperature, axis=-1).astype(
            jnp.int32
        )

    first_token = sample(logits, rng)

    def step(carry, key):
        token, position, cache = carry
        logits, cache = decode_step(params, token, position, cache, config)
        next_token = sample(logits, key)
        return (next_token, position + 1, cache), token

    keys = jax.random.split(rng, max_new_tokens)
    (_, _, _), tokens = jax.lax.scan(
        step,
        (first_token, jnp.int32(prompt_len), cache),
        keys,
    )
    return tokens.T  # [B, max_new_tokens]


# ---------------------------------------------------------------------------
# the engine's seam (models/engine_model.py)
# ---------------------------------------------------------------------------


ENGINE_MODEL = EngineModel(
    name="llama",
    init_params=init_params,
    # every layer keeps every block: one full group
    cache_groups=lambda config: [
        CacheGroup(FULL, tuple(range(config.n_layers)))
    ],
    init_pages=lambda config, num_blocks, block_size: init_kv_pages(
        config, num_blocks[0], block_size
    ),
    # the prefills are plain XLA whatever the kernel choice
    prefill=lambda *args: prefill_into_pages(*args[:-1]),
    decode=lambda *args: decode_step_paged_attn(*args[:-1], args[-1].attn),
    prefill_suffix=lambda *args: prefill_suffix_into_pages(*args[:-1]),
    verify=lambda *args: decode_step_paged_multi(*args[:-1], args[-1].attn),
    param_specs=param_specs,
    heads=lambda config: (config.n_heads, config.n_kv_heads),
)
