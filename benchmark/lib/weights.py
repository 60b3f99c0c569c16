"""Weights from a seed, made on the device by jitted programs.

Both sides of the `correct` comparison call THESE functions: the model files
under ``benchmark/configs/*/model_repository`` (inside the server child) and
the plain references (a child of their own, after the server has gone). The
same jitted program on the same device gives the same bits, so the reference
takes nothing the program has made.

A decoder's weights come one layer to a call (one compiled program, called
``num_hidden_layers`` times), so the reference can hold one layer at a time.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key for any whole number up to 2**63: the low 31 bits seed
    it and the rest are folded in (``--seed`` may pass 2**31)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must not be negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def decoder_dims(model: dict) -> tuple:
    """(d, heads, kv_heads, head_dim, d_ff, vocab, layers) of an HF-style
    decoder config as `config.json`'s ``model`` group holds it."""
    d = int(model["hidden_size"])
    heads = int(model["num_attention_heads"])
    head_dim = int(model.get("head_dim") or d // heads)
    if heads * head_dim != d:
        raise ValueError("the engine's decoder needs heads * head_dim == hidden")
    return (d, heads, int(model["num_key_value_heads"]), head_dim,
            int(model["intermediate_size"]), int(model["vocab_size"]),
            int(model["num_hidden_layers"]))


def _normal(key, shape, scale):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
        jnp.bfloat16)


def _norm_weight(key, d):
    # near 1 and not 1, so that a norm weight left out would show
    return (1.0 + 0.1 * jax.random.normal(key, (d,), jnp.float32)).astype(
        jnp.bfloat16)


@functools.lru_cache(maxsize=None)
def _decoder_layer_fn(dims: tuple):
    d, h, kv, hd, f, _, layers = dims

    @jax.jit
    def make(key, index):
        k = jax.random.split(jax.random.fold_in(key, index), 9)
        s = 1.0 / np.sqrt(d)
        return {
            "wq": _normal(k[0], (d, h, hd), s),
            "wk": _normal(k[1], (d, kv, hd), s),
            "wv": _normal(k[2], (d, kv, hd), s),
            "wo": _normal(k[3], (h, hd, d), s / np.sqrt(2 * layers)),
            "w_gate": _normal(k[4], (d, f), s),
            "w_up": _normal(k[5], (d, f), s),
            "w_down": _normal(k[6], (f, d), 1.0 / np.sqrt(f)),
            "attn_norm": _norm_weight(k[7], d),
            "mlp_norm": _norm_weight(k[8], d),
        }

    return make


@functools.lru_cache(maxsize=None)
def _decoder_top_fn(dims: tuple):
    d, _, _, _, _, vocab, _ = dims

    @jax.jit
    def make(key):
        k = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
        return {
            "embed": _normal(k[0], (vocab, d), 1.0),
            "final_norm": _norm_weight(k[1], d),
            "lm_head": _normal(k[2], (d, vocab), 1.0 / np.sqrt(d)),
        }

    return make


def decoder_layer(seed: int, index: int, dims: tuple) -> dict:
    """bf16 weights of layer ``index`` (the engine's layout: ``wq``
    [d, heads, head_dim] ... ``w_down`` [d_ff, d])."""
    return _decoder_layer_fn(dims)(seed_key(seed), jnp.int32(index))


def decoder_top(seed: int, dims: tuple) -> dict:
    return _decoder_top_fn(dims)(seed_key(seed))


def decoder_params(seed: int, dims: tuple) -> dict:
    """The whole pytree `LlmEngineModel(params=...)` takes."""
    params = dict(decoder_top(seed, dims))
    params["layers"] = [decoder_layer(seed, i, dims) for i in range(dims[6])]
    return params
