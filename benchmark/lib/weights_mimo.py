"""MiMo-V2-Flash's weights from a seed, made on the device by jitted
programs, one layer to a call (`lib/weights.py` has the reasons: both
sides of `correct` call THESE functions, and the same program on the same
device gives the same bits).

``model`` is `config.json`'s ``model`` group. An expert's weights depend
on the seed, the layer and the expert's index among ALL the experts the
router scores, not on which of them are held: every share of a layer
draws the same expert 37, so the shares add up to the whole layer.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.weights import _norm_weight, _normal, seed_key


def held(model: dict) -> tuple:
    """(first, count) of the experts held here, of ``experts_routed_over``."""
    return int(model.get("experts_held_first", 0)), int(model["n_routed_experts"])


def shape_key(model: dict) -> tuple:
    """The numbers a layer's weights depend on, hashable."""
    for twin in ("num_attention_heads", "head_dim", "v_head_dim"):
        if int(model["swa_" + twin]) != int(model[twin]):
            raise ValueError(f"swa_{twin} differs from {twin}: window and "
                             "full layers share their query heads here")
    return tuple(int(model[k]) for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "swa_num_key_value_heads", "head_dim", "v_head_dim",
        "intermediate_size", "moe_intermediate_size", "experts_routed_over",
        "vocab_size", "num_hidden_layers"))


@functools.lru_cache(maxsize=None)
def _layer_fn(shapes: tuple, window: bool, experts: bool, count: int):
    d, h, kv_full, kv_window, dk, dv, f_dense, f_expert, routed, _, layers = shapes
    kv = kv_window if window else kv_full
    s = 1.0 / np.sqrt(d)

    @jax.jit
    def make(key, index, first):
        k = jax.random.split(jax.random.fold_in(key, index), 12)
        layer = {
            "attn_norm": _norm_weight(k[0], d),
            "mlp_norm": _norm_weight(k[1], d),
            "wq": _normal(k[2], (d, h, dk), s),
            "wk": _normal(k[3], (d, kv, dk), s),
            "wv": _normal(k[4], (d, kv, dv), s),
            "wo": _normal(k[5], (h, dv, d), s / np.sqrt(2 * layers)),
        }
        if window:
            # a few units: beside at most 128 keys of about unit logits,
            # a sink left out moves the weights by tenths
            layer["sink"] = 2.0 * jax.random.normal(k[6], (h,), jnp.float32)
        if not experts:
            layer.update(
                w_gate=_normal(k[7], (d, f_dense), s),
                w_up=_normal(k[8], (d, f_dense), s),
                w_down=_normal(k[9], (f_dense, d), 1.0 / np.sqrt(f_dense)))
            return layer
        layer["router"] = _normal(k[7], (d, routed), s)
        # the top scores of 256 lie about 0.01 apart: a bias of 0.02
        # re-orders them for most tokens, and as a weight it moves every
        # expert's share by per cent. Larger, it decides the selection
        # alone (at 0.1 every token chose the same few experts: half the
        # held experts untouched in a step, the fullest at 5 times the
        # mean), which is the opposite of what training fits it for
        layer["router_bias"] = 0.02 * jax.random.normal(
            k[8], (routed,), jnp.float32)

        def expert(e):
            ke = jax.random.split(jax.random.fold_in(k[9], e), 3)
            return {
                "w_gate": _normal(ke[0], (d, f_expert), s),
                "w_up": _normal(ke[1], (d, f_expert), s),
                "w_down": _normal(ke[2], (f_expert, d), 1.0 / np.sqrt(f_expert)),
            }

        layer["experts"] = jax.vmap(expert)(first + jnp.arange(count))
        return layer

    return make


def layer(seed: int, index: int, model: dict, held_experts=None) -> dict:
    """bf16 weights of layer ``index`` in the program's layout
    (`client_tpu/models/mimo_v2.py`); ``held_experts`` (first, count)
    defaults to the configuration's share."""
    first, count = held_experts or held(model)
    make = _layer_fn(shape_key(model),
                     bool(model["hybrid_layer_pattern"][index]),
                     bool(model["moe_layer_freq"][index]), count)
    return make(seed_key(seed), jnp.int32(index), jnp.int32(first))


@functools.lru_cache(maxsize=None)
def _top_fn(d: int, vocab: int):
    @jax.jit
    def make(key):
        k = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
        return {
            "embed": _normal(k[0], (vocab, d), 1.0),
            "final_norm": _norm_weight(k[1], d),
            "lm_head": _normal(k[2], (d, vocab), 1.0 / np.sqrt(d)),
        }

    return make


def top(seed: int, model: dict) -> dict:
    return _top_fn(int(model["hidden_size"]),
                   int(model["vocab_size"]))(seed_key(seed))


def params(seed: int, model: dict) -> dict:
    """The whole pytree `LlmEngineModel(params=...)` takes."""
    out = dict(top(seed, model))
    out["layers"] = [layer(seed, i, model)
                     for i in range(int(model["num_hidden_layers"]))]
    return out
