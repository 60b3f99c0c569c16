"""Trinity-Mini's (``afmoe``) weights from a seed, made on the device by
jitted programs, one layer to a call (`lib/weights.py` has the reasons:
both sides of `correct` call THESE functions, and the same program on the
same device gives the same bits).

``model`` is `config.json`'s ``model`` group. An expert's weights depend
on the seed, the layer and the expert's index among ALL the experts the
router scores, not on which of them are held: every share of a layer
draws the same expert 37, so the shares add up to the whole layer. The
shared expert, the router and everything else of a layer are the same on
every share.

Every norm's scale is near 1 and not 1, and the router's bias re-orders
the top scores: left out of the program, each shows (`tests/test_afmoe.py`).
The embedding is drawn at ``1 / sqrt(hidden)``, so that scaled by
``sqrt(hidden)`` (``mup_enabled``) it enters the residual stream at the
size of one sublayer's normed output, and every layer weighs in the
logits.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.weights import _norm_weight, _normal, seed_key


def held(model: dict) -> tuple:
    """(first, count) of the experts held here, of ``experts_routed_over``."""
    return int(model.get("experts_held_first", 0)), int(model["num_experts"])


def window_layer(model: dict, index: int) -> bool:
    return model["layer_types"][index] == "sliding_attention"


def expert_layer(model: dict, index: int) -> bool:
    return index >= int(model["num_dense_layers"])


def shape_key(model: dict) -> tuple:
    """The numbers a layer's weights depend on, hashable."""
    return tuple(int(model[k]) for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "intermediate_size", "moe_intermediate_size",
        "num_shared_experts", "experts_routed_over"))


@functools.lru_cache(maxsize=None)
def _layer_fn(shapes: tuple, experts: bool, count: int):
    d, h, kv, dh, f_dense, f_expert, n_shared, routed = shapes
    s = 1.0 / np.sqrt(d)

    def swiglu(key, f):
        k = jax.random.split(key, 3)
        return {"w_gate": _normal(k[0], (d, f), s),
                "w_up": _normal(k[1], (d, f), s),
                "w_down": _normal(k[2], (f, d), 1.0 / np.sqrt(f))}

    @jax.jit
    def make(key, index, first):
        k = jax.random.split(jax.random.fold_in(key, index), 16)
        layer = {
            "attn_norm": _norm_weight(k[0], d),
            "post_attn_norm": _norm_weight(k[1], d),
            "mlp_norm": _norm_weight(k[2], d),
            "post_mlp_norm": _norm_weight(k[3], d),
            "q_norm": _norm_weight(k[4], dh),
            "k_norm": _norm_weight(k[5], dh),
            "wq": _normal(k[6], (d, h, dh), s),
            "wk": _normal(k[7], (d, kv, dh), s),
            "wv": _normal(k[8], (d, kv, dh), s),
            # the output gate's logits are of about unit size: the gate
            # lies between 0.1 and 0.9 and not at one half
            "wg": _normal(k[9], (d, h, dh), s),
            "wo": _normal(k[10], (h, dh, d), s),
        }
        if not experts:
            layer.update(swiglu(k[11], f_dense))
            return layer
        layer["router"] = _normal(k[11], (d, routed), s)
        # the top scores of 128 lie about 0.01 to 0.02 apart: a bias of
        # 0.02 re-orders them for most tokens without deciding the
        # selection alone (`weights_mimo.py` has what a larger one does)
        layer["router_bias"] = 0.02 * jax.random.normal(
            k[12], (routed,), jnp.float32)
        layer["shared"] = swiglu(k[13], f_expert * n_shared)

        def expert(e):
            return swiglu(jax.random.fold_in(k[14], e), f_expert)

        layer["experts"] = jax.vmap(expert)(first + jnp.arange(count))
        return layer

    return make


def layer(seed: int, index: int, model: dict, held_experts=None) -> dict:
    """bf16 weights of layer ``index`` in the program's layout
    (`client_tpu/models/afmoe.py`); ``held_experts`` (first, count)
    defaults to the configuration's share."""
    first, count = held_experts or held(model)
    make = _layer_fn(shape_key(model), expert_layer(model, index), count)
    return make(seed_key(seed), jnp.int32(index), jnp.int32(first))


@functools.lru_cache(maxsize=None)
def _top_fn(d: int, vocab: int):
    @jax.jit
    def make(key):
        k = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
        return {
            "embed": _normal(k[0], (vocab, d), 1.0 / np.sqrt(d)),
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
