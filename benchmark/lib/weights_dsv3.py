"""GigaChat3.1-702B-A36B's (``deepseek_v3``) weights from a seed, made on
the device by jitted programs, one layer to a call (`lib/weights.py` has
the reasons: both sides of `correct` call THESE functions, and the same
program on the same device gives the same bits).

``model`` is `config.json`'s ``model`` group. An expert's weights depend
on the seed, the layer and the expert's index among ALL the experts the
router scores, not on which of them are held: every share of a layer
draws the same expert 37, so the shares add up to the whole layer. The
shared expert, the router and everything else of a layer are the same on
every share.

The published ``kv_b_proj`` [512 -> 64 x (128 + 192)] is held as its two
column groups, ``w_uk`` [512, 64, 128] and ``w_uv`` [512, 64, 192]: the
program's decode uses each alone. Every norm's scale (the two latents'
among them) is near 1 and not 1, and the router's bias re-orders the top
scores: left out of the program, each shows (`tests/test_deepseek_v3.py`).
The up-projections are drawn at ``1 / sqrt(rank)``, so that queries, keys
and values are of unit size and the attention is neither flat nor
one-hot.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.weights import _norm_weight, _normal, seed_key


def held(model: dict) -> tuple:
    """(first, count) of the experts held here, of ``experts_routed_over``."""
    return (int(model.get("experts_held_first", 0)),
            int(model["n_routed_experts"]))


def expert_layer(model: dict, index: int) -> bool:
    return index >= int(model["first_k_dense_replace"])


def shape_key(model: dict) -> tuple:
    """The numbers a layer's weights depend on, hashable."""
    return tuple(int(model[k]) for k in (
        "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "intermediate_size", "moe_intermediate_size", "n_shared_experts",
        "experts_routed_over"))


@functools.lru_cache(maxsize=None)
def _layer_fn(shapes: tuple, experts: bool, count: int):
    (d, h, rq, rkv, nope, rope, dv, f_dense, f_expert, n_shared,
     routed) = shapes
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
            "mlp_norm": _norm_weight(k[1], d),
            "q_norm": _norm_weight(k[2], rq),
            "kv_norm": _norm_weight(k[3], rkv),
            "w_dq": _normal(k[4], (d, rq), s),
            "w_uq": _normal(k[5], (rq, h, nope + rope), 1.0 / np.sqrt(rq)),
            "w_dkv": _normal(k[6], (d, rkv + rope), s),
            "w_uk": _normal(k[7], (rkv, h, nope), 1.0 / np.sqrt(rkv)),
            "w_uv": _normal(k[8], (rkv, h, dv), 1.0 / np.sqrt(rkv)),
            "w_o": _normal(k[9], (h, dv, d), 1.0 / np.sqrt(h * dv)),
        }
        if not experts:
            layer.update(swiglu(k[10], f_dense))
            return layer
        layer["router"] = _normal(k[10], (d, routed), s)
        # the top scores of 256 lie about 0.01 apart: a bias of 0.02
        # re-orders them for most tokens, and moves a group's score (the
        # sum of its two best) by as much as the groups lie apart,
        # without deciding either selection alone
        layer["router_bias"] = 0.02 * jax.random.normal(
            k[11], (routed,), jnp.float32)
        layer["shared"] = swiglu(k[12], f_expert * n_shared)

        def expert(e):
            return swiglu(jax.random.fold_in(k[13], e), f_expert)

        layer["experts"] = jax.vmap(expert)(first + jnp.arange(count))
        return layer

    return make


def layer(seed: int, index: int, model: dict, held_experts=None) -> dict:
    """bf16 weights of layer ``index`` in the program's layout
    (`client_tpu/models/deepseek_v3.py`); ``held_experts`` (first, count)
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
