"""Ouro-2.6B's (``ouro``) weights from a seed, made on the device by
jitted programs, one layer to a call (`lib/weights.py` has the reasons:
both sides of `correct` call THESE functions, and the same program on
the same device gives the same bits).

``model`` is `config.json`'s ``model`` group. The program
(`client_tpu/models/ouro.py`) holds every per-layer tensor stacked
``[num_hidden_layers, ...]`` for its rolled loops: :func:`params` stacks
what :func:`layer` draws, a tensor at a time, and the reference takes
:func:`layer`'s one layer at a time, once a pass.

Every norm's scale lies ``0.1 N(0,1)`` around its centre, so that each
left out of the program shows (`tests/test_ouro.py`): 1 for the norms on
a sublayer's input and the one that closes a pass, :func:`out_scale` for
the two on a sublayer's OUTPUT (0.41 at 48 layers, 1 at a toy's depth).
A pass adds ``2 x layers`` normed outputs to a stream of unit size; at
a centre of 1 each is as large as the stream itself, 192 such steps with
random weights are chaotic, and bf16's own rounding arrives at the
logits as a quarter of their size: sound chip runs read
``served_gap_mean`` 0.06-0.19 with a quarter to a half of the decided
tokens off the reference's best, which no limit can tell from a fault
(`PERF.md` section 2). At :func:`out_scale` a pass grows the stream
fourfold whatever the depth; sound runs read 0.003-0.012 and the int8
control 0.044-0.067. The embedding is drawn at 1, the size the stream
has after every pass's closing norm, the projections at ``1 /
sqrt(fan-in)``; the exit gate's weight at ``1 / sqrt(hidden)`` and its
bias at 1, so that its probabilities spread over (0, 1): a gate that
moved a logit would show.

The program's layout: ``wq``, ``wk``, ``wv`` are ``[out, in]`` (a
projection's heads side by side in its rows), ``wo`` ``[heads * head_dim,
hidden]``, the MLP's ``[in, out]``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.weights import _norm_weight, _normal, seed_key


def shape_key(model: dict) -> tuple:
    """The numbers a layer's weights depend on, hashable."""
    return tuple(int(model[k]) for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "intermediate_size", "num_hidden_layers"))


def out_scale(layers: int) -> float:
    """The centre of the two output norms' scales: a pass's ``2 x
    layers`` outputs add up to ``sqrt(1 + 16)`` times the unit stream
    they join, at most 1 each."""
    return min(1.0, float(np.sqrt(8.0 / layers)))


@functools.lru_cache(maxsize=None)
def _layer_fn(shapes: tuple):
    d, h, kv, dh, f, layers = shapes
    s = 1.0 / np.sqrt(d)
    out = jnp.bfloat16(out_scale(layers))

    @jax.jit
    def make(key, index):
        k = jax.random.split(jax.random.fold_in(key, index), 11)
        return {
            "attn_norm": _norm_weight(k[0], d),
            "attn_out_norm": out * _norm_weight(k[1], d),
            "mlp_norm": _norm_weight(k[2], d),
            "mlp_out_norm": out * _norm_weight(k[3], d),
            "wq": _normal(k[4], (h * dh, d), s),
            "wk": _normal(k[5], (kv * dh, d), s),
            "wv": _normal(k[6], (kv * dh, d), s),
            "wo": _normal(k[7], (h * dh, d), 1.0 / np.sqrt(h * dh)),
            "w_gate": _normal(k[8], (d, f), s),
            "w_up": _normal(k[9], (d, f), s),
            "w_down": _normal(k[10], (f, d), 1.0 / np.sqrt(f)),
        }

    return make


def layer(seed: int, index: int, model: dict) -> dict:
    """bf16 weights of layer ``index``, the same in every pass."""
    return _layer_fn(shape_key(model))(seed_key(seed), jnp.int32(index))


@functools.lru_cache(maxsize=None)
def _top_fn(d: int, vocab: int):
    @jax.jit
    def make(key):
        k = jax.random.split(jax.random.fold_in(key, 1 << 20), 5)
        return {"embed": _normal(k[0], (vocab, d), 1.0),
                "final_norm": _norm_weight(k[1], d),
                "lm_head": _normal(k[2], (d, vocab), 1.0 / np.sqrt(d)),
                "exit_w": _normal(k[3], (d,), 1.0 / np.sqrt(d)),
                "exit_b": _normal(k[4], (), 1.0)}

    return make


def top(seed: int, model: dict) -> dict:
    """The embedding, the norm that closes a pass, the untied head and
    the exit gate."""
    return _top_fn(int(model["hidden_size"]),
                   int(model["vocab_size"]))(seed_key(seed))


def params(seed: int, model: dict) -> dict:
    """The whole pytree `LlmEngineModel(params=...)` takes, the layers'
    tensors stacked ``[num_hidden_layers, ...]``, one tensor at a time
    (the unstacked draws of a tensor go as its stack is made)."""
    layers = [layer(seed, i, model)
              for i in range(int(model["num_hidden_layers"]))]
    out = dict(top(seed, model))
    out["layers"] = {name: jnp.stack([w.pop(name) for w in layers])
                     for name in sorted(layers[0])}
    return out
