"""Qwen3-Next-80B-A3B-Instruct's (``qwen3_next``) weights from a seed,
made on the device by jitted programs, one layer to a call
(`lib/weights.py` has the reasons: both sides of `correct` call THESE
functions, and the same program on the same device gives the same bits).

``model`` is `config.json`'s ``model`` group. An expert's weights depend
on the seed, the layer and the expert's index among ALL the experts the
router scores, not on which of them are held: every share of a layer
draws the same expert 37, so the shares add up to the whole layer. The
shared expert with its gate, the router and everything else of a layer
are the same on every share.

Norm scales lie ``0.1 N(0,1)`` around their neutral value: 0 for the
``n(x) (1 + w)`` norms of the residual stream, the ``q`` / ``k`` heads and
the final norm, 1 for the DeltaNet's output norm ``w n(o) silu(z)``; left
out of the program, each shows (`tests/test_qwen3_next.py`). ``A_log`` and
``dt_bias`` are NOT the published initial draw (``A`` uniform on 0-16,
which forgets within one token: a state that is never carried would pass
every comparison) but such that a head's decay a token spreads over about
0.9 to 0.999, half-lives of 7 to 700 tokens (:func:`decay_draw`). The
convolution's taps are drawn at 0.5, so that each of the four weighs.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.weights import _normal, seed_key


def held(model: dict) -> tuple:
    """(first, count) of the experts held here, of ``experts_routed_over``."""
    return int(model.get("experts_held_first", 0)), int(model["num_experts"])


def delta_layer(model: dict, index: int) -> bool:
    """Layer ``index`` is a Gated DeltaNet; every
    ``full_attention_interval``-th layer is gated full attention."""
    return (index + 1) % int(model["full_attention_interval"]) != 0


def rotary_dim(model: dict) -> int:
    return int(int(model["head_dim"]) * float(model["partial_rotary_factor"]))


def conv_dim(model: dict) -> int:
    """Channels of the DeltaNet's convolution: q, k and v side by side."""
    return (2 * int(model["linear_num_key_heads"])
            * int(model["linear_key_head_dim"])
            + int(model["linear_num_value_heads"])
            * int(model["linear_value_head_dim"]))


def shape_key(model: dict) -> tuple:
    """The numbers a layer's weights depend on, hashable."""
    return tuple(int(model[k]) for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "linear_num_key_heads", "linear_num_value_heads",
        "linear_key_head_dim", "linear_value_head_dim",
        "linear_conv_kernel_dim", "moe_intermediate_size",
        "shared_expert_intermediate_size", "experts_routed_over"))


def _around(key, size, neutral):
    return (neutral + 0.1 * jax.random.normal(key, (size,), jnp.float32)
            ).astype(jnp.bfloat16)


def decay_draw(key, heads: int):
    """``(A_log, dt_bias)`` [heads] float32: ``-log`` of a head's decay a
    token is ``exp(A_log) softplus(a + dt_bias)``; it is drawn log-uniform
    on 1e-3..1e-1 at ``softplus(dt_bias) = 0.5`` (the projection's ``a`` is
    of unit size, which moves it by a factor of three either way)."""
    rate = jnp.exp(jax.random.uniform(
        key, (heads,), jnp.float32, np.log(1e-3), np.log(1e-1)))
    dt_bias = jnp.full((heads,), np.log(np.expm1(0.5)), jnp.float32)
    return jnp.log(rate / 0.5), dt_bias


@functools.lru_cache(maxsize=None)
def _layer_fn(shapes: tuple, delta: bool, count: int):
    (d, h, kv, dh, hk, hv, dk, dv, taps, f_expert, f_shared,
     routed) = shapes
    s = 1.0 / np.sqrt(d)
    channels, values = 2 * hk * dk + hv * dv, hv * dv

    def swiglu(key, f):
        k = jax.random.split(key, 3)
        return {"w_gate": _normal(k[0], (d, f), s),
                "w_up": _normal(k[1], (d, f), s),
                "w_down": _normal(k[2], (f, d), 1.0 / np.sqrt(f))}

    @jax.jit
    def make(key, index, first):
        k = jax.random.split(jax.random.fold_in(key, index), 16)
        layer = {"mixer_norm": _around(k[0], d, 0.0),
                 "mlp_norm": _around(k[1], d, 0.0)}
        if delta:
            a_log, dt_bias = decay_draw(k[2], hv)
            layer.update(
                # columns [q | k | v | z]; the projections' outputs are
                # of unit size, as are b and a
                w_qkvz=_normal(k[3], (d, channels + values), s),
                w_ba=_normal(k[4], (d, 2 * hv), s),
                conv_w=_normal(k[5], (taps, channels), 0.5),
                A_log=a_log, dt_bias=dt_bias,
                gdn_norm=_around(k[6], dv, 1.0),
                w_out=_normal(k[7], (values, d), 1.0 / np.sqrt(values)),
            )
        else:
            layer.update(
                q_norm=_around(k[2], dh, 0.0),
                k_norm=_around(k[3], dh, 0.0),
                # a head's columns [q | gate]: the gate's logits are of
                # unit size, so it lies between 0.1 and 0.9
                wq=_normal(k[4], (d, h, 2 * dh), s),
                wk=_normal(k[5], (d, kv, dh), s),
                wv=_normal(k[6], (d, kv, dh), s),
                wo=_normal(k[7], (h, dh, d), 1.0 / np.sqrt(h * dh)),
            )
        layer["router"] = _normal(k[8], (d, routed), s)
        layer["shared"] = dict(swiglu(k[9], f_shared),
                               w_sg=_normal(k[10], (d,), s))

        def expert(e):
            return swiglu(jax.random.fold_in(k[11], e), f_expert)

        layer["experts"] = jax.vmap(expert)(first + jnp.arange(count))
        return layer

    return make


def layer(seed: int, index: int, model: dict, held_experts=None) -> dict:
    """Weights of layer ``index`` in the program's layout
    (`client_tpu/models/qwen3_next.py`), bf16 but ``A_log`` and
    ``dt_bias`` (float32); ``held_experts`` (first, count) defaults to
    the configuration's share."""
    first, count = held_experts or held(model)
    make = _layer_fn(shape_key(model), delta_layer(model, index), count)
    return make(seed_key(seed), jnp.int32(index), jnp.int32(first))


@functools.lru_cache(maxsize=None)
def _top_fn(d: int, vocab: int):
    @jax.jit
    def make(key):
        k = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
        return {
            "embed": _normal(k[0], (vocab, d), 1.0),
            "final_norm": _around(k[1], d, 0.0),
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
