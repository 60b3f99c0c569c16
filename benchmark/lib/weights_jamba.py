"""AI21-Jamba2-3B's (``jamba``) weights from a seed, made on the device by
jitted programs, one layer to a call (`lib/weights.py` has the reasons:
both sides of `correct` call THESE functions, and the same program on
the same device gives the same bits).

``model`` is `config.json`'s ``model`` group. Layer ``i`` is attention
where ``i mod attn_layer_period = attn_layer_offset`` (the published rule
of ``modeling_jamba.py``), else Mamba; every layer's feed-forward is the
dense SwiGLU (``num_experts`` 1).

Norm scales lie ``0.1 N(0,1)`` around 1 (every norm is ``w n(x)``), ``D``
``0.1 N(0,1)`` around 1, the convolution's four taps and its bias are
drawn at 0.5 so that each weighs; left out of the program, each shows
(`tests/test_jamba.py`). ``A_log = log(1..16)`` a channel and ``b_dt`` the
inverse softplus of a log-uniform draw on 0.001-0.1, both as published;
``W_dt`` is drawn so that ``dl @ W_dt`` is of size 0.35 for a normed
``dl`` of unit size (the published draw gives 0.58), so ``delta`` stays
in about 0.001-0.2 and a channel's decay a token ``exp(delta A)`` spreads
over about 0.04-0.999 (:func:`step_draw`): a state that is never carried
would pass every comparison, and one that never forgets is no Mamba. The
embedding is drawn at ``1 / sqrt(hidden)``, so that the tied head's
logits are of unit size as every other configuration's are; each layer's
first norm takes the size out again.

The program's layout (`client_tpu/models/jamba.py`): ``A_log`` is held
``[d_state, d_inner]``, the channels last, as the state is; ``W_in``'s
columns are ``[u | z]`` and ``W_x``'s ``[dl | B | C]``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.weights import _normal, seed_key


def mamba_layer(model: dict, index: int) -> bool:
    """Layer ``index`` is a Mamba layer; the attention layers are those
    with ``index mod attn_layer_period = attn_layer_offset``."""
    return (index % int(model["attn_layer_period"])
            != int(model["attn_layer_offset"]))


def d_inner(model: dict) -> int:
    return int(model["mamba_expand"]) * int(model["hidden_size"])


def shape_key(model: dict) -> tuple:
    """The numbers a layer's weights depend on, hashable."""
    return tuple(int(model[k]) for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "intermediate_size", "mamba_expand", "mamba_d_state",
        "mamba_d_conv", "mamba_dt_rank"))


def _around(key, size, neutral=1.0, dtype=jnp.bfloat16):
    return (neutral + 0.1 * jax.random.normal(key, (size,), jnp.float32)
            ).astype(dtype)


def step_draw(key, states: int, channels: int):
    """``(A_log [states, channels], b_dt [channels])`` float32: ``A = -1
    .. -states`` a channel, ``softplus(b_dt)`` log-uniform on
    0.001-0.1."""
    step = jnp.exp(jax.random.uniform(
        key, (channels,), jnp.float32, np.log(1e-3), np.log(1e-1)))
    a_log = jnp.log(jnp.arange(1, states + 1, dtype=jnp.float32))
    return (jnp.broadcast_to(a_log[:, None], (states, channels)),
            step + jnp.log(-jnp.expm1(-step)))


@functools.lru_cache(maxsize=None)
def _layer_fn(shapes: tuple, mamba: bool):
    d, h, kv, f, expand, n, taps, r = shapes
    di, dh = expand * d, d // h
    s = 1.0 / np.sqrt(d)

    @jax.jit
    def make(key, index):
        k = jax.random.split(jax.random.fold_in(key, index), 16)
        layer = {"mixer_norm": _around(k[0], d),
                 "mlp_norm": _around(k[1], d),
                 "w_gate": _normal(k[2], (d, f), s),
                 "w_up": _normal(k[3], (d, f), s),
                 "w_down": _normal(k[4], (f, d), 1.0 / np.sqrt(f))}
        if mamba:
            a_log, b_dt = step_draw(k[5], n, di)
            layer.update(
                # u and z, and dl, B and C before their norms, of unit size
                w_in=_normal(k[6], (d, 2 * di), s),
                conv_w=_normal(k[7], (taps, di), 0.5),
                conv_b=_normal(k[8], (di,), 0.5),
                w_x=_normal(k[9], (di, r + 2 * n), 1.0 / np.sqrt(di)),
                dt_norm=_around(k[10], r),
                b_norm=_around(k[11], n),
                c_norm=_around(k[12], n),
                w_dt=_normal(k[13], (r, di), 0.35 / np.sqrt(r)),
                b_dt=b_dt, A_log=a_log,
                D=_around(k[14], di, dtype=jnp.float32),
                w_out=_normal(k[15], (di, d), 1.0 / np.sqrt(di)),
            )
        else:
            layer.update(
                wq=_normal(k[5], (d, h, dh), s),
                wk=_normal(k[6], (d, kv, dh), s),
                wv=_normal(k[7], (d, kv, dh), s),
                wo=_normal(k[8], (h, dh, d), 1.0 / np.sqrt(d)),
            )
        return layer

    return make


def layer(seed: int, index: int, model: dict) -> dict:
    """Weights of layer ``index`` in the program's layout
    (`client_tpu/models/jamba.py`), bf16 but ``A_log``, ``b_dt`` and
    ``D`` (float32)."""
    make = _layer_fn(shape_key(model), mamba_layer(model, index))
    return make(seed_key(seed), jnp.int32(index))


@functools.lru_cache(maxsize=None)
def _top_fn(d: int, vocab: int):
    @jax.jit
    def make(key):
        k = jax.random.split(jax.random.fold_in(key, 1 << 20), 2)
        return {"embed": _normal(k[0], (vocab, d), 1.0 / np.sqrt(d)),
                "final_norm": _around(k[1], d)}

    return make


def top(seed: int, model: dict) -> dict:
    """The embedding, which is also the head, and the final norm."""
    return _top_fn(int(model["hidden_size"]),
                   int(model["vocab_size"]))(seed_key(seed))


def params(seed: int, model: dict) -> dict:
    """The whole pytree `LlmEngineModel(params=...)` takes."""
    out = dict(top(seed, model))
    out["layers"] = [layer(seed, i, model)
                     for i in range(int(model["num_hidden_layers"]))]
    return out
