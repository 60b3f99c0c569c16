"""Phi-4-mini-flash-reasoning's (``phi4flash``) weights from a seed, made
on the device by jitted programs, one layer to a call (`lib/weights.py`
has the reasons: both sides of `correct` call THESE functions, and the
same program on the same device gives the same bits).

``model`` is `config.json`'s ``model`` group. With ``N`` layers and
``mb_per_layer`` 2, layer ``i`` is (:func:`layer_kind`): Mamba where ``i``
is even and ``i <= N/2``; attention under ``sliding_window`` where ``i``
is odd and ``i < N/2``; full attention at ``N/2 + 1``; past that a gated
memory unit where ``i`` is even and cross-attention (a query projection
only) where it is odd. Every layer's feed-forward is the dense SwiGLU.

``LN`` scales lie ``0.1 N(0,1)`` around 1 and ``LN`` biases ``0.1
N(0,1)``; the attention's sub-norm scale ``0.1 N(0,1)`` around 1; the
four lambda vectors ``0.1 N(0,1)`` as published, so that ``lam`` stays
near ``lam_init`` and the subtracted softmax weighs a third to four
fifths of the first; the q, k and v biases 0.3 and the output bias 0.1,
each of a size that shows beside a projection of unit size. The Mamba
draws are `lib/weights_jamba.py`'s (``A_log = log(1..16)``, ``b_dt`` the
inverse softplus of a log-uniform draw on 0.001-0.1, ``W_dt`` at 0.35:
``delta`` in about 0.001-0.2, a channel's decay a token over about
0.04-0.999), without Jamba's three inner norms, which this family does
not have. A gated memory unit's ``W_in`` is drawn at ``1 / sqrt(hidden)``
so that its gate ``silu(a W_in)`` is of the size of a Mamba layer's
``silu(z)``. The embedding is drawn at ``1 / sqrt(hidden)``, so that the
tied head's logits are of unit size; each layer's ``LN`` takes the size
out again.

The program's layout (`client_tpu/models/phi4flash.py`): ``W_qkv``'s
columns ``[q | k | v]`` are held as three tensors ``[d, heads, 64]`` in
the published head order (query heads ``2j, 2j + 1`` are ``q_1, q_2`` of
pair ``j``), ``W_o`` as ``[pairs, 128, d]``, the MLP's ``[g | u]`` as two,
``W_in``'s columns ``[u | z]`` and ``W_x``'s ``[dl | B | C]`` as Jamba's,
``A_log`` ``[d_state, d_inner]``, the channels last.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.bytes_ops_phi4flash import (  # noqa: F401 - the one rule
    CROSS, FULL, GMU, MAMBA, WINDOW, layer_kinds,
)
from benchmark.lib.weights import _normal, seed_key
from benchmark.lib.weights_jamba import _around, step_draw


def layer_kind(model: dict, index: int) -> str:
    """The mixer of layer ``index``: the module docstring's rule, which
    `lib/bytes_ops_phi4flash.py` holds (without JAX, for the readers)."""
    return layer_kinds(model)[index]


def memory_layer(model: dict) -> int:
    """The Mamba layer whose ungated scan output the gated memory units
    read: layer ``N/2``, the last of the self-decoder."""
    return int(model["num_hidden_layers"]) // 2


def d_inner(model: dict) -> int:
    return int(model["mamba_expand"]) * int(model["hidden_size"])


def lambda_init(index: int) -> float:
    """``0.8 - 0.6 exp(-0.3 i)`` for layer ``i``."""
    return 0.8 - 0.6 * float(np.exp(-0.3 * index))


def shape_key(model: dict) -> tuple:
    """The numbers a layer's weights depend on, hashable."""
    return tuple(int(model[k]) for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "intermediate_size", "mamba_expand", "mamba_d_state",
        "mamba_d_conv", "mamba_dt_rank"))


@functools.lru_cache(maxsize=None)
def _layer_fn(shapes: tuple, kind: str):
    d, h, kv, f, expand, n, taps, r = shapes
    di, dh = expand * d, d // h
    s = 1.0 / np.sqrt(d)

    def bias(key, shape, scale):
        return _normal(key, shape, scale)

    @jax.jit
    def make(key, index):
        k = jax.random.split(jax.random.fold_in(key, index), 24)
        layer = {"ln1_w": _around(k[0], d), "ln1_b": bias(k[1], (d,), 0.1),
                 "ln2_w": _around(k[2], d), "ln2_b": bias(k[3], (d,), 0.1),
                 "w_gate": _normal(k[4], (d, f), s),
                 "w_up": _normal(k[5], (d, f), s),
                 "w_down": _normal(k[6], (f, d), 1.0 / np.sqrt(f))}
        if kind == MAMBA:
            a_log, b_dt = step_draw(k[7], n, di)
            layer.update(
                w_in=_normal(k[8], (d, 2 * di), s),
                conv_w=_normal(k[9], (taps, di), 0.5),
                conv_b=_normal(k[10], (di,), 0.5),
                w_x=_normal(k[11], (di, r + 2 * n), 1.0 / np.sqrt(di)),
                w_dt=_normal(k[12], (r, di), 0.35 / np.sqrt(r)),
                b_dt=b_dt, A_log=a_log,
                D=_around(k[13], di, dtype=jnp.float32),
                w_out=_normal(k[14], (di, d), 1.0 / np.sqrt(di)),
            )
        elif kind == GMU:
            layer.update(
                w_in=_normal(k[7], (d, di), s),
                w_out=_normal(k[8], (di, d), 1.0 / np.sqrt(di)),
            )
        else:
            layer.update(
                wq=_normal(k[7], (d, h, dh), s),
                bq=bias(k[8], (h, dh), 0.3),
                wo=_normal(k[9], (h // 2, 2 * dh, d), 1.0 / np.sqrt(d)),
                bo=bias(k[10], (d,), 0.1),
                sub_norm=_around(k[11], 2 * dh),
                lambdas=0.1 * jax.random.normal(k[12], (4, dh), jnp.float32),
            )
            if kind != CROSS:
                layer.update(
                    wk=_normal(k[13], (d, kv, dh), s),
                    bk=bias(k[14], (kv, dh), 0.3),
                    wv=_normal(k[15], (d, kv, dh), s),
                    bv=bias(k[16], (kv, dh), 0.3),
                )
        return layer

    return make


def layer(seed: int, index: int, model: dict) -> dict:
    """Weights of layer ``index`` in the program's layout
    (`client_tpu/models/phi4flash.py`), bf16 but ``A_log``, ``b_dt``,
    ``D`` and the lambda vectors (float32)."""
    make = _layer_fn(shape_key(model), layer_kind(model, index))
    return make(seed_key(seed), jnp.int32(index))


@functools.lru_cache(maxsize=None)
def _top_fn(d: int, vocab: int):
    @jax.jit
    def make(key):
        k = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
        return {"embed": _normal(k[0], (vocab, d), 1.0 / np.sqrt(d)),
                "final_w": _around(k[1], d),
                "final_b": _normal(k[2], (d,), 0.1)}

    return make


def top(seed: int, model: dict) -> dict:
    """The embedding, which is also the head, and the final ``LN``."""
    return _top_fn(int(model["hidden_size"]),
                   int(model["vocab_size"]))(seed_key(seed))


def params(seed: int, model: dict) -> dict:
    """The whole pytree `LlmEngineModel(params=...)` takes."""
    out = dict(top(seed, model))
    out["layers"] = [layer(seed, i, model)
                     for i in range(int(model["num_hidden_layers"]))]
    return out
