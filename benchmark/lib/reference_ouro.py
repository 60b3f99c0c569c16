"""Ouro-2.6B's (``model_type: ouro``, the looped language model of
arXiv:2510.25741) plain reference: float32, ``highest`` matmul precision,
no cache, no paging, no kernel, no stacked weights and no rolled loop: a
Python loop over the passes around a Python loop over the layers, one
layer's weights at a time, attention plain and causal over the whole
sequence. Nothing here comes from `client_tpu/models/`.

Equations, with ``h`` the residual stream, ``n(x) = x / sqrt(mean(x^2) +
rms_norm_eps)`` and every norm ``w n(x)``::

    h = E[token]
    for u in 0 .. total_ut_steps - 1:            # the SAME weights every pass
        for l in 0 .. num_hidden_layers - 1:
            a = RMS1_l(h)
            q, k, v = a Wq_l, a Wk_l, a Wv_l;   q, k = rope(q), rope(k)
            o = softmax(q k^T / sqrt(128) over keys j <= i) v
            h = h + RMS2_l(o Wo_l)               # a norm on the sublayer's OUTPUT
            m = RMS3_l(h)
            h = h + RMS4_l((silu(m Wg_l) * (m Wu_l)) Wd_l)
        h = RMS_f(h)                             # closes EVERY pass, feeds the next
        g_u = sigmoid(h w_exit + b_exit)         # the exit gate
    logits = h_exit W_head                       # untied

No cache exists here, so that a pass's keys and values are its own is
not an assumption but the mathematics: pass ``u``'s attention in layer
``l`` reads ``k`` and ``v`` projected from pass ``u``'s own stream.
``h_exit`` is the state after the first pass at which the gates'
cumulated exit probability (``p_u = g_u prod_{j<u} (1 - g_j)``, the last
pass taking what is left) reaches ``early_exit_threshold``, else the
last; at the published threshold of 1 that is the last pass at every
position, whatever the gate says (:func:`exit_state`).

Departures from the published description, each shared with the program
and listed under ``assumed`` in the configuration's file: the rotary
pairs are (2i, 2i+1) and not the published (i, i+64), a fixed
permutation of a head's columns that weights from a seed do not see; the
seeded draws (`lib/weights_ouro.py`), ``wq``, ``wk`` and ``wv`` held
``[out, in]``.

``control=True`` computes the same forward in the nearest precision below
bf16: int8 weights (per output channel) and int8 activations (per token)
at every linear layer, the head among them; the gate stays float32.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights_ouro
from benchmark.lib.reference_jamba import _margins, mlp, norm
from benchmark.lib.reference_llm import _gaps, _rope
from benchmark.lib.reference_mimo import _freeze, _linear

HIGHEST = jax.lax.Precision.HIGHEST


def sublayer_out(y, w, name, model):
    """The sandwich: the norm ``name`` on a sublayer's OUTPUT, before the
    residual takes it (``norm`` is ``w n(x)``, every norm of the model,
    and ``mlp`` the SwiGLU: `reference_jamba`'s, the same equations)."""
    return norm(y, w[name], model)


def attention(a, w, model, control=False):
    """Multi-head attention of the normed ``a`` [L, d], the keys and
    values this pass's own."""
    length, dh = a.shape[0], int(model["head_dim"])
    theta = float(model["rope_theta"])
    q = _rope(_linear(a, w["wq"].T, control).reshape(length, -1, dh), theta)
    k = _rope(_linear(a, w["wk"].T, control).reshape(length, -1, dh), theta)
    v = _linear(a, w["wv"].T, control).reshape(length, -1, dh)
    heads, kv = q.shape[1], k.shape[1]
    scores = jnp.einsum("lkgd,skd->kgls",
                        q.reshape(length, kv, heads // kv, dh), k,
                        precision=HIGHEST) / np.sqrt(dh)
    seen = jnp.arange(length)[None, :] <= jnp.arange(length)[:, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("kgls,skd->lkgd", probs, v, precision=HIGHEST)
    return _linear(out.reshape(length, heads * dh), w["wo"], control)


def layer(x, w, model: dict, control: bool = False):
    """One block over one sequence x [L, d]."""
    a = norm(x, w["attn_norm"], model)
    x = x + sublayer_out(attention(a, w, model, control), w,
                         "attn_out_norm", model)
    m = norm(x, w["mlp_norm"], model)
    return x + sublayer_out(mlp(m, w, control), w, "mlp_out_norm", model)


def close_pass(x, top, model):
    """What ends EVERY pass: the final norm, inside the loop over passes."""
    return norm(x, top["final_norm"], model)


def passes(model: dict) -> int:
    return int(model["total_ut_steps"])


def gate(x, top):
    """The exit gate's probability [L] after a pass."""
    return jax.nn.sigmoid(
        jnp.matmul(x, top["exit_w"].astype(jnp.float32), precision=HIGHEST)
        + top["exit_b"].astype(jnp.float32))


def exit_state(states, gates, model):
    """``h_exit`` [L, d] of the passes' closed states and gate
    probabilities: a position leaves at the first pass where the
    cumulated exit probability reaches ``early_exit_threshold``, else at
    the last. The probabilities sum to 1 only with the last pass's, so a
    threshold of 1 is the last pass always (in float32 three saturated
    gates can round the sum to 1 a pass early: rounding, not an exit)."""
    threshold = float(model["early_exit_threshold"])
    if threshold >= 1:
        return states[-1]
    chosen, stay, total = states[-1], 1.0, 0.0
    left = jnp.zeros(states[0].shape[:1], bool)
    for state, g in zip(states[:-1], gates[:-1]):
        total = total + g * stay
        stay = stay * (1.0 - g)
        leaves = (total >= threshold) & ~left
        chosen = jnp.where(leaves[:, None], state, chosen)
        left = left | leaves
    return chosen


def embed(tokens, top):
    return top["embed"].astype(jnp.float32)[jnp.asarray(tokens)]


def head(x, top, control: bool = False):
    return _linear(x, top["lm_head"], control)


def forward(tokens, top, layers, model: dict, control: bool = False):
    """Logits [L, V] of one sequence of token ids, from the weights given
    (``layers(index)`` gives layer ``index``'s, called once a pass: one
    layer is held at a time)."""
    with jax.default_matmul_precision("highest"):
        x = embed(tokens, top)
        states, gates = [], []
        for _ in range(passes(model)):
            for index in range(int(model["num_hidden_layers"])):
                x = layer(x, layers(index), model, control)
            x = close_pass(x, top, model)
            states.append(x)
            gates.append(gate(x, top))
        return head(exit_state(states, gates, model), top, control)


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen, control):
    """One compiled program for every layer of every pass."""
    model = {k: list(v) if isinstance(v, tuple) else v for k, v in frozen}
    return jax.jit(functools.partial(layer, model=model, control=control))


@functools.lru_cache(maxsize=None)
def _close_fn(frozen):
    model = {k: list(v) if isinstance(v, tuple) else v for k, v in frozen}

    @jax.jit
    def close(x, top):
        x = close_pass(x, top, model)
        return x, gate(x, top)

    return close


@functools.lru_cache(maxsize=None)
def _head_fn(frozen, control):
    model = {k: list(v) if isinstance(v, tuple) else v for k, v in frozen}

    @jax.jit
    def read(states, gates, top):
        return head(exit_state(states, gates, model), top, control)

    return read


def served_token_gaps(seed: int, model: dict, sequences: list,
                      control: bool = False) -> list:
    """As `reference_jamba.served_token_gaps`: the reference runs once
    over prompt + served tokens of each sequence, and each served token's
    logit is read against the reference's best at its position; with
    ``control`` also the int8 forward's first choice there. Beside each
    gap goes its position's ``margins`` entry: by how much the
    reference's best logit there stands over its second."""
    frozen = _freeze(model)
    top = weights_ouro.top(seed, model)
    sides = [False, True] if control else [False]
    results = []
    with jax.default_matmul_precision("highest"):
        logits = {}
        for lowered in sides:
            hidden = [embed(np.asarray(s["prompt"] + s["served"], np.int32),
                            top) for s in sequences]
            states = [[] for _ in sequences]
            gates = [[] for _ in sequences]
            for _ in range(passes(model)):
                for index in range(int(model["num_hidden_layers"])):
                    w = weights_ouro.layer(seed, index, model)
                    hidden = [_layer_fn(frozen, lowered)(h, w)
                              for h in hidden]
                for i, h in enumerate(hidden):
                    hidden[i], g = _close_fn(frozen)(h, top)
                    states[i].append(hidden[i])
                    gates[i].append(g)
            logits[lowered] = [
                _head_fn(frozen, lowered)(
                    [s[len(seq["prompt"]) - 1:-1] for s in states[i]],
                    [g[len(seq["prompt"]) - 1:-1] for g in gates[i]], top)
                for i, seq in enumerate(sequences)]
        for i, seq in enumerate(sequences):
            ref = logits[False][i]
            gaps, control_gaps, first = _gaps(
                ref, jnp.asarray(seq["served"], jnp.int32),
                logits[control][i])
            entry = {"gaps": np.asarray(gaps).tolist(),
                     "margins": np.asarray(_margins(ref)).tolist(),
                     "reference_first": np.asarray(first).tolist()}
            if control:
                entry["control_gaps"] = np.asarray(control_gaps).tolist()
            results.append(entry)
    return results
