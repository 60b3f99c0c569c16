"""MiMo-V2-Flash's plain reference: float32, ``highest`` matmul
precision, no cache, no paging, no kernels, one layer's weights at a
time and one held expert at a time. Nothing here comes from
`client_tpu/models/`.

Equations (the model's public ``config.json``; `PERF.md` section 4):
pre-norm residual blocks with RMSNorm, ``x += Attn(norm(x))``, ``x +=
FFN(norm(x))``. Attention: 64 query heads of 192 (values 128) over 4 KV
heads with rope theta ``rope_theta`` in a *full* layer
(``hybrid_layer_pattern`` 0), over ``swa_num_key_value_heads`` with
``swa_rope_theta`` in a *window* layer (1), where a query at ``p`` sees
keys ``p - sliding_window + 1 .. p`` and one learned sink logit a query
head joins the softmax and is dropped after it. Rope turns the first
``int(partial_rotary_factor * head_dim)`` sizes of q and k; the heads'
output is scaled by ``attention_value_scale``. FFN: a dense SwiGLU
(``moe_layer_freq`` 0) or routed experts: ``s = sigmoid(h W_r)``, the
top ``num_experts_per_tok`` of ``s + b`` (``b`` selects and does not
weigh), weights ``s_e`` over their sum, no shared expert, no scaling
factor; of the selected experts only the HELD ones add their part
(`benchmark/lib/weights_mimo.held`), as on one chip of the
expert-parallel deployment.

Departures, shared with the program: rotary pairs are (2i, 2i+1), a
permutation of HF's columns; the release's multi-token-prediction
layers are not in ``config.json`` and not here.

``control=True`` computes the same forward in the nearest precision
below bf16: int8 weights (per output channel) and int8 activations (per
token) at every linear layer but the router, which a low-precision
deployment keeps in float32.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights_mimo
from benchmark.lib.reference_llm import _fake_int8, _gaps, _rms_norm, _rope

HIGHEST = jax.lax.Precision.HIGHEST


def _linear(x, w, control, contract=1):
    """The last ``contract`` dims of x against the first of w."""
    w = w.astype(jnp.float32)
    k = int(np.prod(w.shape[:contract]))
    w2, x2 = w.reshape(k, -1), x.reshape(-1, k)
    if control:
        w2, x2 = _fake_int8(w2, axis=0), _fake_int8(x2, axis=1)
    out = jnp.matmul(x2, w2, precision=HIGHEST)
    return out.reshape(x.shape[: x.ndim - contract] + w.shape[contract:])


def _partial_rope(x, rotary, theta):
    return jnp.concatenate(
        [_rope(x[..., :rotary], theta), x[..., rotary:]], axis=-1)


def attention(x, w, model: dict, window: bool, control: bool = False):
    """Attn(norm(x)) of one sequence x [L, d]."""
    length = x.shape[0]
    head_dim = int(model["head_dim"])
    rotary = int(float(model["partial_rotary_factor"]) * head_dim)
    theta = float(model["swa_rope_theta" if window else "rope_theta"])
    normed = _rms_norm(x, w["attn_norm"].astype(jnp.float32),
                       float(model["layernorm_epsilon"]))
    q = _partial_rope(_linear(normed, w["wq"], control), rotary, theta)
    k = _partial_rope(_linear(normed, w["wk"], control), rotary, theta)
    v = _linear(normed, w["wv"], control)
    heads, kv = q.shape[1], k.shape[1]
    qg = q.reshape(length, kv, heads // kv, head_dim)
    scores = jnp.einsum("lkgd,skd->kgls", qg, k,
                        precision=HIGHEST) / np.sqrt(head_dim)
    query, key = jnp.arange(length)[:, None], jnp.arange(length)[None, :]
    seen = key <= query
    if window:
        seen &= key > query - int(model["sliding_window"])
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    if window and model["add_swa_attention_sink_bias"]:
        sink = jnp.broadcast_to(
            w["sink"].astype(jnp.float32).reshape(kv, heads // kv, 1, 1),
            scores.shape[:3] + (1,))
        probs = jax.nn.softmax(
            jnp.concatenate([scores, sink], axis=-1), axis=-1)[..., :-1]
    else:
        probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("kgls,skd->lkgd", probs, v, precision=HIGHEST)
    out = out.reshape(length, heads, -1) * float(model["attention_value_scale"])
    return _linear(out, w["wo"], control, 2)


def _swiglu(h, w_gate, w_up, w_down, control):
    gate = jax.nn.silu(_linear(h, w_gate, control))
    return _linear(gate * _linear(h, w_up, control), w_down, control)


def route(h, w, model: dict, held: tuple):
    """The router over all experts for tokens h [L, d]: (chosen [L, K],
    weight [L, K], margin [L]). ``margin`` is how clearly the selection
    stands where it concerns this chip: the least change in one held
    expert's ``s + b`` that would move it across the selection's edge (a
    chosen one under the first left out, another over the last chosen).
    Under it a lower precision may choose otherwise, and a whole held
    expert's output comes or goes with the choice."""
    first, count = held
    top_k = int(model["num_experts_per_tok"])
    scores = jax.nn.sigmoid(jnp.matmul(
        h, w["router"].astype(jnp.float32), precision=HIGHEST))
    biased = scores + w["router_bias"].astype(jnp.float32)
    ranked, order = jax.lax.top_k(biased, top_k + 1)
    chosen = order[:, :top_k]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    last_in, first_out = ranked[:, top_k - 1:top_k], ranked[:, top_k:]
    mine = biased[:, first:first + count]
    margin = jnp.where(
        mine >= last_in, mine - first_out, last_in - mine).min(axis=-1)
    return chosen, picked / picked.sum(axis=-1, keepdims=True), margin


def expert_layer(h, w, model: dict, held: tuple, control: bool = False):
    """The held experts' part of the routed FFN for tokens h [L, d]:
    ``held = (first, count)`` and ``w["experts"]`` stacks those
    ``count`` experts' weights. Every held expert runs over every token
    and is kept where the router chose it: plain, not fast."""
    first, count = held
    chosen, weight, _ = route(h, w, model, held)
    out = jnp.zeros_like(h)
    for local in range(count):
        mine = (chosen == first + local)  # [L, K]
        share = (weight * mine).sum(axis=-1, keepdims=True)
        e = {name: w["experts"][name][local]
             for name in ("w_gate", "w_up", "w_down")}
        out = out + share * _swiglu(
            h, e["w_gate"], e["w_up"], e["w_down"], control)
    return out


def layer_kind(model: dict, index: int) -> tuple:
    """(window attention?, routed experts?) of layer ``index``."""
    return (bool(model["hybrid_layer_pattern"][index]),
            bool(model["moe_layer_freq"][index]))


def layer_and_margin(x, w, model: dict, kind: tuple, held: tuple,
                     control: bool = False):
    """One block of ``kind`` (:func:`layer_kind`) over one sequence x [L,
    d], and its router's margin [L] (:func:`route`; infinity for a dense
    layer)."""
    window, experts = kind
    x = x + attention(x, w, model, window, control)
    h = _rms_norm(x, w["mlp_norm"].astype(jnp.float32),
                  float(model["layernorm_epsilon"]))
    if not experts:
        return (x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], control),
                jnp.full(x.shape[:1], jnp.inf))
    return (x + expert_layer(h, w, model, held, control),
            route(h, w, model, held)[2])


def layer(x, w, model: dict, kind: tuple, held: tuple, control: bool = False):
    return layer_and_margin(x, w, model, kind, held, control)[0]


def head(x, top, model: dict, control: bool = False):
    normed = _rms_norm(x, top["final_norm"].astype(jnp.float32),
                       float(model["layernorm_epsilon"]))
    return _linear(normed, top["lm_head"], control)


def forward(tokens, top, layers, model: dict, held: tuple,
            control: bool = False):
    """Logits [L, V] of one sequence of token ids, from the weights
    given (``layers`` may be a generator: one layer is held at a time)."""
    x = top["embed"].astype(jnp.float32)[jnp.asarray(tokens)]
    for index, w in enumerate(layers):
        x = layer(x, w, model, layer_kind(model, index), held, control)
    return head(x, top, model, control)


def _freeze(model: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()))


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen, kind, held, control):
    """One compiled program a kind of layer, not a layer."""
    model = {k: list(v) if isinstance(v, tuple) else v for k, v in frozen}
    return jax.jit(functools.partial(layer_and_margin, model=model,
                                     kind=kind, held=held, control=control))


def served_token_gaps(seed: int, model: dict, sequences: list,
                      control: bool = False) -> list:
    """As `reference_llm.served_token_gaps`: the reference runs once over
    prompt + served tokens of each sequence, and each served token's
    logit is read against the reference's best at its position; with
    ``control`` also the int8 forward's first choice there. Beside each
    gap goes its position's ``margins`` entry: the narrowest router
    margin (:func:`route`) of the reference's own expert layers there."""
    held = weights_mimo.held(model)
    frozen = _freeze(model)
    top = weights_mimo.top(seed, model)
    embed = top["embed"].astype(jnp.float32)
    # padded at the end to a multiple of 128 (causal: the tail changes
    # nothing before it), so that few lengths compile
    tokens = []
    for s in sequences:
        ids = np.asarray(s["prompt"] + s["served"], np.int32)
        tokens.append(np.pad(ids, (0, -len(ids) % 128)))
    hidden = [embed[t] for t in tokens]
    margins = [jnp.full(len(t), jnp.inf) for t in tokens]
    lowered = list(hidden) if control else None
    for index in range(int(model["num_hidden_layers"])):
        w = weights_mimo.layer(seed, index, model)
        kind = layer_kind(model, index)
        for i, h in enumerate(hidden):
            hidden[i], margin = _layer_fn(frozen, kind, held, False)(h, w)
            margins[i] = jnp.minimum(margins[i], margin)
        if control:
            lowered = [_layer_fn(frozen, kind, held, True)(h, w)[0]
                       for h in lowered]
    results = []
    for i, seq in enumerate(sequences):
        n_prompt, n_served = len(seq["prompt"]), len(seq["served"])
        at = slice(n_prompt - 1, n_prompt + n_served - 1)
        ref = head(hidden[i][at], top, model)
        other = head(lowered[i][at], top, model, True) if control else ref
        gaps, control_gaps, first = _gaps(
            ref, jnp.asarray(seq["served"], jnp.int32), other)
        entry = {"gaps": np.asarray(gaps).tolist(),
                 "margins": np.asarray(margins[i][at]).tolist(),
                 "reference_first": np.asarray(first).tolist()}
        if control:
            entry["control_gaps"] = np.asarray(control_gaps).tolist()
        results.append(entry)
    return results
