"""Trinity-Mini's (``model_type: afmoe``) plain reference: float32,
``highest`` matmul precision, no cache, no paging, no kernels, one
layer's weights at a time and one held expert at a time. Nothing here
comes from `client_tpu/models/`.

Equations, with ``x`` the residual stream and ``N`` an RMSNorm with a
learned scale (``rms_norm_eps``)::

    h0    = embed[tokens] * sqrt(hidden_size)                  # mup_enabled
    a     = N_in(x)
    q,k,v = a@wq [L,32,128], a@wk [L,4,128], a@wv [L,4,128]
    q, k  = N_q(q), N_k(k)                                # per head, over 128
    q, k  = rope(q, k, rope_theta)  in a ``sliding_attention`` layer only
    o     = softmax(q k^T / sqrt(128) over keys j <= i, and i - j <
            sliding_window in a sliding layer) v               # GQA 8 : 1
    o     = o * sigmoid(a @ wg)                       # elementwise, [L, 32*128]
    x     = x + N_post_attn(o @ wo)
    m     = N_pre_mlp(x)
    f     = SwiGLU(m)                                  if l < num_dense_layers
    f     = SwiGLU_shared(m) + sum_{e in top8} w_e SwiGLU_e(m)     otherwise
            s = sigmoid(m @ w_router); top8 by s + b (b selects and does not
            weigh); w_e = route_scale * s_e / sum_top8 s
    x     = x + N_post_mlp(f)
    logits = N_final(x) @ head

Of the selected experts only the HELD ones add their part
(`benchmark/lib/weights_afmoe.held`), as on one chip of the
expert-parallel deployment; the shared expert is on every chip and is
added whole. `config.json` carries the routing and the layer pattern;
the head norms, rope in sliding layers only, the output gate, the four
norms and the selection-only bias are the published ``modeling_afmoe.py``
as recalled (the configuration file's ``assumed``). Rotary pairs are
(2i, 2i+1), a permutation of HF's columns, shared with the program.

Attention is computed a block of queries at a time, so that an
8,192-token request fits: nothing else is blocked or batched.

``control=True`` computes the same forward in the nearest precision
below bf16: int8 weights (per output channel) and int8 activations (per
token) at every linear layer but the router, which a low-precision
deployment keeps in float32.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights_afmoe
from benchmark.lib.reference_llm import _gaps, _rms_norm, _rope
from benchmark.lib.reference_mimo import _freeze, _linear

HIGHEST = jax.lax.Precision.HIGHEST
#: queries scored at once: [heads, 256, L] float32 is 268 MB at 8,192
QUERY_BLOCK = 256


def _norm(x, scale, model):
    return _rms_norm(x, scale.astype(jnp.float32),
                     float(model["rms_norm_eps"]))


def position_signal(q, k, model: dict, window: bool):
    """Rope turns q and k [L, heads, 128] in a sliding layer; a full
    layer carries no position signal."""
    if not window:
        return q, k
    theta = float(model["rope_theta"])
    return _rope(q, theta), _rope(k, theta)


def output_gate(a, w, control: bool = False):
    """sigmoid(a @ wg) [L, heads, 128]: what the heads' output is
    multiplied by before ``wo``."""
    return jax.nn.sigmoid(_linear(a, w["wg"], control))


def attention(x, w, model: dict, window: bool, control: bool = False):
    """N_post_attn(gated attention of N_in(x) @ wo) of one sequence x [L, d]."""
    length = x.shape[0]
    head_dim = int(model["head_dim"])
    a = _norm(x, w["attn_norm"], model)
    q = _norm(_linear(a, w["wq"], control), w["q_norm"], model)
    k = _norm(_linear(a, w["wk"], control), w["k_norm"], model)
    v = _linear(a, w["wv"], control)
    q, k = position_signal(q, k, model, window)
    heads, kv = q.shape[1], k.shape[1]
    block = min(length, QUERY_BLOCK)
    blocks = q.reshape(length // block, block, kv, heads // kv, head_dim)
    key = jnp.arange(length)[None, :]

    def one(args):
        index, q_block = args
        query = index * block + jnp.arange(block)[:, None]
        scores = jnp.einsum("lkgd,skd->kgls", q_block, k,
                            precision=HIGHEST) / np.sqrt(head_dim)
        seen = key <= query
        if window:
            seen &= key > query - int(model["sliding_window"])
        probs = jax.nn.softmax(
            jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("kgls,skd->lkgd", probs, v, precision=HIGHEST)

    out = jax.lax.map(one, (jnp.arange(length // block), blocks))
    out = out.reshape(length, heads, head_dim)
    out = out * output_gate(a, w, control)
    return _norm(_linear(out, w["wo"], control, 2), w["post_attn_norm"], model)


def _swiglu(h, w, control):
    gate = jax.nn.silu(_linear(h, w["w_gate"], control))
    return _linear(gate * _linear(h, w["w_up"], control), w["w_down"], control)


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
    weight = picked * float(model["route_scale"])
    if model["route_norm"]:
        weight = weight / picked.sum(axis=-1, keepdims=True)
    last_in, first_out = ranked[:, top_k - 1:top_k], ranked[:, top_k:]
    mine = biased[:, first:first + count]
    margin = jnp.where(
        mine >= last_in, mine - first_out, last_in - mine).min(axis=-1)
    return chosen, weight, margin


def routed_experts(h, w, model: dict, held: tuple, control: bool = False):
    """The held experts' part of the routed sum for tokens h [L, d]:
    ``held = (first, count)`` and ``w["experts"]`` stacks those
    ``count`` experts' weights. Every held expert runs over every token
    and is kept where the router chose it: plain, not fast."""
    first, count = held
    chosen, weight, _ = route(h, w, model, held)
    out = jnp.zeros_like(h)
    for local in range(count):
        share = (weight * (chosen == first + local)).sum(
            axis=-1, keepdims=True)
        expert = {name: w["experts"][name][local]
                  for name in ("w_gate", "w_up", "w_down")}
        out = out + share * _swiglu(h, expert, control)
    return out


def expert_layer(h, w, model: dict, held: tuple, control: bool = False):
    """What one chip's FFN gives for tokens h [L, d]: the shared expert
    whole, and the held experts' part of the routed sum."""
    out = routed_experts(h, w, model, held, control)
    if int(model["num_shared_experts"]):
        out = out + _swiglu(h, w["shared"], control)
    return out


def layer_kind(model: dict, index: int) -> tuple:
    """(window attention?, routed experts?) of layer ``index``."""
    return (weights_afmoe.window_layer(model, index),
            weights_afmoe.expert_layer(model, index))


def layer_and_margin(x, w, model: dict, kind: tuple, held: tuple,
                     control: bool = False):
    """One block of ``kind`` (:func:`layer_kind`) over one sequence x [L,
    d], and its router's margin [L] (:func:`route`; infinity for a dense
    layer)."""
    window, experts = kind
    x = x + attention(x, w, model, window, control)
    m = _norm(x, w["mlp_norm"], model)
    if experts:
        f, margin = (expert_layer(m, w, model, held, control),
                     route(m, w, model, held)[2])
    else:
        f, margin = _swiglu(m, w, control), jnp.full(x.shape[:1], jnp.inf)
    return x + _norm(f, w["post_mlp_norm"], model), margin


def layer(x, w, model: dict, kind: tuple, held: tuple, control: bool = False):
    return layer_and_margin(x, w, model, kind, held, control)[0]


def embed(tokens, top, model: dict):
    x = top["embed"].astype(jnp.float32)[jnp.asarray(tokens)]
    return x * np.sqrt(int(model["hidden_size"])) if model["mup_enabled"] else x


def head(x, top, model: dict, control: bool = False):
    return _linear(_norm(x, top["final_norm"], model), top["lm_head"], control)


def forward(tokens, top, layers, model: dict, held: tuple,
            control: bool = False):
    """Logits [L, V] of one sequence of token ids, from the weights
    given (``layers`` may be a generator: one layer is held at a time).
    A length over :data:`QUERY_BLOCK` is a whole number of them."""
    x = embed(tokens, top, model)
    for index, w in enumerate(layers):
        x = layer(x, w, model, layer_kind(model, index), held, control)
    return head(x, top, model, control)


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
    held = weights_afmoe.held(model)
    frozen = _freeze(model)
    top = weights_afmoe.top(seed, model)
    # padded at the end to a whole number of query blocks (causal: the
    # tail changes nothing before it), so that few lengths compile
    tokens = []
    for s in sequences:
        ids = np.asarray(s["prompt"] + s["served"], np.int32)
        tokens.append(np.pad(ids, (0, -len(ids) % QUERY_BLOCK)))
    hidden = [embed(t, top, model) for t in tokens]
    margins = [jnp.full(len(t), jnp.inf) for t in tokens]
    lowered = list(hidden) if control else None
    for index in range(int(model["num_hidden_layers"])):
        w = weights_afmoe.layer(seed, index, model)
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
