"""Qwen3-Next-80B-A3B-Instruct's (``model_type: qwen3_next``) plain
reference: float32, ``highest`` matmul precision, no cache, no paging, no
kernels, no chunks: the DeltaNet's recurrence runs token by token
(``lax.scan``) from a zero state, attention is plain and causal over the
whole sequence, one layer's weights at a time and one held expert at a
time. Nothing here comes from `client_tpu/models/`.

Equations, with ``x`` the residual stream, ``n(x) = x / sqrt(mean(x^2) +
rms_norm_eps)`` and ``N(x) = n(x) (1 + w)``::

    h = x + Mixer(N1 x);   y = h + MoE(N2 h);   logits = N_f(y) @ head

Layer ``i`` is gated full attention where ``(i + 1) mod
full_attention_interval = 0``, else Gated DeltaNet.

*Gated full attention* (16 heads of 256 over 2 KV heads)::

    [q | gate] = a @ wq  (a head's 512 split 256 / 256);  k, v = a @ wk, a @ wv
    q, k = N_q(q), N_k(k) a head;  rope (theta 1e7) on the first 64 sizes
    o = softmax(q k^T / 16 over keys j <= i) v            # GQA 8 : 1
    out = (o * sigmoid(gate)) @ wo

*Gated DeltaNet* (16 key heads, 32 value heads, of 128)::

    [q|k|v|z] = a @ w_qkvz;   [b | a'] = a @ w_ba
    [q|k|v]  = silu(sum_j c_j x_{t-3+j})   # causal depthwise, 4 taps, no bias
    beta = sigmoid(b);   g = -exp(A_log) softplus(a' + dt_bias)  # a value head
    q, k = q / sqrt(sum q^2 + 1e-6) / sqrt(128),  k / sqrt(sum k^2 + 1e-6)
    key head j // 2 serves value head j; S [128, 128] = 0 at the start
    S <- exp(g_t) S;  u_t = beta_t (v_t - S^T k_t);  S <- S + k_t u_t^T
    o_t = S^T q_t;   out = (w * n(o_t) * silu(z_t)) a head, joined, @ w_out

*Experts*: ``p = softmax(m @ router)`` over all 512 in float32, the 10
largest, ``w_e = p_e / sum_10 p`` (``norm_topk_prob``), each a SwiGLU of
width 512; the shared expert a SwiGLU of width 512 times ``sigmoid(m .
w_sg)``. Of the selected experts only the HELD ones add their part
(`benchmark/lib/weights_qwen3next.held`), as on one chip of the
expert-parallel deployment; the shared expert is on every chip and is
added whole.

Departures from the published description, each shared with the program
and listed under ``assumed`` in the configuration's file: rotary pairs
(2i, 2i+1) on the first 64 sizes (a permutation of HF's columns); the
``[q|k|v|z]`` / ``[b|a]`` column order (HF interleaves them a key head: a
permutation of columns); the multi-token-prediction layer left out; the
seeded draws of ``A_log``, ``dt_bias`` and the norm scales
(`lib/weights_qwen3next.py`).

``control=True`` computes the same forward in the nearest precision below
bf16: int8 weights (per output channel) and int8 activations (per token)
at every linear layer but the router, which a low-precision deployment
keeps in float32; the recurrence and its state stay float32.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights_qwen3next
from benchmark.lib.reference_llm import _gaps, _rope
from benchmark.lib.reference_mimo import _freeze, _linear

HIGHEST = jax.lax.Precision.HIGHEST
#: sequences are padded to a whole number of these, so few lengths compile
PAD = 256
L2_EPS = 1e-6


def unit(x, model):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        + float(model["rms_norm_eps"]))


def norm(x, w, model):
    """``n(x) (1 + w)``: the residual stream's, the ``q`` / ``k`` heads'
    and the final norm."""
    return unit(x, model) * (1.0 + w.astype(jnp.float32))


def partial_rope(x, model):
    """Rope on the first ``partial_rotary_factor`` of the head's sizes."""
    rotary = weights_qwen3next.rotary_dim(model)
    return jnp.concatenate(
        [_rope(x[..., :rotary], float(model["rope_theta"])), x[..., rotary:]],
        axis=-1)


def attention_gate(gate):
    return jax.nn.sigmoid(gate)


def attention(a, w, model, control=False):
    """Gated full attention of the normed ``a`` [L, d]."""
    length, dh = a.shape[0], int(model["head_dim"])
    both = _linear(a, w["wq"], control)
    q, gate = both[..., :dh], both[..., dh:]
    q = partial_rope(norm(q, w["q_norm"], model), model)
    k = partial_rope(norm(_linear(a, w["wk"], control), w["k_norm"], model),
                     model)
    v = _linear(a, w["wv"], control)
    heads, kv = q.shape[1], k.shape[1]
    grouped = q.reshape(length, kv, heads // kv, dh)
    scores = jnp.einsum("lkgd,skd->kgls", grouped, k,
                        precision=HIGHEST) / np.sqrt(dh)
    causal = jnp.arange(length)[:, None] >= jnp.arange(length)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("kgls,skd->lkgd", probs, v, precision=HIGHEST)
    out = out.reshape(length, heads, dh) * attention_gate(gate)
    return _linear(out, w["wo"], control, 2)


def convolution(inputs, taps):
    """Causal depthwise convolution, no bias, then SiLU: ``inputs`` [L,
    C], ``taps`` [4, C], tap 3 the token itself."""
    length, count = inputs.shape[0], taps.shape[0]
    padded = jnp.pad(inputs, ((count - 1, 0), (0, 0)))
    taps = taps.astype(jnp.float32)
    return jax.nn.silu(sum(
        taps[j] * padded[j:j + length] for j in range(count)))


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.square(x).sum(axis=-1, keepdims=True)
                             + L2_EPS)


def beta_of(b):
    return jax.nn.sigmoid(b)


def decay_of(a, w):
    """``g`` [L, Hv], the log of a value head's decay a token."""
    return -jnp.exp(w["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        a + w["dt_bias"].astype(jnp.float32))


def gated_output_norm(out, z, w, model):
    """``w n(o) silu(z)`` a head."""
    return unit(out, model) * w.astype(jnp.float32) * jax.nn.silu(z)


def recurrence(q, k, v, g, beta):
    """The gated delta rule token by token from a zero state: q, k [L, H,
    Dk] (a row a value head), v [L, H, Dv], g, beta [L, H] -> o [L, H,
    Dv]."""
    def token(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state * jnp.exp(g_t)[:, None, None]
        seen = jnp.einsum("hkv,hk->hv", state, k_t, precision=HIGHEST)
        u = beta_t[:, None] * (v_t - seen)
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t, precision=HIGHEST)

    zero = jnp.zeros((v.shape[1], k.shape[2], v.shape[2]), jnp.float32)
    return jax.lax.scan(token, zero, (q, k, v, g, beta))[1]


def delta_net(a, w, model, control=False):
    """Gated DeltaNet of the normed ``a`` [L, d]."""
    hk, hv = (int(model["linear_num_key_heads"]),
              int(model["linear_num_value_heads"]))
    dk, dv = (int(model["linear_key_head_dim"]),
              int(model["linear_value_head_dim"]))
    channels = weights_qwen3next.conv_dim(model)
    mixed = _linear(a, w["w_qkvz"], control)
    ba = _linear(a, w["w_ba"], control)
    conv = convolution(mixed[:, :channels], w["conv_w"])
    z = mixed[:, channels:].reshape(-1, hv, dv)
    q = l2norm(conv[:, :hk * dk].reshape(-1, hk, dk)) / np.sqrt(dk)
    k = l2norm(conv[:, hk * dk:2 * hk * dk].reshape(-1, hk, dk))
    v = conv[:, 2 * hk * dk:].reshape(-1, hv, dv)
    q, k = (jnp.repeat(x, hv // hk, axis=1) for x in (q, k))
    out = recurrence(q, k, v, decay_of(ba[:, hv:], w), beta_of(ba[:, :hv]))
    out = gated_output_norm(out, z, w["gdn_norm"], model)
    return _linear(out.reshape(out.shape[0], -1), w["w_out"], control)


def _swiglu(h, w, control):
    gate = jax.nn.silu(_linear(h, w["w_gate"], control))
    return _linear(gate * _linear(h, w["w_up"], control), w["w_down"], control)


def route(h, w, model: dict, held: tuple):
    """The router over all experts for tokens h [L, d]: (chosen [L, K],
    weight [L, K], margin [L]). ``margin`` is how clearly the selection
    stands where it concerns this chip, in the router's LOGITS (softmax
    keeps their order): the least change in one held expert's logit that
    would move it across the selection's edge (a chosen one under the
    first left out, another over the last chosen). Under it a lower
    precision may choose otherwise, and a whole held expert's output
    comes or goes with the choice."""
    first, count = held
    top_k = int(model["num_experts_per_tok"])
    logits = jnp.matmul(h, w["router"].astype(jnp.float32), precision=HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    ranked, order = jax.lax.top_k(logits, top_k + 1)
    chosen = order[:, :top_k]
    weight = jnp.take_along_axis(probs, chosen, axis=-1)
    if model["norm_topk_prob"]:
        weight = weight / weight.sum(axis=-1, keepdims=True)
    last_in, first_out = ranked[:, top_k - 1:top_k], ranked[:, top_k:]
    mine = logits[:, first:first + count]
    margin = jnp.where(
        mine >= last_in, mine - first_out, last_in - mine).min(axis=-1)
    return chosen, weight, margin


def routed_experts(h, w, model: dict, held: tuple, control: bool = False):
    """The held experts' part of the routed sum for tokens h [L, d]. Every
    held expert runs over every token and is kept where the router chose
    it: plain, not fast."""
    first, count = held
    chosen, weight, _ = route(h, w, model, held)

    def one(out, local):
        share = (weight * (chosen == first + local)).sum(
            axis=-1, keepdims=True)
        expert = {name: w["experts"][name][local]
                  for name in ("w_gate", "w_up", "w_down")}
        return out + share * _swiglu(h, expert, control), None

    return jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(count))[0]


def shared_gate(h, w):
    return jax.nn.sigmoid(jnp.matmul(
        h, w["w_sg"].astype(jnp.float32)[:, None], precision=HIGHEST))


def expert_layer(h, w, model: dict, held: tuple, control: bool = False):
    """What one chip's MoE gives for tokens h [L, d]: the gated shared
    expert whole, and the held experts' part of the routed sum."""
    shared = _swiglu(h, w["shared"], control) * shared_gate(h, w["shared"])
    return routed_experts(h, w, model, held, control) + shared


def layer_and_margin(x, w, model: dict, delta: bool, held: tuple,
                     control: bool = False):
    """One block over one sequence x [L, d], and its router's margin
    [L] (:func:`route`)."""
    a = norm(x, w["mixer_norm"], model)
    x = x + (delta_net if delta else attention)(a, w, model, control)
    m = norm(x, w["mlp_norm"], model)
    return (x + expert_layer(m, w, model, held, control),
            route(m, w, model, held)[2])


def layer(x, w, model: dict, delta: bool, held: tuple, control: bool = False):
    return layer_and_margin(x, w, model, delta, held, control)[0]


def embed(tokens, top):
    return top["embed"].astype(jnp.float32)[jnp.asarray(tokens)]


def head(x, top, model: dict, control: bool = False):
    return _linear(norm(x, top["final_norm"], model), top["lm_head"], control)


def forward(tokens, top, layers, model: dict, held: tuple,
            control: bool = False):
    """Logits [L, V] of one sequence of token ids, from the weights
    given (``layers`` may be a generator: one layer is held at a time)."""
    with jax.default_matmul_precision("highest"):
        x = embed(tokens, top)
        for index, w in enumerate(layers):
            x = layer(x, w, model, weights_qwen3next.delta_layer(model, index),
                      held, control)
        return head(x, top, model, control)


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen, delta, held, control):
    """One compiled program a kind of layer, not a layer."""
    model = {k: list(v) if isinstance(v, tuple) else v for k, v in frozen}
    return jax.jit(functools.partial(layer_and_margin, model=model,
                                     delta=delta, held=held, control=control))


def served_token_gaps(seed: int, model: dict, sequences: list,
                      control: bool = False) -> list:
    """As `reference_llm.served_token_gaps`: the reference runs once over
    prompt + served tokens of each sequence, and each served token's
    logit is read against the reference's best at its position; with
    ``control`` also the int8 forward's first choice there. Beside each
    gap goes its position's ``margins`` entry: the narrowest router
    margin (:func:`route`) of the reference's own layers there."""
    held = weights_qwen3next.held(model)
    frozen = _freeze(model)
    top = weights_qwen3next.top(seed, model)
    tokens = []
    for s in sequences:
        ids = np.asarray(s["prompt"] + s["served"], np.int32)
        tokens.append(np.pad(ids, (0, -len(ids) % PAD)))
    with jax.default_matmul_precision("highest"):
        hidden = [embed(t, top) for t in tokens]
        margins = [jnp.full(len(t), jnp.inf) for t in tokens]
        lowered = list(hidden) if control else None
        for index in range(int(model["num_hidden_layers"])):
            w = weights_qwen3next.layer(seed, index, model)
            delta = weights_qwen3next.delta_layer(model, index)
            for i, h in enumerate(hidden):
                hidden[i], margin = _layer_fn(frozen, delta, held, False)(h, w)
                margins[i] = jnp.minimum(margins[i], margin)
            if control:
                lowered = [_layer_fn(frozen, delta, held, True)(h, w)[0]
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
