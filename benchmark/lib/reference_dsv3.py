"""GigaChat3.1-702B-A36B's (``model_type: deepseek_v3``) plain reference:
float32, ``highest`` matmul precision, no cache, no paging, no kernels,
no absorbed projections, one layer's weights at a time and one held
expert at a time. Nothing here comes from `client_tpu/models/`.

Equations, with ``x`` the residual stream and ``N`` an RMSNorm with a
learned scale (``rms_norm_eps``), pre-norm blocks::

    a      = N_in(x)
    c_q    = N_q(a @ W_dq)                                        [L, 1536]
    q      = c_q @ W_uq -> [L, 64, 192] = [q_nope 128 | q_rope 64]
    [c|r]  = a @ W_dkv [L, 576];  c_kv = N_kv(c) [L, 512]
    q_rope, k_rope = rope(q_rope), rope(r)        # ONE k_rope for all heads
    [k_nope | v] = c_kv @ W_ukv -> [L, 64, 128 + 192];  k = [k_nope | k_rope]
    o      = softmax(scale * q.k^T over keys j <= i) . v         [L, 64, 192]
             scale = 192^-0.5 * (0.1 mscale_all_dim ln factor + 1)^2
    x      = x + o @ W_o
    m      = N_mlp(x)
    f      = SwiGLU(m)                              if l < first_k_dense_replace
    f      = SwiGLU_shared(m) + sum_{e in top8} w_e SwiGLU_e(m)       otherwise
             s = sigmoid(m @ W_r);  c = s + b
             g_j = sum of the 2 largest c in group j (n_group groups of
                   consecutive experts); keep the topk_group groups of
                   largest g; c' = c in a kept group, 0 elsewhere
             top8 by c';  w_e = routed_scaling_factor * s_e / (sum_top8 s + 1e-20)
    x      = x + f
    logits = N_final(x) @ head

    rope (YaRN) on the 64 rope sizes, pairs (2i, 2i+1), i = 0..31:
    f_i = theta^(-2i/64);  corr(n) = 64 ln(orig / (2 pi n)) / (2 ln theta)
    low = floor(corr(beta_fast)), high = ceil(corr(beta_slow))
    ramp_i = clip((i - low) / (high - low), 0, 1)
    inv_freq_i = f_i (1 - ramp_i) + (f_i / factor) ramp_i
    cos and sin times (0.1 mscale ln factor + 1) / (0.1 mscale_all_dim ln factor + 1)

(``W_ukv`` is held as its two column groups, `lib/weights_dsv3.py`.) Of
the selected experts only the HELD ones add their part
(`weights_dsv3.held`), as on one chip of the expert-parallel deployment;
the shared expert is on every chip and is added whole. `config.json`
carries the routing's numbers and YaRN's; YaRN's arithmetic, the place
of the two latent norms, ``c'`` masked with 0, the ``1e-20`` and the
selection-only bias are the published ``modeling_deepseek.py`` as
recalled (the configuration file's ``assumed``). Rotary pairs are (2i,
2i+1), a permutation of HF's columns, shared with the program. The
multi-token-prediction layer is not here (``num_nextn_predict_layers``
0 in the configuration as run).

Attention is computed a block of queries at a time, so that an
8,192-token request fits: nothing else is blocked or batched.

``control=True`` computes the same forward in the nearest precision
below bf16: int8 weights (per output channel) and int8 activations (per
token) at every linear layer but the router, which a low-precision
deployment keeps in float32.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights_dsv3
from benchmark.lib.reference_llm import _gaps, _rms_norm
from benchmark.lib.reference_mimo import _freeze, _linear

HIGHEST = jax.lax.Precision.HIGHEST
#: queries scored at once: [heads, 256, L] float32 is 537 MB at 8,192
QUERY_BLOCK = 256


def _norm(x, scale, model):
    return _rms_norm(x, scale.astype(jnp.float32),
                     float(model["rms_norm_eps"]))


def _mscale(factor: float, mscale: float) -> float:
    if factor <= 1 or not mscale:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(model: dict) -> float:
    yarn = model["rope_scaling"]
    head = int(model["qk_nope_head_dim"]) + int(model["qk_rope_head_dim"])
    return head ** -0.5 * _mscale(
        float(yarn["factor"]), float(yarn["mscale_all_dim"])) ** 2


def inv_freq(model: dict) -> np.ndarray:
    """YaRN's frequency of each of the ``qk_rope_head_dim / 2`` pairs."""
    yarn = model["rope_scaling"]
    dim, theta = int(model["qk_rope_head_dim"]), float(model["rope_theta"])
    original = float(yarn["original_max_position_embeddings"])
    plain = np.array([theta ** (-2.0 * i / dim) for i in range(dim // 2)])

    def corr(turns):
        return (dim * math.log(original / (2 * math.pi * turns))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(float(yarn["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(yarn["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return plain * (1 - ramp) + plain / float(yarn["factor"]) * ramp


def rope(x, model: dict):
    """x [L, ..., 64] at positions 0..L-1; pairs (2i, 2i+1)."""
    yarn = model["rope_scaling"]
    angles = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
              * jnp.asarray(inv_freq(model), jnp.float32)[None, :])
    angles = angles.reshape(
        (x.shape[0],) + (1,) * (x.ndim - 2) + (angles.shape[1],))
    size = (_mscale(float(yarn["factor"]), float(yarn["mscale"]))
            / _mscale(float(yarn["factor"]), float(yarn["mscale_all_dim"])))
    cos, sin = jnp.cos(angles) * size, jnp.sin(angles) * size
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def latent_norm(x, scale, model):
    """N_q and N_kv: the norm on each low-rank latent."""
    return _norm(x, scale, model)


def attention(x, w, model: dict, control: bool = False):
    """Attn(N_in(x)) @ W_o of one sequence x [L, d], in the plain form."""
    length = x.shape[0]
    nope, rank = int(model["qk_nope_head_dim"]), int(model["kv_lora_rank"])
    a = _norm(x, w["attn_norm"], model)
    c_q = latent_norm(_linear(a, w["w_dq"], control), w["q_norm"], model)
    q = _linear(c_q, w["w_uq"], control)
    down = _linear(a, w["w_dkv"], control)
    c_kv = latent_norm(down[:, :rank], w["kv_norm"], model)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], model)], axis=-1)
    k_rope = rope(down[:, rank:], model)
    k_nope = _linear(c_kv, w["w_uk"], control)
    v = _linear(c_kv, w["w_uv"], control)
    heads = q.shape[1]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, None], (length, heads, k_rope.shape[-1]))], axis=-1)
    scale = softmax_scale(model)
    block = min(length, QUERY_BLOCK)
    blocks = q.reshape(length // block, block, heads, -1)
    key = jnp.arange(length)[None, :]

    def one(args):
        index, q_block = args
        query = index * block + jnp.arange(block)[:, None]
        scores = jnp.einsum("lhd,shd->hls", q_block, k,
                            precision=HIGHEST) * scale
        probs = jax.nn.softmax(
            jnp.where((key <= query)[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hls,shd->lhd", probs, v, precision=HIGHEST)

    out = jax.lax.map(one, (jnp.arange(length // block), blocks))
    return _linear(out.reshape(length, heads, -1), w["w_o"], control, 2)


def _swiglu(h, w, control):
    gate = jax.nn.silu(_linear(h, w["w_gate"], control))
    return _linear(gate * _linear(h, w["w_up"], control), w["w_down"], control)


def route(h, w, model: dict, held: tuple):
    """The router over all experts for tokens h [L, d]: (chosen [L, K],
    weight [L, K], margin [L]). ``margin`` is how clearly both
    selections stand where they concern this chip, the lesser of two
    distances. Of the experts': the least change in one held expert's
    ``c'`` that would move it across the top-K's edge (a chosen one
    under the first left out, another over the last chosen). Of the
    groups': where a group with a held expert is kept, the distance in
    ``g`` between the last group kept and the first left out (any swap
    changes whom the held experts compete with); where none is, what the
    best such group lacks to the last group kept. Under it a lower
    precision may choose otherwise, and a whole held expert's output
    comes or goes with the choice."""
    first, count = held
    top_k = int(model["num_experts_per_tok"])
    n_group, topk_group = int(model["n_group"]), int(model["topk_group"])
    scores = jax.nn.sigmoid(jnp.matmul(
        h, w["router"].astype(jnp.float32), precision=HIGHEST))
    biased = scores + w["router_bias"].astype(jnp.float32)
    tokens, experts = biased.shape
    margin = jnp.full((tokens,), jnp.inf)
    if n_group > 1:
        size = experts // n_group
        grouped = biased.reshape(tokens, n_group, size)
        g = jnp.sort(grouped, axis=-1)[..., -2:].sum(axis=-1)
        ranked = jnp.sort(g, axis=-1)[:, ::-1]
        last_kept = ranked[:, topk_group - 1]
        # a group is kept when fewer than topk_group groups lie before it
        # (larger g, or equal g at a smaller index)
        before = ((g[:, None, :] > g[:, :, None])
                  | ((g[:, None, :] == g[:, :, None])
                     & (jnp.arange(n_group)[None, None, :]
                        < jnp.arange(n_group)[None, :, None]))).sum(axis=-1)
        keep = before < topk_group
        biased = jnp.where(keep[:, :, None], grouped, 0.0).reshape(
            tokens, experts)
        mine = sorted({e // size for e in range(first, first + count)})
        if topk_group < n_group:
            some_kept = keep[:, mine].any(axis=-1)
            margin = jnp.where(
                some_kept, last_kept - ranked[:, topk_group],
                last_kept - g[:, mine].max(axis=-1))
    ranked, order = jax.lax.top_k(biased, top_k + 1)
    chosen = order[:, :top_k]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = picked * float(model["routed_scaling_factor"])
    if model["norm_topk_prob"]:
        weight = weight / (picked.sum(axis=-1, keepdims=True) + 1e-20)
    last_in, first_out = ranked[:, top_k - 1:top_k], ranked[:, top_k:]
    held_c = biased[:, first:first + count]
    edge = jnp.where(
        held_c >= last_in, held_c - first_out, last_in - held_c).min(axis=-1)
    return chosen, weight, jnp.minimum(margin, edge)


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
    if int(model["n_shared_experts"]):
        out = out + _swiglu(h, w["shared"], control)
    return out


def layer_and_margin(x, w, model: dict, experts: bool, held: tuple,
                     control: bool = False):
    """One block over one sequence x [L, d] (``experts``: routed, else
    the dense MLP), and its router's margin [L] (:func:`route`; infinity
    for a dense layer)."""
    x = x + attention(x, w, model, control)
    m = _norm(x, w["mlp_norm"], model)
    if experts:
        return (x + expert_layer(m, w, model, held, control),
                route(m, w, model, held)[2])
    return x + _swiglu(m, w, control), jnp.full(x.shape[:1], jnp.inf)


def layer(x, w, model: dict, experts: bool, held: tuple,
          control: bool = False):
    return layer_and_margin(x, w, model, experts, held, control)[0]


def embed(tokens, top):
    return top["embed"].astype(jnp.float32)[jnp.asarray(tokens)]


def head(x, top, model: dict, control: bool = False):
    return _linear(_norm(x, top["final_norm"], model), top["lm_head"], control)


def forward(tokens, top, layers, model: dict, held: tuple,
            control: bool = False):
    """Logits [L, V] of one sequence of token ids, from the weights
    given (``layers`` may be a generator: one layer is held at a time).
    A length over :data:`QUERY_BLOCK` is a whole number of them."""
    x = embed(tokens, top)
    for index, w in enumerate(layers):
        x = layer(x, w, model, weights_dsv3.expert_layer(model, index), held,
                  control)
    return head(x, top, model, control)


def _thaw(frozen):
    return {k: dict(v) if k == "rope_scaling" else
            list(v) if isinstance(v, tuple) else v for k, v in frozen}


def _freeze_model(model: dict):
    flat = dict(model)
    flat["rope_scaling"] = tuple(sorted(model["rope_scaling"].items()))
    return _freeze(flat)


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen, experts, held, control):
    """One compiled program a kind of layer, not a layer."""
    return jax.jit(functools.partial(
        layer_and_margin, model=_thaw(frozen), experts=experts, held=held,
        control=control))


def served_token_gaps(seed: int, model: dict, sequences: list,
                      control: bool = False) -> list:
    """As `reference_llm.served_token_gaps`: the reference runs once over
    prompt + served tokens of each sequence, and each served token's
    logit is read against the reference's best at its position; with
    ``control`` also the int8 forward's first choice there. Beside each
    gap goes its position's ``margins`` entry: the narrowest router
    margin (:func:`route`) of the reference's own expert layers there."""
    held = weights_dsv3.held(model)
    frozen = _freeze_model(model)
    top = weights_dsv3.top(seed, model)
    # padded at the end to a whole number of query blocks (causal: the
    # tail changes nothing before it), so that few lengths compile
    tokens = []
    for s in sequences:
        ids = np.asarray(s["prompt"] + s["served"], np.int32)
        tokens.append(np.pad(ids, (0, -len(ids) % QUERY_BLOCK)))
    hidden = [embed(t, top) for t in tokens]
    margins = [jnp.full(len(t), jnp.inf) for t in tokens]
    lowered = list(hidden) if control else None
    for index in range(int(model["num_hidden_layers"])):
        w = weights_dsv3.layer(seed, index, model)
        experts = weights_dsv3.expert_layer(model, index)
        for i, h in enumerate(hidden):
            hidden[i], margin = _layer_fn(frozen, experts, held, False)(h, w)
            margins[i] = jnp.minimum(margins[i], margin)
        if control:
            lowered = [_layer_fn(frozen, experts, held, True)(h, w)[0]
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
