"""The decoder's plain reference: float32, ``highest`` matmul precision,
no cache, no paging, no batching tricks, one layer's weights at a time.

Equations: pre-norm decoder block with RMSNorm, grouped-query attention
with rotary embeddings, SwiGLU MLP (Mistral-7B, Jiang et al. 2023,
arXiv:2310.06825; no sliding window in v0.3). One departure, shared with
the program: the rotary pairs are (2i, 2i+1) as in the original RoPE
paper, not HF's (i, i+d/2) layout; with weights from a seed the two are
a permutation of ``wq``/``wk`` columns apart.

``control=True`` computes the same forward in the nearest precision
below the configuration's bf16: int8 weights (per output channel) and
int8 activations (per token) at every linear layer, the step that would
tempt a later PR. It reports, at each position, the token IT puts first.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights


def _fake_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def _rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def _rope(x, theta):
    """x [L, H, D]; positions 0..L-1; pairs (2i, 2i+1)."""
    length, _, head_dim = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                             / head_dim))
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _linear(x, w, control, contract):
    """x [..., contract dims] @ w: ``contract`` trailing dims of x against
    the leading dims of w. Under ``control`` both sides pass through int8."""
    w = w.astype(jnp.float32)
    k = int(np.prod(w.shape[:contract]))
    w2 = w.reshape(k, -1)
    x2 = x.reshape(-1, k)
    if control:
        w2 = _fake_int8(w2, axis=0)
        x2 = _fake_int8(x2, axis=1)
    out = jnp.matmul(x2, w2, precision=jax.lax.Precision.HIGHEST)
    return out.reshape(x.shape[: x.ndim - contract] + w.shape[contract:])


def _layer(x, w, dims, theta, eps, control):
    """One block over one sequence x [L, d]."""
    d, h, kv, hd, _, _, _ = dims
    length = x.shape[0]
    normed = _rms_norm(x, w["attn_norm"].astype(jnp.float32), eps)
    q = _rope(_linear(normed, w["wq"], control, 1), theta)
    k = _rope(_linear(normed, w["wk"], control, 1), theta)
    v = _linear(normed, w["wv"], control, 1)
    group = h // kv
    qg = q.reshape(length, kv, group, hd)
    scores = jnp.einsum("lkgd,skd->kgls", qg, k,
                        precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
    causal = jnp.arange(length)[:, None] >= jnp.arange(length)[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("kgls,skd->lkgd", probs, v,
                     precision=jax.lax.Precision.HIGHEST).reshape(length, h, hd)
    x = x + _linear(out, w["wo"], control, 2)
    normed = _rms_norm(x, w["mlp_norm"].astype(jnp.float32), eps)
    gate = jax.nn.silu(_linear(normed, w["w_gate"], control, 1))
    up = _linear(normed, w["w_up"], control, 1)
    return x + _linear(gate * up, w["w_down"], control, 1)


@functools.lru_cache(maxsize=None)
def _layer_fn(dims, theta, eps, control):
    return jax.jit(functools.partial(_layer, dims=dims, theta=theta, eps=eps,
                                     control=control))


@functools.lru_cache(maxsize=None)
def _head_fn(eps, control):
    @jax.jit
    def head(x, top):
        normed = _rms_norm(x, top["final_norm"].astype(jnp.float32), eps)
        return _linear(normed, top["lm_head"], control, 1)

    return head


@jax.jit
def _gaps(ref_logits, next_tokens, other_logits):
    """At each position: how far the served next token's reference logit
    lies below the reference's best, and the same for the token that
    ``other_logits`` (the control's) puts first."""
    best = jnp.max(ref_logits, axis=-1)
    served = jnp.take_along_axis(ref_logits, next_tokens[:, None], axis=-1)[:, 0]
    first = jnp.argmax(other_logits, axis=-1)
    other = jnp.take_along_axis(ref_logits, first[:, None], axis=-1)[:, 0]
    return best - served, best - other, jnp.argmax(ref_logits, axis=-1)


def served_token_gaps(seed: int, model: dict, sequences: list,
                      control: bool = False) -> list:
    """For each ``{"prompt": [...], "served": [...]}``: the reference runs
    ONCE over prompt + served tokens, and every served token's logit is
    read against the reference's best at its position.

    Returns one dict a sequence: ``gaps`` (one per served token),
    ``reference_first`` (the reference's own first choice there) and,
    with ``control``, ``control_gaps`` (the int8 forward's first choice
    at the same positions, read on the reference's logits)."""
    dims = weights.decoder_dims(model)
    theta = float(model["rope_theta"])
    eps = float(model["rms_norm_eps"])
    top = weights.decoder_top(seed, dims)
    embed = top["embed"].astype(jnp.float32)
    # padded at the end to a multiple of 128 (causal: the tail changes
    # nothing before it), so that few lengths compile
    tokens = []
    for s in sequences:
        ids = np.asarray(s["prompt"] + s["served"], np.int32)
        tokens.append(np.pad(ids, (0, -len(ids) % 128)))
    hidden = [embed[t] for t in tokens]
    lowered = [h for h in hidden] if control else None
    for index in range(dims[6]):
        layer = weights.decoder_layer(seed, index, dims)
        hidden = [_layer_fn(dims, theta, eps, False)(h, layer) for h in hidden]
        if control:
            lowered = [_layer_fn(dims, theta, eps, True)(h, layer)
                       for h in lowered]
    results = []
    for i, seq in enumerate(sequences):
        n_prompt, n_served = len(seq["prompt"]), len(seq["served"])
        at = slice(n_prompt - 1, n_prompt + n_served - 1)
        ref = _head_fn(eps, False)(hidden[i][at], top)
        other = _head_fn(eps, True)(lowered[i][at], top) if control else ref
        gaps, control_gaps, first = _gaps(
            ref, jnp.asarray(seq["served"], jnp.int32), other)
        entry = {"gaps": np.asarray(gaps).tolist(),
                 "reference_first": np.asarray(first).tolist()}
        if control:
            entry["control_gaps"] = np.asarray(control_gaps).tolist()
        results.append(entry)
    return results
