"""AI21-Jamba2-3B's (``model_type: jamba``) plain reference: float32,
``highest`` matmul precision, no cache, no paging, no kernels, no chunks:
the Mamba layers' recurrence runs token by token (``lax.scan``) from a
zero state, attention is plain and causal over the whole sequence, one
layer's weights at a time. Nothing here comes from `client_tpu/models/`.

Equations, with ``x`` the residual stream, ``n(x) = x / sqrt(mean(x^2) +
rms_norm_eps)`` and every norm ``w n(x)``::

    h = x + Mixer(w1 n(x));   y = h + MLP(w2 n(h));   logits = w_f n(y) @ E^T
    MLP(m) = (silu(m W_g) * m W_u) W_d                  # 2,560 -> 8,192 -> 2,560

``E`` is the embedding (``tie_word_embeddings``). Layer ``i`` is attention
where ``i mod attn_layer_period = attn_layer_offset`` (layers 7 and 21 of
28), else Mamba; ``num_experts`` is 1, so every feed-forward is the dense
MLP.

*Attention* (20 heads of 128 over 1 KV head), no biases, **no rotary and
no other position signal**::

    q, k, v = a W_q, a W_k, a W_v
    out = softmax(q k^T / sqrt(128) over keys j <= i) v W_o

*Mamba* (``d_inner`` 5,120, ``d_state`` 16, ``dt_rank`` 160, 4 taps)::

    [u | z] = a W_in                                   # no bias
    u = silu(sum_j c_j u_{t-3+j} + bias)    # causal depthwise, 4 taps, bias
    [dl | B | C] = u W_x;   dl, B, C = w n(dl), w n(B), w n(C)
    delta = softplus(dl W_dt + b_dt);   A = -exp(A_log)
    h_t[n, d] = exp(delta_t[d] A[n, d]) h_{t-1}[n, d] + delta_t[d] B_t[n] u_t[d]
    y_t[d] = sum_n h_t[n, d] C_t[n] + D[d] u_t[d];   out = (y_t * silu(z_t)) W_out

with ``h`` zero at the start of a sequence.

Departures from the published description, each shared with the program
and listed under ``assumed`` in the configuration's file: ``A_log`` and
the state are held ``[d_state, d_inner]`` (the published ``[d_inner,
d_state]`` transposed: a layout, not a change of the mathematics); the
seeded draws (`lib/weights_jamba.py`).

Attention is computed a block of queries at a time and the head a block
of positions at a time, so that an 8,192-token request fits: nothing else
is blocked or batched.

``control=True`` computes the same forward in the nearest precision below
bf16: int8 weights (per output channel) and int8 activations (per token)
at every linear layer, the tied head among them; the convolution, the
recurrence and its state stay float32.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights_jamba
from benchmark.lib.reference_llm import _gaps
from benchmark.lib.reference_mimo import _freeze, _linear

HIGHEST = jax.lax.Precision.HIGHEST
#: queries of one attention block; a length over it is a whole number of
#: them, so few lengths compile
QUERY_BLOCK = 256
#: positions of one block of the head's logits (65,536 wide)
HEAD_BLOCK = 1024


def norm(x, w, model):
    """``w n(x)``: every norm of the model."""
    unit = x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        + float(model["rms_norm_eps"]))
    return unit * w.astype(jnp.float32)


def inner_norm(x, w, name, model):
    """Jamba's norm ``name`` (``dt_norm``, ``b_norm`` or ``c_norm``) on
    ``dl``, ``B`` or ``C`` inside the Mamba mixer."""
    return norm(x, w[name], model)


def positioned(x):
    """q or k [L, heads, D] with its position signal: none."""
    return x


def attention(a, w, model, control=False):
    """Multi-query attention of the normed ``a`` [L, d]."""
    length = a.shape[0]
    q = positioned(_linear(a, w["wq"], control))
    k = positioned(_linear(a, w["wk"], control))
    v = _linear(a, w["wv"], control)
    heads, kv, dh = q.shape[1], k.shape[1], q.shape[2]
    block = min(length, QUERY_BLOCK)
    blocks = q.reshape(length // block, block, kv, heads // kv, dh)

    def one(args):
        index, q_block = args
        query = index * block + jnp.arange(block)[:, None]
        scores = jnp.einsum("lkgd,skd->kgls", q_block, k,
                            precision=HIGHEST) / np.sqrt(dh)
        seen = jnp.arange(length)[None, :] <= query
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("kgls,skd->lkgd", probs, v, precision=HIGHEST)

    out = jax.lax.map(one, (jnp.arange(length // block), blocks))
    return _linear(out.reshape(length, heads, dh), w["wo"], control, 2)


def convolution(inputs, taps, bias):
    """Causal depthwise convolution WITH bias, then SiLU: ``inputs`` [L,
    C], ``taps`` [4, C], tap 3 the token itself."""
    length, count = inputs.shape[0], taps.shape[0]
    padded = jnp.pad(inputs, ((count - 1, 0), (0, 0)))
    taps = taps.astype(jnp.float32)
    return jax.nn.silu(sum(
        taps[j] * padded[j:j + length] for j in range(count))
        + bias.astype(jnp.float32))


def step_of(projected, w):
    """``delta`` [L, Di] of ``dl W_dt``."""
    return jax.nn.softplus(projected + w["b_dt"].astype(jnp.float32))


def decay_of(delta, w):
    """``exp(delta A)`` [L, N, Di], a channel's decay a token and state."""
    a = -jnp.exp(w["A_log"].astype(jnp.float32))
    return jnp.exp(delta[:, None, :] * a)


def skip(u, w):
    return w["D"].astype(jnp.float32) * u


def gate(z):
    return jax.nn.silu(z)


def mamba(a, w, model, control=False):
    """The Mamba mixer of the normed ``a`` [L, d]: the selective scan
    token by token from a zero state, a token's decays made where it is
    turned (never one ``[L, 16, 5120]`` tensor)."""
    di = weights_jamba.d_inner(model)
    r, n = int(model["mamba_dt_rank"]), int(model["mamba_d_state"])
    mixed = _linear(a, w["w_in"], control)
    u = convolution(mixed[:, :di], w["conv_w"], w["conv_b"])
    z = mixed[:, di:]
    projected = _linear(u, w["w_x"], control)
    dl = inner_norm(projected[:, :r], w, "dt_norm", model)
    b = inner_norm(projected[:, r:r + n], w, "b_norm", model)
    c = inner_norm(projected[:, r + n:], w, "c_norm", model)
    delta = step_of(_linear(dl, w["w_dt"], control), w)

    def token(h, xs):
        delta_t, u_t, b_t, c_t = xs
        h = (decay_of(delta_t[None], w)[0] * h
             + (delta_t * u_t)[None, :] * b_t[:, None])
        return h, (h * c_t[:, None]).sum(axis=0)

    read = jax.lax.scan(token, jnp.zeros((n, di), jnp.float32),
                        (delta, u, b, c))[1]
    return _linear((read + skip(u, w)) * gate(z), w["w_out"], control)


def mlp(m, w, control=False):
    hidden = jax.nn.silu(_linear(m, w["w_gate"], control))
    return _linear(hidden * _linear(m, w["w_up"], control), w["w_down"],
                   control)


def layer(x, w, model: dict, is_mamba: bool, control: bool = False):
    """One block over one sequence x [L, d]."""
    a = norm(x, w["mixer_norm"], model)
    x = x + (mamba if is_mamba else attention)(a, w, model, control)
    return x + mlp(norm(x, w["mlp_norm"], model), w, control)


def embed(tokens, top):
    return top["embed"].astype(jnp.float32)[jnp.asarray(tokens)]


def head(x, top, model: dict, control: bool = False):
    """The tied head: the final norm, then the embedding transposed."""
    return _linear(norm(x, top["final_norm"], model), top["embed"].T,
                   control)


def forward(tokens, top, layers, model: dict, control: bool = False):
    """Logits [L, V] of one sequence of token ids, from the weights
    given (``layers`` may be a generator: one layer is held at a time).
    A length over :data:`QUERY_BLOCK` is a whole number of them."""
    with jax.default_matmul_precision("highest"):
        x = embed(tokens, top)
        for index, w in enumerate(layers):
            x = layer(x, w, model, weights_jamba.mamba_layer(model, index),
                      control)
        return head(x, top, model, control)


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen, is_mamba, control):
    """One compiled program a kind of layer, not a layer."""
    model = {k: list(v) if isinstance(v, tuple) else v for k, v in frozen}
    return jax.jit(functools.partial(layer, model=model, is_mamba=is_mamba,
                                     control=control))


@functools.lru_cache(maxsize=None)
def _head_fn(frozen, control):
    model = {k: list(v) if isinstance(v, tuple) else v for k, v in frozen}
    return jax.jit(functools.partial(head, model=model, control=control))


@jax.jit
def _margins(ref_logits):
    """How far the reference's best stands over its second at each
    position."""
    best = jax.lax.top_k(ref_logits, 2)[0]
    return best[:, 0] - best[:, 1]


def served_token_gaps(seed: int, model: dict, sequences: list,
                      control: bool = False) -> list:
    """As `reference_llm.served_token_gaps`: the reference runs once over
    prompt + served tokens of each sequence, and each served token's
    logit is read against the reference's best at its position; with
    ``control`` also the int8 forward's first choice there. Beside each
    gap goes its position's ``margins`` entry: by how much the
    reference's best logit there stands over its second."""
    frozen = _freeze(model)
    top = weights_jamba.top(seed, model)
    tokens = []
    for s in sequences:
        ids = np.asarray(s["prompt"] + s["served"], np.int32)
        # padded at the end to a whole number of query blocks (causal: the
        # tail changes nothing before it)
        tokens.append(np.pad(ids, (0, -len(ids) % QUERY_BLOCK)))
    with jax.default_matmul_precision("highest"):
        hidden = [embed(t, top) for t in tokens]
        lowered = list(hidden) if control else None
        for index in range(int(model["num_hidden_layers"])):
            w = weights_jamba.layer(seed, index, model)
            is_mamba = weights_jamba.mamba_layer(model, index)
            hidden = [_layer_fn(frozen, is_mamba, False)(h, w)
                      for h in hidden]
            if control:
                lowered = [_layer_fn(frozen, is_mamba, True)(h, w)
                           for h in lowered]
        results = []
        for i, seq in enumerate(sequences):
            n_prompt, n_served = len(seq["prompt"]), len(seq["served"])
            served = np.asarray(seq["served"], np.int32)
            entry = {"gaps": [], "margins": [], "reference_first": []}
            if control:
                entry["control_gaps"] = []
            for start in range(0, n_served, HEAD_BLOCK):
                at = slice(n_prompt - 1 + start,
                           n_prompt - 1 + min(n_served, start + HEAD_BLOCK))
                ref = _head_fn(frozen, False)(hidden[i][at], top)
                other = (_head_fn(frozen, True)(lowered[i][at], top)
                         if control else ref)
                gaps, control_gaps, first = _gaps(
                    ref, jnp.asarray(served[start:start + HEAD_BLOCK]),
                    other)
                entry["gaps"] += np.asarray(gaps).tolist()
                entry["margins"] += np.asarray(_margins(ref)).tolist()
                entry["reference_first"] += np.asarray(first).tolist()
                if control:
                    entry["control_gaps"] += np.asarray(
                        control_gaps).tolist()
            results.append(entry)
    return results
