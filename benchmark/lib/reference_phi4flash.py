"""Phi-4-mini-flash-reasoning's (``model_type: phi4flash``, SambaY) plain
reference: float32, ``highest`` matmul precision, no cache, no paging, no
kernels, no chunks, no skipped cross-decoder: EVERY layer runs over EVERY
position, the Mamba layers' recurrence token by token (``lax.scan``) from
a zero state, the two softmaxes of every attention layer written out over
the whole sequence, one layer's weights at a time. Nothing here comes
from `client_tpu/models/`.

Equations, with ``x`` the residual stream and ``LN(x) = w (x - mean x) /
sqrt(var x + layer_norm_eps) + b``::

    h = x + Mixer(LN1 x);   y = h + MLP(LN2 h);   logits = LN_f(y) @ E^T
    MLP(m) = (silu(m W_g) * m W_u) W_d               # 2,560 -> 10,240 -> 2,560

``E`` is the embedding (``tie_word_embeddings``); **no rotary and no other
position signal**. Layer ``i`` of ``N`` = 32 (`weights_phi4flash.layer_kind`):
Mamba where even and ``i <= 16``; attention under a window of 512 where
odd and ``i < 16``; full attention at 17; past it a gated memory unit
where even, cross-attention where odd.

*Mamba* (``d_inner`` 5,120, ``d_state`` 16, ``dt_rank`` 160, 4 taps; no
norm on ``dl``, ``B`` or ``C``)::

    [u | z] = a W_in                                   # no bias
    u = silu(sum_j c_j u_{t-3+j} + bias)    # causal depthwise, 4 taps, bias
    [dl | B | C] = u W_x
    delta = softplus(dl W_dt + b_dt);   A = -exp(A_log)
    h_t[n, d] = exp(delta_t[d] A[n, d]) h_{t-1}[n, d] + delta_t[d] B_t[n] u_t[d]
    m_t[d] = sum_n h_t[n, d] C_t[n] + D[d] u_t[d];   out = (m_t * silu(z_t)) W_out

with ``h`` zero at the start of a sequence. Layer 16's ``m_t``, before
its gate, is the MEMORY. *Gated memory unit*: ``out_t = (m_t * silu(a_t
W_in)) W_out`` with ``m_t`` the memory at the same position.

*Differential attention* (40 heads of 64 over 20 key heads of 64):
``q_1, q_2`` are query heads ``2j, 2j + 1`` (20 pairs), ``k_1, k_2`` key
heads ``2p, 2p + 1`` (10 pairs), ``V_p = [v_2p | v_2p+1]``; query pair
``j`` belongs to key pair ``j // 2``::

    A_s = softmax(q_s k_s^T / sqrt(64) over the keys seen) V      # s = 1, 2
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init(i)
    o_j = (1 - lam_init(i)) * w_sub * rms(A_1 - lam A_2);   out = [o_j] W_o + b_o

``lam_init(i) = 0.8 - 0.6 exp(-0.3 i)``. A key is seen when it is not
after the query and, in a window layer, fewer than 512 positions before
it (the query itself counts). A self layer projects ``q, k, v`` with
biases; a cross layer projects ``q`` alone and takes ``k, v`` as layer 17
made them.

Departures from the published description, each shared with the program
and listed under ``assumed`` in the configuration's file: tensors are
held apart that the published weights hold side by side (``[q | k | v]``,
``[g | u]``); ``A_log`` and the state are ``[d_state, d_inner]``; the
seeded draws (`lib/weights_phi4flash.py`).

Attention is computed a block of queries at a time and the head a block
of positions at a time, so that an 8,192-token request fits: nothing else
is blocked or batched.

``control=True`` computes the same forward in the nearest precision below
bf16: int8 weights (per output channel) and int8 activations (per token)
at every linear layer, the tied head among them; the convolution, the
recurrence and its state, the softmaxes and the subtraction stay float32.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights_phi4flash as weights
from benchmark.lib.reference_jamba import (  # noqa: F401 - the same mixer's
    _margins, convolution, embed, skip,
)
from benchmark.lib.reference_llm import _gaps
from benchmark.lib.reference_mimo import _freeze, _linear

HIGHEST = jax.lax.Precision.HIGHEST
#: queries of one attention block; a length over it is a whole number of
#: them, so few lengths compile
QUERY_BLOCK = 256
#: positions of one block of the head's logits (200,064 wide)
HEAD_BLOCK = 512


def norm(x, w, b, model):
    """``LN``: mean-centred, scaled, with bias."""
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    unit = centred * jax.lax.rsqrt(
        jnp.mean(jnp.square(centred), axis=-1, keepdims=True)
        + float(model["layer_norm_eps"]))
    return unit * w.astype(jnp.float32) + b.astype(jnp.float32)


def positioned(x):
    """q or k [L, heads, D] with its position signal: none."""
    return x


def window_of(model):
    """Keys a window layer's query sees, itself included."""
    return int(model["sliding_window"])


def softmax_attention(q, k, v, window):
    """``q`` [L, P, D] over ``k`` [L, KVP, D] and ``v`` [L, KVP, Dv], query
    head ``j`` over key head ``j // (P / KVP)``: causal, under ``window``
    when given. One softmax, a block of queries at a time."""
    length, heads, dh = q.shape
    kv = k.shape[1]
    block = min(length, QUERY_BLOCK)
    blocks = q.reshape(length // block, block, kv, heads // kv, dh)

    def one(args):
        index, q_block = args
        query = index * block + jnp.arange(block)[:, None]
        key = jnp.arange(length)[None, :]
        scores = jnp.einsum("lkgd,skd->kgls", q_block, k,
                            precision=HIGHEST) / np.sqrt(dh)
        seen = key <= query
        if window is not None:
            seen &= key > query - window
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("kgls,skd->lkgd", probs, v, precision=HIGHEST)

    out = jax.lax.map(one, (jnp.arange(length // block), blocks))
    return out.reshape(length, heads, -1)


def paired(q, k):
    """``(q_1, q_2, k_1, k_2)``: the even and the odd heads."""
    return q[:, 0::2], q[:, 1::2], k[:, 0::2], k[:, 1::2]


def lambda_init(index):
    return weights.lambda_init(index)


def lam_of(w, init):
    """The weight of the subtracted softmax."""
    lq1, lk1, lq2, lk2 = w["lambdas"].astype(jnp.float32)
    return jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + init


def sub_norm(diff, w, model):
    """``w_sub rms(diff)`` over a pair's columns."""
    unit = diff * jax.lax.rsqrt(
        jnp.mean(jnp.square(diff), axis=-1, keepdims=True)
        + float(model["layer_norm_eps"]))
    return unit * w["sub_norm"].astype(jnp.float32)


def out_scale(init):
    return 1.0 - init


def cross_source(carry, a, full_w, control):
    """The ``(k, v)`` a cross layer reads: the full layer's, as it made
    them. (``a`` the cross layer's own input and ``full_w`` the full
    layer's weights are here for the tests' departures.)"""
    del a, full_w, control
    return carry["full_k"], carry["full_v"]


def attention(a, w, model, kind, init, carry, control=False):
    """Differential attention of the normed ``a`` [L, d]; ``init`` is the
    layer's ``lam_init``. Returns (out [L, d], the ``(k, v)`` it made or
    read)."""
    length = a.shape[0]
    q = positioned(_linear(a, w["wq"], control) + w["bq"].astype(jnp.float32))
    if kind == weights.CROSS:
        k, v = cross_source(carry, a, carry["full_w"], control)
    else:
        k = positioned(
            _linear(a, w["wk"], control) + w["bk"].astype(jnp.float32))
        v = _linear(a, w["wv"], control) + w["bv"].astype(jnp.float32)
    q1, q2, k1, k2 = paired(q, k)
    values = v.reshape(length, v.shape[1] // 2, -1)
    window = window_of(model) if kind == weights.WINDOW else None
    diff = (softmax_attention(q1, k1, values, window)
            - lam_of(w, init) * softmax_attention(q2, k2, values, window))
    out = out_scale(init) * sub_norm(diff, w, model)
    return (_linear(out, w["wo"], control, 2) + w["bo"].astype(jnp.float32),
            (k, v))


def inner_norm(x, name):
    """What stands on ``dl``, ``B`` and ``C`` inside the mixer: nothing
    (Jamba has a norm on each)."""
    del name
    return x


def memory_of(read, skipped, z):
    """What a Mamba layer hands the gated memory units: its scan output
    with ``D u``, BEFORE the gate ``silu(z)``."""
    del z
    return read + skipped


def memory_layer(model):
    return weights.memory_layer(model)


def mamba(a, w, model, control=False):
    """The Mamba mixer of the normed ``a`` [L, d]: the selective scan
    token by token from a zero state. Returns (out [L, d], the layer's
    memory [L, Di])."""
    di = weights.d_inner(model)
    r, n = int(model["mamba_dt_rank"]), int(model["mamba_d_state"])
    mixed = _linear(a, w["w_in"], control)
    u = convolution(mixed[:, :di], w["conv_w"], w["conv_b"])
    z = mixed[:, di:]
    projected = _linear(u, w["w_x"], control)
    dl = inner_norm(projected[:, :r], "dt")
    b = inner_norm(projected[:, r:r + n], "b")
    c = inner_norm(projected[:, r + n:], "c")
    delta = jax.nn.softplus(
        _linear(dl, w["w_dt"], control) + w["b_dt"].astype(jnp.float32))
    a_neg = -jnp.exp(w["A_log"].astype(jnp.float32))

    def token(h, xs):
        delta_t, u_t, b_t, c_t = xs
        h = (jnp.exp(delta_t[None, :] * a_neg) * h
             + (delta_t * u_t)[None, :] * b_t[:, None])
        return h, (h * c_t[:, None]).sum(axis=0)

    read = jax.lax.scan(token, jnp.zeros((n, di), jnp.float32),
                        (delta, u, b, c))[1]
    skipped = skip(u, w)
    out = _linear((read + skipped) * jax.nn.silu(z), w["w_out"], control)
    return out, memory_of(read, skipped, z)


def gated_memory(a, w, memory, control=False):
    gate = jax.nn.silu(_linear(a, w["w_in"], control))
    return _linear(memory * gate, w["w_out"], control)


def mlp(m, w, control=False):
    hidden = jax.nn.silu(_linear(m, w["w_gate"], control))
    return _linear(hidden * _linear(m, w["w_up"], control), w["w_down"],
                   control)


def start(length, model):
    """What the layers hand on beside ``x``: the memory, the full layer's
    ``(k, v)`` and weights, the last window layer's ``(k, v)``; zeros
    until the layer that makes each has run."""
    kv, dh = (int(model["num_key_value_heads"]),
              int(model["hidden_size"]) // int(model["num_attention_heads"]))
    blank = jnp.zeros((length, kv, dh), jnp.float32)
    return {"memory": jnp.zeros((length, weights.d_inner(model)),
                                jnp.float32),
            "full_k": blank, "full_v": blank, "full_w": None,
            "window_k": blank, "window_v": blank}


def layer(x, carry, w, init, model: dict, kind: str, is_memory: bool,
          control: bool = False):
    """One block over one sequence x [L, d]; ``init`` the layer's
    ``lam_init`` (unused outside attention), ``carry`` as :func:`start`
    has it. Returns (x, carry)."""
    a = norm(x, w["ln1_w"], w["ln1_b"], model)
    carry = dict(carry)
    if kind == weights.MAMBA:
        out, memory = mamba(a, w, model, control)
        if is_memory:
            carry["memory"] = memory
    elif kind == weights.GMU:
        out = gated_memory(a, w, carry["memory"], control)
    else:
        out, (k, v) = attention(a, w, model, kind, init, carry, control)
        if kind == weights.FULL:
            carry.update(full_k=k, full_v=v, full_w={
                name: w[name] for name in ("wk", "bk", "wv", "bv")})
        elif kind == weights.WINDOW:
            carry.update(window_k=k, window_v=v)
    x = x + out
    return (x + mlp(norm(x, w["ln2_w"], w["ln2_b"], model), w, control),
            carry)


def head(x, top, model: dict, control: bool = False):
    """The tied head: the final ``LN``, then the embedding transposed."""
    return _linear(norm(x, top["final_w"], top["final_b"], model),
                   top["embed"].T, control)


def forward(tokens, top, layers, model: dict, control: bool = False):
    """Logits [L, V] of one sequence of token ids, from the weights
    given (``layers`` may be a generator: one layer is held at a time).
    A length over :data:`QUERY_BLOCK` is a whole number of them."""
    with jax.default_matmul_precision("highest"):
        x = embed(tokens, top)
        carry = start(x.shape[0], model)
        for index, w in enumerate(layers):
            x, carry = layer(
                x, carry, w, lambda_init(index), model,
                weights.layer_kind(model, index),
                index == memory_layer(model), control)
        return head(x, top, model, control)


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen, kind, is_memory, control):
    """One compiled program a kind of layer, not a layer: ``lam_init``,
    which differs by layer, is an argument."""
    model = {k: list(v) if isinstance(v, tuple) else v for k, v in frozen}
    return jax.jit(functools.partial(
        layer, model=model, kind=kind, is_memory=is_memory, control=control))


@functools.lru_cache(maxsize=None)
def _head_fn(frozen, control):
    model = {k: list(v) if isinstance(v, tuple) else v for k, v in frozen}
    return jax.jit(functools.partial(head, model=model, control=control))


def served_token_gaps(seed: int, model: dict, sequences: list,
                      control: bool = False) -> list:
    """As `reference_llm.served_token_gaps`: the reference runs once over
    prompt + served tokens of each sequence, and each served token's
    logit is read against the reference's best at its position; with
    ``control`` also the int8 forward's first choice there. Beside each
    gap goes its position's ``margins`` entry: by how much the
    reference's best logit there stands over its second."""
    frozen = _freeze(model)
    top = weights.top(seed, model)
    tokens = []
    for s in sequences:
        ids = np.asarray(s["prompt"] + s["served"], np.int32)
        # padded at the end to a whole number of query blocks (causal: the
        # tail changes nothing before it)
        tokens.append(np.pad(ids, (0, -len(ids) % QUERY_BLOCK)))
    with jax.default_matmul_precision("highest"):
        hidden = [(embed(t, top), start(len(t), model)) for t in tokens]
        lowered = list(hidden) if control else None
        for index in range(int(model["num_hidden_layers"])):
            w = weights.layer(seed, index, model)
            which = (weights.layer_kind(model, index),
                     index == memory_layer(model))
            init = jnp.float32(lambda_init(index))
            hidden = [_layer_fn(frozen, *which, False)(*h, w, init)
                      for h in hidden]
            if control:
                lowered = [_layer_fn(frozen, *which, True)(*h, w, init)
                           for h in lowered]
        results = []
        for i, seq in enumerate(sequences):
            n_prompt, n_served = len(seq["prompt"]), len(seq["served"])
            served = np.asarray(seq["served"], np.int32)
            entry = {"gaps": [], "margins": [], "reference_first": []}
            if control:
                entry["control_gaps"] = []
            for begin in range(0, n_served, HEAD_BLOCK):
                at = slice(n_prompt - 1 + begin,
                           n_prompt - 1 + min(n_served, begin + HEAD_BLOCK))
                ref = _head_fn(frozen, False)(hidden[i][0][at], top)
                other = (_head_fn(frozen, True)(lowered[i][0][at], top)
                         if control else ref)
                gaps, control_gaps, first = _gaps(
                    ref, jnp.asarray(served[begin:begin + HEAD_BLOCK]),
                    other)
                entry["gaps"] += np.asarray(gaps).tolist()
                entry["margins"] += np.asarray(_margins(ref)).tolist()
                entry["reference_first"] += np.asarray(first).tolist()
                if control:
                    entry["control_gaps"] += np.asarray(
                        control_gaps).tolist()
            results.append(entry)
    return results
