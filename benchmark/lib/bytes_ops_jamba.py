"""What `jamba2_3b`'s (``jamba``) decode step has to move or compute, from
the configuration's ``model`` group alone: the same work whatever
implements it. JAX-free: the harness's parent reads the metrics.

A Mamba layer's cost a step is per LANE and not per cached token: the
lane's float32 state (16 states of 5,120 channels, 327,680 B) is read and
written once, with its convolution inputs (3 x 5,120 in bf16, 30,720 B):
358,400 B a lane a layer each way, as much as an attention layer's K/V at
700 tokens of context. An attention layer's cached token is 512 B (K and
V of ONE head of 128 in bf16) that 20 heads score and weigh: 10,240 FLOP,
20 FLOP a byte against the v5e's ridge of 240. The head is the embedding
transposed, so the embedding is streamed once a step, as the head."""

BF16, F32 = 2, 4


def mamba_layers(model: dict) -> int:
    layers = int(model["num_hidden_layers"])
    return layers - attention_layers(model)


def attention_layers(model: dict) -> int:
    """Layers ``i`` with ``i mod attn_layer_period = attn_layer_offset``."""
    period, offset = (int(model["attn_layer_period"]),
                      int(model["attn_layer_offset"]))
    return sum(i % period == offset
               for i in range(int(model["num_hidden_layers"])))


def d_inner(model: dict) -> int:
    return int(model["mamba_expand"]) * int(model["hidden_size"])


def state_bytes(model: dict) -> int:
    """One lane's recurrent state in one Mamba layer, float32."""
    return int(model["mamba_d_state"]) * d_inner(model) * F32


def conv_state_bytes(model: dict) -> int:
    """One lane's convolution inputs in one Mamba layer, bf16."""
    return (int(model["mamba_d_conv"]) - 1) * d_inner(model) * BF16


def slot_bytes(model: dict) -> int:
    """What a lane holds in one Mamba layer (358,400 B as published)."""
    return state_bytes(model) + conv_state_bytes(model)


def kernel_state_bytes(updates: float, model: dict) -> float:
    """What ``updates`` (lane, layer) state updates have to move through
    the scan's decode kernel: each state in and out once. The
    convolution's inputs are shifted outside it and counted with the step
    (:func:`step_state_bytes`), not here, so that the kernel's share is
    of bytes its own time has to cover."""
    return updates * 2 * state_bytes(model)


def step_state_bytes(updates: float, model: dict) -> float:
    """State and convolution inputs of ``updates`` (lane, layer) pairs,
    read and written once: what the Mamba layers add to a step."""
    return updates * 2 * slot_bytes(model)


def kv_bytes_per_token(model: dict) -> int:
    """K and V of one cached token in one attention layer, bf16."""
    head_dim = int(model["hidden_size"]) // int(model["num_attention_heads"])
    return 2 * int(model["num_key_value_heads"]) * head_dim * BF16


def kv_flops_per_token(model: dict) -> int:
    """Every head scores the token's key and weighs its value."""
    return 2 * 2 * int(model["hidden_size"])


def decode_attention_work(model: dict, tokens_full: float) -> tuple:
    """(bytes, FLOPs) of one decode step's attention over the cache:
    ``tokens_full`` is the lanes' contexts summed (the engine's
    ``attn_tokens_full`` a step), times the attention layers."""
    layers = attention_layers(model)
    return (layers * tokens_full * kv_bytes_per_token(model),
            layers * tokens_full * kv_flops_per_token(model))


def mamba_mixer_params(model: dict) -> int:
    """``W_in``, the convolution's taps and bias, ``W_x``, the three inner
    norms, ``W_dt`` with ``b_dt``, ``A_log``, ``D`` and ``W_out``
    (41,241,792 as published)."""
    d, di = int(model["hidden_size"]), d_inner(model)
    n, r = int(model["mamba_d_state"]), int(model["mamba_dt_rank"])
    return (d * 2 * di + (int(model["mamba_d_conv"]) + 1) * di
            + di * (r + 2 * n) + r + 2 * n + r * di + di + n * di + di
            + di * d)


def attention_mixer_params(model: dict) -> int:
    """``W_q``, ``W_k``, ``W_v`` and ``W_o`` (13,762,560 as published)."""
    d = int(model["hidden_size"])
    head_dim = d // int(model["num_attention_heads"])
    return 2 * d * d + 2 * d * int(model["num_key_value_heads"]) * head_dim


def mlp_params(model: dict) -> int:
    """The dense SwiGLU's three matrices (62,914,560 as published)."""
    return 3 * int(model["hidden_size"]) * int(model["intermediate_size"])


def model_params(model: dict) -> int:
    """Every parameter, the tied embedding once (3,029M as published)."""
    d = int(model["hidden_size"])
    layers = int(model["num_hidden_layers"])
    return (mamba_layers(model) * mamba_mixer_params(model)
            + attention_layers(model) * attention_mixer_params(model)
            + layers * (mlp_params(model) + 2 * d)
            + int(model["vocab_size"]) * d + d)


def decode_step_weight_bytes(model: dict) -> float:
    """Bytes of weights one decode step has to stream: every parameter
    once, the embedding as the head (its gather of a few rows beside
    that is not counted). bf16, but ``A_log``, ``D`` and ``b_dt``, which
    the program holds in float32."""
    in_float32 = mamba_layers(model) * (
        int(model["mamba_d_state"]) + 2) * d_inner(model)
    return BF16 * model_params(model) + (F32 - BF16) * in_float32
