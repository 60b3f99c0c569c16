"""What `qwen3_next_80b`'s (``qwen3_next``) decode step has to move or
compute, from the configuration's ``model`` group alone: the same work
whatever implements it. JAX-free: the harness's parent reads the metrics.

A Gated DeltaNet layer's cost a step is per LANE and not per cached
token: the lane's float32 state (32 heads of [128, 128], 2,097,152 B) is
read and written once, with its convolution inputs (3 x 8,192 in bf16,
49,152 B): 2,146,304 B a lane a layer each way, as much as a full
layer's K/V at 2,100 tokens of context. A full layer's cached token is
2,048 B (K and V of 2 heads of 256 in bf16) that 16 heads score and
weigh: 16,384 FLOP, 8 FLOP a byte against the v5e's ridge of 240."""

BF16, F32 = 2, 4


def delta_layers(model: dict) -> int:
    layers = int(model["num_hidden_layers"])
    return layers - layers // int(model["full_attention_interval"])


def full_layers(model: dict) -> int:
    return int(model["num_hidden_layers"]) - delta_layers(model)


def conv_channels(model: dict) -> int:
    return (2 * int(model["linear_num_key_heads"])
            * int(model["linear_key_head_dim"])
            + int(model["linear_num_value_heads"])
            * int(model["linear_value_head_dim"]))


def state_bytes(model: dict) -> int:
    """One lane's recurrent state in one DeltaNet layer, float32."""
    return (int(model["linear_num_value_heads"])
            * int(model["linear_key_head_dim"])
            * int(model["linear_value_head_dim"]) * F32)


def conv_state_bytes(model: dict) -> int:
    """One lane's convolution inputs in one DeltaNet layer, bf16."""
    return (int(model["linear_conv_kernel_dim"]) - 1) * conv_channels(
        model) * BF16


def slot_bytes(model: dict) -> int:
    """What a lane holds in one DeltaNet layer (2,146,304 B as published)."""
    return state_bytes(model) + conv_state_bytes(model)


def kernel_state_bytes(updates: float, model: dict) -> float:
    """What ``updates`` (lane, layer) state updates have to move through
    the state-update kernel: each state in and out once. The
    convolution's inputs are shifted outside it and counted with the
    step (:func:`step_state_bytes`), not here, so that the kernel's
    share is of bytes its own time has to cover."""
    return updates * 2 * state_bytes(model)


def step_state_bytes(updates: float, model: dict) -> float:
    """State and convolution inputs of ``updates`` (lane, layer) pairs,
    read and written once: what the DeltaNet layers add to a step."""
    return updates * 2 * slot_bytes(model)


def kv_bytes_per_token(model: dict) -> int:
    """K and V of one cached token in one full layer, bf16."""
    return 2 * int(model["num_key_value_heads"]) * int(model["head_dim"]) * BF16


def kv_flops_per_token(model: dict) -> int:
    """Every head scores the token's key and weighs its value."""
    return 2 * int(model["num_attention_heads"]) * 2 * int(model["head_dim"])


def decode_attention_work(model: dict, tokens_full: float) -> tuple:
    """(bytes, FLOPs) of one decode step's attention over the cache:
    ``tokens_full`` is the lanes' contexts summed (the engine's
    ``attn_tokens_full`` a step), times the full layers."""
    layers = full_layers(model)
    return (layers * tokens_full * kv_bytes_per_token(model),
            layers * tokens_full * kv_flops_per_token(model))


def expert_bytes(model: dict) -> int:
    """One routed expert's three matrices, bf16: what touching it streams."""
    return (3 * int(model["hidden_size"])
            * int(model["moe_intermediate_size"]) * BF16)


def pair_flops(model: dict) -> int:
    """One (token, expert) pair through the expert's SwiGLU."""
    return 2 * 3 * int(model["hidden_size"]) * int(model["moe_intermediate_size"])


def delta_mixer_params(model: dict) -> int:
    """``w_qkvz``, ``w_ba``, the convolution's taps, ``A_log``,
    ``dt_bias``, the output norm and ``w_out`` (33,718,464 as published)."""
    d = int(model["hidden_size"])
    heads = int(model["linear_num_value_heads"])
    values = heads * int(model["linear_value_head_dim"])
    channels = conv_channels(model)
    return (d * (channels + values) + d * 2 * heads
            + int(model["linear_conv_kernel_dim"]) * channels
            + 2 * heads + int(model["linear_value_head_dim"]) + values * d)


def full_mixer_params(model: dict) -> int:
    """``wq`` with its gate, ``wk``, ``wv``, ``wo`` and the two head
    norms (27,263,488 as published)."""
    d, dh = int(model["hidden_size"]), int(model["head_dim"])
    heads, kv = (int(model["num_attention_heads"]),
                 int(model["num_key_value_heads"]))
    return d * heads * 2 * dh + 2 * d * kv * dh + heads * dh * d + 2 * dh


def decode_step_weight_bytes(model: dict, touched_experts: float) -> float:
    """bf16 bytes of weights one decode step has to stream: every layer's
    mixer and its two block norms, the router over all experts routed
    over, the shared expert with its gate and the routed experts some
    lane chose (``touched_experts`` of them summed over the layers; the
    rest of the held experts is not read); the final norm and the output
    head (the embedding is a gather of a few rows)."""
    d = int(model["hidden_size"])
    layers = int(model["num_hidden_layers"])
    params = (delta_layers(model) * delta_mixer_params(model)
              + full_layers(model) * full_mixer_params(model)
              + layers * (2 * d + d * int(model["experts_routed_over"])
                          + 3 * d * int(model["shared_expert_intermediate_size"])
                          + d)
              + d * int(model["vocab_size"]) + d)
    return BF16 * params + touched_experts * expert_bytes(model)
