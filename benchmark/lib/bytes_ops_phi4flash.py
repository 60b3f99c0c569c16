"""What `phi4_mini_flash`'s (``phi4flash``, SambaY) decode step has to move
or compute, from the configuration's ``model`` group alone: the same work
whatever implements it. JAX-free: the harness's parent reads the metrics.

A cached token is 5,120 B in a layer that STORES it (K: 20 heads of 64, V:
10 heads of 128, bf16): the one full layer and the eight window layers.
The cross-decoder stores nothing: its seven cross-attention layers read
the full layer's pool, so a token of context costs 5,120 B to keep and 8 x
5,120 B a step to read (each reader's queries depend on the layer before,
so no two reads can be joined). A window layer reads what of a lane's
context its window of 512 reaches. Every attention call scores a row's 20
key heads with 2 query heads each (128 FLOP a head) and weighs its 10
value heads with 4 (256 FLOP a head): 15,360 FLOP a 5,120 B row, 3 FLOP a
byte against the v5e's ridge of 240. A Mamba layer's cost a step is per
LANE (`lib/bytes_ops_jamba.py`: 358,400 B a lane a layer each way), a
gated memory unit's its two matrices. The head is the embedding
transposed, so the embedding is streamed once a step, as the head."""

from benchmark.lib.bytes_ops_jamba import (  # noqa: F401 - this model's too
    BF16, F32, d_inner, kernel_state_bytes, slot_bytes, state_bytes,
    step_state_bytes,
)

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"


def layer_kinds(model: dict) -> list:
    """The mixer of every layer (`lib/weights_phi4flash.py`'s docstring
    has the rule in words), without JAX."""
    layers, per = int(model["num_hidden_layers"]), int(model["mb_per_layer"])
    half = layers // 2

    def kind(i):
        if i % per == 0:
            return MAMBA if i <= half else GMU
        if i < half:
            return WINDOW
        return FULL if i == half + 1 else CROSS

    return [kind(i) for i in range(layers)]


def count(model: dict, *kinds) -> int:
    return sum(k in kinds for k in layer_kinds(model))


def shared_readers(model: dict) -> int:
    """Layers that read the full layer's pool: itself and the cross
    layers (8 as published)."""
    return count(model, FULL, CROSS)


def head_dim(model: dict) -> int:
    return int(model["hidden_size"]) // int(model["num_attention_heads"])


def kv_bytes_per_token(model: dict) -> int:
    """K and V of one cached token in one storing layer, bf16 (5,120 B)."""
    return 2 * int(model["num_key_value_heads"]) * head_dim(model) * BF16


def kv_flops_per_token(model: dict) -> int:
    """Every query head scores its key head (``D`` wide) and weighs the
    pair's value head (``2 D`` wide) over one cached token."""
    return int(model["num_attention_heads"]) * (2 + 4) * head_dim(model)


def decode_attention_work(model: dict, shared_rows: float,
                          window_tokens: float) -> tuple:
    """(bytes of the shared pool, bytes of the rings, FLOPs) of one decode
    step's attention: ``shared_rows`` the rows of the full group's pool
    the step reads (the program's ``shared_kv_rows_read`` a step: the
    lanes' contexts x the readers), ``window_tokens`` what a window
    layer's lanes see (the engine's ``attn_tokens_window`` a step), times
    the window layers."""
    rings = count(model, WINDOW) * window_tokens
    row = kv_bytes_per_token(model)
    return (shared_rows * row, rings * row,
            (shared_rows + rings) * kv_flops_per_token(model))


def mamba_mixer_params(model: dict) -> int:
    """``W_in``, the convolution's taps and bias, ``W_x``, ``W_dt`` with
    ``b_dt``, ``A_log``, ``D`` and ``W_out`` (41,241,600 as published:
    Jamba's less its three inner norms)."""
    d, di = int(model["hidden_size"]), d_inner(model)
    n, r = int(model["mamba_d_state"]), int(model["mamba_dt_rank"])
    return (d * 2 * di + (int(model["mamba_d_conv"]) + 1) * di
            + di * (r + 2 * n) + r * di + di + n * di + di + di * d)


def _attention_own(model: dict) -> int:
    """The four lambda vectors and the sub-norm."""
    return 4 * head_dim(model) + 2 * head_dim(model)


def attention_mixer_params(model: dict) -> int:
    """``W_qkv`` and ``W_o`` with their biases, the lambdas, the sub-norm
    (19,668,864 as published)."""
    d = int(model["hidden_size"])
    kv = int(model["num_key_value_heads"]) * head_dim(model)
    return d * (d + 2 * kv) + d + 2 * kv + d * d + d + _attention_own(model)


def cross_mixer_params(model: dict) -> int:
    """``W_q`` and ``W_o`` with their biases, the lambdas, the sub-norm
    (13,112,704 as published)."""
    d = int(model["hidden_size"])
    return 2 * (d * d + d) + _attention_own(model)


def gmu_params(model: dict) -> int:
    """A gated memory unit's two matrices (26,214,400 as published)."""
    return 2 * int(model["hidden_size"]) * d_inner(model)


def mlp_params(model: dict) -> int:
    """The dense SwiGLU's three matrices (78,643,200 as published)."""
    return 3 * int(model["hidden_size"]) * int(model["intermediate_size"])


def model_params(model: dict) -> int:
    """Every parameter, the tied embedding once (3,853M as published)."""
    d = int(model["hidden_size"])
    return (count(model, MAMBA) * mamba_mixer_params(model)
            + count(model, WINDOW, FULL) * attention_mixer_params(model)
            + count(model, CROSS) * cross_mixer_params(model)
            + count(model, GMU) * gmu_params(model)
            + int(model["num_hidden_layers"]) * (mlp_params(model) + 4 * d)
            + int(model["vocab_size"]) * d + 2 * d)


def decode_step_weight_bytes(model: dict) -> float:
    """Bytes of weights one decode step has to stream: every parameter
    once, the embedding as the head. bf16, but ``A_log``, ``D`` and
    ``b_dt`` of a Mamba layer and the lambdas of an attention layer, which
    the program holds in float32."""
    in_float32 = (
        count(model, MAMBA) * (int(model["mamba_d_state"]) + 2)
        * d_inner(model)
        + count(model, WINDOW, FULL, CROSS) * 4 * head_dim(model))
    return BF16 * model_params(model) + (F32 - BF16) * in_float32
