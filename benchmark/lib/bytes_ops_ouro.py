"""What `ouro_2_6b`'s (``ouro``, a looped decoder) decode step has to move
or compute, from the configuration's ``model`` group and the program's
two counters alone: the same work whatever implements the loop. JAX-free:
the harness's parent reads the metrics.

A decode step runs ``layer_passes`` layer bodies (192 as published: 48
layers x ``total_ut_steps`` 4; an early exit would run fewer), and each
streams its layer's weights: the 48 layers' 4.93 GB cross HBM once a
PASS, 19.73 GB a step, because a pass's input is the pass before's
output and 16 lanes' activations are a thousandth of a layer's weights.
The untied head (0.20 GB) is streamed once; the embedding is a gather of
a few rows. A cached token is 8,192 B in one (pass, layer) pair (K and V
of 16 heads of 128, bf16) and every pair keeps its own: 1,572,864 B a
token over the 192 pairs, each row read once a step. The attention does
16 heads x (2 x 128 to score + 2 x 128 to weigh) = 8,192 FLOP a row: 1
FLOP a byte against the v5e's ridge of 240, bound by bytes."""

BF16 = 2


def layer_params(model: dict) -> int:
    """One layer: q, k, v, o, the SwiGLU's three matrices, four norms
    (51,388,416 as published)."""
    d, dh = int(model["hidden_size"]), int(model["head_dim"])
    heads, kv = (int(model["num_attention_heads"]),
                 int(model["num_key_value_heads"]))
    return (2 * d * heads * dh + 2 * d * kv * dh
            + 3 * d * int(model["intermediate_size"]) + 4 * d)


def cache_pairs(model: dict) -> int:
    """(pass, layer) pairs, each with K/V of its own (192)."""
    return int(model["total_ut_steps"]) * int(model["num_hidden_layers"])


def model_params(model: dict) -> int:
    """Every parameter: the layers once, embedding and head apart, the
    closing norm and the exit gate (2,667,974,657 as published)."""
    d = int(model["hidden_size"])
    return (int(model["num_hidden_layers"]) * layer_params(model)
            + 2 * int(model["vocab_size"]) * d + 2 * d + 1)


def loop_weight_bytes(model: dict, layer_passes: float) -> float:
    """Bytes of layer weights a step streams: one layer's a layer body
    run (19,733,151,744 at the published 192)."""
    return BF16 * layer_params(model) * layer_passes


def head_bytes(model: dict) -> int:
    """The untied head, streamed once a step (201,326,592 B)."""
    return BF16 * int(model["hidden_size"]) * int(model["vocab_size"])


def kv_row_bytes(model: dict) -> int:
    """K and V of one cached token in ONE (pass, layer) pair (8,192 B)."""
    return 2 * int(model["num_key_value_heads"]) * int(model["head_dim"]) * BF16


def kv_bytes_per_token(model: dict) -> int:
    """A cached token over all pairs (1,572,864 B)."""
    return cache_pairs(model) * kv_row_bytes(model)


def kv_row_flops(model: dict) -> int:
    """Every query head scores and weighs one cached row of its pair."""
    return int(model["num_attention_heads"]) * 4 * int(model["head_dim"])


def step_bytes(model: dict, layer_passes: float, kv_rows: float) -> tuple:
    """(layer weights', the head's, the K/V rows') bytes of one decode
    step: ``layer_passes`` and ``kv_rows`` are the program's counters a
    step (``loop_layer_passes``, ``loop_kv_rows_read``)."""
    return (loop_weight_bytes(model, layer_passes), head_bytes(model),
            kv_rows * kv_row_bytes(model))
