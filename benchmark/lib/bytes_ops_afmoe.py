"""What `trinity_mini`'s (``afmoe``) decode step has to move or compute,
from the configuration's ``model`` group alone: the same work whatever
implements it. JAX-free: the harness's parent reads the metrics."""

BF16 = 2


def expert_bytes(model: dict) -> int:
    """One routed expert's three matrices, bf16: what touching it streams."""
    return (3 * int(model["hidden_size"])
            * int(model["moe_intermediate_size"]) * BF16)


def pair_flops(model: dict) -> int:
    """One (token, expert) pair through the expert's SwiGLU."""
    return 2 * 3 * int(model["hidden_size"]) * int(model["moe_intermediate_size"])


def layer_counts(model: dict) -> tuple:
    """(full layers, window layers, expert layers)."""
    layers = int(model["num_hidden_layers"])
    window = model["layer_types"][:layers].count("sliding_attention")
    return (layers - window, window,
            layers - int(model["num_dense_layers"]))


def kv_bytes_per_token(model: dict) -> int:
    """K and V of one token in one layer (either kind), bf16."""
    return (2 * int(model["num_key_value_heads"]) * int(model["head_dim"])
            * BF16)


def decode_attention_bytes(model: dict, tokens_full: float,
                           tokens_window: float) -> tuple:
    """(full layers', window layers') K/V bytes of one decode step:
    ``tokens_full`` is the lanes' contexts summed, ``tokens_window`` what
    of each the window reaches, summed (the engine's
    ``attn_tokens_full`` / ``attn_tokens_window`` a step). Queries and
    outputs are three orders of magnitude smaller and left out."""
    full, window, _ = layer_counts(model)
    a_token = kv_bytes_per_token(model)
    return full * a_token * tokens_full, window * a_token * tokens_window


def decode_step_weight_bytes(model: dict, touched_experts: float) -> float:
    """bf16 bytes of weights one decode step has to stream: every
    layer's attention (wq, wk, wv, the output gate, wo, the two head
    norms), its four norms, the dense MLPs, and in an expert layer the
    router over all experts routed over, the shared expert and the
    routed experts some lane chose (``touched_experts`` of them summed
    over the expert layers; the rest of the held experts is not read);
    the final norm and the output head (the embedding is a gather of a
    few rows)."""
    d = int(model["hidden_size"])
    heads, kv = (int(model["num_attention_heads"]),
                 int(model["num_key_value_heads"]))
    dh, f = int(model["head_dim"]), int(model["moe_intermediate_size"])
    full, window, experts = layer_counts(model)
    layers = full + window
    attention = 3 * d * heads * dh + 2 * d * kv * dh + 2 * dh
    params = (layers * (attention + 4 * d)
              + (layers - experts) * 3 * d * int(model["intermediate_size"])
              + experts * (d * int(model["experts_routed_over"])
                           + 3 * d * f * int(model["num_shared_experts"]))
              + d * int(model["vocab_size"]) + d)
    return BF16 * params + touched_experts * expert_bytes(model)
