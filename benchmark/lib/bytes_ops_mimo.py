"""What `mimo_v2_flash`'s kernels have to move or compute, from the
configuration's ``model`` group alone (published widths: a K row counts
192 sizes, whatever the cache pads it to)."""

BF16 = 2


def expert_bytes(model: dict) -> int:
    """One expert's three matrices, bf16: what touching it streams."""
    return (3 * int(model["hidden_size"])
            * int(model["moe_intermediate_size"]) * BF16)


def pair_flops(model: dict) -> int:
    """One (token, expert) pair through the expert's SwiGLU."""
    return 2 * 3 * int(model["hidden_size"]) * int(model["moe_intermediate_size"])


def layer_counts(model: dict) -> tuple:
    """(full layers, window layers, expert layers)."""
    window = sum(model["hybrid_layer_pattern"])
    return (len(model["hybrid_layer_pattern"]) - window, window,
            sum(model["moe_layer_freq"]))


def decode_step_weight_bytes(model: dict, touched_experts: float) -> float:
    """bf16 bytes of weights one decode step has to stream: every
    layer's attention (wq, wk, wv, wo at its kind's KV heads), the dense
    MLPs, the routers over all experts routed over, the norms, the output
    head (the embedding is a gather of a few rows), and the experts some
    lane chose, ``touched_experts`` of them summed over the expert
    layers. The rest of the held experts is not read."""
    d = int(model["hidden_size"])
    heads = int(model["num_attention_heads"])
    dk, dv = int(model["head_dim"]), int(model["v_head_dim"])
    full, window, experts = layer_counts(model)

    def attention(kv: int) -> int:
        return d * heads * dk + d * kv * (dk + dv) + heads * dv * d

    params = (full * attention(int(model["num_key_value_heads"]))
              + window * attention(int(model["swa_num_key_value_heads"]))
              + (full + window - experts) * 3 * d
              * int(model["intermediate_size"])
              + experts * d * int(model["experts_routed_over"])
              + (full + window) * 2 * d
              + d * int(model["vocab_size"]) + d)
    return BF16 * params + touched_experts * expert_bytes(model)


def kv_bytes_per_token(model: dict, window: bool) -> int:
    """K and V of one token in one layer of that kind, bf16."""
    kv = int(model["swa_num_key_value_heads" if window
                   else "num_key_value_heads"])
    return kv * (int(model["head_dim"]) + int(model["v_head_dim"])) * BF16


def decode_attention_bytes(model: dict, contexts: list) -> int:
    """One decode step's attention over every layer: a full layer reads
    each lane's whole context, a window layer the last ``sliding_window``
    tokens of it."""
    full, window, _ = layer_counts(model)
    reach = int(model["sliding_window"])
    return (full * kv_bytes_per_token(model, False) * sum(contexts)
            + window * kv_bytes_per_token(model, True)
            * sum(min(c, reach) for c in contexts))
