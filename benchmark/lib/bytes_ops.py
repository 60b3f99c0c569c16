"""What the algorithm has to move or compute, from shapes alone."""

BF16 = 2


def decoder_layer_params(model: dict) -> int:
    d = int(model["hidden_size"])
    heads = int(model["num_attention_heads"])
    kv = int(model["num_key_value_heads"])
    head_dim = int(model.get("head_dim") or d // heads)
    d_ff = int(model["intermediate_size"])
    attention = d * heads * head_dim * 2 + d * kv * head_dim * 2
    return attention + 3 * d * d_ff + 2 * d


def decoder_step_weight_bytes(model: dict) -> int:
    """bf16 bytes one decode step has to stream: every layer and the
    output head (the embedding is a gather of a few rows, not streamed)."""
    d, vocab = int(model["hidden_size"]), int(model["vocab_size"])
    layers = int(model["num_hidden_layers"])
    return BF16 * (layers * decoder_layer_params(model) + d * vocab + d)


def kv_bytes_per_token_per_layer(model: dict) -> int:
    """K and V of one token in one layer, bf16."""
    d = int(model["hidden_size"])
    heads = int(model["num_attention_heads"])
    head_dim = int(model.get("head_dim") or d // heads)
    return 2 * int(model["num_key_value_heads"]) * head_dim * BF16


def paged_attention_bytes(model: dict, contexts: list) -> int:
    """One call of the decode attention kernel (one layer, one step):
    the K/V of every live context, once. Queries and outputs are three
    orders of magnitude smaller and left out."""
    return kv_bytes_per_token_per_layer(model) * sum(contexts)


def roofline_share(bytes_moved: float, flops: float, seconds: float,
                   peak: dict) -> tuple:
    """(share in %, which bound) of the least time the chip could take."""
    by_bytes = bytes_moved / peak["hbm_bytes_per_s"]
    by_flops = flops / peak["bf16_flops_per_s"]
    least = max(by_bytes, by_flops)
    return 100.0 * least / seconds, ("hbm" if by_bytes >= by_flops else "mxu")
