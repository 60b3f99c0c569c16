"""What `gigachat3_702b`'s (``deepseek_v3``) decode step has to move or
compute, from the configuration's ``model`` group alone: the same work
whatever implements it. JAX-free: the harness's parent reads the metrics.

The latent attention is the one kernel here whose FLOPs can outlast its
bytes: a cached token is ONE row of ``kv_lora_rank + qk_rope_head_dim``
numbers a layer (1,152 B in bf16) that all 64 heads score against (576
multiply-adds a head) and weigh (512 more), 139 kFLOP: 121 FLOP a byte
against the v5e's ridge of 240."""

BF16 = 2


def latent_row(model: dict) -> int:
    """Numbers a cached token holds a layer: the latent and the roped key."""
    return int(model["kv_lora_rank"]) + int(model["qk_rope_head_dim"])


def latent_bytes_per_token(model: dict) -> int:
    """One cached token in one layer as the arithmetic needs it, bf16
    (the pool stores it in rows of whole 128 lanes: padding is not
    counted, so it cannot flatter a roofline share)."""
    return latent_row(model) * BF16


def latent_flops_per_token(model: dict) -> int:
    """One cached token in one layer under the absorbed form: every head
    scores the row (``row`` multiply-adds) and weighs its latent
    (``kv_lora_rank`` more)."""
    return (2 * int(model["num_attention_heads"])
            * (latent_row(model) + int(model["kv_lora_rank"])))


def decode_attention_work(model: dict, tokens_full: float) -> tuple:
    """(bytes, FLOPs) of one decode step's attention over the cache:
    ``tokens_full`` is the lanes' contexts summed (the engine's
    ``attn_tokens_full`` a step), times the layers. Queries, outputs and
    the absorbed projections are the weights' and the step's, not the
    kernel's."""
    layers = int(model["num_hidden_layers"])
    return (layers * tokens_full * latent_bytes_per_token(model),
            layers * tokens_full * latent_flops_per_token(model))


def expert_bytes(model: dict) -> int:
    """One routed expert's three matrices, bf16: what touching it streams."""
    return (3 * int(model["hidden_size"])
            * int(model["moe_intermediate_size"]) * BF16)


def pair_flops(model: dict) -> int:
    """One (token, expert) pair through the expert's SwiGLU."""
    return 2 * 3 * int(model["hidden_size"]) * int(model["moe_intermediate_size"])


def expert_layers(model: dict) -> int:
    return int(model["num_hidden_layers"]) - int(model["first_k_dense_replace"])


def attention_params(model: dict) -> int:
    """A layer's attention: the two down-projections, their latent
    norms, the two up-projections (``kv_b_proj`` whole) and ``W_o``."""
    d, heads = int(model["hidden_size"]), int(model["num_attention_heads"])
    rq, rkv = int(model["q_lora_rank"]), int(model["kv_lora_rank"])
    nope, rope, dv = (int(model["qk_nope_head_dim"]),
                      int(model["qk_rope_head_dim"]), int(model["v_head_dim"]))
    return (d * rq + rq + rq * heads * (nope + rope) + d * (rkv + rope) + rkv
            + rkv * heads * (nope + dv) + heads * dv * d)


def decode_step_weight_bytes(model: dict, touched_experts: float) -> float:
    """bf16 bytes of weights one decode step has to stream: every
    layer's attention and its two block norms, the dense MLPs, and in an
    expert layer the router over all experts routed over, the shared
    expert and the routed experts some lane chose (``touched_experts``
    of them summed over the expert layers; the rest of the held experts
    is not read); the final norm and the output head (the embedding is a
    gather of a few rows)."""
    d = int(model["hidden_size"])
    f = int(model["moe_intermediate_size"])
    layers, experts = int(model["num_hidden_layers"]), expert_layers(model)
    params = (layers * (attention_params(model) + 2 * d)
              + (layers - experts) * 3 * d * int(model["intermediate_size"])
              + experts * (d * int(model["experts_routed_over"])
                           + 3 * d * f * int(model["n_shared_experts"]))
              + d * int(model["vocab_size"]) + d)
    return BF16 * params + touched_experts * expert_bytes(model)

