"""`qwen3_next_80b` inside the server child: the program's
`LlmEngineModel` over `client_tpu.models.qwen3_next`, at the sizes
`config.json` states (its ``toy`` group under ``BENCH_TOY=1``), weights
from ``BENCH_SEED`` by `benchmark.lib.weights_qwen3next`
(`lib/serving_side.py` has the rest)."""

from client_tpu.llm.engine import EngineConfig
from client_tpu.models import qwen3_next

from benchmark.lib import serving_side, weights_qwen3next


def qwen3next_config(model: dict) -> qwen3_next.Qwen3NextConfig:
    """`config.json`'s ``model`` group (HF's keys) as the program's
    config. A setting the program's ``qwen3_next`` does not implement is
    refused here, not passed over."""
    if (not model["norm_topk_prob"] or model["tie_word_embeddings"]
            or model["hidden_act"] != "silu" or model["rope_scaling"]
            or model["use_sliding_window"] or model["mlp_only_layers"]
            or int(model["decoder_sparse_step"]) != 1):
        raise ValueError("a routing, rope, window or dense-layer setting "
                         "the program's qwen3_next does not implement")
    return qwen3_next.Qwen3NextConfig(
        vocab_size=int(model["vocab_size"]),
        d_model=int(model["hidden_size"]),
        n_layers=int(model["num_hidden_layers"]),
        full_interval=int(model["full_attention_interval"]),
        n_heads=int(model["num_attention_heads"]),
        n_kv_heads=int(model["num_key_value_heads"]),
        head_dim=int(model["head_dim"]),
        rotary_dim=weights_qwen3next.rotary_dim(model),
        lin_key_heads=int(model["linear_num_key_heads"]),
        lin_value_heads=int(model["linear_num_value_heads"]),
        lin_key_dim=int(model["linear_key_head_dim"]),
        lin_value_dim=int(model["linear_value_head_dim"]),
        conv_kernel=int(model["linear_conv_kernel_dim"]),
        d_expert=int(model["moe_intermediate_size"]),
        d_shared=int(model["shared_expert_intermediate_size"]),
        n_experts=int(model["experts_routed_over"]),
        top_k=int(model["num_experts_per_tok"]),
        held=weights_qwen3next.held(model),
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        max_seq_len=int(model["max_position_embeddings"]),
    )


def make_qwen3next_model(config_dir: str):
    config = serving_side.load_config(config_dir)
    model, engine = config["model"], config["engine"]
    return serving_side.BenchLlmModel(
        name=config["name"],
        model=qwen3_next.ENGINE_MODEL,
        config=qwen3next_config(model),
        params=weights_qwen3next.params(serving_side.seed(), model),
        engine_config=EngineConfig(
            block_size=int(engine["block_size"]),
            num_blocks=int(engine["num_blocks"]),
            max_active=int(engine["max_active"]),
            max_queue=int(engine["max_queue"]),
            max_seq_len=int(model["max_position_embeddings"]),
            prefix_sharing=bool(engine["prefix_sharing"]),
        ),
        speculation=None,
    )
