"""`gigachat3_702b` inside the server child: the program's
`LlmEngineModel` over `client_tpu.models.deepseek_v3`, at the sizes
`config.json` states (its ``toy`` group under ``BENCH_TOY=1``), weights
from ``BENCH_SEED`` by `benchmark.lib.weights_dsv3` (`lib/serving_side.py`
has the rest)."""

from client_tpu.llm.engine import EngineConfig
from client_tpu.models import deepseek_v3

from benchmark.lib import serving_side, weights_dsv3


def dsv3_config(model: dict) -> deepseek_v3.DeepseekV3Config:
    """`config.json`'s ``model`` group (HF's keys) as the program's
    config. A setting the program's ``deepseek_v3`` does not implement is
    refused here, not passed over."""
    yarn = model["rope_scaling"]
    if (model["scoring_func"] != "sigmoid" or not model["norm_topk_prob"]
            or model["topk_method"] != "noaux_tc"
            or model["tie_word_embeddings"] or model["attention_bias"]
            or model["hidden_act"] != "silu" or yarn["rope_type"] != "yarn"
            or int(model["moe_layer_freq"]) != 1
            or int(model["num_nextn_predict_layers"])
            or int(model["num_key_value_heads"])
            != int(model["num_attention_heads"])):
        raise ValueError("a routing, rope, bias or prediction setting the "
                         "program's deepseek_v3 does not implement")
    return deepseek_v3.DeepseekV3Config(
        vocab_size=int(model["vocab_size"]),
        d_model=int(model["hidden_size"]),
        n_layers=int(model["num_hidden_layers"]),
        n_heads=int(model["num_attention_heads"]),
        q_lora_rank=int(model["q_lora_rank"]),
        kv_lora_rank=int(model["kv_lora_rank"]),
        qk_nope_head_dim=int(model["qk_nope_head_dim"]),
        qk_rope_head_dim=int(model["qk_rope_head_dim"]),
        v_head_dim=int(model["v_head_dim"]),
        n_dense_layers=int(model["first_k_dense_replace"]),
        d_ff=int(model["intermediate_size"]),
        d_expert=int(model["moe_intermediate_size"]),
        n_experts=int(model["experts_routed_over"]),
        top_k=int(model["num_experts_per_tok"]),
        n_group=int(model["n_group"]),
        topk_group=int(model["topk_group"]),
        held=weights_dsv3.held(model),
        n_shared_experts=int(model["n_shared_experts"]),
        route_scale=float(model["routed_scaling_factor"]),
        rope_theta=float(model["rope_theta"]),
        rope_factor=float(yarn["factor"]),
        rope_original_max=int(yarn["original_max_position_embeddings"]),
        rope_beta_fast=float(yarn["beta_fast"]),
        rope_beta_slow=float(yarn["beta_slow"]),
        rope_mscale=float(yarn["mscale"]),
        rope_mscale_all_dim=float(yarn["mscale_all_dim"]),
        norm_eps=float(model["rms_norm_eps"]),
        max_seq_len=int(model["max_position_embeddings"]),
    )


def make_dsv3_model(config_dir: str):
    config = serving_side.load_config(config_dir)
    model, engine = config["model"], config["engine"]
    return serving_side.BenchLlmModel(
        name=config["name"],
        model=deepseek_v3.ENGINE_MODEL,
        config=dsv3_config(model),
        params=weights_dsv3.params(serving_side.seed(), model),
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
