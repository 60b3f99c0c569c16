"""`mimo_v2_flash` inside the server child: the program's `LlmEngineModel`
over `client_tpu.models.mimo_v2`, at the sizes `config.json` states (its
``toy`` group under ``BENCH_TOY=1``), weights from ``BENCH_SEED`` by
`benchmark.lib.weights_mimo` (`lib/serving_side.py` has the rest)."""

from client_tpu.llm.engine import EngineConfig
from client_tpu.models import mimo_v2

from benchmark.lib import serving_side, weights_mimo


def mimo_config(model: dict) -> mimo_v2.MimoV2Config:
    """`config.json`'s ``model`` group (HF's keys) as the program's
    config. What the program has one number for and HF two has to agree."""
    weights_mimo.shape_key(model)  # raises where swa_* twins differ
    if (model.get("n_shared_experts") or model.get("routed_scaling_factor")
            or int(model["n_group"]) != 1 or int(model["topk_group"]) != 1
            or model["scoring_func"] != "sigmoid"
            or not model["norm_topk_prob"]
            or model["add_full_attention_sink_bias"]
            or not model["add_swa_attention_sink_bias"]):
        raise ValueError("a routing or sink setting the program's "
                         "mimo_v2 does not implement")
    head_dim = int(model["head_dim"])
    return mimo_v2.MimoV2Config(
        vocab_size=int(model["vocab_size"]),
        d_model=int(model["hidden_size"]),
        n_heads=int(model["num_attention_heads"]),
        n_kv_heads=int(model["num_key_value_heads"]),
        swa_n_kv_heads=int(model["swa_num_key_value_heads"]),
        head_dim=head_dim,
        v_head_dim=int(model["v_head_dim"]),
        rotary_dim=int(float(model["partial_rotary_factor"]) * head_dim),
        layer_kinds=tuple(model["hybrid_layer_pattern"]),
        moe_layers=tuple(model["moe_layer_freq"]),
        d_ff=int(model["intermediate_size"]),
        d_expert=int(model["moe_intermediate_size"]),
        n_experts=int(model["experts_routed_over"]),
        top_k=int(model["num_experts_per_tok"]),
        held=weights_mimo.held(model),
        window=int(model["sliding_window"]),
        rope_theta=float(model["rope_theta"]),
        swa_rope_theta=float(model["swa_rope_theta"]),
        value_scale=float(model["attention_value_scale"]),
        norm_eps=float(model["layernorm_epsilon"]),
        max_seq_len=int(model["max_position_embeddings"]),
    )


def make_mimo_model(config_dir: str):
    config = serving_side.load_config(config_dir)
    model, engine = config["model"], config["engine"]
    return serving_side.BenchLlmModel(
        name=config["name"],
        model=mimo_v2.ENGINE_MODEL,
        config=mimo_config(model),
        params=weights_mimo.params(serving_side.seed(), model),
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
