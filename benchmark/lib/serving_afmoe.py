"""`trinity_mini` inside the server child: the program's `LlmEngineModel`
over `client_tpu.models.afmoe`, at the sizes `config.json` states (its
``toy`` group under ``BENCH_TOY=1``), weights from ``BENCH_SEED`` by
`benchmark.lib.weights_afmoe` (`lib/serving_side.py` has the rest)."""

from client_tpu.llm.engine import EngineConfig
from client_tpu.models import afmoe

from benchmark.lib import serving_side, weights_afmoe


def afmoe_config(model: dict) -> afmoe.AfmoeConfig:
    """`config.json`'s ``model`` group (HF's keys) as the program's
    config. A setting the program's ``afmoe`` does not implement is
    refused here, not passed over."""
    if (model["score_func"] != "sigmoid" or not model["route_norm"]
            or not model["mup_enabled"] or model["tie_word_embeddings"]
            or model["rope_scaling"] is not None
            or model["hidden_act"] != "silu"
            or any(int(model[k]) != 1 for k in (
                "n_group", "topk_group", "num_expert_groups",
                "num_limited_groups"))):
        raise ValueError("a routing, scaling or embedding setting the "
                         "program's afmoe does not implement")
    kinds = {"sliding_attention": 1, "full_attention": 0}
    return afmoe.AfmoeConfig(
        vocab_size=int(model["vocab_size"]),
        d_model=int(model["hidden_size"]),
        n_heads=int(model["num_attention_heads"]),
        n_kv_heads=int(model["num_key_value_heads"]),
        head_dim=int(model["head_dim"]),
        layer_kinds=tuple(kinds[t] for t in model["layer_types"]),
        n_dense_layers=int(model["num_dense_layers"]),
        d_ff=int(model["intermediate_size"]),
        d_expert=int(model["moe_intermediate_size"]),
        n_experts=int(model["experts_routed_over"]),
        top_k=int(model["num_experts_per_tok"]),
        held=weights_afmoe.held(model),
        n_shared_experts=int(model["num_shared_experts"]),
        route_scale=float(model["route_scale"]),
        window=int(model["sliding_window"]),
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        max_seq_len=int(model["max_position_embeddings"]),
    )


def make_afmoe_model(config_dir: str):
    config = serving_side.load_config(config_dir)
    model, engine = config["model"], config["engine"]
    if len(model["layer_types"]) != int(model["num_hidden_layers"]):
        raise ValueError("layer_types and num_hidden_layers differ")
    return serving_side.BenchLlmModel(
        name=config["name"],
        model=afmoe.ENGINE_MODEL,
        config=afmoe_config(model),
        params=weights_afmoe.params(serving_side.seed(), model),
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
