"""`jamba2_3b` inside the server child: the program's `LlmEngineModel`
over `client_tpu.models.jamba`, at the sizes `config.json` states (its
``toy`` group under ``BENCH_TOY=1``), weights from ``BENCH_SEED`` by
`benchmark.lib.weights_jamba` (`lib/serving_side.py` has the rest)."""

from client_tpu.llm.engine import EngineConfig
from client_tpu.models import jamba

from benchmark.lib import serving_side, weights_jamba


def jamba_config(model: dict) -> jamba.JambaConfig:
    """`config.json`'s ``model`` group (HF's keys) as the program's
    config. A setting the program's ``jamba`` does not implement is
    refused here, not passed over."""
    if (int(model["num_experts"]) != 1 or not model["tie_word_embeddings"]
            or model["hidden_act"] != "silu" or model["sliding_window"]
            or model["mamba_proj_bias"] or not model["mamba_conv_bias"]):
        raise ValueError("an expert, head, window or bias setting the "
                         "program's jamba does not implement")
    return jamba.JambaConfig(
        vocab_size=int(model["vocab_size"]),
        d_model=int(model["hidden_size"]),
        n_layers=int(model["num_hidden_layers"]),
        attn_period=int(model["attn_layer_period"]),
        attn_offset=int(model["attn_layer_offset"]),
        n_heads=int(model["num_attention_heads"]),
        n_kv_heads=int(model["num_key_value_heads"]),
        d_ff=int(model["intermediate_size"]),
        d_state=int(model["mamba_d_state"]),
        d_conv=int(model["mamba_d_conv"]),
        expand=int(model["mamba_expand"]),
        dt_rank=int(model["mamba_dt_rank"]),
        norm_eps=float(model["rms_norm_eps"]),
        max_seq_len=int(model["max_position_embeddings"]),
    )


def make_jamba_model(config_dir: str):
    config = serving_side.load_config(config_dir)
    model, engine = config["model"], config["engine"]
    return serving_side.BenchLlmModel(
        name=config["name"],
        model=jamba.ENGINE_MODEL,
        config=jamba_config(model),
        params=weights_jamba.params(serving_side.seed(), model),
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
