"""`ouro_2_6b` inside the server child: the program's `LlmEngineModel`
over `client_tpu.models.ouro`, at the sizes `config.json` states (its
``toy`` group under ``BENCH_TOY=1``), weights from ``BENCH_SEED`` by
`benchmark.lib.weights_ouro` (`lib/serving_side.py` has the rest)."""

from client_tpu.llm.engine import EngineConfig
from client_tpu.models import ouro

from benchmark.lib import serving_side, weights_ouro


def ouro_config(model: dict) -> ouro.OuroConfig:
    """`config.json`'s ``model`` group (HF's keys) as the program's
    config. A setting the program's ``ouro`` does not implement is
    refused here, not passed over."""
    if (model["tie_word_embeddings"] or model["sliding_window"]
            or model["use_sliding_window"] or model["rope_scaling"]
            or model["hidden_act"] != "silu"
            or float(model["early_exit_threshold"]) < 1
            or set(model["layer_types"]) != {"full_attention"}
            or len(model["layer_types"]) != int(model["num_hidden_layers"])):
        raise ValueError("a head, window, rope scaling, activation, exit "
                         "threshold or layer type the program's ouro does "
                         "not implement")
    return ouro.OuroConfig(
        vocab_size=int(model["vocab_size"]),
        d_model=int(model["hidden_size"]),
        n_layers=int(model["num_hidden_layers"]),
        n_heads=int(model["num_attention_heads"]),
        n_kv_heads=int(model["num_key_value_heads"]),
        head_dim=int(model["head_dim"]),
        d_ff=int(model["intermediate_size"]),
        ut_steps=int(model["total_ut_steps"]),
        max_seq_len=int(model["max_position_embeddings"]),
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
    )


def make_ouro_model(config_dir: str):
    config = serving_side.load_config(config_dir)
    model, engine = config["model"], config["engine"]
    return serving_side.BenchLlmModel(
        name=config["name"],
        model=ouro.ENGINE_MODEL,
        config=ouro_config(model),
        params=weights_ouro.params(serving_side.seed(), model),
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
