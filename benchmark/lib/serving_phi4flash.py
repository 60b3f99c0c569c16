"""`phi4_mini_flash` inside the server child: the program's
`LlmEngineModel` over `client_tpu.models.phi4flash`, at the sizes
`config.json` states (its ``toy`` group under ``BENCH_TOY=1``), weights
from ``BENCH_SEED`` by `benchmark.lib.weights_phi4flash`
(`lib/serving_side.py` has the rest)."""

from client_tpu.llm.engine import EngineConfig
from client_tpu.models import phi4flash

from benchmark.lib import serving_side, weights_phi4flash


def phi4flash_config(model: dict) -> phi4flash.Phi4FlashConfig:
    """`config.json`'s ``model`` group (HF's keys, and the ``assumed``
    Mamba sizes under Jamba's names) as the program's config. A setting
    the program's ``phi4flash`` does not implement is refused here, not
    passed over."""
    if (int(model["mb_per_layer"]) != 2 or not model["tie_word_embeddings"]
            or model["hidden_act"] != "silu" or model["mlp_bias"]
            or model["lm_head_bias"]):
        raise ValueError("a layer order, head, activation or bias setting "
                         "the program's phi4flash does not implement")
    config = phi4flash.Phi4FlashConfig(
        vocab_size=int(model["vocab_size"]),
        d_model=int(model["hidden_size"]),
        n_layers=int(model["num_hidden_layers"]),
        n_heads=int(model["num_attention_heads"]),
        n_kv_heads=int(model["num_key_value_heads"]),
        d_ff=int(model["intermediate_size"]),
        window=int(model["sliding_window"]),
        d_state=int(model["mamba_d_state"]),
        d_conv=int(model["mamba_d_conv"]),
        expand=int(model["mamba_expand"]),
        dt_rank=int(model["mamba_dt_rank"]),
        norm_eps=float(model["layer_norm_eps"]),
        max_seq_len=int(model["max_position_embeddings"]),
    )
    if list(config.layer_kinds) != weights_phi4flash.layer_kinds(model):
        raise ValueError("the program's layer order is not the weights'")
    return config


def make_phi4flash_model(config_dir: str):
    config = serving_side.load_config(config_dir)
    model, engine = config["model"], config["engine"]
    return serving_side.BenchLlmModel(
        name=config["name"],
        model=phi4flash.ENGINE_MODEL,
        config=phi4flash_config(model),
        params=weights_phi4flash.params(serving_side.seed(), model),
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
