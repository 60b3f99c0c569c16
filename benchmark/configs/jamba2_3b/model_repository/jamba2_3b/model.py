"""AI21-Jamba2-3B as `../../config.json` states it (one chip holds the
model whole: all 28 layers, all 65,536 rows), through the program's
`LlmEngineModel` over `client_tpu.models.jamba`; weights from
``BENCH_SEED`` (`benchmark/lib/weights_jamba.py`)."""

import os

from benchmark.lib.serving_jamba import make_jamba_model

CONFIG_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def create_model():
    return make_jamba_model(CONFIG_DIR)
