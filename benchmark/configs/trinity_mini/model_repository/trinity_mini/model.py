"""Trinity-Mini as `../../config.json` states it (one chip's share of an
8-way expert-parallel deployment, layers 0-15), through the program's
`LlmEngineModel` over `client_tpu.models.afmoe`; weights from
``BENCH_SEED`` (`benchmark/lib/weights_afmoe.py`)."""

import os

from benchmark.lib.serving_afmoe import make_afmoe_model

CONFIG_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def create_model():
    return make_afmoe_model(CONFIG_DIR)
