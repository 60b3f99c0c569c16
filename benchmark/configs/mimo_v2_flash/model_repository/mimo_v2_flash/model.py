"""MiMo-V2-Flash as `../../config.json` states it (one chip's share of a
16-way expert-parallel deployment), through the program's
`LlmEngineModel` over `client_tpu.models.mimo_v2`; weights from
``BENCH_SEED`` (`benchmark/lib/weights_mimo.py`)."""

import os

from benchmark.lib.serving_mimo import make_mimo_model

CONFIG_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def create_model():
    return make_mimo_model(CONFIG_DIR)
