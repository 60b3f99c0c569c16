"""Ouro-2.6B as `../../config.json` states it (one chip holds the model
whole: all 48 layers, run four times a token, all 49,152 rows), through
the program's `LlmEngineModel` over `client_tpu.models.ouro`; weights
from ``BENCH_SEED`` (`benchmark/lib/weights_ouro.py`)."""

import os

from benchmark.lib.serving_ouro import make_ouro_model

CONFIG_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def create_model():
    return make_ouro_model(CONFIG_DIR)
