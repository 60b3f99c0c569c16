"""Phi-4-mini-flash-reasoning as `../../config.json` states it (one chip
holds the model whole: all 32 layers, all 200,064 rows), through the
program's `LlmEngineModel` over `client_tpu.models.phi4flash`; weights
from ``BENCH_SEED`` (`benchmark/lib/weights_phi4flash.py`)."""

import os

from benchmark.lib.serving_phi4flash import make_phi4flash_model

CONFIG_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def create_model():
    return make_phi4flash_model(CONFIG_DIR)
