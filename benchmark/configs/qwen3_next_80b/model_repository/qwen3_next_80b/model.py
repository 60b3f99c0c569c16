"""Qwen3-Next-80B-A3B-Instruct as `../../config.json` states it (one
chip's share of a deployment in which 16 chips share each layer,
published layers 0-15), through the program's `LlmEngineModel` over
`client_tpu.models.qwen3_next`; weights from ``BENCH_SEED``
(`benchmark/lib/weights_qwen3next.py`)."""

import os

from benchmark.lib.serving_qwen3next import make_qwen3next_model

CONFIG_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def create_model():
    return make_qwen3next_model(CONFIG_DIR)
