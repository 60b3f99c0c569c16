"""Mistral-7B-v0.3 as `../../config.json` states it, through the program's
`LlmEngineModel`; weights from ``BENCH_SEED`` (`benchmark/lib/weights.py`)."""

import os

from benchmark.lib.serving_side import make_llm_model

CONFIG_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def create_model():
    return make_llm_model(CONFIG_DIR)
