"""GigaChat3.1-702B-A36B as `../../config.json` states it (one chip's
share of a 16-way expert-parallel deployment, published layers 0 and
3-6), through the program's `LlmEngineModel` over
`client_tpu.models.deepseek_v3`; weights from ``BENCH_SEED``
(`benchmark/lib/weights_dsv3.py`)."""

import os

from benchmark.lib.serving_dsv3 import make_dsv3_model

CONFIG_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def create_model():
    return make_dsv3_model(CONFIG_DIR)
