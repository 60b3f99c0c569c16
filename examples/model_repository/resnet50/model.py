"""ResNet-50 at its published widths, bf16 compute, 224x224 NHWC input.

One of the two models ``chip_smoke.py`` serves on the attached chip:
``python -m client_tpu.server --model-repository examples/model_repository``.
Weights are random, from seed 0. ``CLIENT_TPU_SMOKE_TINY=1`` swaps in the
ResNet18-thin test variant for CPU rehearsals of the smoke; nothing
measured at that size means anything.
"""

import os

from client_tpu.models.serving import ImageClassifierModel


def create_model():
    tiny = os.environ.get("CLIENT_TPU_SMOKE_TINY") == "1"
    return ImageClassifierModel(image_size=224, small=tiny)
