"""A decoder at the Llama-2-7B widths, depth cut to fit one 16 GB chip.

Widths are ``LlamaConfig()``'s defaults, which are the published ones:
d_model 4096, 32 heads of 128 (MHA, 32 KV heads), d_ff 11008, vocab
32000, bf16. Block size 16, ``max_seq_len`` 2048 (128 blocks a sequence,
so the multi-block decode buckets are exercised). Weights are random,
from seed 0.

Depth: ``N_LAYERS = 16`` of the published 32. One layer is 0.405 GB of
bf16 weights (4 x 4096^2 attention + 3 x 4096 x 11008 MLP), embedding
plus head another 0.524 GB, and one 16-token block holds 0.262 MB of K/V
per layer. The engine's default pool is 8 full-length sequences (1025
blocks), so 16 layers take 7.0 GB of weights + 4.3 GB of pool = 11.3 GB
and leave room for ResNet-50, prefill scratch and the logits; 20 layers
(14.0 GB) would not. Under ``LLAMA_SMOKE_TP=4`` the same model is
sharded over four chips (heads and KV heads 8 a chip).

This is the serving path's smoke, not a benchmark configuration: the
Llama family may not be one (ROADMAP queue R). ``CLIENT_TPU_SMOKE_TINY=1``
swaps in ``LlamaConfig.tiny`` for CPU rehearsals of the smoke.
"""

import os

from client_tpu.llm.serving import LlmEngineModel
from client_tpu.models.llama import LlamaConfig

N_LAYERS = 16


def create_model():
    if os.environ.get("CLIENT_TPU_SMOKE_TINY") == "1":
        config = LlamaConfig.tiny(max_seq_len=2048)
    else:
        config = LlamaConfig(n_layers=N_LAYERS, max_seq_len=2048)
    return LlmEngineModel(
        config=config,
        speculation={"mode": "ngram", "k": 4},
        tp=int(os.environ.get("LLAMA_SMOKE_TP", "1")),
    )
