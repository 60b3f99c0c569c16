"""What the benchmark's model files run INSIDE the server child.

The model files under ``benchmark/configs/*/model_repository`` are the
benchmark's own; they build the program's model classes unchanged, at the
sizes `config.json` states (or its ``toy`` group under ``BENCH_TOY=1``,
for CPU rehearsals), with weights from ``BENCH_SEED`` made by
`benchmark.lib.weights`. The one thing added is a report of the device
and its peak memory in the model's config, which nothing in the program
exports (`/metrics` has bytes in use, not the peak).
"""

import json
import os

from client_tpu.llm.engine import EngineConfig
from client_tpu.llm.serving import LlmEngineModel
from client_tpu.models.llama import LlamaConfig

from benchmark.lib import serving_config, weights


def load_config(config_dir: str) -> dict:
    return serving_config.load_config(
        config_dir, toy=os.environ.get("BENCH_TOY") == "1")


def seed() -> int:
    return int(os.environ.get("BENCH_SEED", "0"))


def device_report() -> dict:
    """Platform, kind, count and the fullest device's peak memory."""
    import jax

    devices = jax.local_devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


class _Reporting:
    def config(self):
        doc = super().config()
        parameters = doc.setdefault("parameters", {})
        parameters["bench_device"] = {
            "string_value": json.dumps(device_report())}
        engine = getattr(self, "engine", None)
        if engine is not None:
            parameters["bench_engine"] = {
                "string_value": json.dumps(engine.stats())}
        return doc


class BenchLlmModel(_Reporting, LlmEngineModel):
    def warmup(self) -> None:
        super().warmup()
        if os.environ.get("BENCH_BREAK") == "token":
            # the tests' broken timed path: every fifth decoded token is
            # altered where it is produced (`benchmark/tests`)
            sample, vocab = self.engine._sample_rows, self._config.vocab_size
            self.engine._sample_rows = lambda items: [
                (pick + 1) % vocab if gen_index % 5 == 4 else pick
                for pick, (_, _, gen_index) in zip(sample(items), items)]


def make_llm_model(config_dir: str) -> LlmEngineModel:
    config = load_config(config_dir)
    model, engine = config["model"], config["engine"]
    dims = weights.decoder_dims(model)
    d, heads, kv, _, d_ff, vocab, layers = dims
    llama = LlamaConfig(
        vocab_size=vocab, d_model=d, n_layers=layers, n_heads=heads,
        n_kv_heads=kv, d_ff=d_ff,
        max_seq_len=int(model["max_position_embeddings"]),
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
    )
    return BenchLlmModel(
        name=config["name"],
        config=llama,
        params=weights.decoder_params(seed(), dims),
        engine_config=EngineConfig(
            block_size=int(engine["block_size"]),
            num_blocks=int(engine["num_blocks"]),
            max_active=int(engine["max_active"]),
            max_queue=int(engine["max_queue"]),
            max_seq_len=int(model["max_position_embeddings"]),
            prefix_sharing=bool(engine["prefix_sharing"]),
        ),
        speculation=None,
    )
