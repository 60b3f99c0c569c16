"""Built-in JAX models for the in-repo server.

These mirror the fixture models the reference test/bench flows rely on:
``simple`` (the add_sub model every quick-start and integration test uses,
reference src/c++/tests/cc_client_test.cc), ``identity`` variants (BYTES and
fixed-size passthrough), and a decoupled ``repeat`` model for token-streaming
paths (reference custom_repeat example) — implemented as jitted JAX
functions, not torch/CUDA.
"""

import asyncio
from typing import Any, AsyncIterator, Dict

import numpy as np

from client_tpu.server.model_repository import Model
from client_tpu.utils import InferenceServerException


def pad_batch_bucket(rows: int, minimum: int = 1) -> int:
    """Next power-of-two batch bucket — bounds XLA retraces under dynamic
    batching to O(log max_batch) compiled programs."""
    bucket = max(minimum, 1)
    while bucket < rows:
        bucket *= 2
    return bucket


def run_bucketed(fn, *arrays):
    """Zero-pad the leading (batch) dim of every array to a shared
    power-of-two bucket, call ``fn(*padded)``, read ALL outputs back with
    ONE batched transfer, and slice back to the true batch size.

    One transfer instead of one blocking readback per output; the
    bucket bounds XLA retraces to O(log max_batch).
    ``fn`` must return a tuple/list of arrays batched on the leading dim.
    """
    import jax

    rows = arrays[0].shape[0]
    bucket = pad_batch_bucket(rows)
    if bucket != rows:
        arrays = tuple(
            np.concatenate(
                [a, np.zeros((bucket - rows,) + a.shape[1:], a.dtype)]
            )
            for a in arrays
        )
    outputs = jax.device_get(fn(*arrays))
    return tuple(np.asarray(o)[:rows] for o in outputs)


class AddSubModel(Model):
    """The canonical 'simple' model: OUTPUT0=IN0+IN1, OUTPUT1=IN0-IN1.

    INT32 [1,16] like the reference quick-start model (perf baselines in
    BASELINE.md target this model's request path).
    """

    platform = "jax"
    backend = "jax"
    max_batch_size = 64
    inputs = [
        {"name": "INPUT0", "datatype": "INT32", "shape": [16]},
        {"name": "INPUT1", "datatype": "INT32", "shape": [16]},
    ]
    outputs = [
        {"name": "OUTPUT0", "datatype": "INT32", "shape": [16]},
        {"name": "OUTPUT1", "datatype": "INT32", "shape": [16]},
    ]

    # Device placement: the reference's quick-start 'simple' config is a
    # host model (BASELINE.json configs: "'simple' add_sub model (CPU, no
    # shm)"): a tiny elementwise model has no FLOPs to earn a device
    # round-trip, so it is host-placed; accelerator models (resnet, llama)
    # run on the TPU.
    device = "cpu"

    def __init__(self, name: str = "simple"):
        self.name = name
        self._fn = None

    def warmup(self) -> None:
        import jax

        @jax.jit
        def add_sub(a, b):
            return a + b, a - b

        self._fn = add_sub
        # Compile the batch-1 bucket so the first request is fast; other
        # power-of-two buckets compile on first use and are cached.
        z = np.zeros([1, 16], dtype=np.int32)
        with self.placement():
            jax.block_until_ready(self._fn(z, z))

    def execute(self, inputs, parameters):
        a, b = inputs.get("INPUT0"), inputs.get("INPUT1")
        if a is None or b is None:
            raise InferenceServerException(
                "model 'simple' expects inputs INPUT0 and INPUT1"
            )
        if a.shape != b.shape:
            raise InferenceServerException(
                f"INPUT0 shape {list(a.shape)} != INPUT1 shape {list(b.shape)}"
            )
        out0, out1 = run_bucketed(self._fn, a, b)
        return {"OUTPUT0": out0, "OUTPUT1": out1}


class IdentityModel(Model):
    """Fixed-dtype passthrough (any shape): OUTPUT0 = INPUT0."""

    max_batch_size = 0

    def __init__(self, name: str = "identity_fp32", datatype: str = "FP32"):
        self.name = name
        self._datatype = datatype
        self.inputs = [{"name": "INPUT0", "datatype": datatype, "shape": [-1]}]
        self.outputs = [{"name": "OUTPUT0", "datatype": datatype, "shape": [-1]}]

    def execute(self, inputs, parameters):
        if "INPUT0" not in inputs:
            raise InferenceServerException(
                f"model '{self.name}' expects input INPUT0"
            )
        # Execution-delay knob for timeout/deadline tests (the role of the
        # reference identity backend's execute_delay parameter): requests
        # carrying delay_ms sleep that long before responding.
        delay_ms = parameters.get("delay_ms") if parameters else None
        if delay_ms:
            import time as _time

            _time.sleep(min(float(delay_ms), 10_000) / 1000.0)
        return {"OUTPUT0": inputs["INPUT0"]}


class BytesIdentityModel(IdentityModel):
    """BYTES passthrough — exercises string-tensor serialization."""

    def __init__(self, name: str = "identity_bytes"):
        super().__init__(name=name, datatype="BYTES")


class RepeatModel(Model):
    """Decoupled model: streams IN[i] back as one response per element.

    The minimal stand-in for token-by-token LLM decode streaming (reference
    decoupled custom_repeat example; token streaming contract SURVEY.md §5
    long-context notes). Honors a ``delay_us`` parameter between responses.
    """

    decoupled = True
    max_batch_size = 0
    inputs = [{"name": "IN", "datatype": "INT32", "shape": [-1]}]
    outputs = [{"name": "OUT", "datatype": "INT32", "shape": [1]}]

    def __init__(self, name: str = "repeat_int32"):
        self.name = name

    async def execute_decoupled(
        self, inputs: Dict[str, np.ndarray], parameters: Dict[str, Any]
    ) -> AsyncIterator[Dict[str, np.ndarray]]:
        if "IN" not in inputs:
            raise InferenceServerException("model 'repeat' expects input IN")
        delay_us = int(parameters.get("delay_us", 0))
        values = inputs["IN"].reshape(-1)
        for i, v in enumerate(values):
            if delay_us:
                await asyncio.sleep(delay_us / 1e6)
            yield {
                "OUT": np.array([v], dtype=np.int32),
                "__final__": i == len(values) - 1,
            }


class SequenceAccumulatorModel(Model):
    """Stateful sequence model: OUTPUT = running sum of INPUT per sequence.

    Declares ``sequence_batching`` in its config so clients auto-detect the
    scheduler kind (reference model_parser.cc sequence detection; the
    perf harness then drives it with sequence_id/start/end control
    parameters instead of needing a --sequence-model flag). State is keyed
    by the request's ``sequence_id`` parameter; ``sequence_start`` resets,
    ``sequence_end`` evicts.
    """

    max_batch_size = 0
    sequence_batching: Dict[str, Any] = {}
    inputs = [{"name": "INPUT", "datatype": "INT32", "shape": [1]}]
    outputs = [{"name": "OUTPUT", "datatype": "INT32", "shape": [1]}]

    def __init__(self, name: str = "sequence_accumulate"):
        import threading

        self.name = name
        self._totals: Dict[int, int] = {}
        self._lock = threading.Lock()

    def execute(self, inputs, parameters):
        if "INPUT" not in inputs:
            raise InferenceServerException(
                f"model '{self.name}' expects input INPUT"
            )
        seq_id = int(parameters.get("sequence_id", 0))
        if seq_id == 0:
            raise InferenceServerException(
                f"model '{self.name}' is a sequence model; requests need a "
                "non-zero sequence_id"
            )
        value = int(np.asarray(inputs["INPUT"]).reshape(-1)[0])
        with self._lock:
            if parameters.get("sequence_start"):
                self._totals[seq_id] = 0
            if seq_id not in self._totals:
                raise InferenceServerException(
                    f"sequence {seq_id} has no open state; send "
                    "sequence_start first"
                )
            # int32 wraparound semantics: load generators feed arbitrary
            # int32 values, and a running sum must not overflow numpy's
            # bounds checking.
            self._totals[seq_id] = (self._totals[seq_id] + value) & 0xFFFFFFFF
            total = self._totals[seq_id]
            if parameters.get("sequence_end"):
                del self._totals[seq_id]
        return {
            "OUTPUT": np.array([total], dtype=np.uint32).astype(np.int32)
        }


class EnsembleModel(Model):
    """Composes other models into a pipeline (Triton ensembles).

    The config declares ``ensemble_scheduling.step`` entries with Triton's
    semantics: each step's ``input_map`` maps the composing model's input
    name to an ensemble-scope tensor name, ``output_map`` maps its outputs
    into ensemble scope. Steps execute in order inside ONE server-side
    execution — intermediate tensors never touch the wire (the reason
    ensembles exist; reference docs architecture.md ensemble section).
    """

    platform = "ensemble"
    backend = "ensemble"

    def __init__(self, name, repository, inputs, outputs, steps,
                 max_batch_size: int = 0):
        self.name = name
        self._repository = repository
        self.inputs = inputs
        self.outputs = outputs
        self.max_batch_size = max_batch_size
        self._steps = steps
        self.ensemble_scheduling = {"step": steps}

    def warmup(self) -> None:
        produced = {i["name"] for i in self.inputs}
        for step in self._steps:
            model = self._repository.get(step["model_name"])
            if model.decoupled:
                raise InferenceServerException(
                    f"ensemble '{self.name}' cannot compose decoupled "
                    f"model '{model.name}'"
                )
            produced.update(step["output_map"].values())
        # Output coverage is statically checkable: every declared ensemble
        # output must be produced by some step (or be a passthrough input).
        for out in self.outputs:
            if out["name"] not in produced:
                raise InferenceServerException(
                    f"ensemble '{self.name}' declares output "
                    f"'{out['name']}' but no step's output_map produces it"
                )

    def execute(self, inputs, parameters):
        pool = dict(inputs)
        for step in self._steps:
            model = self._repository.get(step["model_name"])
            sub_inputs = {}
            for comp_name, ens_name in step["input_map"].items():
                if ens_name not in pool:
                    raise InferenceServerException(
                        f"ensemble '{self.name}' step "
                        f"'{step['model_name']}' needs tensor '{ens_name}' "
                        "which no prior step produced"
                    )
                sub_inputs[comp_name] = pool[ens_name]
            with model.placement():
                # Request parameters flow to every composing model
                # (sequence controls, sampling knobs, ...), matching the
                # core's behavior on non-ensemble paths.
                raw = model.execute(sub_inputs, parameters)
            for comp_name, ens_name in step["output_map"].items():
                if comp_name not in raw:
                    raise InferenceServerException(
                        f"composing model '{step['model_name']}' produced "
                        f"no output '{comp_name}'"
                    )
                pool[ens_name] = raw[comp_name]
        missing = [o["name"] for o in self.outputs if o["name"] not in pool]
        if missing:
            raise InferenceServerException(
                f"ensemble '{self.name}' produced no tensor for declared "
                f"outputs {missing}"
            )
        return {o["name"]: pool[o["name"]] for o in self.outputs}


def register_builtin_models(repository) -> None:
    """Install the fixture models into a repository."""
    repository.add_model(AddSubModel())
    repository.add_model(IdentityModel("identity_fp32", "FP32"))
    repository.add_model(IdentityModel("identity_bf16", "BF16"))
    repository.add_model(BytesIdentityModel())
    repository.add_model(RepeatModel())
    repository.add_model(SequenceAccumulatorModel())
    # Demo ensemble: simple -> simple. OUTPUT0 = 2*INPUT0, OUTPUT1 =
    # 2*INPUT1 ((a+b)+(a-b), (a+b)-(a-b)).
    repository.add_model(
        EnsembleModel(
            "add_sub_chain",
            repository,
            inputs=[
                {"name": "INPUT0", "datatype": "INT32", "shape": [16]},
                {"name": "INPUT1", "datatype": "INT32", "shape": [16]},
            ],
            outputs=[
                {"name": "OUTPUT0", "datatype": "INT32", "shape": [16]},
                {"name": "OUTPUT1", "datatype": "INT32", "shape": [16]},
            ],
            steps=[
                {
                    "model_name": "simple",
                    "input_map": {"INPUT0": "INPUT0", "INPUT1": "INPUT1"},
                    "output_map": {"OUTPUT0": "mid0", "OUTPUT1": "mid1"},
                },
                {
                    "model_name": "simple",
                    "input_map": {"INPUT0": "mid0", "INPUT1": "mid1"},
                    "output_map": {
                        "OUTPUT0": "OUTPUT0",
                        "OUTPUT1": "OUTPUT1",
                    },
                },
            ],
            max_batch_size=64,
        )
    )
