"""Sharded multi-device serving (client_tpu.parallel sharding/executor).

Every test runs on the CPU mesh (the hermetic tier pins
``XLA_FLAGS=--xla_force_host_platform_device_count=8``); the ``sharded``
marker + ``sharded_devices`` fixture re-exec a test in a subprocess with
that flag when the current process's backend initialized single-device.

Coverage: declaration validation, resolution failures with operator
reasons, the executor's pad/place/gather contract, exact-tolerance
parity of a tensor-parallel model vs its single-device reference through
ALL FOUR ServerCore execution paths, ring-attention prefill vs dense
prefill, per-device metrics/debug/metadata surfaces, load-failure
ergonomics (UNAVAILABLE + reason, not a 500), and the perf-harness
per-device duty reduction.
"""

import asyncio
import json

import numpy as np
import pytest

from client_tpu.parallel import (
    MeshDeclarationError,
    MeshSpec,
    MeshUnavailableError,
    ShardedExecutor,
)
from client_tpu.parallel.sharding import resolve
from client_tpu.server.core import CoreRequest, CoreTensor, ServerCore
from client_tpu.server.model_repository import (
    ModelRepository,
    ModelUnavailableError,
)

pytestmark = pytest.mark.sharded

# numerical tolerance for sharded-vs-reference float32 parity: the tp
# reduction split and the ring's online softmax reorder float adds (same
# tolerance the ring_attention kernel tests use); measured max diff on
# this mesh is ~1e-6
TOL = dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# declaration + resolution


def test_mesh_spec_validation():
    spec = MeshSpec.parse(
        {
            "axes": {"dp": 2, "tp": 2},
            "inputs": {"X": ["dp", None]},
            "outputs": {"Y": [["dp", "tp"], None]},
        }
    )
    assert spec.device_count == 4
    assert spec.axis_sizes == {"dp": 2, "tp": 2}
    assert spec.inputs["X"] == ("dp", None)
    assert spec.outputs["Y"] == (("dp", "tp"), None)

    with pytest.raises(MeshDeclarationError, match="non-empty 'axes'"):
        MeshSpec.parse({"inputs": {}})
    with pytest.raises(MeshDeclarationError, match="positive int"):
        MeshSpec.parse({"axes": {"dp": 0}})
    with pytest.raises(MeshDeclarationError, match="positive int"):
        MeshSpec.parse({"axes": {"dp": True}})
    with pytest.raises(MeshDeclarationError, match="unknown axis"):
        MeshSpec.parse({"axes": {"dp": 2}, "inputs": {"X": ["tp"]}})
    with pytest.raises(MeshDeclarationError, match="unknown mesh"):
        MeshSpec.parse({"axes": {"dp": 2}, "input": {}})
    with pytest.raises(MeshDeclarationError, match="must be a list"):
        MeshSpec.parse({"axes": {"dp": 2}, "inputs": {"X": "dp"}})


def test_resolve_too_few_devices_reason(sharded_devices):
    spec = MeshSpec.parse({"axes": {"dp": 2, "tp": 2}})
    with pytest.raises(
        MeshUnavailableError, match="mesh requires 4 devices, host has 1"
    ):
        resolve(spec, devices=sharded_devices[:1])
    plan = resolve(spec, devices=sharded_devices)
    assert plan.device_labels == tuple(
        str(d.id) for d in sharded_devices[:4]
    )
    doc = plan.describe()
    assert doc["axes"] == {"dp": 2, "tp": 2}
    assert doc["device_count"] == 4
    assert doc["inputs"] == {} and doc["outputs"] == {}


def test_executor_pads_places_and_trims(sharded_devices):
    spec = MeshSpec.parse(
        {
            "axes": {"dp": 2},
            "inputs": {"X": ["dp", None]},
            "outputs": {"Y": ["dp", None]},
        }
    )
    plan = resolve(spec, devices=sharded_devices)
    assert plan.batch_multiple("X") == 2
    assert plan.batch_multiple("UNDECLARED") == 1
    executor = ShardedExecutor(plan, lambda arrays: {"Y": arrays["X"] * 2.0})
    # odd batch: pads 3 -> 4 for dp=2, output trimmed back to 3 rows
    x = np.arange(12, dtype=np.float32).reshape(3, 4)
    out = executor({"X": x}, rows=3)
    assert out["Y"].shape == (3, 4)
    np.testing.assert_array_equal(out["Y"], x * 2.0)
    snap = executor.snapshot()
    assert snap["executions"] == 1
    assert snap["device_put_ns"] >= 0 and snap["compute_ns"] > 0


# ---------------------------------------------------------------------------
# model fixtures (cached in-process: warmup compiles once per session)

_CACHE = {}


def _bert_setup():
    if "bert" not in _CACHE:
        import jax
        import jax.numpy as jnp

        from client_tpu.models import bert
        from client_tpu.models.serving import (
            ShardedTextEncoderModel,
            TextEncoderModel,
        )

        config = bert.BertConfig.tiny(dtype=jnp.float32)
        params = bert.init_params(jax.random.PRNGKey(0), config)
        repo = ModelRepository()
        repo.add_model(TextEncoderModel("text_encoder", config=config,
                                        params=params))
        repo.add_model(ShardedTextEncoderModel(config=config, params=params))
        core = ServerCore(repo)
        _CACHE["bert"] = (core, repo, config, params)
    return _CACHE["bert"]


@pytest.fixture
def bert_core(sharded_devices):
    return _bert_setup()


def _encode_request(model: str, ids: np.ndarray) -> CoreRequest:
    return CoreRequest(
        model_name=model,
        inputs=[CoreTensor("INPUT_IDS", "INT32", list(ids.shape), ids)],
    )


# ---------------------------------------------------------------------------
# parity: sharded == single-device reference through all four paths


def test_sharded_model_matches_reference_on_all_four_paths(bert_core):
    core, _repo, _config, _params = bert_core
    rng = np.random.default_rng(7)
    ids = rng.integers(1, 1000, size=(3, 13)).astype(np.int32)

    async def drive():
        reference = await core.infer(_encode_request("text_encoder", ids))
        via_infer = await core.infer(_encode_request("text_encoder_tp", ids))
        via_nowait = await core.infer_nowait(
            _encode_request("text_encoder_tp", ids)
        )
        decoupled = []
        async for response in core.infer_decoupled(
            _encode_request("text_encoder_tp", ids)
        ):
            decoupled.append(response)
        return reference, via_infer, via_nowait, decoupled

    reference, via_infer, via_nowait, decoupled = asyncio.run(drive())
    via_direct = core.infer_direct([_encode_request("text_encoder_tp", ids)])
    assert not isinstance(via_direct[0], Exception)
    expected = reference.outputs[0].data
    assert expected.shape == (3, _config.d_model)
    for label, response in (
        ("infer", via_infer),
        ("infer_nowait", via_nowait),
        ("infer_decoupled", decoupled[0]),
        ("infer_direct", via_direct[0]),
    ):
        got = response.outputs[0].data
        np.testing.assert_allclose(got, expected, err_msg=label, **TOL)


def _ring_setup():
    if "ring" not in _CACHE:
        import jax
        import jax.numpy as jnp

        from client_tpu.models import llama
        from client_tpu.models.serving import RingPrefillLlamaModel

        config = llama.LlamaConfig.tiny(max_seq_len=256, dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), config)
        model = RingPrefillLlamaModel(config=config, params=params)
        model.warmup()
        _CACHE["ring"] = (model, config, params)
    return _CACHE["ring"]


def test_llama_ring_matches_dense_prefill(sharded_devices):
    import jax.numpy as jnp

    from client_tpu.models import llama

    model, config, params = _ring_setup()
    assert model.mesh_plan.spec.axis_sizes["sp"] == 2

    rng = np.random.default_rng(11)
    prompt = rng.integers(1, 250, size=(2, 21)).astype(np.int32)
    got = model.execute({"INPUT_IDS": prompt}, {})["LOGITS"]
    dense = np.asarray(
        llama.forward(params, jnp.asarray(prompt), config)
    )[:, -1]
    assert got.shape == (2, config.vocab_size)
    np.testing.assert_allclose(got, dense, **TOL)
    # greedy next-token choice agrees with the dense reference
    np.testing.assert_array_equal(got.argmax(-1), dense.argmax(-1))

    # an empty prompt is a 400-shaped rejection, not garbage logits
    # computed at a wrapped padding position (LAST_INDEX -1)
    from client_tpu.utils import InferenceServerException

    with pytest.raises(InferenceServerException, match="non-empty"):
        model.execute({"INPUT_IDS": np.zeros((1, 0), np.int32)}, {})


def test_llama_ring_batcher_merge_preserves_last_index(sharded_devices):
    """Through the MERGING batcher path (not a direct execute() call):
    llama_ring does not declare ragged batching, so the batcher merges
    only identical lengths and never pads — LAST_INDEX must stay the
    true last token for every merged row."""
    import jax.numpy as jnp

    from client_tpu.models import llama

    model, config, params = _ring_setup()
    repo = ModelRepository()
    repo.add_model(model)
    core = ServerCore(repo)
    try:
        rng = np.random.default_rng(5)
        prompts = [
            rng.integers(1, 250, size=(1, 21)).astype(np.int32)
            for _ in range(2)
        ]

        def ring_request(ids):
            return CoreRequest(
                model_name="llama_ring",
                inputs=[
                    CoreTensor("INPUT_IDS", "INT32", list(ids.shape), ids)
                ],
            )

        async def drive():
            return await asyncio.gather(
                *(core.infer(ring_request(p)) for p in prompts)
            )

        responses = asyncio.run(drive())
        stats = core.stats["llama_ring"].snapshot()
        # the two same-length requests shared ONE device execution
        assert stats["execution_count"] == 1
        assert stats["inference_count"] == 2
        for prompt, response in zip(prompts, responses):
            dense = np.asarray(
                llama.forward(params, jnp.asarray(prompt), config)
            )[:, -1]
            np.testing.assert_allclose(
                response.outputs[0].data, dense, **TOL
            )
    finally:
        core.close()


# ---------------------------------------------------------------------------
# per-device telemetry + topology surfaces


def test_per_device_metrics_families(bert_core):
    from client_tpu.observability.metrics import parse_exposition

    core, _repo, _config, _params = bert_core
    ids = np.ones((2, 9), dtype=np.int32)

    async def drive():
        await core.infer(_encode_request("text_encoder_tp", ids))

    asyncio.run(drive())
    mesh_devices = core.repository.peek(
        "text_encoder_tp"
    ).mesh_plan.device_labels
    families = parse_exposition(core.metrics.render())
    compute = families["tpu_device_compute_ns_total"]
    by_device = {s.labels["device"]: s.value for s in compute.samples}
    for device in mesh_devices:
        assert by_device.get(device, 0) > 0, (device, by_device)
    # every host device reports a memory sample (0 on the CPU mesh)
    import jax

    memory = families["tpu_device_memory_bytes"]
    assert len(memory.samples) == len(jax.devices())


def test_device_topology_and_debug_state(bert_core):
    core, repo, _config, _params = bert_core
    topology = core.device_topology()
    assert topology["platform"] == "cpu"
    assert topology["device_count"] >= 4
    doc = topology["models"]["text_encoder_tp"]
    assert doc["axes"] == {"dp": 2, "tp": 2}
    assert len(doc["devices"]) == 4
    assert doc["inputs"]["INPUT_IDS"] == ["dp", None]
    assert doc["executor"]["executions"] >= 1
    state = core.debug_state()
    assert state["devices"]["device_count"] == topology["device_count"]
    # the model's config carries the same document for gRPC clients
    config = repo.get("text_encoder_tp").config()
    payload = json.loads(config["parameters"]["mesh"]["string_value"])
    assert payload["axes"] == {"dp": 2, "tp": 2}
    assert payload["devices"] == [int(d) for d in doc["devices"]]


def test_metadata_surfaces_over_the_wire(bert_core):
    import client_tpu.grpc as grpcclient
    import client_tpu.http as httpclient
    from client_tpu.testing import InProcessServer

    _core, repo, _config, _params = bert_core
    # a fresh core over the same (already-warm) repository: stop()
    # closes its core, and the cached one must outlive this test
    with InProcessServer(
        core=ServerCore(repo), builtin_models=False
    ) as server:
        with httpclient.InferenceServerClient(server.http_url) as http:
            meta = http.get_server_metadata()
            assert "sharding" in meta["extensions"]
            devices = meta["devices"]
            assert devices["platform"] == "cpu"
            assert (
                devices["models"]["text_encoder_tp"]["axes"]
                == {"dp": 2, "tp": 2}
            )
            import urllib.request

            with urllib.request.urlopen(
                f"http://{server.http_url}/v2/debug/state"
            ) as resp:
                state = json.loads(resp.read().decode("utf-8"))
            assert "text_encoder_tp" in state["devices"]["models"]
        with grpcclient.InferenceServerClient(server.grpc_url) as grpc:
            config = grpc.get_model_config("text_encoder_tp")
            payload = json.loads(
                config.config.parameters["mesh"].string_value
            )
            assert payload["axes"] == {"dp": 2, "tp": 2}
            assert len(payload["devices"]) == 4


# ---------------------------------------------------------------------------
# load-failure ergonomics: UNAVAILABLE + reason, never a 500 at first infer


def test_oversized_mesh_surfaces_as_load_failure(bert_core):
    from client_tpu.models.serving import ShardedTextEncoderModel

    core, _repo, config, params = bert_core

    class HugeMeshEncoder(ShardedTextEncoderModel):
        mesh = {
            "axes": {"dp": 64, "tp": 2},
            "inputs": {"INPUT_IDS": ["dp", None]},
            "outputs": {"EMBEDDING": ["dp", None]},
        }

    repo = ModelRepository()
    big_core = ServerCore(repo)
    try:
        repo.add_model(HugeMeshEncoder(name="huge", config=config,
                                       params=params))
        entry = {m["name"]: m for m in repo.index()}["huge"]
        assert entry["state"] == "UNAVAILABLE"
        assert entry["reason"] == (
            "load failed: mesh requires 128 devices, host has "
            f"{len(__import__('jax').devices())}"
        )
        # a capacity failure must NOT degrade whole-server readiness
        assert not repo.degraded()
        assert big_core.ready
        # and the first infer is a clean 503/UNAVAILABLE, not a 500
        with pytest.raises(ModelUnavailableError) as exc_info:
            asyncio.run(
                big_core.infer(
                    _encode_request("huge", np.ones((1, 8), np.int32))
                )
            )
        assert exc_info.value.http_status == 503
        assert exc_info.value.grpc_code == "UNAVAILABLE"
        # the topology block shows the unresolved declaration + reason
        doc = big_core.device_topology()["models"]["huge"]
        assert doc["resolved"] is False
        assert doc["reason"].startswith("load failed: mesh requires")
    finally:
        big_core.close()


def test_capacity_failure_then_real_failure_degrades(bert_core):
    """A capacity miss must not mask a LATER real load bug: the
    non-degrading classification tracks the latest failure, not the
    first one."""
    from client_tpu.models.serving import ShardedTextEncoderModel
    from client_tpu.utils import InferenceServerException

    _core, _repo, config, params = bert_core

    class HugeMeshEncoder(ShardedTextEncoderModel):
        mesh = {
            "axes": {"dp": 64, "tp": 2},
            "inputs": {"INPUT_IDS": ["dp", None]},
            "outputs": {"EMBEDDING": ["dp", None]},
        }
        explode = False

        def warmup(self):
            if self.explode:
                raise RuntimeError("corrupt weights")
            super().warmup()

    repo = ModelRepository()
    model = HugeMeshEncoder(name="flaky", config=config, params=params)
    repo.add_model(model)
    assert not repo.degraded()  # capacity miss: host property, not a bug
    model.explode = True
    with pytest.raises(InferenceServerException, match="corrupt weights"):
        repo.load("flaky")
    entry = {m["name"]: m for m in repo.index()}["flaky"]
    assert entry["reason"] == "load failed: corrupt weights"
    assert repo.degraded()  # the real bug degrades, capacity history or not


def test_malformed_mesh_declaration_is_load_failure(bert_core):
    from client_tpu.models.serving import ShardedTextEncoderModel

    _core, _repo, config, params = bert_core

    class BadSpecEncoder(ShardedTextEncoderModel):
        mesh = {
            "axes": {"dp": 2},
            "inputs": {"INPUT_IDS": ["nope", None]},
            "outputs": {"EMBEDDING": [None, None]},
        }

    repo = ModelRepository()
    repo.add_model(BadSpecEncoder(name="badspec", config=config,
                                  params=params))
    entry = {m["name"]: m for m in repo.index()}["badspec"]
    assert entry["state"] == "UNAVAILABLE"
    assert "unknown axis" in entry["reason"]
    # a config bug (unlike a capacity miss) IS a degraded repository
    assert repo.degraded()


# ---------------------------------------------------------------------------
# perf-harness reduction: per-device duty


def _exposition(busy: dict) -> str:
    lines = ["# TYPE tpu_device_compute_ns_total counter"]
    for device, ns in busy.items():
        lines.append(
            f'tpu_device_compute_ns_total{{device="{device}"}} {ns}'
        )
    return "\n".join(lines) + "\n"


def test_collector_reduces_per_device_duty():
    from client_tpu.perf.metrics_collector import MetricsCollector

    now = {"ns": 0}
    texts = iter(
        [
            _exposition({"0": 0, "1": 0}),
            _exposition({"0": 500_000_000, "1": 250_000_000}),
        ]
    )

    async def fetch():
        return next(texts)

    collector = MetricsCollector(
        "fake:1/metrics", fetch=fetch, clock_ns=lambda: now["ns"]
    )

    async def run():
        await collector.scrape_now()
        now["ns"] = 1_000_000_000
        await collector.scrape_now()

    asyncio.run(run())
    summary = collector.summary()
    assert summary.device_duty == pytest.approx({"0": 0.5, "1": 0.25})
    # aggregate divides by the device count: (0.5 + 0.25) / 2
    assert summary.duty_avg == pytest.approx(0.375)


def test_report_prints_per_device_duty():
    from client_tpu.perf.records import ServerMetricsSummary
    from client_tpu.perf.report import format_server_metrics

    summary = ServerMetricsSummary(
        scrape_count=2,
        window_s=1.0,
        duty_avg=0.375,
        duty_max=0.5,
        device_duty={"0": 0.5, "1": 0.25},
    )
    text = format_server_metrics(summary)
    assert "Per-device duty" in text
    assert "dev0: 50.0%" in text and "dev1: 25.0%" in text
    assert "skew 2.00x" in text


# ---------------------------------------------------------------------------
# lint satellite


def test_metric_lint_device_label_conventions():
    from tools.metric_lint import check_labels, check_source, run_metric_lint

    assert check_labels("tpu_x_total", ["device", "model"]) == []
    findings = check_labels("tpu_x_total", ["device_id"])
    assert findings and "spelled 'device'" in findings[0]
    findings = check_labels("tpu_x_total", ["Device"])
    assert findings and "snake_case" in findings[0]
    source = (
        "Counter('tpu_sharded_ops_total', 'h', ('chip',), registry=r)\n"
    )
    assert any(
        "spelled 'device'" in message
        for _line, message in check_source(source, "x.py")
    )
    # the real registry is clean under the new rules
    assert run_metric_lint() == []
