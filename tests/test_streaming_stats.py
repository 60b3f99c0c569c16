"""Decoupled/streaming server statistics (VERDICT r1 weak #7).

A stream's server-side accounting must split model-compute from output-
packaging time and report time-to-first-response — not book the whole
lifetime as one opaque compute_infer blob (the reference's own stats blind
spot, grpc_client.cc:1650-1653).
"""

import asyncio

import numpy as np

from client_tpu.server.core import CoreRequest, CoreTensor, ServerCore
from client_tpu.server.model_repository import ModelRepository
from client_tpu.server.models import RepeatModel


def _repeat_request(values, delay_us=2000):
    data = np.asarray(values, dtype=np.int32)
    return CoreRequest(
        model_name="repeat_int32",
        inputs=[CoreTensor("IN", "INT32", [len(values)], data)],
        parameters={"delay_us": delay_us},
    )


def test_decoupled_stats_split_under_load():
    repository = ModelRepository()
    repository.add_model(RepeatModel())
    core = ServerCore(repository)
    try:
        async def consume(request):
            out = []
            async for response in core.infer_decoupled(request):
                if response.outputs:
                    out.append(int(response.outputs[0].data[0]))
            return out

        async def run():
            return await asyncio.gather(
                *[consume(_repeat_request([1, 2, 3, 4, 5])) for _ in range(4)]
            )

        results = asyncio.run(run())
        assert all(r == [1, 2, 3, 4, 5] for r in results)

        snap = core.statistics("repeat_int32")["model_stats"][0]
        stats = snap["inference_stats"]
        assert stats["success"]["count"] == 4
        # compute vs packaging split: the 2 ms/element delays dominate, so
        # infer ns must far exceed packaging ns (which must still be > 0).
        assert stats["compute_output"]["ns"] > 0
        assert stats["compute_infer"]["ns"] > 5 * stats["compute_output"]["ns"]
        # per-response stats (Triton response_stats shape): 4 streams of 5
        # responses -> keys "0".."4", 4 successes each
        rs = snap["response_stats"]
        assert set(rs) == {"0", "1", "2", "3", "4"}
        assert all(rs[k]["success"]["count"] == 4 for k in rs)
        # key "0" is time-to-first-response: well before the stream ends
        avg_first = rs["0"]["success"]["ns"] / 4
        avg_infer = stats["compute_infer"]["ns"] / 4
        assert avg_first < avg_infer
        # later responses carry the 2 ms inter-response model delay
        assert rs["1"]["compute_infer"]["ns"] > rs["1"]["compute_output"]["ns"]
    finally:
        core.close()


def test_mid_stream_failure_books_per_response_fail_entry():
    """A mid-stream exception must land in response_stats[index].fail, not
    only the aggregate 'fail' field (InferResponseStatistics parity)."""

    class ExplodingModel(RepeatModel):
        async def execute_decoupled(self, inputs, parameters):
            yield {"OUT": np.array([1], dtype=np.int32), "__final__": False}
            raise RuntimeError("boom mid-stream")

    repository = ModelRepository()
    repository.add_model(ExplodingModel())
    core = ServerCore(repository)
    try:
        async def run():
            out = []
            async for response in core.infer_decoupled(
                _repeat_request([1, 2, 3])
            ):
                out.append(response)
            return out

        try:
            asyncio.run(run())
            raise AssertionError("expected mid-stream failure")
        except RuntimeError:
            pass
        snap = core.statistics("repeat_int32")["model_stats"][0]
        assert snap["inference_stats"]["fail"]["count"] == 1
        rs = snap["response_stats"]
        # response 0 succeeded; the failure is booked at in-flight index 1
        assert rs["0"]["success"]["count"] == 1
        assert rs["1"]["fail"]["count"] == 1
        assert rs["1"]["fail"]["ns"] > 0
        assert rs["1"]["success"]["count"] == 0
    finally:
        core.close()


def test_abandoned_stream_books_cancel_entry():
    """Generator close (the front-end's client-disconnect path) must book a
    cancel entry at the in-flight response index, like task cancellation."""
    repository = ModelRepository()
    repository.add_model(RepeatModel())
    core = ServerCore(repository)
    try:
        async def run():
            gen = core.infer_decoupled(_repeat_request([1, 2, 3, 4, 5]))
            async for _response in gen:
                break  # client disconnects after the first response
            await gen.aclose()

        asyncio.run(run())
        rs = core.statistics("repeat_int32")["model_stats"][0]["response_stats"]
        assert rs["0"]["success"]["count"] == 1
        assert rs["1"]["cancel"]["count"] == 1
        assert rs["1"]["cancel"]["ns"] > 0
        # ...and that entry is ALL it books (ROADMAP D5): the one request
        # in four paths that ends as neither a success nor a failure, with
        # no flight-recorder record and no telemetry observation
        stats = core.statistics("repeat_int32")["model_stats"][0]
        assert stats["inference_stats"]["success"]["count"] == 0
        assert stats["inference_stats"]["fail"]["count"] == 0
        assert core.flight_recorder.stats()["recorded_total"] == 0
        assert core.metrics.telemetry.models() == []
    finally:
        core.close()


def test_break_on_final_response_is_success_not_cancel():
    """Stopping iteration at the triton_final_response-marked response (the
    standard decoupled-client pattern) is normal completion: aggregate
    success books, and no phantom cancel entry appears past the end."""
    repository = ModelRepository()
    repository.add_model(RepeatModel())
    core = ServerCore(repository)
    try:
        async def run():
            gen = core.infer_decoupled(_repeat_request([1, 2, 3], delay_us=0))
            async for response in gen:
                if response.parameters.get("triton_final_response"):
                    break
            await gen.aclose()

        asyncio.run(run())
        snap = core.statistics("repeat_int32")["model_stats"][0]
        assert snap["inference_stats"]["success"]["count"] == 1
        rs = snap["response_stats"]
        assert set(rs) == {"0", "1", "2"}
        assert all(rs[k]["cancel"]["count"] == 0 for k in rs)
    finally:
        core.close()


def test_non_decoupled_stream_has_no_decoupled_stats():
    from client_tpu.server.models import AddSubModel

    repository = ModelRepository()
    repository.add_model(AddSubModel())
    core = ServerCore(repository)
    try:
        req = CoreRequest(
            model_name="simple",
            inputs=[
                CoreTensor(
                    "INPUT0", "INT32", [1, 16],
                    np.zeros([1, 16], np.int32),
                ),
                CoreTensor(
                    "INPUT1", "INT32", [1, 16],
                    np.ones([1, 16], np.int32),
                ),
            ],
        )

        async def run():
            return [r async for r in core.infer_decoupled(req)]

        responses = asyncio.run(run())
        assert len(responses) == 1
        snap = core.statistics("simple")["model_stats"][0]
        assert "response_stats" not in snap
    finally:
        core.close()
