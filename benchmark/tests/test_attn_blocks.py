"""CPU tests of `engine.attn_blocks_live_share`: the share of the page
tables' columns that hold a live block, from two engine counters.
"""

import json
import os
import types

import pytest

from benchmark import run as harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "engine.attn_blocks_live_share"


def run_with(before, after):
    return types.SimpleNamespace(before={"at": 0, "engine": before},
                                 after={"at": 1, "engine": after})


def test_live_share_is_the_window_delta_in_percent():
    # 10 steps of 16 lanes x 64 columns, 48.5 live blocks a lane
    run = run_with({"attn_blocks_live": 1_000, "attn_blocks_bucket": 4_096},
                   {"attn_blocks_live": 1_000 + 10 * 16 * 48.5,
                    "attn_blocks_bucket": 4_096 + 10 * 16 * 64})
    value, unit = harness.read_metric(run, NAME)
    assert unit == "%"
    assert value == pytest.approx(100 * 48.5 / 64)


@pytest.mark.parametrize("before,after", [
    ({"steps": 1}, {"steps": 11}),  # an engine without the counters
    ({"attn_blocks_live": 5, "attn_blocks_bucket": 8},
     {"attn_blocks_live": 5, "attn_blocks_bucket": 8}),  # no step in the window
], ids=["no_counters", "no_steps"])
def test_live_share_is_left_out_where_there_is_nothing_to_read(before, after):
    assert harness.read_metric(run_with(before, after), NAME)[0] is None


def test_the_cell_reports_the_metric_in_traced_runs():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    (entry,) = [m for m in benchmark["per_layer"] if m["name"] == NAME]
    assert entry["workloads"] == ["mistral7b.batch"]
    assert NAME in harness.cell_metrics(benchmark, "mistral7b.batch", trace=True)
    assert NAME not in harness.cell_metrics(benchmark, "mistral7b.batch",
                                            trace=False)
