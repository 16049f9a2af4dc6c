"""CPU check of the reader of ``attn_kv_passes_per_lane``: on hand-made
counters, on runs of a program that has no such counter, and its entry in
the manifest. No test starts a chip run."""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402

NAME = "attn_kv_passes_per_lane"


def test_kv_passes_per_lane_on_hand_made_counters():
    """Ten steps of 32 lanes over 8 attention layers: nine at a chunk rung
    whose plan walks each lane's keys four times, one at a decode rung that
    takes every head a grid step."""
    read = run.reader_for("layer_metrics", NAME).read
    lane_layers = 10 * 32 * 8
    passes = 9 * 32 * 8 * 4 + 32 * 8
    value, note = read({"counters": {
        "serving_attn_kv_passes": float(passes),
        "serving_attn_lane_layers": float(lane_layers),
        "serving_steps": 10.0}})
    assert value == pytest.approx(3.7)
    assert note == {"kv_passes": passes, "lane_layers": lane_layers}
    # every head a grid step at every rung: one pass
    assert read({"counters": {"serving_attn_kv_passes": 768.0,
                              "serving_attn_lane_layers": 768.0}})[0] == 1.0


@pytest.mark.parametrize("record", [
    {"counters": {"serving_steps": 3, "serving_attn_query_rows": 9}},
    {"counters": {"serving_attn_lane_layers": 0}},
    {"counters": {}}, {"counters": None}, {"trace": None}],
    ids=["parent", "idle-window", "no-counters", "counters-none", "no-run"])
def test_kv_passes_per_lane_reads_nothing_where_the_counter_is_absent(record):
    """A program without the counters (the parent) gives nothing to read,
    and the reader does not raise."""
    assert run.reader_for("layer_metrics", NAME).read(record) is None


def test_the_manifest_lists_the_metric_for_the_per_head_pool_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry["workloads"] == [
        "serve-590m-doc-sat", "serve-590m-chat",
        "serve-cmdaplus-mixedlen-decode", "serve-smallthinker-docqa16k"]
    assert (entry["unit"], entry["better"], entry["source"], entry["moves"],
            entry["layer"]) == ("passes", "lower", "program_counter",
                                "served_tok_s", "kernels, serving")
    # appended last: the entries before it are the accepted benchmark's
    assert manifest["per_layer"][-1] is entry
