"""CPU check of the reader PR 35 adds: ``step_row_fill_share`` on a hand-made
run, and on runs of a program that has no such counter. No test starts a chip
run."""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402

NAME = "step_row_fill_share"


def test_step_row_fill_share_on_a_hand_made_run():
    """Ten steps: six at the rung of 32 rows, three at 96, one at 512, which
    held 24, 88 and 400 rows each."""
    read = run.reader_for("layer_metrics", NAME).read
    share, note = read({"counters": {
        "serving_rows_run{rung=32}": 6 * 32, "serving_rows_run{rung=96}": 3 * 96,
        "serving_rows_run{rung=512}": 512, "serving_rows_run{rung=160}": 0,
        "serving_rows_prefill": 3 * 64 + 380,
        "serving_rows_decode": 6 * 24 + 3 * 24 + 20, "serving_steps": 10}})
    assert share == pytest.approx(100.0 * 808 / 992)
    assert note == {"rows_held": 808, "rows_run": 992,
                    "step_share_by_rung": {"32": 60.0, "96": 30.0,
                                           "512": 10.0}}
    # a decode pool: every step at the smallest rung, every row of it held
    assert read({"counters": {"serving_rows_run{rung=16}": 1600,
                              "serving_rows_decode": 1600}})[0] == 100.0


@pytest.mark.parametrize("record", [
    {"counters": {"serving_steps": 3, "serving_rows_decode": 9,
                  "serving_rows_prefill": 40}},      # the parent's program
    {"counters": {"serving_rows_run{rung=32}": 0, "serving_rows_decode": 0}},
    {"counters": {}}, {"counters": None}, {"trace": None}],
    ids=["parent", "idle-window", "no-counters", "counters-none", "no-run"])
def test_step_row_fill_share_reads_nothing_where_the_counter_is_absent(record):
    """The parent's program runs its whole budget and has no counter: the
    reader returns nothing and does not raise."""
    assert run.reader_for("layer_metrics", NAME).read(record) is None


def test_the_manifest_lists_the_metric_for_the_four_serving_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = [m for m in manifest["per_layer"] if m["name"] == NAME]
    serving = [w["name"] for w in manifest["workloads"]
               if w["name"].startswith("serve-")]
    assert len(entry) == 1 and entry[0]["workloads"] == serving
    assert entry[0]["moves"] == "served_tok_s"
    assert entry[0]["layer"] == "step program"
