"""The benchmark's CPU tests of the ``attn_kv_passes_per_lane`` reader
(``benchmark/tests/test_attn_kv_passes_per_lane.py``), collected here so that
they run under tier-1's command, which collects ``tests/``.

The manifest test is defined again below, under the same name: the metric's
``workloads`` now end with the Ouro cell, and per-layer entries added since
this one come after it in ``BENCHMARK.json``. The benchmark's own copy keeps
the list it was written against, as files the benchmark holds are only added
to, never edited."""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.tests.test_attn_kv_passes_per_lane import *  # noqa: E402,F401,F403
from benchmark.tests.test_attn_kv_passes_per_lane import NAME  # noqa: E402

#: the per-layer entries of the benchmark as the metric was added to it
ENTRIES_BEFORE = 51


def test_the_manifest_lists_the_metric_for_the_per_head_pool_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry["workloads"] == [
        "serve-590m-doc-sat", "serve-590m-chat",
        "serve-cmdaplus-mixedlen-decode", "serve-smallthinker-docqa16k",
        "serve-ouro-reasoning2k"]
    assert (entry["unit"], entry["better"], entry["source"], entry["moves"],
            entry["layer"]) == ("passes", "lower", "program_counter",
                                "served_tok_s", "kernels, serving")
    # appended after every entry the benchmark had before it
    assert manifest["per_layer"][ENTRIES_BEFORE] is entry
