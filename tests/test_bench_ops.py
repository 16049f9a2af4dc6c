"""Opt-in per-op perf regression gate (reference
tools/check_op_benchmark_result.py).

Run with ``pytest -m bench tests/test_bench_ops.py``. Compares a fresh
bench_ops sweep against the newest committed BENCH_OPS_r*.json for the SAME
platform; fails on >TOL regressions. Skipped when no same-platform
reference exists (the committed file is measured on the TPU chip; CI legs
on CPU only gate once a CPU reference is recorded).
"""
import glob
import json
import os
import re
import sys

import pytest

pytestmark = [pytest.mark.bench, pytest.mark.slow]

TOL = 2.0  # ratio gate; CI noise makes tighter gates flaky

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _latest_reference(platform):
    best = None
    for path in sorted(glob.glob(os.path.join(REPO, "BENCH_OPS_r*.json"))):
        with open(path) as f:
            data = json.load(f)
        if data.get("platform") == platform:
            best = (path, data)
    return best


def test_op_perf_vs_previous_round():
    sys.path.insert(0, REPO)
    import bench_ops

    result = bench_ops.bench(iters=10)
    ref = _latest_reference(result["platform"])
    if ref is None:
        pytest.skip(f"no committed reference for platform "
                    f"{result['platform']}")
    path, ref_data = ref
    regressions = []
    for name, cur in result["ops"].items():
        prev = ref_data["ops"].get(name)
        if prev is None or "us" not in prev:
            continue
        if "error" in cur:
            regressions.append(f"{name}: now errors: {cur['error']}")
            continue
        ratio = cur["us"] / max(prev["us"], 1e-9)
        if ratio > TOL:
            regressions.append(
                f"{name}: {prev['us']}us -> {cur['us']}us ({ratio:.2f}x, "
                f"ref {os.path.basename(path)})")
    assert not regressions, "op perf regressions:\n" + "\n".join(regressions)
