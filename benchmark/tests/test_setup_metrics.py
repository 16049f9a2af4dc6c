"""The three set-up readers (``setup_state_s``, ``setup_trace_s``,
``setup_compile_s``) on a planted record and run: what they keep (what ended
before the window), how they add (overlaps once), and that a program without
the record reads nothing. CPU, no JAX work."""
from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402

NAMES = ("setup_state_s", "setup_trace_s", "setup_compile_s")


class PlantedRecord:
    def __init__(self, entries):
        self._entries = entries

    def entries(self):
        return list(self._entries)


def planted():
    from paddle_tpu.observability.startup import SetupEntry as E

    return [
        E("weights.make", 10.0, 14.0),
        E("weights.place", 13.0, 15.0),               # overlaps the make
        E("kv.pools", 16.0, 16.5),
        E("step.build", 16.5, 16.6),                  # not state
        E("jax.trace", 17.0, 19.0, None, "step"),
        E("step.rung.32", 17.2, 17.6, None),
        E("step.rung.160", 17.6, 18.5, None),
        E("jax.trace", 17.5, 18.0, None, "kernel"),   # nested: once
        E("jax.lower", 19.0, 20.0, None, "step"),
        E("jax.compile", 20.0, 23.0, None, "step", "miss"),
        E("jax.trace", 11.0, 11.5, None, "_normal"),
        E("jax.lower", 11.5, 11.6, None, "_normal"),
        E("jax.compile", 11.6, 12.0, None, "_normal", "hit"),
        E("jax.compile", 23.0, 23.5, None, "step", "hit"),
        # after the window opened at 30: read by none of the sums
        E("kv.pools", 31.0, 40.0),
        E("jax.trace", 31.0, 32.0, None, "step"),
        E("jax.lower", 32.0, 33.0, None, "step"),
        E("jax.compile", 33.0, 34.0, None, "step"),
    ]


@pytest.fixture
def record(monkeypatch):
    import paddle_tpu.observability as obs

    monkeypatch.setattr(obs, "setup_record", PlantedRecord(planted()))


RUN = {"clock": {"t_open": 30.0, "t_close": 60.0, "set_up": 25.0}}


def read(name, run_=RUN):
    return run.reader_for("layer_metrics", name).read(run_)


def test_state_is_the_union_of_its_three_phases_before_the_window(record):
    value, note = read("setup_state_s")
    assert value == pytest.approx(5.5)  # 10-15 and 16-16.5
    assert note["by_phase_s"] == {"weights.make": 4.0, "weights.place": 2.0,
                                  "kv.pools": 0.5}
    # the process started at 30 - 25 = 5; the record's first entry at 10
    assert note["to_first_record_s"] == pytest.approx(5.0)


def test_trace_counts_a_nested_trace_once_and_names_the_rungs(record):
    value, note = read("setup_trace_s")
    # step 17-19 (the kernel inside it), lower 19-20, _normal 11-11.6
    assert value == pytest.approx(3.6)
    assert (note["trace_s"], note["lower_s"]) == (2.5, 1.1)
    assert list(note["largest_s"])[0] == "step"
    assert note["largest_s"]["step"] == pytest.approx(3.0)
    assert note["step_rungs_s"] == {32: 0.4, 160: 0.9}
    assert note["record_own_s"] >= 0


def test_compile_reads_cache_hits_and_misses_and_late_lowerings(record):
    value, note = read("setup_compile_s")
    assert value == pytest.approx(3.9)  # 20-23.5 and 11.6-12
    assert (note["programs"], note["cache_hits"], note["cache_misses"]) == (
        3, 2, 1)
    assert note["largest_s"]["step"] == [3.5, {"hit": 1, "miss": 1}]
    assert note["largest_s"]["_normal"] == [0.4, {"hit": 1, "miss": 0}]
    assert note["jax_lowerings_in_window"] == {"step": 1}


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_record_reads_nothing(monkeypatch, name):
    import paddle_tpu.observability as obs

    monkeypatch.delattr(obs, "setup_record")
    assert read(name) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_record_with_nothing_before_the_window_reads_nothing(monkeypatch,
                                                               name):
    import paddle_tpu.observability as obs

    monkeypatch.setattr(obs, "setup_record", PlantedRecord(
        [e for e in planted() if e.start > 30]))
    assert read(name) is None


@pytest.mark.parametrize("name", NAMES)
def test_the_manifest_asks_every_cell_for_each(name):
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    metric, = [x for x in m["per_layer"] if x["name"] == name]
    assert metric["workloads"] == [w["name"] for w in m["workloads"]]
    assert (metric["moves"], metric["layer"]) == ("setup_s", "set-up")
