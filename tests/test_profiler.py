"""Profiler facade: scheduler states, trace windows, export, timer, summary."""
import json
import os

import paddle_tpu as paddle
from paddle_tpu import profiler as prof_mod
from paddle_tpu.profiler import (
    Benchmark,
    Profiler,
    ProfilerState,
    RecordEvent,
    SortedKeys,
    export_chrome_tracing,
    load_profiler_result,
    make_scheduler,
)
from paddle_tpu.profiler.record import recorder


def test_make_scheduler_states():
    sch = make_scheduler(closed=1, ready=1, record=2, repeat=1, skip_first=1)
    states = [sch(i) for i in range(6)]
    assert states == [
        ProfilerState.CLOSED,  # skip_first
        ProfilerState.CLOSED,
        ProfilerState.READY,
        ProfilerState.RECORD,
        ProfilerState.RECORD_AND_RETURN,
        ProfilerState.CLOSED,  # repeat exhausted
    ]


def test_profiler_records_ops_and_exports(tmp_path):
    p = Profiler(
        scheduler=(0, 2), on_trace_ready=export_chrome_tracing(str(tmp_path))
    )
    p.start()
    with RecordEvent("forward"):
        x = paddle.randn([4, 4])
        y = (x @ x).sum()
    p.step()
    _ = paddle.randn([2, 2]) + 1.0
    p.step()  # closes the window -> export
    p.stop()
    files = list(tmp_path.iterdir())
    assert files, "no chrome trace exported"
    events = load_profiler_result(str(files[0]))
    names = {e["name"] for e in events}
    assert "forward" in names
    assert any(n not in ("forward",) for n in names), "no op events recorded"
    assert not recorder.enabled


def test_profiler_windows_do_not_leak_events(tmp_path):
    """A second session must not re-export events from the first."""
    for i in range(2):
        p = Profiler(
            scheduler=(0, 1),
            on_trace_ready=export_chrome_tracing(str(tmp_path), f"w{i}"),
        )
        p.start()
        with RecordEvent(f"span{i}"):
            pass
        p.step()
        p.stop()
    second = [f for f in os.listdir(tmp_path) if f.startswith("w1")]
    assert second
    events = load_profiler_result(str(tmp_path / second[0]))
    names = {e["name"] for e in events}
    assert "span0" not in names


def test_summary_tables(capsys):
    p = Profiler()
    p.start()
    with RecordEvent("stage"):
        _ = paddle.ones([3]) * 2
    p.stop()
    p.summary(sorted_by=SortedKeys.CPUTotal)
    out = capsys.readouterr().out
    assert "Overview Summary" in out and "stage" in out


def test_benchmark_timer():
    b = Benchmark()
    b.begin()
    b.before_reader()
    b.after_reader()
    b.step(num_samples=32)
    b.step(num_samples=32)
    assert b.speed() > 0
    info = b.step_info()
    assert "avg_batch_cost" in info and "avg_ips" in info
    b.end()
    # window reset by step_info
    assert b.batch.get_average() == 0.0


def test_profiler_module_importable():
    assert hasattr(prof_mod, "Profiler")
    assert hasattr(prof_mod, "benchmark")


def test_summary_available_after_scheduled_window(capsys):
    p = Profiler(scheduler=(0, 1))
    p.start()
    with RecordEvent("windowed"):
        pass
    p.step()  # closes + clears the shared recorder
    p.stop()
    p.summary()
    out = capsys.readouterr().out
    assert "windowed" in out


def test_scheduler_validation():
    import pytest

    with pytest.raises(ValueError):
        make_scheduler(closed=0, ready=0, record=0)
    with pytest.raises(ValueError):
        Profiler(scheduler=(2, 2))
    with pytest.raises(ValueError):
        make_scheduler(closed=-1, ready=0, record=1)
    with pytest.raises(ValueError):
        make_scheduler(closed=0, ready=0, record=1, skip_first=-1)


def test_make_scheduler_repeat_forever_and_edges():
    """Round-15 edge coverage of the cycle state machine: repeat=0 cycles
    forever; closed=0/ready=0 degenerate phases; record=1 jumps straight
    to RECORD_AND_RETURN; skip_first offsets the whole cycle."""
    # repeat=0: the cycle must continue indefinitely (probe deep in)
    sch = make_scheduler(closed=1, ready=1, record=2, repeat=0)
    cycle = [ProfilerState.CLOSED, ProfilerState.READY,
             ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN]
    for step in range(40):
        assert sch(step) == cycle[step % 4], step
    # no closed, no ready phase: every cycle is pure recording
    sch = make_scheduler(closed=0, ready=0, record=1, repeat=0)
    assert [sch(i) for i in range(3)] == [
        ProfilerState.RECORD_AND_RETURN] * 3
    # record=1 with warmup phases
    sch = make_scheduler(closed=2, ready=1, record=1, repeat=1)
    assert [sch(i) for i in range(5)] == [
        ProfilerState.CLOSED, ProfilerState.CLOSED, ProfilerState.READY,
        ProfilerState.RECORD_AND_RETURN, ProfilerState.CLOSED]
    # skip_first shifts the first cycle only
    sch = make_scheduler(closed=0, ready=1, record=1, repeat=2,
                         skip_first=3)
    assert [sch(i) for i in range(8)] == [
        ProfilerState.CLOSED, ProfilerState.CLOSED, ProfilerState.CLOSED,
        ProfilerState.READY, ProfilerState.RECORD_AND_RETURN,
        ProfilerState.READY, ProfilerState.RECORD_AND_RETURN,
        ProfilerState.CLOSED]


def test_chrome_export_round_trips_aux_events(tmp_path):
    """Round 15: async request phases recorded through the observability
    span API, and a raw counter-track sample, ride the chrome export and
    json.load back with their phase/id/args intact."""
    from paddle_tpu.observability import (request_begin, request_end,
                                          request_event, span)

    p = Profiler(on_trace_ready=export_chrome_tracing(str(tmp_path), "aux"))
    p.start()
    with span("pack_dispatch"):
        pass
    assert request_begin(7, args={"req_id": 7})
    request_event(7, "admit", args={"slot": 0})
    recorder.record_raw("inflight_steps", "C", category="counter",
                        args={"value": 2.0})
    request_end(7)
    p.stop()
    events = load_profiler_result(str(p._last_export))
    by_ph = {}
    for e in events:
        by_ph.setdefault(e["ph"], []).append(e)
    assert any(e["name"] == "pack_dispatch" for e in by_ph["X"])
    assert [e["name"] for e in by_ph["b"]] == ["request"]
    assert by_ph["b"][0]["id"] == "7" and by_ph["b"][0]["cat"] == "request"
    assert by_ph["e"][0]["id"] == "7"
    admits = [e for e in by_ph["n"] if e["name"] == "admit"]
    assert admits and admits[0]["args"] == {"slot": 0}
    counters = by_ph["C"]
    assert counters[0]["name"] == "inflight_steps"
    assert counters[0]["args"] == {"value": 2.0}
    # timestamps are µs floats ordered begin <= end
    assert by_ph["b"][0]["ts"] <= by_ph["e"][0]["ts"]


def test_dataloader_marks_reader_cost():
    import numpy as np

    from paddle_tpu.io import DataLoader, Dataset
    from paddle_tpu.profiler.timer import benchmark

    class DS(Dataset):
        def __len__(self):
            return 8

        def __getitem__(self, i):
            return np.zeros((2,), np.float32)

    b = benchmark()
    b.__init__()  # reset global state
    b.begin()
    for batch in DataLoader(DS(), batch_size=4):
        b.step(num_samples=4)
    assert b.reader.total > 0.0
    b.end()
