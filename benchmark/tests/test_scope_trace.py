"""``scope_trace`` and the readers built on it, on a hand-built trace.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import run, scope_trace  # noqa: E402

US = 2.0 ** -20   # about a microsecond, and exact in sums
SERVE = TRAIN = "jit(step)/jit(main)/"


def serve_events():
    """One serving step, 100 us busy. The ``while`` (70 us) holds 60 us of
    children, so 10 us are its own."""
    w = SERVE + "layers/while"
    return [
        ("%copy.1 = bf16[2,8,2,4,4]{4,3,2,1,0} copy(", 0 * US, 10 * US, ""),
        ("%fusion.2 = bf16[16,8]{1,0} fusion(", 10 * US, 5 * US,
         SERVE + "embed/add"),
        ("%while.3 = (s32[], bf16[16,8]) while(", 15 * US, 70 * US, w),
        ("%constant_dynamic-slice_fusion = bf16[1,8,2,4,4]{4,3,2,1,0} "
         "fusion(", 15 * US, 8 * US, w + "/body/dynamic_slice"),
        ("%fusion.4 = bf16[16,8]{1,0} fusion(", 23 * US, 2 * US,
         w + "/body/ln/mul"),
        ("%copy_bitcast_fusion = bf16[8,2,4,4]{3,2,1,0} fusion(", 25 * US,
         10 * US, w + "/body/kv_write/scatter"),
        ("%copy.5 = bf16[8,2,4,4]{3,2,1,0} copy(", 35 * US, 6 * US,
         w + "/body/attn/ragged_paged_attention"),
        ("%ragged_paged_attention.6 = f32[4,2,4,4]{3,2,1,0} custom-call(",
         41 * US, 20 * US, w + "/body/attn/ragged_paged_attention"),
        ("%fusion.7 = bf16[16,8]{1,0} fusion(", 61 * US, 4 * US,
         w + "/body/mlp/dot_general"),
        ("%copy_dynamic-update-slice_fusion = bf16[2,8,2,4,4]{4,3,2,1,0} "
         "fusion(", 65 * US, 10 * US, w + "/body/dynamic_update_slice"),
        ("%fusion.8 = f32[4,32]{1,0} fusion(", 85 * US, 15 * US,
         SERVE + "head/dot_general"),
    ]


def train_events():
    """A forward and a backward pass, 100 us busy."""
    fwd = TRAIN + "jvp(pipeline)/jvp(layers)/while/body/closed_call/"
    bwd = (TRAIN + "transpose(jvp(pipeline))/transpose(jvp(layers))/while/"
           "body/closed_call/checkpoint/")
    return [
        ("%fusion.1 = bf16[2,16,8]{2,1,0} fusion(", 0, 10 * US,
         fwd + "ln/mul"),
        ("%fusion.2 = bf16[2,16,8]{2,1,0} fusion(", 10 * US, 20 * US,
         fwd + "mlp/dot_general"),
        ("%fusion.3 = f32[2,16]{1,0} fusion(", 30 * US, 10 * US,
         TRAIN + "jvp(head_loss)/reduce_sum"),
        ("%multiply_reduce_fusion = bf16[8]{0} fusion(", 40 * US, 25 * US,
         bwd + "ln/reduce_sum"),
        ("%fusion.4 = bf16[2,16,8]{2,1,0} fusion(", 65 * US, 5 * US,
         bwd + "rematted_computation/ln/mul"),
        ("%fusion.5 = bf16[2,8,8]{2,1,0} fusion(", 70 * US, 10 * US,
         TRAIN + "transpose(jvp(pipeline))/transpose(jvp(layers))/while/"
         "body/dynamic_update_slice"),
        ("%fusion.6 = bf16[8,8]{1,0} fusion(", 80 * US, 12 * US,
         TRAIN + "optimizer/sub"),
        ("%fusion.7 = bf16[8]{0} fusion(", 92 * US, 8 * US,
         TRAIN + "convert_element_type"),
    ]


def traced(kind, events):
    """A run record as the readers see one, its table already charged."""
    return {kind: {}, "trace": {"devices": []},
            "scope_table": scope_trace.merge([scope_trace.charge(events)])}


@pytest.mark.parametrize("path, want", [
    ("jit(step)/layers/while/body/attn/scatter", ("attn", False)),
    ("jit(step)/layers/while/body/dynamic_slice", ("layers.carry", False)),
    ("jit(step)/layers/while", ("layers.carry", False)),
    ("jit(s)/transpose(jvp(layers))/while/body/checkpoint/ln/add_any",
     ("ln", True)),
    ("jit(s)/transpose(jvp(ln))/mul", ("ln", True)),
    ("jit(s)/jvp(pipeline)/jvp(layers)/while/body/mlp/tanh",
     ("mlp", False)),
    ("jit(s)/jvp(pipeline)/while/body/dynamic_slice", ("pipeline", False)),
    ("jit(s)/vmap(jvp(layers))/while/body/qkv/dot_general", ("qkv", False)),
    ("jit(step)/convert_element_type", (None, False)),
    ("", (None, False)),
    # a primitive or a function that merely contains a scope's name
    ("jit(sample_epilogue)/headroom/attn_mask", (None, False)),
])
def test_scope_of_takes_the_innermost_name_of_the_closed_list(path, want):
    assert scope_trace.scope_of(path) == want


def test_the_scope_list_is_the_programs_own():
    from paddle_tpu.observability.tracing import STEP_SCOPES

    assert scope_trace.SCOPES == STEP_SCOPES


def test_charge_on_a_hand_built_serving_step():
    charged = scope_trace.charge(serve_events())
    sec = lambda *a, **k: scope_trace.seconds(charged, *a, **k)[0]  # noqa: E731
    assert sec() == pytest.approx(100 * US)
    # the while's own 10 us, the slice and the stacking write: the scan's
    # own work, and no part's; its children are not counted twice
    assert sec("layers.carry") == pytest.approx((10 + 8 + 10) * US)
    assert sec("kv_write") == pytest.approx(10 * US)
    assert sec("attn") == pytest.approx(26 * US)
    assert sec("ln") == pytest.approx(2 * US)
    # an event whose path holds no known scope is unscoped
    assert sec(scope_trace.UNSCOPED) == pytest.approx(10 * US)
    assert scope_trace.seconds(charged, "attn")[1] == 2
    rows = scope_trace.by_scope(charged)
    assert list(rows)[0] == "layers.carry"
    assert rows["attn"] == {"seconds": pytest.approx(26 * US, abs=1e-6),
                            "share": pytest.approx(26.0), "calls": 2}
    owners = {op: scope for op, scope, _
              in scope_trace.top_operations(charged, 20)}
    assert owners["copy_bitcast_fusion_bf16_8_2_4_4_"] == "kv_write"
    assert owners["constant_dynamic-slice_fusion_bf16_1_8_2_4_4_"] == \
        "layers.carry"
    assert owners["copy_bf16_2_8_2_4_4_"] == scope_trace.UNSCOPED


def test_charge_keeps_forward_apart_from_backward():
    charged = scope_trace.charge(train_events())
    sec = lambda *a, **k: scope_trace.seconds(charged, *a, **k)[0]  # noqa: E731
    assert sec("ln", backward=False) == pytest.approx(10 * US)
    # transpose(jvp(...)) is backward, the recomputed forward inside it too
    assert sec("ln", backward=True) == pytest.approx(30 * US)
    assert sec("layers.carry", backward=True) == pytest.approx(10 * US)
    rows = scope_trace.by_scope(charged, split_backward=True)
    assert rows["ln.bwd"]["share"] == pytest.approx(30.0)
    assert rows["ln"]["share"] == pytest.approx(10.0)
    assert "mlp.bwd" not in rows


def test_merge_averages_over_the_devices():
    one = scope_trace.charge(serve_events())
    other = {("attn", False, "x"): [50 * US, 2]}
    merged = scope_trace.merge([one, other])
    assert scope_trace.seconds(merged, "attn") == (
        pytest.approx((26 + 50) / 2 * US), pytest.approx(2.0))


SERVE_WANT = {
    "serve_scoped_share": 90.0, "scan_carry_busy_share": 28.0,
    "kv_write_busy_share": 10.0,
    "attn_operand_busy_share": 6.0,   # attn's 26 us less the kernel's 20
}
TRAIN_WANT = {
    "train_scoped_share": 92.0, "ln_busy_share": 40.0,
    "head_loss_busy_share": 10.0, "optimizer_busy_share": 12.0,
}


@pytest.mark.parametrize("kind, events, want", [
    ("serve", serve_events, SERVE_WANT), ("train", train_events, TRAIN_WANT)])
def test_scope_readers_on_the_hand_built_trace(kind, events, want):
    record = traced(kind, events())
    other = traced("train" if kind == "serve" else "serve", events())
    for name, value in want.items():
        got = run.reader_for("layer_metrics", name).read(record)
        note = None
        if isinstance(got, tuple):
            got, note = got
        assert got == pytest.approx(value), name
        if name.endswith("scoped_share"):
            assert note["table"] and note["top_operations"]
            assert all(scope == scope_trace.UNSCOPED
                       for _, scope, _ in note["unscoped_operations"])
        # the other driver's run, an untraced run, and a traced run of a
        # program that names no part of itself give nothing
        read = run.reader_for("layer_metrics", name).read
        assert read(other) is None, name
        assert read({kind: {}, "trace": None}) is None, name
        assert read({kind: {}, "trace": {"devices": []},
                     "scope_table": None}) is None, name
    note = run.reader_for("layer_metrics", "train_scoped_share").read(
        traced("train", train_events()))[1]
    assert "ln.bwd" in note["table"]


def test_table_of_a_program_without_scopes_is_nothing(tmp_path, monkeypatch):
    assert scope_trace.find_capture(str(tmp_path)) is None
    bare = [(name, start, dur, "") for name, start, dur, _ in serve_events()]
    monkeypatch.setattr(scope_trace, "find_capture", lambda root: "x.pb")
    monkeypatch.setattr(scope_trace, "load_ops", lambda path: {"d0": bare})
    record = {"serve": {}, "trace": {"devices": []}}
    assert scope_trace.table(record) is None
    assert run.reader_for("layer_metrics", "kv_write_busy_share").read(
        record) is None
    # and with scopes it is parsed once and kept
    monkeypatch.setattr(scope_trace, "load_ops",
                        lambda path: {"d0": serve_events()})
    record = {"serve": {}, "trace": {"devices": []}}
    first = scope_trace.table(record)
    monkeypatch.setattr(scope_trace, "load_ops", None)
    assert scope_trace.table(record) is first and first


def counters_run(**counters):
    return {"serve": {}, "counters": counters}


def test_counter_readers():
    read = lambda name, record: run.reader_for(  # noqa: E731
        "layer_metrics", name).read(record)
    record = counters_run(
        serving_rows_prefill=300.0, serving_rows_decode=100.0,
        serving_steps=10.0, serving_queue_wait_ms_sum=90.0,
        serving_queue_wait_ms_count=3.0, serving_prefill_ms_sum=500.0,
        serving_prefill_ms_count=2.0, serving_reconcile_lag_steps_sum=15.0,
        serving_reconcile_lag_steps_count=10.0)
    share, note = read("sched_prefill_row_share", record)
    assert share == pytest.approx(75.0) and note["rows_per_step"] == 40.0
    assert read("queue_wait_mean_ms", record) == (pytest.approx(30.0),
                                                  {"samples": 3})
    assert read("prefill_mean_ms", record)[0] == pytest.approx(250.0)
    assert read("reconcile_lag_steps", record)[0] == pytest.approx(1.5)
    # a program without the instruments, or a window that observed nothing
    older = counters_run(serving_steps=10.0)
    quiet = counters_run(serving_rows_prefill=0.0, serving_rows_decode=0.0,
                         serving_queue_wait_ms_sum=0.0,
                         serving_queue_wait_ms_count=0.0)
    for name in ("sched_prefill_row_share", "queue_wait_mean_ms",
                 "prefill_mean_ms", "reconcile_lag_steps"):
        assert read(name, older) is None, name
        assert read(name, quiet) is None, name
        assert read(name, {"train": {}}) is None, name
