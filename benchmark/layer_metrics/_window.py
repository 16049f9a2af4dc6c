"""What the readers of the window/full attention metrics share.

Device time of the paged attention kernel by the KIND of layer that called it:
the serving step names ``attn_window`` and ``attn_full`` inside ``attn``
(``observability/tracing.py`` ``STEP_SUBSCOPES``; ``scope_trace`` charges both
to ``attn``), and this module reads the same capture with the two in its
list. A program that names neither (one older than they are, or a model whose
layers are of one kind) gives nothing to read and every function here returns
``None``."""
from __future__ import annotations

from collections import defaultdict

from .. import reduce_trace, scope_trace
from ..kernels import gqa_window_paged_attention as kernel
from ..kernels.roofline import least_seconds

KINDS = ("attn_window", "attn_full")


def kind_of(path):
    """The innermost component of an ``op_name`` path that is one of
    :data:`KINDS`; ``None`` where the path holds neither."""
    for component in reversed(path.split("/")):
        m = scope_trace._COMPONENT.match(component)
        if m and m.group("name") in KINDS:
            return m.group("name")
    return None


def charge(events):
    """``({kind: [kernel's own seconds, calls]}, all own seconds)`` of one
    device's ``[(name, start, duration, path)]``."""
    own = reduce_trace.self_times([e[:3] for e in events])
    out = defaultdict(lambda: [0.0, 0])
    for (name, _, _, path), mine in zip(events, own):
        if reduce_trace.op_label(name)[1].startswith(kernel.NAME):
            cell = out[kind_of(path)]
            cell[0] += mine
            cell[1] += 1
    return dict(out), sum(own)


def table(run):
    """``{kind: (seconds, calls), "busy": seconds}`` of the FIRST device,
    parsed once and kept on the run; ``None`` without a capture or where the
    kernel ran under neither kind."""
    if "window_attn_table" not in run:
        run["window_attn_table"] = None
        path = (scope_trace.find_capture(scope_trace.ROOT)
                if run.get("trace") else None)
        if path:
            planes = scope_trace.load_ops(path)
            if planes:
                charged, busy = charge(planes[sorted(planes)[0]])
                if any(k in charged for k in KINDS):
                    run["window_attn_table"] = dict(
                        {k: tuple(charged.get(k, (0.0, 0))) for k in KINDS},
                        busy=busy)
    return run["window_attn_table"]


def busy_share(run, kind):
    """The kernel's own time under ``kind`` over the device's busy time, in
    percent."""
    t = table(run)
    if not t or "serve" not in run or not t[kind][1]:
        return None
    return 100.0 * t[kind][0] / t["busy"]


def roofline(run, kind, window):
    """Least time the chip could take for what the captured calls of the
    kernel under ``kind`` need, over the time they took, in percent. What a
    call needs comes from the lanes the captured stretch's steps really had
    (``kernels/gqa_window_paged_attention.py``); the mean over the stretch's
    steps stands for each captured call."""
    t = table(run)
    s = run.get("serve") or {}
    if not t or "kv_heads" not in s or not t[kind][1]:
        return None
    seconds, calls = t[kind]
    t0 = run["clock"].get("trace_t0")
    steps = [lanes for at, _, lanes in s["steps"]
             if lanes and (t0 is None or at >= t0)]
    if not steps:
        return None
    least, bound_by = 0.0, {"compute": 0, "memory": 0}
    for lanes in steps:
        sec, bound = least_seconds(*kernel.needs(
            lanes, window=window, num_heads=s["heads"],
            kv_heads=s["kv_heads"], head_dim=s["head_dim"],
            kv_bytes=s["kv_bytes"], q_bytes=s["kv_bytes"],
            out_bytes=s["kv_bytes"]), run["peak"])
        least += sec
        bound_by[bound] += 1
    return 100.0 * (least / len(steps)) * calls / seconds, {
        "calls": calls, "steps_read": len(steps), "bound_by": bound_by,
        "kernel_s": seconds}
