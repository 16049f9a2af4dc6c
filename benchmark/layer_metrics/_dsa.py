"""What the readers of the learned-sparse-attention metrics share.

Device time by sub-scope with ``attn_index`` and ``attn_select`` in the list
(``observability/tracing.py`` ``STEP_SUBSCOPES``; ``_subscopes.py`` holds the
list as it was and charges these two to ``attn``), and a kernel's share of
its roofline from the context lengths the captured stretch's steps really
had. A program that names no such scope or kernel gives nothing to read and
every function here returns ``None``."""
from __future__ import annotations

from collections import defaultdict

from .. import reduce_trace, scope_trace
from ..kernels.roofline import least_seconds
from . import _subscopes, _trace

SUBSCOPES = _subscopes.SUBSCOPES + ("attn_index", "attn_select")
_KNOWN = frozenset(scope_trace.SCOPES) | frozenset(SUBSCOPES)


def scope_of(path):
    """The innermost component of an ``op_name`` path that is a scope or a
    sub-scope of the longer list."""
    for component in reversed(path.split("/")):
        m = scope_trace._COMPONENT.match(component)
        if m and m.group("name") in _KNOWN:
            return m.group("name")
    return None


def charge(events):
    """``{scope: own seconds}`` of one device's ``[(name, start, duration,
    path)]``."""
    own = reduce_trace.self_times([e[:3] for e in events])
    out = defaultdict(float)
    for (_, _, _, path), mine in zip(events, own):
        out[scope_of(path)] += mine
    return dict(out)


def table(run):
    """``{scope: seconds}`` averaged over the devices, parsed once and kept
    on the run; ``None`` without a capture or where neither scope is named."""
    if "dsa_subscope_table" not in run:
        run["dsa_subscope_table"] = None
        path = (scope_trace.find_capture(scope_trace.ROOT)
                if run.get("trace") else None)
        if path:
            per_device = [charge(ev)
                          for ev in scope_trace.load_ops(path).values()]
            merged = defaultdict(float)
            for charged in per_device:
                for scope, sec in charged.items():
                    merged[scope] += sec / len(per_device)
            if "attn_index" in merged or "attn_select" in merged:
                run["dsa_subscope_table"] = dict(merged)
    return run["dsa_subscope_table"]


def share(run, scope):
    """Own time under ``scope`` over all own time, in percent."""
    charged = table(run)
    if not charged or "serve" not in run:
        return None
    return 100.0 * charged.get(scope, 0.0) / sum(charged.values())


def roofline(run, name, needs):
    """Least time the chip could take for what the captured calls of kernel
    ``name`` need, over the time they took, in percent. ``needs(lanes)``:
    ``(operations, bytes)`` of one call over a step's ``lanes [(q_len,
    kv_len)]``; the mean over the captured stretch's steps stands for each
    captured call."""
    devs = _trace.devices(run)
    s = run.get("serve") or {}
    if not devs or "index_topk" not in s:
        return None
    seconds, calls = _trace.op_seconds(devs[0], lambda op: op == name)
    t0 = run["clock"].get("trace_t0")
    steps = [lanes for t, _, lanes in s["steps"]
             if lanes and (t0 is None or t >= t0)]
    if not calls or not steps:
        return None
    least, bound_by = 0.0, {"compute": 0, "memory": 0}
    for lanes in steps:
        t, bound = least_seconds(*needs(lanes), run["peak"])
        least += t
        bound_by[bound] += 1
    return 100.0 * (least / len(steps)) * calls / seconds, {
        "calls": calls, "steps_read": len(steps), "bound_by": bound_by,
        "kernel_s": seconds}
