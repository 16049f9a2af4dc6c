"""Device time by the parts OF a part of the serving step: the names of
``observability/tracing.py``'s ``STEP_SUBSCOPES`` (``moe_route``,
``moe_experts``, ``moe_shared`` inside ``mlp``; ``attn_absorb`` inside
``attn``). ``scope_trace`` charges an operation to the innermost name of its
closed list, so it charges these to the part around them; this module reads
the same capture with the longer list. A program that names none of them (one
older than they are) gives nothing to read, and every reader built on
:func:`share` returns ``None``."""
from __future__ import annotations

from collections import defaultdict

from .. import reduce_trace, scope_trace

SUBSCOPES = ("moe_route", "moe_experts", "moe_shared", "attn_absorb")
_KNOWN = frozenset(scope_trace.SCOPES) | frozenset(SUBSCOPES)


def scope_of(path):
    """The innermost component of an ``op_name`` path that is a scope or a
    sub-scope; ``None`` where the path holds none."""
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
    on the run; ``None`` without a capture or where no sub-scope is named."""
    if "subscope_table" not in run:
        run["subscope_table"] = None
        path = (scope_trace.find_capture(scope_trace.ROOT)
                if run.get("trace") else None)
        if path:
            per_device = [charge(ev)
                          for ev in scope_trace.load_ops(path).values()]
            merged = defaultdict(float)
            for charged in per_device:
                for scope, sec in charged.items():
                    merged[scope] += sec / len(per_device)
            if any(s in merged for s in SUBSCOPES):
                run["subscope_table"] = dict(merged)
    return run["subscope_table"]


def share(run, scope):
    """Own time under ``scope`` over all own time, in percent."""
    charged = table(run)
    if not charged or "serve" not in run:
        return None
    return 100.0 * charged.get(scope, 0.0) / sum(charged.values())
