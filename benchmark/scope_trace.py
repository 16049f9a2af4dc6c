"""From a profiler capture to device time by named part of the step program.

The program puts ``jax.named_scope`` names on the parts of its two step
programs (``observability/tracing.py``: ``STEP_SCOPES``); XLA keeps them as
the ``op_name`` path of every HLO instruction, and the TPU profiler writes
that path, followed by a colon, as the ``tf_op`` stat (``PATH_STAT``) of the
device event's *metadata* (the event itself has only its offset and
duration; its name is the instruction's text, without the path).
``reduce_trace`` keeps no stats, so this module reads the capture once more:

- each device's ``XLA Ops`` events with their path (:func:`load_ops`);
- each event's own time (``reduce_trace.self_times``: a ``while`` does not
  count its children twice) charged to the innermost scope name of its path
  (:func:`scope_of`), forward apart from backward (``transpose(`` in the
  path), and an event under ``layers`` but under none of its parts apart as
  ``layers.carry``: the layer scan's own slicing and stacking of what it
  carries (:func:`charge`).

Everything but :func:`load_ops` and :func:`find_capture` works on plain
tuples, so the reduction is tested on a hand-built trace without a chip.
This module knows no cell, configuration or metric. A program that names no
part of itself (one older than the scopes) gives a table with no scope in it,
and every reader built on :func:`table` then returns nothing.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

from . import reduce_trace

#: the closed list the program's ``step_scope`` accepts, letter for letter
SCOPES = (
    "cow", "embed", "layers", "ln", "qkv", "kv_write", "attn", "attn_out",
    "mlp", "head", "sample", "head_loss", "optimizer", "pipeline",
)
CARRY = "layers.carry"
UNSCOPED = "_unscoped_"
PATH_STAT = "tf_op"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one component of an op_name path: a scope, or a scope inside the wrappers
# transformations put around it, as in ``transpose(jvp(ln))``
_COMPONENT = re.compile(r"^(?:\w+\()*(?P<name>[\w.\-]+)\)*$")
_KNOWN = frozenset(SCOPES)


def find_capture(root):
    """The newest ``.xplane.pb`` under ``<root>/.bench_trace``: a capture
    empties its cell's directory when it opens, so the newest is this run's.
    ``None`` where there is none."""
    found = glob.glob(os.path.join(root, ".bench_trace", "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a ``memoryview`` for a length-delimited field; fixed-width
    fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif kind == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
        else:
            raise ValueError(f"xplane: wire type {kind} at byte {i}")


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _plane_ops(plane):
    """``(plane name, [(event name, start_s, duration_s, path)])`` of one
    ``XPlane`` message; the events of its ``XLA Ops`` line."""
    name, lines, event_meta, stat_names = "", [], {}, {}
    for field, value in _fields(plane):
        if field == 2:
            name = _text(value)
        elif field == 3:
            lines.append(value)
        elif field in (4, 5):     # map entries: key = 1, message = 2
            entry = dict(_fields(value))
            (event_meta if field == 4 else stat_names)[entry[1]] = entry[2]
    if not reduce_trace.DEVICE_PLANE.match(name):
        return name, None
    stat_names = {k: next((_text(v) for f, v in _fields(m) if f == 2), "")
                  for k, m in stat_names.items()}
    wanted = {k for k, n in stat_names.items() if n == PATH_STAT}
    described = {}    # event metadata id -> (name, path)
    for key, meta in event_meta.items():
        label = shown = path = ""
        for field, value in _fields(meta):
            if field == 2:
                label = _text(value)
            elif field == 4:
                shown = _text(value)
            elif field == 5:      # an XStat of the metadata
                stat = dict(_fields(value))
                if stat.get(1) in wanted:
                    path = (_text(stat[5]) if 5 in stat
                            else stat_names.get(stat.get(7), ""))
        described[key] = (label or shown, path)
    events = []
    for line in lines:
        fields = list(_fields(line))
        if not any(f == 2 and _text(v) == reduce_trace.OPS_LINE
                   for f, v in fields):
            continue
        t0_ps = 1000 * next((v for f, v in fields if f == 3), 0)
        for field, value in fields:
            if field != 4:
                continue
            event = dict(_fields(value))
            label, path = described.get(event.get(1), ("", ""))
            events.append((label, (t0_ps + event.get(2, 0)) * 1e-12,
                           event.get(3, 0) * 1e-12, path))
    return name, events


def load_ops(path):
    """``{device plane: [(event name, start_s, duration_s, op_name path)]}``
    of the capture's ``XLA Ops`` lines. The path is a stat of the event's
    *metadata*, which ``jax.profiler.ProfileData`` does not hand out, so
    this reads the file's protobuf wire format itself (``XSpace.planes``
    is field 1; see ``xplane.proto`` for the rest)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = {}
    for field, plane in _fields(space):
        if field == 1:
            name, events = _plane_ops(plane)
            if events:
                planes[name] = events
    return planes


def scope_of(path):
    """``(scope, backward)`` of an ``op_name`` path: the innermost component
    that is a name of the closed list, ``layers.carry`` where that is
    ``layers`` itself, ``None`` where the path holds none."""
    backward = "transpose(" in path
    for component in reversed(path.split("/")):
        m = _COMPONENT.match(component)
        if m and m.group("name") in _KNOWN:
            name = m.group("name")
            return (CARRY if name == "layers" else name), backward
    return None, backward


def charge(events):
    """``{(scope, backward, operation): [own seconds, calls]}`` of one
    device's ``[(name, start, duration, path)]``."""
    own = reduce_trace.self_times([e[:3] for e in events])
    out = defaultdict(lambda: [0.0, 0])
    for (name, _, _, path), mine in zip(events, own):
        scope, backward = scope_of(path)
        cell = out[(scope or UNSCOPED, backward,
                    reduce_trace.op_label(name)[1])]
        cell[0] += mine
        cell[1] += 1
    return dict(out)


def merge(per_device):
    """The devices' tables as one: seconds and calls averaged over them."""
    out = defaultdict(lambda: [0.0, 0.0])
    for charged in per_device:
        for key, (sec, calls) in charged.items():
            out[key][0] += sec / len(per_device)
            out[key][1] += calls / len(per_device)
    return dict(out)


def table(run):
    """The run's merged table, parsed once and kept on the run; ``None``
    for a run without a capture or whose program names no part of itself."""
    if "scope_table" not in run:
        run["scope_table"] = None
        path = find_capture(ROOT) if run.get("trace") else None
        if path:
            merged = merge([charge(events)
                            for events in load_ops(path).values()])
            if any(scope != UNSCOPED for scope, _, _ in merged):
                run["scope_table"] = merged
    return run["scope_table"]


def seconds(charged, scope=None, *, backward=None, op=None):
    """``(seconds, calls)`` of a table's entries that match every given
    filter; ``op`` is a predicate on the operation's label."""
    sec = calls = 0.0
    for (s, b, label), (t, n) in charged.items():
        if ((scope is None or s == scope)
                and (backward is None or b == backward)
                and (op is None or op(label))):
            sec += t
            calls += n
    return sec, calls


def share(run, scope, *, op=None):
    """Own time under ``scope`` over all own time, in percent; ``None``
    where the run has no table."""
    charged = table(run)
    if not charged:
        return None
    sec, _ = seconds(charged, scope, op=op)
    total, _ = seconds(charged)
    return 100.0 * sec / total


def by_scope(charged, *, split_backward=False):
    """``{scope: {"seconds", "share", "calls"}}``, largest first; with
    ``split_backward`` a backward entry is keyed ``<scope>.bwd``."""
    total, _ = seconds(charged)
    rows = defaultdict(lambda: [0.0, 0.0])
    for (scope, backward, _), (t, n) in charged.items():
        key = scope + ".bwd" if split_backward and backward else scope
        rows[key][0] += t
        rows[key][1] += n
    return {k: {"seconds": round(t, 6), "share": round(100.0 * t / total, 3),
                "calls": round(n)}
            for k, (t, n) in sorted(rows.items(), key=lambda kv: -kv[1][0])}


def top_operations(charged, limit=12, *, scope=None):
    """``[[operation, scope, seconds]]`` of the operations with most own
    time (under ``scope`` only, if given): who owns each large operation."""
    rows = defaultdict(float)
    for (s, backward, label), (t, _) in charged.items():
        if scope is None or s == scope:
            rows[(label, s + (".bwd" if backward else ""))] += t
    top = sorted(rows.items(), key=lambda kv: -kv[1])[:limit]
    return [[label, s, round(t, 6)] for (label, s), t in top]


def scoped_share(run, *, split_backward=False):
    """``(percent, note)``: own time charged to any scope over all own
    time, with the whole table and the owners of the largest operations."""
    charged = table(run)
    if not charged:
        return None
    total, _ = seconds(charged)
    loose, _ = seconds(charged, UNSCOPED)
    return 100.0 * (1.0 - loose / total), {
        "table": by_scope(charged, split_backward=split_backward),
        "top_operations": top_operations(charged),
        "unscoped_operations": top_operations(charged, 8, scope=UNSCOPED)}
