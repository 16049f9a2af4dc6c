"""From a profiler capture to the few tables the per-layer readers use.

``jax.profiler`` writes an ``.xplane.pb``; :func:`load_xplane` turns it into
plain tuples and :func:`reduce` turns those into a *reduced trace*:

- per device: the union of the intervals in which an operation ran (busy),
  the window they span, every operation's own time (a ``while`` that holds a
  layer loop does not count its children twice), and the program launches;
- the idle gaps of the device that idles most, each charged to the innermost
  host span that covers its middle.

Everything here works on the plain tuples, so the reduction is tested on a
hand-built trace without a chip. This module knows no model, cell or metric.

Times are seconds. A device *plane* is one chip; its ``XLA Ops`` line is the
core's own sequential stream (what runs there is what keeps the core busy),
``XLA Modules`` holds one event per program launch, and the asynchronous
copies of ``Async XLA Ops`` overlap the stream and are left out of busy time.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
NO_SPAN = "_no_span_"

_HLO = re.compile(r"^%?(?P<op>[^\s=]+?)(?:\.\d+)? = \(?(?P<shape>\w+\[[\d,]*\])?")
_MODULE = re.compile(r"^(?P<name>.+?)\(\d+\)$")


def find_xplane(trace_dir):
    """The newest capture under ``trace_dir`` (jax's layout)."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path):
    """``{plane name: {line name: [(event name, start_s, duration_s)]}}``
    with nothing but JAX."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            events = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                      for e in line.events]
            if events:
                lines.setdefault(line.name, []).extend(events)
        if lines:
            planes[plane.name] = lines
    return planes


def op_label(event_name):
    """``(operation, label)`` of a device event. The event's name is the HLO
    instruction's text: ``%copy_fusion.8 = bf16[768,12,64,128]{...} fusion(``
    gives ``("copy_fusion", "copy_fusion_bf16_768_12_64_128_")``."""
    m = _HLO.match(event_name)
    if not m:
        return event_name, event_name
    op, shape = m.group("op"), m.group("shape")
    label = op if not shape else op + "_" + re.sub(r"[^\w]", "_", shape)
    return op, label


def union_seconds(intervals):
    """Merged ``[(start, end)]`` of possibly overlapping intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def self_times(events):
    """Each event's duration less the time of the events nested directly in
    it, in the input's order. ``events``: ``[(name, start, duration)]``."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [e[2] for e in events]
    stack = []  # indices of the enclosing events, innermost last
    for i in order:
        _, start, dur = events[i]
        while stack and start >= events[stack[-1]][1] + events[stack[-1]][2]:
            stack.pop()
        if stack:
            own[stack[-1]] -= dur
        stack.append(i)
    return [max(t, 0.0) for t in own]


def attribute_gaps(gaps, spans):
    """``{span name: idle seconds}``: each gap ``(start, end)`` goes to the
    shortest host span ``(name, start, duration)`` that covers its middle.
    One sweep in time order, so a capture of many thousand gaps stays cheap."""
    spans = sorted(spans, key=lambda s: s[1])
    out, active, i = defaultdict(float), [], 0
    for start, end in sorted(gaps):
        t = (start + end) / 2
        while i < len(spans) and spans[i][1] <= t:
            active.append(spans[i])
            i += 1
        active = [s for s in active if s[1] + s[2] >= t]
        name = min(active, key=lambda s: s[2])[0] if active else NO_SPAN
        out[name] += end - start
    return dict(out)


def _host_spans(planes, marker):
    """The events of the host thread that carries the benchmark's own
    annotations (names starting with ``marker``); every host thread's
    events if none does."""
    host = planes.get(HOST_PLANE, {})
    for events in host.values():
        if any(name.startswith(marker) for name, _, _ in events):
            return list(events)
    return [e for events in host.values() for e in events]


def reduce(planes, *, marker="bench."):
    """The reduced trace of :func:`load_xplane`'s planes."""
    devices = []
    for plane_name in sorted(p for p in planes if DEVICE_PLANE.match(p)):
        lines = planes[plane_name]
        ops = lines.get(OPS_LINE, [])
        if not ops:
            continue
        busy = union_seconds((s, s + d) for _, s, d in ops)
        own = self_times(ops)
        by_label = defaultdict(float)
        by_op = defaultdict(lambda: [0.0, 0])
        for (name, start, dur), mine in zip(ops, own):
            op, label = op_label(name)
            by_label[label] += mine
            by_op[op][0] += mine
            by_op[op][1] += 1
        launches = defaultdict(list)
        for name, start, dur in lines.get(MODULES_LINE, []):
            m = _MODULE.match(name)
            launches[m.group("name") if m else name].append((start, dur))
        devices.append({
            "name": plane_name,
            "busy_s": sum(e - s for s, e in busy),
            "window": (busy[0][0], busy[-1][1]),
            "busy": busy,
            "op_seconds": dict(by_label),
            "ops": {op: {"seconds": v[0], "calls": v[1]}
                    for op, v in by_op.items()},
            "launches": {k: sorted(v) for k, v in launches.items()},
        })
    if not devices:
        return {"devices": [], "idle_gaps": {}, "device_ops": []}

    spans = _host_spans(planes, marker)
    # idle gaps of the device that idles most, by what the host was doing
    worst = min(devices, key=lambda d: d["busy_s"]
                / (d["window"][1] - d["window"][0]))
    gaps = attribute_gaps(
        [(end, start) for (_, end), (start, _)
         in zip(worst["busy"], worst["busy"][1:])], spans)

    top = defaultdict(float)
    for d in devices:
        for label, sec in d["op_seconds"].items():
            top[label] += sec / len(devices)
    return {
        "devices": devices,
        "idle_gaps": gaps,
        "device_ops": sorted(top.items(), key=lambda kv: -kv[1]),
    }


def busy_and_window(reduced):
    """``(busy_s, window_s)``: busy time averaged over the devices, and the
    window from the first operation on any device to the last."""
    devs = reduced["devices"]
    if not devs:
        return 0.0, 0.0
    start = min(d["window"][0] for d in devs)
    end = max(d["window"][1] for d in devs)
    return sum(d["busy_s"] for d in devs) / len(devs), end - start


def breakdown(reduced, limit=10):
    """The ``breakdown`` object of a traced run's result line."""
    gaps = sorted(reduced["idle_gaps"].items(), key=lambda kv: -kv[1])
    return {"device_ops": [[k, v] for k, v in reduced["device_ops"][:limit]],
            "idle_gaps": [[k, v] for k, v in gaps[:limit]]}
