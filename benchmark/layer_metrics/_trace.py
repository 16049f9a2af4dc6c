"""Helpers the trace readers share."""
from __future__ import annotations


def devices(run):
    trace = run.get("trace")
    return trace["devices"] if trace else []


def common_window(devs):
    return (min(d["window"][0] for d in devs),
            max(d["window"][1] for d in devs))


def op_seconds(dev, match):
    """(seconds, calls) of a device's operations whose name ``match``
    accepts, by their own time."""
    sec = calls = 0
    for op, v in dev["ops"].items():
        if match(op):
            sec += v["seconds"]
            calls += v["calls"]
    return sec, calls


def busy_share(run, match):
    """Own time of the matching operations over busy time, averaged over
    the devices, in percent; ``None`` where none ran."""
    devs = devices(run)
    if not devs:
        return None
    shares = [op_seconds(d, match)[0] / d["busy_s"] for d in devs]
    total = sum(shares)
    return None if total == 0 else 100.0 * total / len(devs)


def idle_share(run):
    """1 - busy / window on the device that idles most, in percent."""
    devs = devices(run)
    if not devs:
        return None
    start, end = common_window(devs)
    return 100.0 * max(1.0 - d["busy_s"] / (end - start) for d in devs)
