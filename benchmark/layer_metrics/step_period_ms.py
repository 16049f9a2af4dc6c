"""Median distance between the starts of consecutive launches of the step
program (the program launched most often in the capture) on the device."""
import statistics

from . import _trace

LAYER, UNIT, BETTER, SOURCE = "step program", "ms", "lower", "device_trace"


def read(run):
    devs = _trace.devices(run)
    if not devs or not devs[0]["launches"]:
        return None
    name, launches = max(devs[0]["launches"].items(),
                         key=lambda kv: len(kv[1]))
    starts = [s for s, _ in launches]
    if len(starts) < 3:
        return None
    period = statistics.median(b - a for a, b in zip(starts, starts[1:]))
    return period * 1e3, {"program": name, "launches": len(starts)}
