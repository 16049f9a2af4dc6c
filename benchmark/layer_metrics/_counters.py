"""Helpers the readers of the program's own counters share. ``run["counters"]``
holds every numeric key of ``ServingPredictor.telemetry()`` differenced over
the window, a histogram as ``<name>_sum`` and ``<name>_count``."""
from __future__ import annotations


def histogram_mean(run, name):
    """``(mean, {"samples": n})`` of the window's observations of a
    histogram; ``None`` where the program has no such histogram or it saw
    nothing."""
    counters = run.get("counters") or {}
    count = counters.get(name + "_count")
    if not count:
        return None
    return counters[name + "_sum"] / count, {"samples": int(count)}
