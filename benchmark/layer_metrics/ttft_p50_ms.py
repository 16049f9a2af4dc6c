"""Median submit-to-first-token of the requests submitted and first
answered inside the window, on the benchmark's clock."""
from .. import stats

LAYER, UNIT, BETTER, SOURCE = "scheduler", "ms", "lower", "host_clock"


def read(run):
    if "serve" not in run:
        return None
    s, c = run["serve"], run["clock"]
    waits = stats.times_to_first_token(s["deliveries"], s["requests"],
                                       c["t_open"], c["t_close"])
    p50 = stats.percentile(waits, 50)
    return None if p50 is None else (p50 * 1e3, {"samples": len(waits)})
