"""95th percentile of the time between consecutive deliveries to one
request, undivided: what a client waits between lumps of tokens. It moves in
steps of one step period, so it may jump; it decides nothing and is here so
that a deeper deferral cannot hide behind the evenly spread gap."""
from .. import stats

LAYER, UNIT, BETTER, SOURCE = "scheduler", "ms", "lower", "host_clock"


def read(run):
    if "serve" not in run:
        return None
    s, c = run["serve"], run["clock"]
    stalls = stats.delivery_stalls(s["deliveries"], c["t_open"], c["t_close"])
    p95 = stats.percentile(stalls, 95)
    return None if p95 is None else (p95 * 1e3, {"samples": len(stalls)})
