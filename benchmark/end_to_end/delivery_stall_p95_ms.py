"""95th percentile of the time between consecutive deliveries to one
request, undivided, over the deliveries inside the window: what a streaming
client waits between lumps of tokens (see ``stats.delivery_stalls``). The
async engine hands tokens back in lumps a few steps apart, so the tail of
these waits is a cluster around a whole number of step periods, and the
percentile stands in the middle of it."""
from .. import stats

UNIT, BETTER, SOURCE = "ms", "lower", "host_clock"


def read(run):
    s, c = run["serve"], run["clock"]
    stalls = stats.delivery_stalls(s["deliveries"], c["t_open"], c["t_close"])
    p95 = stats.percentile(stalls, 95)
    return None if p95 is None else (p95 * 1e3, {"samples": len(stalls)})
