"""95th percentile over every inter-token gap inside the window (see
``stats.token_gaps``: a lump of n tokens is read as evenly spread)."""
from .. import stats

UNIT, BETTER, SOURCE = "ms", "lower", "host_clock"


def read(run):
    s, c = run["serve"], run["clock"]
    gaps = stats.token_gaps(s["deliveries"], c["t_open"], c["t_close"])
    p95 = stats.percentile(gaps, 95)
    return None if p95 is None else (p95 * 1e3, {"samples": len(gaps)})
