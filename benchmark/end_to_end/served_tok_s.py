"""Tokens served per second: prompt tokens of the requests whose first
token came inside the window, credited at that token, plus every output
token handed back inside it, over the window's seconds."""
from .. import stats

UNIT, BETTER, SOURCE = "tokens/s", "higher", "host_clock"


def read(run):
    s, c = run["serve"], run["clock"]
    return stats.served_tokens(s["deliveries"], s["requests"], c["t_open"],
                               c["t_close"]) / c["window_s"]
