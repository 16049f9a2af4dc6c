"""Tokens trained per second: batch x sequence x steps started inside the
window, over the time from window open to the end of the last of them."""
UNIT, BETTER, SOURCE = "tokens/s", "higher", "host_clock"


def read(run):
    t, c = run["train"], run["clock"]
    return t["steps"] * t["tokens_per_step"] / (c["window_s"] - c["paused_s"])
