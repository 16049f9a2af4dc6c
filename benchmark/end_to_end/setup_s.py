"""Process start to window open: imports, model build, compile or cache
hit, warm-up, the reference check, and the fill the traffic needs."""
UNIT, BETTER, SOURCE = "s", "lower", "host_clock"


def read(run):
    return run["clock"]["set_up"]
