"""Mean time from a request's first admission to the dispatch of the step
that fed its prompt's last chunk, from the scheduler's histogram
``serving_prefill_ms``."""
from . import _counters

LAYER, UNIT, BETTER, SOURCE = "scheduler", "ms", "lower", "program_counter"


def read(run):
    return _counters.histogram_mean(run, "serving_prefill_ms")
