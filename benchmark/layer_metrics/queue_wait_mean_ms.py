"""Mean wait from submission to first admission of the requests admitted
inside the window, from the scheduler's histogram ``serving_queue_wait_ms``."""
from . import _counters

LAYER, UNIT, BETTER, SOURCE = "scheduler", "ms", "lower", "program_counter"


def read(run):
    return _counters.histogram_mean(run, "serving_queue_wait_ms")
