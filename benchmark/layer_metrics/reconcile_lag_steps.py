"""Mean number of steps dispatched between an in-flight step's dispatch and
its reconcile, from the scheduler's histogram ``serving_reconcile_lag_steps``:
how far behind the async engine hands tokens back. A count, a function of
the schedule."""
from . import _counters

LAYER, UNIT, BETTER, SOURCE = "scheduler", "steps", "lower", "program_counter"


def read(run):
    return _counters.histogram_mean(run, "serving_reconcile_lag_steps")
