"""Device time of ``ragged_paged_attention`` over device busy time."""
from ..kernels import ragged_paged_attention as kernel
from . import _trace

LAYER, UNIT, BETTER, SOURCE = "kernels, serving", "%", "lower", "device_trace"


def read(run):
    return _trace.busy_share(run, lambda op: op == kernel.NAME)
