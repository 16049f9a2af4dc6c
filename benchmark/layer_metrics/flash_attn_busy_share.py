"""Device time of ``flash_attention_fwd`` + ``flash_attention_bwd`` over
device busy time."""
from ..kernels import flash_attention as kernel
from . import _trace

LAYER, UNIT, BETTER, SOURCE = "kernels, training", "%", "lower", "device_trace"


def read(run):
    return _trace.busy_share(
        run, lambda op: op in (kernel.FWD_NAME, kernel.BWD_NAME))
