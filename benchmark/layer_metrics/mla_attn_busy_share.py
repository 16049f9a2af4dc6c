"""Device time of ``mla_ragged_paged_attention``, the paged kernel over the
latent cache, over device busy time."""
from ..kernels import mla_paged_attention as kernel
from . import _trace

LAYER, UNIT, BETTER, SOURCE = "kernels, serving", "%", "lower", "device_trace"


def read(run):
    return _trace.busy_share(run, lambda op: op == kernel.NAME)
