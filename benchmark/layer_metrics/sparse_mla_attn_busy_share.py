"""Device time of ``sparse_mla_paged_attention``, the paged kernel over the
selected keys of the latent cache, over device busy time."""
from ..kernels import sparse_mla_paged_attention as kernel
from . import _trace

LAYER, UNIT, BETTER, SOURCE = "kernels, serving", "%", "lower", "device_trace"


def read(run):
    return _trace.busy_share(run, lambda op: op == kernel.NAME)
