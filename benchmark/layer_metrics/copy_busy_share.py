"""Share of device busy time in XLA's own copies: ``copy*`` operations and
fusions built on ``dynamic-slice`` / ``dynamic-update-slice``, outside any
Pallas kernel. In the serving step these move whole KV pools."""
from . import _trace

LAYER, UNIT, BETTER, SOURCE = "step program", "%", "lower", "device_trace"


def is_copy(op):
    return (op.startswith("copy") or "dynamic-update-slice" in op
            or "dynamic-slice" in op)


def read(run):
    return _trace.busy_share(run, is_copy)
