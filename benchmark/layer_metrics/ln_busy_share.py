"""Share of device busy time under the train step's ``ln`` scope, forward
and backward: the two LayerNorms of every block."""
from .. import scope_trace

LAYER, UNIT, BETTER, SOURCE = "train step", "%", "lower", "device_trace"


def read(run):
    return scope_trace.share(run, "ln") if "train" in run else None
