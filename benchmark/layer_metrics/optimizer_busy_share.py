"""Share of device busy time under the train step's ``optimizer`` scope:
the momentum update of every parameter."""
from .. import scope_trace

LAYER, UNIT, BETTER, SOURCE = "train step", "%", "lower", "device_trace"


def read(run):
    return scope_trace.share(run, "optimizer") if "train" in run else None
