"""Share of device busy time under the train step's ``head_loss`` scope,
forward and backward: the final norm, the chunked tied head and the
cross-entropy."""
from .. import scope_trace

LAYER, UNIT, BETTER, SOURCE = "train step", "%", "lower", "device_trace"


def read(run):
    return scope_trace.share(run, "head_loss") if "train" in run else None
