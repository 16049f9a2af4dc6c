"""Share of device busy time under the serving step's ``layers`` scope and
under none of its parts: the layer scan's own slicing of the stacked KV
pools and stacking of its outputs."""
from .. import scope_trace

LAYER, UNIT, BETTER, SOURCE = "step program", "%", "lower", "device_trace"


def read(run):
    return scope_trace.share(run, scope_trace.CARRY) if "serve" in run else None
