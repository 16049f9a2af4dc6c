"""Share of device busy time under the serving step's ``kv_write`` scope:
``paged_write_packed`` putting the step's new K and V rows into the pool."""
from .. import scope_trace

LAYER, UNIT, BETTER, SOURCE = "step program", "%", "lower", "device_trace"


def read(run):
    return scope_trace.share(run, "kv_write") if "serve" in run else None
