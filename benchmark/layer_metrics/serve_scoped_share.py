"""Share of device busy time that the serving step program charges to one of
its named parts (``scope_trace``). The note is the whole table, ``{scope:
seconds, share, calls}``, and who owns each of the largest operations."""
from .. import scope_trace

LAYER, UNIT, BETTER, SOURCE = "step program", "%", "higher", "device_trace"


def read(run):
    return scope_trace.scoped_share(run) if "serve" in run else None
