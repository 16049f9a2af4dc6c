"""Share of device busy time that the train step charges to one of its named
parts (``scope_trace``). The note's table keeps forward apart from backward
(``<scope>.bwd``: ``transpose(`` in the operation's path)."""
from .. import scope_trace

LAYER, UNIT, BETTER, SOURCE = "train step", "%", "higher", "device_trace"


def read(run):
    if "train" not in run:
        return None
    return scope_trace.scoped_share(run, split_backward=True)
