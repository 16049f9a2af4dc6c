"""Share of device busy time under the serving step's ``moe_route``: the
router's product, the top-k, the sort and gather of the rows into expert
order, and the weighted sum of the experts' outputs back onto the tokens."""
from . import _subscopes

LAYER, UNIT, BETTER, SOURCE = "step program", "%", "lower", "device_trace"


def read(run):
    return _subscopes.share(run, "moe_route")
