"""Share of device busy time under the serving step's ``moe_experts``: the
routed experts' two grouped GEMMs and the activation between them, with the
packing of the rows into the kernel's tiles."""
from . import _subscopes

LAYER, UNIT, BETTER, SOURCE = "kernels, serving", "%", "lower", "device_trace"


def read(run):
    return _subscopes.share(run, "moe_experts")
