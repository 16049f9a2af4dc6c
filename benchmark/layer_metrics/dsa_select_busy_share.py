"""Share of device busy time under the serving step's ``attn_select``: the
exact top-k that turns the index scores into the selection attention reads."""
from . import _dsa

LAYER, UNIT, BETTER, SOURCE = "kernels, serving", "%", "lower", "device_trace"


def read(run):
    return _dsa.share(run, "attn_select")
