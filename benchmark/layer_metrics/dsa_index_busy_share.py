"""Share of device busy time under the serving step's ``attn_index``: the
indexer's projections, the index-key write and the score kernel, in the
layers that have an indexer."""
from . import _dsa

LAYER, UNIT, BETTER, SOURCE = "kernels, serving", "%", "lower", "device_trace"


def read(run):
    return _dsa.share(run, "attn_index")
