"""Device time of ``ragged_paged_attention`` under the serving step's
``attn_window`` (the calls of the model's window attention layers), over device
busy time."""
from . import _window

LAYER, UNIT, BETTER, SOURCE = "kernels, serving", "%", "lower", "device_trace"


def read(run):
    return _window.busy_share(run, "attn_window")
