"""Share of the traced window in which no operation ran on the device that
idles most. The window runs from the first device operation of the capture
to the last, so idle time before the first and after the last is not seen."""
from . import _trace

LAYER, UNIT, BETTER, SOURCE = "device", "%", "lower", "device_trace"


def read(run):
    return _trace.idle_share(run)
