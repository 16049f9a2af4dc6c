"""Time the core's own stream spends in collective operations (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all, send, recv and
their ``-start`` / ``-done`` halves): while one of them holds the stream no
compute runs on that device. Over the traced window, on the worst device."""
from . import _trace

LAYER, UNIT, BETTER, SOURCE = "collectives", "%", "lower", "device_trace"

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "send", "recv")


def is_collective(op):
    return op.startswith(COLLECTIVES)


def read(run):
    devs = _trace.devices(run)
    if len(devs) < 2:
        return None
    start, end = _trace.common_window(devs)
    worst = max(_trace.op_seconds(d, is_collective)[0] for d in devs)
    return 100.0 * worst / (end - start)
