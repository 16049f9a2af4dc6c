"""Least time the chip could take for what the captured flash attention
calls need, over the time they took, on the first device. One call serves
one microbatch of one layer on this device's share of the heads."""
from ..kernels import flash_attention as kernel
from ..kernels.roofline import least_seconds
from . import _trace

LAYER, UNIT, BETTER, SOURCE = "kernels, training", "%", "higher", "device_trace"


def read(run):
    devs = _trace.devices(run)
    if not devs or "train" not in run:
        return None
    t, mesh = run["train"], run["train"]["mesh"]
    shape = dict(batch=t["batch"] // t["num_micro"] // mesh.get("dp", 1),
                 seq=t["seq"], heads=t["heads"] // mesh.get("mp", 1),
                 head_dim=t["head_dim"], elem_bytes=t["elem_bytes"])
    least = seconds = 0.0
    note = {"call_shape": shape}
    for name, needs in ((kernel.FWD_NAME, kernel.needs_fwd),
                        (kernel.BWD_NAME, kernel.needs_bwd)):
        sec, calls = _trace.op_seconds(devs[0], lambda op, n=name: op == n)
        each, bound = least_seconds(*needs(**shape), run["peak"])
        least += each * calls
        seconds += sec
        note[name] = {"calls": calls, "seconds": sec, "bound_by": bound}
    return (100.0 * least / seconds, note) if seconds else None
