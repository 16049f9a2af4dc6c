"""Share of device busy time under the serving step's ``attn`` scope less
the Mosaic kernel's own time: what XLA does to the ragged kernel's operands
and result (the query scatter, the gather back, any layout copy of the
pools)."""
from .. import scope_trace
from ..kernels import ragged_paged_attention as kernel

LAYER, UNIT, BETTER, SOURCE = "kernels, serving", "%", "lower", "device_trace"


def read(run):
    if "serve" not in run:
        return None
    return scope_trace.share(
        run, "attn", op=lambda label: not label.startswith(kernel.NAME))
