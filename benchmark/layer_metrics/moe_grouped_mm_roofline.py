"""Least time the chip could take for what the routed experts' layers of the
captured stretch need from their grouped GEMMs, over the time the captured
``grouped_matmul`` calls took. What the layers need
(``kernels/grouped_matmul.py``) comes from the program's own counters over
the window: ``serving_moe_rows_routed`` (token-expert pairs computed) and
``serving_moe_experts_fed`` ((layer, expert) pairs that received a row, whose
weights had to be read). A layer launches two calls; the window's mean layer
stands for each captured pair. The bound is taken on the window's sums, which
is never more than the sum of the layers' own bounds."""
from ..kernels import grouped_matmul as kernel
from ..kernels.roofline import least_seconds
from . import _trace

LAYER, UNIT, BETTER, SOURCE = "kernels, serving", "%", "higher", "device_trace"


def read(run):
    devs = _trace.devices(run)
    s, counters = run.get("serve") or {}, run.get("counters") or {}
    rows = counters.get("serving_moe_rows_routed")
    steps = counters.get("serving_steps")
    if not devs or not rows or not steps or "expert_width" not in s:
        return None
    seconds, calls = _trace.op_seconds(devs[0], lambda op: op == kernel.NAME)
    if not calls:
        return None
    ops, nbytes = kernel.needs(
        rows, counters["serving_moe_experts_fed"], hidden=s["hidden"],
        width=s["expert_width"], w_bytes=s["kv_bytes"], x_bytes=s["kv_bytes"])
    least, bound = least_seconds(ops, nbytes, run["peak"])
    layers = steps * s["moe_layers"]      # routed layers run in the window
    return 100.0 * (least / layers) * (calls / 2) / seconds, {
        "calls": calls, "bound_by": bound, "kernel_s": seconds,
        "rows_per_layer": rows / layers,
        "experts_fed_per_layer": counters["serving_moe_experts_fed"] / layers}
