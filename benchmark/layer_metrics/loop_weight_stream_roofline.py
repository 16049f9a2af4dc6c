"""Least time the chip could take for what a looped stack's layer matrices
need in the captured steps, over the device time the program charged to the
parts that run them (``qkv``, ``attn_out`` and ``mlp``) and to the layer
scan's own slicing of the stacked weights (``layers.carry``,
``scope_trace``: on the chip the scan reads ``wqkv`` out of its stack there,
and the product under ``qkv`` then reads that slice): do the four passes over
the weights a step run at the memory's rate.

What a step needs (``kernels/loop_weight_stream.py``) comes from the serving
record and the program's own counters over the window: ``layers * loops``
(loop, layer) passes over the matrices a step (every step runs every loop),
and the window's mean row-passes a step, ``serving_loop_passes`` summed over
its loops over ``serving_steps``. The captured steps are counted from the
trace itself: each step calls ``ragged_paged_attention`` once a (loop,
layer). A program without the counter (an earlier commit, a model that runs
its stack once) gives nothing to read."""
from .. import scope_trace
from ..kernels import loop_weight_stream as kernel
from ..kernels import ragged_paged_attention as attention
from ..kernels.roofline import least_seconds
from . import _trace

LAYER, UNIT, BETTER, SOURCE = "step program", "%", "higher", "device_trace"
SCOPES = ("qkv", "attn_out", "mlp", scope_trace.CARRY)


def read(run):
    s, counters = run.get("serve") or {}, run.get("counters") or {}
    row_passes = sum(v for k, v in counters.items()
                     if k.startswith("serving_loop_passes{"))
    steps = counters.get("serving_steps")
    devs = _trace.devices(run)
    if not row_passes or not steps or not devs or "loops" not in s:
        return None
    _, calls = _trace.op_seconds(devs[0], lambda op: op == attention.NAME)
    charged = scope_trace.table(run)
    if not calls or not charged:
        return None
    seconds = sum(scope_trace.seconds(charged, scope)[0] for scope in SCOPES)
    passes = s["layers"] * s["loops"]
    captured = calls / passes
    ops, nbytes = kernel.needs(
        row_passes / steps, passes, hidden=s["hidden"], heads=s["heads"],
        head_dim=s["head_dim"], ffn=s["ffn"], w_bytes=s["kv_bytes"],
        x_bytes=s["kv_bytes"])
    least, bound = least_seconds(ops, nbytes, run["peak"])
    return 100.0 * least * captured / seconds, {
        "captured_steps": captured, "bound_by": bound,
        "scoped_s": seconds, "least_s_per_step": least,
        "row_passes_per_step": row_passes / steps,
        "weight_bytes_per_step": kernel.layer_values(
            hidden=s["hidden"], heads=s["heads"], head_dim=s["head_dim"],
            ffn=s["ffn"]) * s["kv_bytes"] * passes}
