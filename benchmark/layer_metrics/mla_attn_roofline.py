"""Least time the chip could take for what the captured calls of
``mla_ragged_paged_attention`` need, over the time they took. What a call
needs comes from the context lengths the steps of the captured stretch really
had (``kernels/mla_paged_attention.py``); each step launches one call per
layer, and the mean over the stretch's steps stands for each captured call
(as ``ragged_attn_roofline`` does)."""
from ..kernels import mla_paged_attention as kernel
from ..kernels.roofline import least_seconds
from . import _trace

LAYER, UNIT, BETTER, SOURCE = "kernels, serving", "%", "higher", "device_trace"


def read(run):
    devs = _trace.devices(run)
    s = run.get("serve") or {}
    if not devs or "latent_row" not in s:
        return None
    seconds, calls = _trace.op_seconds(devs[0], lambda op: op == kernel.NAME)
    t0 = run["clock"].get("trace_t0")
    steps = [lanes for t, _, lanes in s["steps"]
             if lanes and (t0 is None or t >= t0)]
    if not calls or not steps:
        return None
    least, bound_by = 0.0, {"compute": 0, "memory": 0}
    for lanes in steps:
        ops, nbytes = kernel.needs(
            lanes, num_heads=s["heads"], row=s["latent_row"],
            value=s["latent_value"], kv_bytes=s["kv_bytes"],
            q_bytes=s["kv_bytes"], out_bytes=s["kv_bytes"])
        t, bound = least_seconds(ops, nbytes, run["peak"])
        least += t
        bound_by[bound] += 1
    per_call = least / len(steps)
    return 100.0 * per_call * calls / seconds, {
        "calls": calls, "steps_read": len(steps), "bound_by": bound_by,
        "kernel_s": seconds}
