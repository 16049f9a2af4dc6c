"""Least time the chip could take for what the captured calls of
``sparse_mla_paged_attention`` need (``kernels/sparse_mla_paged_attention.py``:
each row's SELECTED cache rows, not its whole context), over the time they
took: one call a step and layer."""
import functools

from ..kernels import sparse_mla_paged_attention as kernel
from . import _dsa

LAYER, UNIT, BETTER, SOURCE = "kernels, serving", "%", "higher", "device_trace"


def read(run):
    s = run.get("serve") or {}
    if "index_topk" not in s:
        return None
    return _dsa.roofline(run, kernel.NAME, functools.partial(
        kernel.needs, topk=s["index_topk"], num_heads=s["heads"],
        row=s["latent_row"], value=s["latent_value"], kv_bytes=s["kv_bytes"],
        q_bytes=s["kv_bytes"], out_bytes=s["kv_bytes"]))
