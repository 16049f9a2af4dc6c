"""Least time the chip could take for what the captured calls of
``dsa_topk_select`` need (``kernels/dsa_topk_select.py``: every score read
once, every kept key written once), over the time they took."""
import functools

from ..kernels import dsa_topk_select as kernel
from . import _dsa

LAYER, UNIT, BETTER, SOURCE = "kernels, serving", "%", "higher", "device_trace"


def read(run):
    s = run.get("serve") or {}
    if "index_topk" not in s:
        return None
    return _dsa.roofline(run, kernel.NAME, functools.partial(
        kernel.needs, topk=s["index_topk"]))
