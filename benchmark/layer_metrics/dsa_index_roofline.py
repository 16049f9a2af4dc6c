"""Least time the chip could take for what the captured calls of
``dsa_index_scores`` need (``kernels/dsa_index_scores.py``), over the time
they took: one call a step and layer with an indexer."""
import functools

from ..kernels import dsa_index_scores as kernel
from . import _dsa

LAYER, UNIT, BETTER, SOURCE = "kernels, serving", "%", "higher", "device_trace"


def read(run):
    s = run.get("serve") or {}
    if "index_heads" not in s:
        return None
    return _dsa.roofline(run, kernel.NAME, functools.partial(
        kernel.needs, heads=s["index_heads"], dim=s["index_dim"],
        key_bytes=s["kv_bytes"], q_bytes=s["kv_bytes"]))
