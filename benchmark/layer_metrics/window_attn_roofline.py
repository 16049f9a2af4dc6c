"""Least time the chip could take for what the captured calls of
``ragged_paged_attention`` under ``attn_window`` need
(``kernels/gqa_window_paged_attention.py``: each row's last ``window`` keys,
K and V for the key-value heads alone), over the time they took: one call a
step and window layer."""
from . import _window

LAYER, UNIT, BETTER, SOURCE = "kernels, serving", "%", "higher", "device_trace"


def read(run):
    s = run.get("serve") or {}
    if "window" not in s:
        return None
    return _window.roofline(run, "attn_window", s["window"])
