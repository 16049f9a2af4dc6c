"""Least time the chip could take for what the captured calls of
``ragged_paged_attention`` under ``attn_full`` need
(``kernels/gqa_window_paged_attention.py``: each lane's whole context, K and V
for the key-value heads alone), over the time they took: one call a step and
full layer."""
from . import _window

LAYER, UNIT, BETTER, SOURCE = "kernels, serving", "%", "higher", "device_trace"


def read(run):
    if "kv_heads" not in (run.get("serve") or {}):
        return None
    return _window.roofline(run, "attn_full", None)
