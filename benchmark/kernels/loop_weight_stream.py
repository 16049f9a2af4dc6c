"""Operations and bytes the matrices of a looped stack's layers need in a
serving step (``paddle_tpu/models/ouro.py``): ``wqkv``, ``wo`` and the MLP's
``w_gu`` and ``w_d``, run once a (loop, layer) pass, whatever implements them.
No one kernel computes them; the program's parts ``qkv``, ``attn_out`` and
``mlp`` do.

A step makes ``weight_passes`` (loop, layer) passes over the matrices and
``row_passes`` row-passes (rows times the layers they ran, summed over the
loops: the counter ``serving_loop_passes``). A layer's matrices hold ``P =
h * 3 nh hd + nh hd * h + h * 2 f + f * h`` values:

- operations: ``2 P`` a row-pass (a multiply-add a value);
- bytes: the matrices read ONCE a (loop, layer) pass, however many rows, plus
  each row-pass's inputs and outputs of the four products (``h`` in and ``3
  nh hd`` out, ``nh hd`` in and ``h`` out, ``h`` in and ``2 f`` out, ``f`` in
  and ``h`` out). What lies between the products (the attention, the norms,
  the activation) is not these matrices' work.
"""
from __future__ import annotations

NAME = "loop_weight_stream"


def layer_values(*, hidden, heads, head_dim, ffn):
    """``P``: the values of one layer's four matrices."""
    width = heads * head_dim
    return hidden * 3 * width + width * hidden + hidden * 2 * ffn \
        + ffn * hidden


def needs(row_passes, weight_passes, *, hidden, heads, head_dim, ffn,
          w_bytes, x_bytes):
    """``(operations, bytes)`` of ``row_passes`` row-passes over
    ``weight_passes`` (loop, layer) passes (one step, or sums over
    several)."""
    width = heads * head_dim
    values = layer_values(hidden=hidden, heads=heads, head_dim=head_dim,
                          ffn=ffn)
    rows_io = (hidden + 3 * width) + (width + hidden) + (hidden + 2 * ffn) \
        + (ffn + hidden)
    ops = 2 * values * row_passes
    nbytes = values * w_bytes * weight_passes + rows_io * x_bytes * row_passes
    return ops, nbytes
