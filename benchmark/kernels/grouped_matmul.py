"""Operations and bytes a layer of routed gated experts needs from its two
grouped GEMMs (``grouped_matmul``: the gate and up projections as one, then
the down projection), whatever implements them.

``rows`` token-expert pairs were routed, and ``fed`` experts received at
least one of them. Each expert is ``(silu(x W_g) * x W_u) W_d`` with ``W_g``,
``W_u`` ``[hidden, width]`` and ``W_d`` ``[width, hidden]``:

- operations: every routed row takes ``3 * hidden * width`` multiply-adds;
- bytes: the three matrices of every expert that received a row, ONCE (an
  expert nobody chose costs nothing), plus each routed row read at ``hidden``
  wide and its result written at ``hidden`` wide. The ``2 * width`` values
  between the GEMMs are the implementation's, not the layer's: a fused
  kernel would keep them on the chip.
"""
from __future__ import annotations

NAME = "grouped_matmul"


def needs(rows, fed, *, hidden, width, w_bytes, x_bytes):
    """``(operations, bytes)`` for ``rows`` routed rows over ``fed`` experts
    that received any (one layer, or sums over several)."""
    ops = 2 * 3 * hidden * width * rows
    nbytes = fed * 3 * hidden * width * w_bytes + 2 * rows * hidden * x_bytes
    return ops, nbytes
