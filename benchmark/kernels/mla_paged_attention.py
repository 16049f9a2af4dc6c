"""Operations and bytes one call of ``mla_ragged_paged_attention`` needs.

A call serves one layer of one serving step over a LATENT cache: one row
``[c | k_pe]`` of ``row`` values per context token, shared by every head; in
the absorbed form a query head is as wide as the row, the score is one dot
with it and the value is the row's first ``value`` entries. Every scheduled
lane feeds ``q_len`` query rows whose context, themselves included, is
``kv_len`` tokens. What the algorithm needs, whatever the kernel's tiles do
(and whatever padding the pool's rows carry):

- operations: each query row at position p scores p + 1 rows and sums as
  many values, in every head: ``row + value`` multiply-adds each;
- bytes: each lane's context rows read ONCE (not once per head: that is the
  point of the latent), its query rows read and its output rows written once.
"""
from __future__ import annotations

NAME = "mla_ragged_paged_attention"


def needs(lanes, *, num_heads, row, value, kv_bytes, q_bytes, out_bytes):
    """``lanes``: iterable of ``(q_len, kv_len)``. Returns
    ``(operations, bytes)`` for one call."""
    ops = 0
    nbytes = 0
    for q_len, kv_len in lanes:
        if q_len <= 0:
            continue
        first = kv_len - q_len  # rows before the first new one
        rows_scored = q_len * first + q_len * (q_len + 1) // 2
        ops += 2 * (row + value) * num_heads * rows_scored
        nbytes += kv_len * row * kv_bytes
        nbytes += q_len * num_heads * (row * q_bytes + value * out_bytes)
    return ops, nbytes
