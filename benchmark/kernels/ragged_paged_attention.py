"""Operations and bytes one call of ``ragged_paged_attention`` needs.

A call serves one layer of one serving step: every scheduled lane feeds
``q_len`` new query rows whose context, the new rows included, is ``kv_len``
tokens long. What the algorithm needs, whatever the kernel's grid does:

- operations: each query row at position p scores p + 1 keys and sums as
  many values, 2 * head_dim multiply-adds each, in every head;
- bytes: each lane's K and V context read once, its query rows read and its
  output rows written once (idle lanes and unused pages cost nothing).
"""
from __future__ import annotations

NAME = "ragged_paged_attention"


def needs(lanes, *, num_heads, head_dim, kv_bytes, q_bytes, out_bytes):
    """``lanes``: iterable of ``(q_len, kv_len)``. Returns
    ``(operations, bytes)`` for one call."""
    ops = 0
    nbytes = 0
    for q_len, kv_len in lanes:
        if q_len <= 0:
            continue
        first = kv_len - q_len  # keys before the first new row
        keys_scored = q_len * first + q_len * (q_len + 1) // 2
        ops += 4 * head_dim * num_heads * keys_scored
        nbytes += 2 * kv_len * num_heads * head_dim * kv_bytes
        nbytes += q_len * num_heads * head_dim * (q_bytes + out_bytes)
    return ops, nbytes
