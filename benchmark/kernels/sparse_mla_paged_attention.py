"""Operations and bytes one call of the attention kernel over a SELECTION of
a latent cache needs (``sparse_mla_paged_attention``): one layer, one serving
step.

As ``kernels/mla_paged_attention.py``, but a query row at position p reads
only the ``min(p + 1, topk)`` keys its selection names, and every row has a
selection of its own. What the algorithm needs, whatever implements it (a
kernel that reads whole pages and masks reads more than this, and its share
of this roofline says how much more):

- operations: each query row scores its selected rows and sums as many
  values, in every head: ``row + value`` multiply-adds each;
- bytes: each query row's selected cache rows read once (``row`` values
  each, shared by its heads), its query rows read and its output rows
  written once.
"""
from __future__ import annotations

NAME = "sparse_mla_paged_attention"


def needs(lanes, *, topk, num_heads, row, value, kv_bytes, q_bytes,
          out_bytes):
    """``lanes``: iterable of ``(q_len, kv_len)``. Returns
    ``(operations, bytes)`` for one call."""
    ops = nbytes = 0
    for q_len, kv_len in lanes:
        if q_len <= 0:
            continue
        read = sum(min(seen, topk)
                   for seen in range(kv_len - q_len + 1, kv_len + 1))
        ops += 2 * (row + value) * num_heads * read
        nbytes += read * row * kv_bytes
        nbytes += q_len * num_heads * (row * q_bytes + value * out_bytes)
    return ops, nbytes
