"""Operations and bytes one call of the index-score kernel needs
(``dsa_index_scores``): one layer with an indexer, one serving step.

Every scheduled lane feeds ``q_len`` query rows whose context, themselves
included, is ``kv_len`` tokens. A row at position p scores the p + 1 index
keys it sees: per key and index head one dot of ``dim`` values, a relu, and a
weighted sum over the heads. What the algorithm needs, whatever implements it:

- operations: ``heads * (dim + 1)`` multiply-adds a (row, key);
- bytes: each lane's live index keys read ONCE (``dim`` values a token), its
  rows' index queries (``heads * dim``) and head weights (``heads`` float32)
  read once. The scores themselves are the implementation's: a fused
  selection would keep them on the chip.
"""
from __future__ import annotations

NAME = "dsa_index_scores"


def needs(lanes, *, heads, dim, key_bytes, q_bytes):
    """``lanes``: iterable of ``(q_len, kv_len)``. Returns
    ``(operations, bytes)`` for one call."""
    ops = nbytes = 0
    for q_len, kv_len in lanes:
        if q_len <= 0:
            continue
        first = kv_len - q_len
        scored = q_len * first + q_len * (q_len + 1) // 2
        ops += 2 * heads * (dim + 1) * scored
        nbytes += kv_len * dim * key_bytes
        nbytes += q_len * heads * (dim * q_bytes + 4)
    return ops, nbytes
