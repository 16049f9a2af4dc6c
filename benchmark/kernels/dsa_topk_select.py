"""Operations and bytes one call of the selection kernel needs
(``dsa_topk_select``): the exact top-k of every scheduled row's index scores,
one layer with an indexer, one serving step.

A row at position p holds p + 1 float32 scores and keeps ``min(p + 1, k)`` of
them. What any exact selection needs:

- bytes: every score read once, every kept key's index written once (4 bytes
  each way; how the selection is handed on, as indices or as a mask, is the
  implementation's);
- operations: one comparison a score at the least (a selection is bound by
  its memory, never by this).
"""
from __future__ import annotations

NAME = "dsa_topk_select"


def needs(lanes, *, topk):
    """``lanes``: iterable of ``(q_len, kv_len)``. Returns
    ``(operations, bytes)`` for one call."""
    ops = nbytes = 0
    for q_len, kv_len in lanes:
        for seen in range(kv_len - q_len + 1, kv_len + 1):
            ops += seen
            nbytes += 4 * (seen + min(seen, topk))
    return ops, nbytes
