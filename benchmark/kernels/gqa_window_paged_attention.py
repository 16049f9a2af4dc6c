"""Operations and bytes one call of paged attention needs where the query
heads are GROUPED over fewer key-value heads and the layer may see a WINDOW
(``ragged_paged_attention`` under the serving step's ``attn_window`` or
``attn_full``): one layer, one serving step.

As ``kernels/ragged_paged_attention.py``, which reckons K and V for as many
heads as there are query heads over the whole context; here, what the
algorithm needs, whatever implements it (a kernel that reads whole pages and
masks reads more than this, and its share of this roofline says how much
more):

- operations: each query row at position p scores the keys its mask admits
  (``p + 1``, or under a window ``min(p + 1, window)``) and sums as many
  values, ``2 * head_dim`` multiply-adds each, in every QUERY head;
- bytes: each lane's K and V rows of the positions any of its rows sees
  (its whole context, or under a window its last ``window + q_len - 1``
  positions) for the KEY-VALUE heads, once; its query rows read and its
  output rows written once.
"""
from __future__ import annotations

NAME = "ragged_paged_attention"


def keys_admitted(q_len, kv_len, window=None):
    """Keys the masks of a lane's ``q_len`` new rows admit, its context
    ``kv_len`` long with them."""
    first = kv_len - q_len
    if window is None:
        return q_len * first + q_len * (q_len + 1) // 2
    return sum(min(p + 1, window) for p in range(first, kv_len))


def needs(lanes, *, window, num_heads, kv_heads, head_dim, kv_bytes, q_bytes,
          out_bytes):
    """``lanes``: iterable of ``(q_len, kv_len)``; ``window``: keys a row
    sees, itself counted (None: all before it). Returns ``(operations,
    bytes)`` for one call."""
    ops = nbytes = 0
    for q_len, kv_len in lanes:
        if q_len <= 0:
            continue
        ops += 4 * head_dim * num_heads * keys_admitted(q_len, kv_len, window)
        positions = (kv_len if window is None
                     else min(kv_len, window + q_len - 1))
        nbytes += 2 * positions * kv_heads * head_dim * kv_bytes
        nbytes += q_len * num_heads * head_dim * (q_bytes + out_bytes)
    return ops, nbytes
