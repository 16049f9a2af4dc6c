"""Operations and bytes one call of the flash attention kernels needs.

Causal self-attention over ``[batch, seq, heads, head_dim]``: half of the
seq x seq score matrix is needed.

- forward: scores and the weighted sum of values, two matrix products;
- backward: dV, dP, dQ and dK, four matrix products. The kernel also
  recomputes the scores; that is its choice, not the algorithm's need, and
  is not counted;
- bytes: forward reads q, k, v and writes o; backward reads q, k, v, o, do
  and writes dq, dk, dv (the log-sum-exp rows are a thousandth of that).
"""
from __future__ import annotations

FWD_NAME = "flash_attention_fwd"
BWD_NAME = "flash_attention_bwd"


def _product_ops(batch, seq, heads, head_dim):
    """One causal seq x seq x head_dim matrix product per head."""
    return 2 * batch * heads * head_dim * seq * (seq + 1) // 2


def needs_fwd(*, batch, seq, heads, head_dim, elem_bytes):
    tensor = batch * seq * heads * head_dim * elem_bytes
    return 2 * _product_ops(batch, seq, heads, head_dim), 4 * tensor


def needs_bwd(*, batch, seq, heads, head_dim, elem_bytes):
    tensor = batch * seq * heads * head_dim * elem_bytes
    return 4 * _product_ops(batch, seq, heads, head_dim), 8 * tensor
