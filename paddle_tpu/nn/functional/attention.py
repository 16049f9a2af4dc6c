"""Attention functionals.

Parity: paddle's scaled_dot_product_attention / flash_attention surface
(reference: python/paddle/nn/functional/flash_attention.py, kernel
paddle/phi/kernels/gpu/flash_attn_kernel.cu:128-245). TPU-native: the hot path
is a Pallas flash-attention kernel (paddle_tpu/ops/pallas/flash_attention.py);
a pure-XLA fallback covers CPU tests and odd shapes.
"""
from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

from ...autograd.engine import apply_op


def _sdpa_ref(q, k, v, mask=None, causal=False, scale=None, dropout_p=0.0, dropout_key=None):
    """Reference attention over [B, S, H, D] (paddle layout)."""
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    # [B, S, H, D] -> [B, H, S, D]
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    if kh.shape[1] != qh.shape[1]:  # GQA: repeat kv heads
        rep = qh.shape[1] // kh.shape[1]
        kh = jnp.repeat(kh, rep, axis=1)
        vh = jnp.repeat(vh, rep, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * s
    logits = logits.astype(jnp.float32)
    if causal:
        q_len, k_len = logits.shape[-2], logits.shape[-1]
        causal_mask = jnp.tril(jnp.ones((q_len, k_len), bool), k_len - q_len)
        logits = jnp.where(causal_mask, logits, -1e30)
    if mask is not None:
        logits = logits + mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0).astype(q.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.swapaxes(out, 1, 2)  # back to [B, S, H, D]


def scaled_dot_product_attention(
    query,
    key,
    value,
    attn_mask=None,
    dropout_p=0.0,
    is_causal=False,
    training=True,
    name=None,
    scale=None,
):
    """Inputs [batch, seq, heads, head_dim] (paddle layout)."""
    from ...framework.random import rng_arg

    with_dropout = dropout_p > 0.0 and training
    use_flash = (
        _flash_usable(query)
        and query.shape[1] == key.shape[1]
        and query.shape[2] % key.shape[2] == 0  # GQA rides the kernel
    )
    if use_flash and attn_mask is not None:
        # mask streams into the kernel block-wise only for broadcastable
        # shapes; anything else (e.g. singleton sk) takes the reference path
        from ...ops.pallas.flash_attention import mask_kernel_compatible

        ms = tuple(attn_mask.shape)
        if len(ms) == 2:
            ms = (1, 1) + ms
        elif len(ms) == 3:
            ms = (ms[0], 1) + ms[1:]
        use_flash = mask_kernel_compatible(
            ms, query.shape[0], query.shape[2], query.shape[1], key.shape[1])

    def fn(q, k, v, *rest, dkey=None):
        mask = rest[0] if rest else None
        if use_flash and dkey is None:
            from ...ops.pallas.flash_attention import flash_attention

            return flash_attention(q, k, v, causal=is_causal, scale=scale,
                                   mask=mask)
        return _sdpa_ref(
            q, k, v, mask=mask, causal=is_causal, scale=scale,
            dropout_p=dropout_p if training else 0.0, dropout_key=dkey,
        )

    args = [query, key, value] + ([attn_mask] if attn_mask is not None else [])
    kwargs = {"dkey": rng_arg()} if with_dropout else {}
    return apply_op("scaled_dot_product_attention", fn, *args, **kwargs)


def _kernel_backend_ok() -> bool:
    import jax as _jax

    return _jax.devices()[0].platform == "tpu"


# Below this sequence length the fused XLA softmax-attention beats the
# Pallas kernel: the S x S score block is small enough to live in VMEM and
# XLA fuses the whole attention, while the flash grid degenerates to tiny
# per-head programs dominated by launch/prologue cost (measured on v5e:
# BERT-base seq128 runs 0.55 MFU via XLA vs 0.45 via the kernel; at seq
# >= 512 the kernel wins and is mandatory for memory). Tunable via
# FLAGS_flash_attention_min_seq.
_FLASH_MIN_SEQ = 512


def _flash_min_seq() -> int:
    from ...framework import flags

    try:
        return int(flags.flag("flash_attention_min_seq"))
    except Exception:
        return _FLASH_MIN_SEQ


def _flash_usable(query) -> bool:
    """Pallas flash attention needs TPU + aligned head dims + long enough
    sequences to beat the fused XLA path (see _FLASH_MIN_SEQ)."""
    if not _kernel_backend_ok():
        return False
    d = query._data.shape[-1] if hasattr(query, "_data") else query.shape[-1]
    s = query._data.shape[1] if hasattr(query, "_data") else query.shape[1]
    return d % 64 == 0 and s % 128 == 0 and s >= _flash_min_seq()


def flash_attention(
    query, key, value, dropout=0.0, causal=False, return_softmax=False,
    fixed_seed_offset=None, rng_name="", training=True, name=None,
):
    """paddle.nn.functional.flash_attention.flash_attention parity."""
    out = scaled_dot_product_attention(
        query, key, value, dropout_p=dropout, is_causal=causal, training=training
    )
    if return_softmax:
        return out, None
    return out, None


def flash_attn_unpadded(
    query, key, value, cu_seqlens_q, cu_seqlens_k, max_seqlen_q, max_seqlen_k,
    scale=None, dropout=0.0, causal=False, return_softmax=False, training=True, name=None,
):
    """Varlen flash attention: [total_tokens, H, D] with cumulative seqlens
    (reference: FlashAttnUnpaddedKernel, flash_attn_kernel.cu:235).

    TPU-native path: scatter the packed tokens into the static padded layout
    [b, max_seqlen, H, D] (XLA wants static shapes — a true ragged kernel
    would defeat tiling), run the varlen Pallas kernel (per-batch lengths in
    SMEM; padding costs no FLOPs), gather back. Off-TPU fallback:
    segment-masked attention over the packed batch.
    """
    b = int((cu_seqlens_q.shape if hasattr(cu_seqlens_q, "shape")
             else np.shape(cu_seqlens_q))[0]) - 1
    d_head = (query._data.shape[-1] if hasattr(query, "_data")
              else query.shape[-1])
    use_kernel = (
        _kernel_backend_ok()
        and d_head % 64 == 0
        and int(max_seqlen_q) % 128 == 0
        and int(max_seqlen_k) % 128 == 0
    )

    def kernel_fn(q, k, v, cu_q, cu_k):
        from ...ops.pallas.flash_attention import flash_attention

        h, d = q.shape[-2], q.shape[-1]
        q_lens = (cu_q[1:] - cu_q[:-1]).astype(jnp.int32)
        k_lens = (cu_k[1:] - cu_k[:-1]).astype(jnp.int32)
        seg_q = jnp.searchsorted(cu_q, jnp.arange(q.shape[0]), side="right") - 1
        pos_q = jnp.arange(q.shape[0]) - jnp.take(cu_q, seg_q)
        seg_k = jnp.searchsorted(cu_k, jnp.arange(k.shape[0]), side="right") - 1
        pos_k = jnp.arange(k.shape[0]) - jnp.take(cu_k, seg_k)
        qp = jnp.zeros((b, int(max_seqlen_q), h, d), q.dtype
                       ).at[seg_q, pos_q].set(q)
        kp = jnp.zeros((b, int(max_seqlen_k), h, d), k.dtype
                       ).at[seg_k, pos_k].set(k)
        vp = jnp.zeros((b, int(max_seqlen_k), h, d), v.dtype
                       ).at[seg_k, pos_k].set(v)
        out = flash_attention(qp, kp, vp, causal=causal, scale=scale,
                              q_seqlens=q_lens, kv_seqlens=k_lens)
        return out[seg_q, pos_q]

    def fallback_fn(q, k, v, cu_q, cu_k):
        total_q = q.shape[0]
        seg_q = jnp.searchsorted(cu_q, jnp.arange(total_q), side="right") - 1
        total_k = k.shape[0]
        seg_k = jnp.searchsorted(cu_k, jnp.arange(total_k), side="right") - 1
        d = q.shape[-1]
        s = scale if scale is not None else 1.0 / math.sqrt(d)
        logits = jnp.einsum("qhd,khd->hqk", q, k) * s
        logits = logits.astype(jnp.float32)
        same = seg_q[:, None] == seg_k[None, :]
        if causal:
            pos_q = jnp.arange(total_q) - jnp.take(cu_q, seg_q)
            pos_k = jnp.arange(total_k) - jnp.take(cu_k, seg_k)
            same = same & (pos_k[None, :] <= pos_q[:, None])
        logits = jnp.where(same[None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    fn = kernel_fn if use_kernel else fallback_fn
    out = apply_op("flash_attn_unpadded", fn, query, key, value, cu_seqlens_q, cu_seqlens_k)
    return out, None
