"""paddle.incubate.nn.functional — fused op surface.

Reference: python/paddle/incubate/nn/functional (fused_multi_head_attention,
fused_feedforward, fused_rotary_position_embedding, fused_dropout_add,
fused_rms_norm, fused_layer_norm, fused_linear,
variable_length_memory_efficient_attention…) backed by phi fusion kernels
(phi/kernels/fusion/gpu/ — fused_rope, fused_layernorm, fused attention).

TPU stance: "fused" means "expressed so XLA fuses it" — each function is a
single apply_op whose jaxpr XLA tiles into one kernel (elementwise chains
fold into the matmul epilogues); flash attention uses the Pallas kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...autograd.engine import apply_op
from ...tensor.tensor import Tensor


def fused_linear(x, weight, bias=None, transpose_weight=False):
    def fn(x_, w, b):
        w_ = w.T if transpose_weight else w
        y = x_ @ w_
        return y + b if b is not None else y

    return apply_op("fused_linear", fn, x, weight, bias)


def fused_linear_activation(x, weight, bias=None, activation="gelu"):
    act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu,
           "none": lambda v: v}[activation]

    def fn(x_, w, b):
        y = x_ @ w
        if b is not None:
            y = y + b
        return act(y)

    return apply_op("fused_linear_activation", fn, x, weight, bias)


def fused_dropout_add(x, y, p=0.5, training=True, mode="upscale_in_train",
                      seed=None, name=None):
    """out = dropout(x) + y in one kernel (reference:
    fused_dropout_add op)."""
    from ...framework.random import rng_arg

    if not training or p == 0.0:
        return apply_op("fused_dropout_add", lambda a, b: a + b, x, y)
    keep = 1.0 - p

    def fn(a, b, key):
        mask = jax.random.bernoulli(key, keep, a.shape)
        if mode == "upscale_in_train":
            return jnp.where(mask, a / keep, 0.0) + b
        return jnp.where(mask, a, 0.0) + b

    # explicit seed stays a baked constant (deterministic, reference parity);
    # generator-drawn keys go through rng_arg so static replays re-randomize
    karg = rng_arg() if seed is None else jax.random.PRNGKey(seed)
    return apply_op("fused_dropout_add", fn, x, y, karg)


def fused_rms_norm(x, norm_weight, norm_bias=None, epsilon=1e-6,
                   begin_norm_axis=-1, **kw):
    def fn(x_, w, b):
        var = jnp.mean(jnp.square(x_.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        y = (x_.astype(jnp.float32) * jax.lax.rsqrt(var + epsilon)).astype(
            x_.dtype)
        y = y * w
        return y + b if b is not None else y

    return apply_op("fused_rms_norm", fn, x, norm_weight, norm_bias)


def fused_layer_norm(x, norm_weight, norm_bias, epsilon=1e-5,
                     begin_norm_axis=-1, use_pallas=None, **kw):
    """Fused LayerNorm. On TPU (or with ``use_pallas=True`` — interpret
    mode off-TPU) the single-pass Pallas kernel
    (ops/pallas/fused_mlp.fused_layer_norm) runs fwd AND custom-VJP bwd;
    otherwise one XLA-fused jnp composite."""
    from ...ops.pallas import fused_mlp as _fm

    def fn(x_, w, b):
        if w is not None and b is not None:
            # gate + reference fallback live in the kernel module
            return _fm.fused_layer_norm(x_, w, b, eps=epsilon,
                                        use_kernel=use_pallas)
        xf = x_.astype(jnp.float32)
        mean = xf.mean(-1, keepdims=True)
        var = xf.var(-1, keepdims=True)
        y = ((xf - mean) * jax.lax.rsqrt(var + epsilon)).astype(x_.dtype)
        if w is not None:
            y = y * w
        if b is not None:
            y = y + b
        return y

    return apply_op("fused_layer_norm", fn, x, norm_weight, norm_bias)


def fused_ln_residual(x, residual, norm_weight, norm_bias, epsilon=1e-5,
                      use_pallas=None):
    """Residual-in/residual-out fused LayerNorm:
    ``s = x + residual; y = LN(s)``; returns ``(y, s)`` — the pre-LN
    transformer block's residual + norm in ONE kernel (Pallas on TPU,
    jnp composite elsewhere)."""
    from ...ops.pallas import fused_mlp as _fm

    def fn(x_, r, w, b):
        return _fm.fused_ln_residual(x_, r, w, b, eps=epsilon,
                                     use_kernel=use_pallas)

    return apply_op("fused_ln_residual", fn, x, residual, norm_weight,
                    norm_bias)


def fused_bias_gelu(x, bias=None, use_pallas=None):
    """``gelu(x + bias)`` epilogue (tanh approximation) — the GEMM epilogue
    fused into one Pallas kernel on TPU (jnp composite elsewhere)."""
    from ...ops.pallas import fused_mlp as _fm

    def fn(x_, b):
        return _fm.fused_bias_gelu(x_, b, use_kernel=use_pallas)

    return apply_op("fused_bias_gelu", fn, x, bias)


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None, use_neox_rotary_style=True):
    """RoPE applied to q/k (v passthrough) — reference: fused_rope kernel
    (phi/kernels/fusion/gpu/fused_rope*). Shapes [B, S, H, D]."""

    def rope_one(x, sin_, cos_):
        if x is None:
            return None
        if use_neox_rotary_style:
            half = x.shape[-1] // 2
            x1, x2 = x[..., :half], x[..., half:]
            rot = jnp.concatenate([-x2, x1], axis=-1)
        else:
            x1 = x[..., 0::2]
            x2 = x[..., 1::2]
            rot = jnp.stack([-x2, x1], axis=-1).reshape(x.shape)
        return x * cos_ + rot * sin_

    def fn(q_, k_, v_, sin_, cos_):
        S, D = q_.shape[1], q_.shape[-1]
        if sin_ is None:
            inv = 1.0 / (10000.0 ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
            t = jnp.arange(S, dtype=jnp.float32)
            freqs = jnp.outer(t, inv)
            if use_neox_rotary_style:
                emb = jnp.concatenate([freqs, freqs], axis=-1)
            else:
                emb = jnp.repeat(freqs, 2, axis=-1)
            sin_, cos_ = jnp.sin(emb), jnp.cos(emb)
        # accept [S, D] or the broadcast form [1, S, 1, D]; canonicalize
        sin2d = sin_.reshape(-1, D).astype(q_.dtype)
        cos2d = cos_.reshape(-1, D).astype(q_.dtype)
        if position_ids is not None:
            pid = jnp.asarray(position_ids._data if isinstance(
                position_ids, Tensor) else position_ids)  # [B, S]
            sin_b = sin2d[pid][:, :, None, :]  # [B, S, 1, D]
            cos_b = cos2d[pid][:, :, None, :]
        else:
            sin_b = sin2d.reshape(1, S, 1, D)
            cos_b = cos2d.reshape(1, S, 1, D)
        outs = tuple(rope_one(t_, sin_b, cos_b) if t_ is not None else None
                     for t_ in (q_, k_))
        return outs + ((v_,) if v_ is not None else (None,))

    out = apply_op("fused_rope", fn, q, k, v,
                   sin._data if isinstance(sin, Tensor) else sin,
                   cos._data if isinstance(cos, Tensor) else cos)
    return out


def fused_bias_dropout_residual_layer_norm(x, residual, bias=None,
                                           ln_scale=None, ln_bias=None,
                                           dropout_rate=0.0, epsilon=1e-5,
                                           training=True, **kw):
    from ...framework.random import rng_arg

    with_dropout = training and dropout_rate > 0.0
    keep = 1.0 - dropout_rate

    def fn(x_, res, b, w, lb, key=None):
        y = x_ + b if b is not None else x_
        if key is not None:
            mask = jax.random.bernoulli(key, keep, y.shape)
            y = jnp.where(mask, y / keep, 0.0).astype(y.dtype)
        y = y + res
        xf = y.astype(jnp.float32)
        mean = xf.mean(-1, keepdims=True)
        var = xf.var(-1, keepdims=True)
        out = ((xf - mean) * jax.lax.rsqrt(var + epsilon)).astype(y.dtype)
        if w is not None:
            out = out * w
        if lb is not None:
            out = out + lb
        return out

    return apply_op("fused_bias_dropout_residual_ln", fn, x, residual, bias,
                    ln_scale, ln_bias,
                    **({"key": rng_arg()} if with_dropout else {}))


def memory_efficient_attention(query, key, value, attn_bias=None, p=0.0,
                               scale=None, training=True):
    """Reference: incubate/nn/memory_efficient_attention.py (xformers-style).
    On TPU this IS flash attention (same blockwise-softmax trick); inputs
    [B, S, H, D]."""
    from ...nn.functional.attention import scaled_dot_product_attention

    return scaled_dot_product_attention(
        query, key, value, attn_mask=attn_bias, dropout_p=p,
        training=training, scale=scale)


def variable_length_memory_efficient_attention(query, key, value, seq_lens,
                                               kv_seq_lens, mask=None,
                                               scale=None, causal=False):
    """Variable-length batched attention: positions past each sequence's
    length are masked out (reference: phi fused
    variable_length_memory_efficient_attention; q [B,H,S,D])."""

    def fn(q_, k_, v_, sl, kvl, m):
        B, H, S, D = q_.shape
        s = scale if scale is not None else 1.0 / jnp.sqrt(D).astype(q_.dtype)
        scores = jnp.einsum("bhsd,bhtd->bhst", q_, k_) * s
        kv_pos = jnp.arange(k_.shape[2])
        key_mask = kv_pos[None, :] < kvl.reshape(-1, 1)  # [B, T]
        # finite fill: -inf would make a fully-masked row (kv_seq_len == 0)
        # produce NaN through softmax that survives the final q-mask
        neg = jnp.asarray(-1e30, scores.dtype)
        scores = jnp.where(key_mask[:, None, None, :], scores, neg)
        if causal:
            q_pos = jnp.arange(S)
            scores = jnp.where(
                q_pos[:, None] >= kv_pos[None, :], scores, neg)
        if m is not None:
            scores = scores + m
        p_ = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhst,bhtd->bhsd", p_, v_)
        q_mask = jnp.arange(S)[None, :] < sl.reshape(-1, 1)
        out = jnp.where(q_mask[:, None, :, None], out, 0.0)
        # rows with no valid key at all contribute zeros, not a uniform avg
        any_key = key_mask.any(axis=-1)[:, None, None, None]
        return jnp.where(any_key, out, 0.0)

    return apply_op("varlen_mem_efficient_attention", fn, query, key, value,
                    seq_lens, kv_seq_lens, mask)


def paged_attention(q, k_pages, v_pages, page_table, seq_lens, scale=None,
                    use_kernel=None):
    """Paged decode attention over the block-paged KV cache (round-7
    serving path; reference surface: the block_multihead_attention family's
    decode step, vLLM page-table layout). One query token per sequence:
    ``q`` [b, num_q_heads, head_dim] attends its slot's cached prefix read
    through ``page_table`` [b, pages_per_slot] from the page pools
    [num_pages, kv_heads, page_size, head_dim]; ``seq_lens`` [b] are the
    ragged context lengths (0 = empty slot -> zero output). Pallas kernel
    on TPU (``use_kernel=True`` forces interpret mode off-TPU), jnp gather
    reference elsewhere. Decode-only: not differentiable."""
    from ...ops.pallas import paged_attention as _pa

    def fn(q_, kp, vp, pt, lens):
        return _pa.paged_attention(q_, kp, vp, pt, lens, scale=scale,
                                   use_kernel=use_kernel)

    return apply_op("paged_attention", fn, q, k_pages, v_pages, page_table,
                    seq_lens)


def ragged_paged_attention(q, k_pages, v_pages, page_table, kv_lens, q_lens,
                           scale=None, use_kernel=None, k_scales=None,
                           v_scales=None):
    """Ragged prefill+decode attention over the block-paged KV cache (the
    round-9 unified serving step's kernel; Ragged Paged Attention, arxiv
    2604.15464). Each slot contributes ``q_lens`` (0..chunk) query tokens
    — ``q`` [b, chunk, num_q_heads, head_dim] right-padded — causal within
    its chunk, attending its whole paged context of ``kv_lens`` tokens
    (chunk included; its K/V must already be written). Rows past
    ``q_lens`` are unspecified. With ``k_scales``/``v_scales``
    ([num_pages, kv_heads, page_size]) the page pools are int8 (round-10
    quantized KV cache) and dequantize inside the kernel's page loop.
    Pallas kernel on TPU (``use_kernel=True`` forces interpret mode
    off-TPU), jnp gather reference elsewhere. Decode-only: not
    differentiable."""
    from ...ops.pallas import paged_attention as _pa

    def fn(q_, kp, vp, pt, kl, ql, ks, vs):
        return _pa.ragged_paged_attention(q_, kp, vp, pt, kl, ql,
                                          scale=scale,
                                          use_kernel=use_kernel,
                                          k_scales=ks, v_scales=vs)

    return apply_op("ragged_paged_attention", fn, q, k_pages, v_pages,
                    page_table, kv_lens, q_lens, k_scales, v_scales)


def quant_matmul(x, qweight, scales, bias=None, use_kernel=None):
    """Fused weight-only quantized GEMM (round-10 serving weight path):
    ``y = x @ dequant(qweight) + bias`` with ``qweight`` int8 ``[in,
    out]`` or nibble-packed int4 ``[in/2, out]`` staying quantized in HBM
    and per-channel (``[out]``) / per-group (``[groups, out]``) scales
    applied tile-by-tile inside the Pallas kernel. ``use_kernel`` as in
    :func:`paged_attention`. (One implementation — this re-exports the
    ``nn.quant`` op.)"""
    from ...nn.quant import quant_matmul as _impl

    return _impl(x, qweight, scales, bias=bias, use_kernel=use_kernel)


def grouped_matmul(x, weights, group_offsets, scales=None, use_kernel=None):
    """Ragged grouped GEMM (round-25 MoE expert dispatch): ``out[i] =
    x[i] @ dequant(weights)[g(i)]`` — one fused Pallas pass over an
    ``[E, K, N]`` expert weight stack with rows of ``x`` pre-sorted by
    expert and ``group_offsets [E+1]`` marking each expert's row range
    (empty experts allowed). ``weights`` may be fp, int8, or nibble-packed
    int4 with per-expert ``scales``. ``use_kernel`` as in
    :func:`paged_attention`. (One implementation — this re-exports the
    ``nn.quant`` op.)"""
    from ...nn.quant import grouped_matmul as _impl

    return _impl(x, weights, group_offsets, scales=scales,
                 use_kernel=use_kernel)


def swiglu(x, y=None):
    """SwiGLU activation (reference: incubate fused swiglu): if y is None, x
    splits in half on the last dim."""

    def fn(x_, y_):
        if y_ is None:
            x_, y_ = jnp.split(x_, 2, axis=-1)
        return jax.nn.silu(x_) * y_

    return apply_op("swiglu", fn, x, y)


__all__ = [
    "fused_linear", "fused_linear_activation", "fused_dropout_add",
    "fused_rms_norm", "fused_layer_norm", "fused_ln_residual",
    "fused_bias_gelu", "fused_rotary_position_embedding",
    "fused_bias_dropout_residual_layer_norm", "memory_efficient_attention",
    "variable_length_memory_efficient_attention", "swiglu",
    "fused_matmul_bias", "fused_dot_product_attention", "fused_feedforward",
    "fused_multi_head_attention", "masked_multihead_attention",
    "fused_multi_transformer", "fused_ec_moe", "fused_gate_attention",
    "block_multihead_attention", "paged_attention",
    "ragged_paged_attention", "quant_matmul", "grouped_matmul",
]


# --- round-4: the fused-transformer serving family -------------------------
# Reference: incubate/nn/functional/fused_transformer.py (+ the standalone
# fused_matmul_bias / fused_dot_product_attention / masked_multihead_attention
# files). On TPU these "fused ops" are pure jnp compositions — XLA fuses the
# epilogues into the GEMMs, which is exactly what the reference's hand-fused
# CUDA kernels exist to do; the API shapes are kept for switch-over parity.


def fused_matmul_bias(x, y, bias=None, transpose_x=False, transpose_y=False,
                      name=None):
    """matmul + bias epilogue (reference fused_matmul_bias.py:21)."""
    def fn(a, b, *rest):
        if transpose_x:
            a = jnp.swapaxes(a, -1, -2)
        if transpose_y:
            b = jnp.swapaxes(b, -1, -2)
        out = a @ b
        if rest:
            out = out + rest[0]
        return out

    args = [x, y] + ([bias] if bias is not None else [])
    return apply_op("fused_matmul_bias", fn, *args)


def fused_dot_product_attention(q, k, v, mask=None, scaling_factor=None,
                                dropout_prob=0.0, is_training=True,
                                is_causal_masking=False,
                                return_softmax=False, name=None):
    """Scaled dot-product attention, [b, s, h, d] layout (reference
    fused_dot_product_attention.py:20 — cuDNN there, flash/XLA here)."""
    if return_softmax:
        raise NotImplementedError(
            "fused_dot_product_attention: return_softmax=True is a cuDNN "
            "debug output the TPU kernel does not materialize")
    from ...nn import functional as F

    return F.scaled_dot_product_attention(
        q, k, v, attn_mask=mask, dropout_p=dropout_prob,
        is_causal=is_causal_masking, training=is_training,
        scale=scaling_factor)


def fused_feedforward(x, linear1_weight, linear2_weight, linear1_bias=None,
                      linear2_bias=None, ln1_scale=None, ln1_bias=None,
                      ln2_scale=None, ln2_bias=None, dropout1_rate=0.5,
                      dropout2_rate=0.5, activation="relu",
                      ln1_epsilon=1e-5, ln2_epsilon=1e-5,
                      pre_layer_norm=False, training=True,
                      mode="upscale_in_train", ring_id=-1,
                      add_residual=True, name=None):
    """residual + LN + (linear, act, dropout, linear, dropout)
    (reference fused_transformer.py:36)."""
    from ...nn import functional as F

    def ln(t, scale, bias, eps):
        # scale=None still normalizes (gamma=1/beta=0), matching the
        # reference fused kernel's optional-affine semantics
        return F.layer_norm(t, [t.shape[-1]], weight=scale, bias=bias,
                            epsilon=eps)

    residual = x
    out = ln(x, ln1_scale, ln1_bias, ln1_epsilon) if pre_layer_norm else x
    out = fused_matmul_bias(out, linear1_weight, linear1_bias)
    out = getattr(F, activation)(out)
    out = F.dropout(out, p=dropout1_rate, training=training, mode=mode)
    out = fused_matmul_bias(out, linear2_weight, linear2_bias)
    out = F.dropout(out, p=dropout2_rate, training=training, mode=mode)
    if add_residual:
        out = out + residual
    if not pre_layer_norm:
        out = ln(out, ln2_scale, ln2_bias, ln2_epsilon)
    return out


def fused_multi_head_attention(x, qkv_weight, linear_weight,
                               pre_layer_norm=False, pre_ln_scale=None,
                               pre_ln_bias=None, ln_scale=None, ln_bias=None,
                               pre_ln_epsilon=1e-5, qkv_bias=None,
                               linear_bias=None, cache_kv=None,
                               attn_mask=None, dropout_rate=0.5,
                               attn_dropout_rate=0.5, ln_epsilon=1e-5,
                               training=True, mode="upscale_in_train",
                               ring_id=-1, add_residual=True, num_heads=-1,
                               transpose_qkv_wb=False, name=None):
    """Self-attention block: residual + LN + qkv GEMM + attention + out
    proj + dropout (reference fused_transformer.py:514). qkv_weight is the
    reference layout [3, num_heads, head_dim, embed_dim] (or [embed_dim,
    3*embed_dim] with transpose_qkv_wb); returns the block output (and the
    updated cache when ``cache_kv`` is given: [2, bsz, nh, seq, hd])."""
    from ...nn import functional as F

    B, S, E = x.shape
    if transpose_qkv_wb:
        if num_heads <= 0:
            raise ValueError("transpose_qkv_wb=True requires num_heads")
        nh = num_heads
    else:
        nh = qkv_weight.shape[1]
    hd = E // nh

    residual = x
    out = x
    if pre_layer_norm:
        out = F.layer_norm(out, [E], weight=pre_ln_scale, bias=pre_ln_bias,
                           epsilon=pre_ln_epsilon)

    def qkv_fn(h, w, *rest):
        if transpose_qkv_wb:
            q3 = h @ w  # [B, S, 3E]
            if rest:
                q3 = q3 + rest[0]
            q3 = q3.reshape(B, S, 3, nh, hd)
        else:
            wf = w.reshape(3 * nh * hd, E)
            q3 = jnp.einsum("bse,fe->bsf", h, wf)
            if rest:
                q3 = q3 + rest[0].reshape(-1)
            q3 = q3.reshape(B, S, 3, nh, hd)
        return q3[:, :, 0], q3[:, :, 1], q3[:, :, 2]

    qargs = [out, qkv_weight] + ([qkv_bias] if qkv_bias is not None else [])
    q, k, v = apply_op("fused_qkv", qkv_fn, *qargs)

    new_cache = None
    if cache_kv is not None:
        def cat_cache(c, kk, vv):
            # cache [2, B, nh, s_past, hd]; new k/v [B, s, nh, hd]
            kk = jnp.transpose(kk, (0, 2, 1, 3))
            vv = jnp.transpose(vv, (0, 2, 1, 3))
            k_all = jnp.concatenate([c[0], kk], axis=2)
            v_all = jnp.concatenate([c[1], vv], axis=2)
            return jnp.stack([k_all, v_all])

        new_cache = apply_op("fused_cache_concat", cat_cache, cache_kv, k, v)
        k = new_cache[0].transpose([0, 2, 1, 3])
        v = new_cache[1].transpose([0, 2, 1, 3])

    ctx = F.scaled_dot_product_attention(
        q, k, v, attn_mask=attn_mask, dropout_p=attn_dropout_rate,
        training=training)
    ctx = ctx.reshape([B, S, E])
    out = fused_matmul_bias(ctx, linear_weight, linear_bias)
    out = F.dropout(out, p=dropout_rate, training=training, mode=mode)
    if add_residual:
        out = out + residual
    if not pre_layer_norm:
        out = F.layer_norm(out, [E], weight=ln_scale, bias=ln_bias,
                           epsilon=ln_epsilon)
    if cache_kv is not None:
        return out, new_cache
    return out


def masked_multihead_attention(x, cache_kv=None, bias=None, src_mask=None,
                               cum_offsets=None, sequence_lengths=None,
                               rotary_tensor=None, beam_cache_offset=None,
                               qkv_out_scale=None, out_shift=None,
                               out_smooth=None, seq_len=1, rotary_emb_dims=0,
                               use_neox_rotary_style=False,
                               compute_dtype="default", out_scale=-1,
                               quant_round_type=1, quant_max_bound=127.0,
                               quant_min_bound=-127.0):
    """One-token decode attention over a kv cache (reference
    masked_multihead_attention.py:19): x is the packed qkv of the CURRENT
    step [bsz, 3*nh*hd]; the cache [2, bsz, nh, max_len, hd] is updated at
    position ``sequence_lengths`` and attention runs over the valid
    prefix. Quant/beam arguments are the reference's int8 serving path and
    are not supported."""
    if any(a is not None for a in (qkv_out_scale, out_shift, out_smooth,
                                   beam_cache_offset)) or out_scale != -1:
        raise NotImplementedError(
            "masked_multihead_attention: int8/beam-search serving "
            "arguments are not supported on the TPU build")
    if cache_kv is None:
        raise ValueError("masked_multihead_attention requires cache_kv")
    import math as _m

    nh = cache_kv.shape[2]
    hd = cache_kv.shape[4]
    max_len = cache_kv.shape[3]

    has_bias = bias is not None
    has_mask = src_mask is not None
    has_lens = sequence_lengths is not None
    has_rot = rotary_tensor is not None

    def fn(xv, cache, *rest):
        b = xv.shape[0]
        ri = 0
        bias_v = mask_v = lens_v = rot_v = None
        if has_bias:
            bias_v = rest[ri]; ri += 1
        if has_mask:
            mask_v = rest[ri]; ri += 1
        if has_lens:
            lens_v = rest[ri]; ri += 1
        if has_rot:
            rot_v = rest[ri]; ri += 1
        qkv = xv.reshape(b, 3, nh, hd)
        if bias_v is not None:
            qkv = qkv + bias_v.reshape(1, 3, nh, hd)
        q, k_new, v_new = qkv[:, 0], qkv[:, 1], qkv[:, 2]
        if lens_v is None:
            pos = jnp.zeros((b,), jnp.int32)
        else:
            pos = lens_v.reshape(b).astype(jnp.int32)
        if has_rot and rotary_emb_dims > 0:
            # rotary_tensor [b, 1, 1, max_len, hd] (cos/sin packed per
            # reference); apply at the current position, GPT-NeoX or
            # interleaved style
            rot = rot_v[jnp.arange(b), 0, 0, pos]  # [b, hd]
            cos, sin = rot[..., : hd // 2], rot[..., hd // 2:]

            def rope(t):
                if use_neox_rotary_style:
                    # half-split rotation (GPT-NeoX)
                    t1, t2 = t[..., : hd // 2], t[..., hd // 2:]
                    return jnp.concatenate(
                        [t1 * cos[:, None] - t2 * sin[:, None],
                         t2 * cos[:, None] + t1 * sin[:, None]], -1)
                # interleaved even/odd pairing (GPT-J / reference default)
                t1, t2 = t[..., 0::2], t[..., 1::2]
                out = jnp.stack(
                    [t1 * cos[:, None] - t2 * sin[:, None],
                     t2 * cos[:, None] + t1 * sin[:, None]], axis=-1)
                return out.reshape(t.shape)

            q = rope(q)
            k_new = rope(k_new)
        # write k/v at pos
        bidx = jnp.arange(b)
        cache_k = cache[0].at[bidx, :, pos].set(k_new)
        cache_v = cache[1].at[bidx, :, pos].set(v_new)
        # attend over [0, pos]
        scores = jnp.einsum("bnd,bnld->bnl", q, cache_k) / _m.sqrt(hd)
        valid = jnp.arange(max_len)[None, None, :] <= pos[:, None, None]
        scores = jnp.where(valid, scores, -1e30)
        if mask_v is not None:
            scores = scores + mask_v.reshape(b, 1, -1)[:, :, :max_len]
        p = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bnl,bnld->bnd", p, cache_v)
        out = ctx.reshape(b, nh * hd)
        return out, jnp.stack([cache_k, cache_v])

    args = [x, cache_kv]
    for a in (bias, src_mask, sequence_lengths, rotary_tensor):
        if a is not None:
            args.append(a)
    return apply_op("masked_multihead_attention", fn, *args)


def _nh_from_cache(cache_kvs, i):
    """num_heads for the [embed_dim, 3*embed_dim] qkv layout — only the
    caches carry the head split there."""
    if cache_kvs is None:
        raise ValueError(
            "fused_multi_transformer: trans_qkvw=False needs cache_kvs to "
            "recover num_heads (the flat qkv weight does not carry it)")
    return cache_kvs[i].shape[2]


def fused_multi_transformer(x, ln_scales, ln_biases, qkv_weights, qkv_biases,
                            linear_weights, linear_biases, ffn_ln_scales,
                            ffn_ln_biases, ffn1_weights, ffn1_biases,
                            ffn2_weights, ffn2_biases, pre_layer_norm=True,
                            epsilon=1e-5, cache_kvs=None, pre_caches=None,
                            rotary_embs=None, rotary_emb_dims=0,
                            time_step=None, attn_mask=None,
                            dropout_rate=0.0, activation="gelu",
                            training=False, mode="upscale_in_train",
                            trans_qkvw=True, ring_id=-1, name=None):
    """The inference fast path: L fused decoder layers in one call
    (reference fused_transformer.py fused_multi_transformer). Composed
    from fused_multi_head_attention + fused_feedforward; cache_kvs (one
    [2, bsz, nh, len, hd] per layer) are updated and returned when given."""
    if pre_caches is not None or rotary_embs is not None:
        raise NotImplementedError(
            "fused_multi_transformer: pre_caches/rotary_embs are not "
            "wired on the TPU build yet (pass rotary via the model)")
    if not pre_layer_norm:
        raise NotImplementedError(
            "fused_multi_transformer: the reference only ships "
            "pre_layer_norm=True kernels; same here")
    out = x
    new_caches = []
    L = len(qkv_weights)
    for i in range(L):
        cache = cache_kvs[i] if cache_kvs is not None else None
        r = fused_multi_head_attention(
            out, qkv_weights[i], linear_weights[i], pre_layer_norm=True,
            pre_ln_scale=ln_scales[i],
            pre_ln_bias=ln_biases[i] if ln_biases is not None else None,
            qkv_bias=qkv_biases[i] if qkv_biases is not None else None,
            linear_bias=(linear_biases[i]
                         if linear_biases is not None else None),
            cache_kv=cache, attn_mask=attn_mask,
            dropout_rate=dropout_rate, attn_dropout_rate=dropout_rate,
            pre_ln_epsilon=epsilon, training=training, mode=mode,
            transpose_qkv_wb=not trans_qkvw,
            num_heads=(qkv_weights[i].shape[1] if trans_qkvw
                       else _nh_from_cache(cache_kvs, i)))
        if cache is not None:
            out, new_cache = r
            new_caches.append(new_cache)
        else:
            out = r
        out = fused_feedforward(
            out, ffn1_weights[i], ffn2_weights[i],
            linear1_bias=ffn1_biases[i] if ffn1_biases is not None else None,
            linear2_bias=ffn2_biases[i] if ffn2_biases is not None else None,
            ln1_scale=ffn_ln_scales[i],
            ln1_bias=ffn_ln_biases[i] if ffn_ln_biases is not None else None,
            dropout1_rate=dropout_rate, dropout2_rate=dropout_rate,
            activation=activation, ln1_epsilon=epsilon,
            pre_layer_norm=True, training=training, mode=mode)
    if cache_kvs is not None:
        return out, new_caches
    return out


def fused_ec_moe(x, gate, bmm0_weight, bmm0_bias, bmm1_weight, bmm1_bias,
                 act_type):
    """Expert-choice MoE: every token is routed through EVERY expert's
    FFN weighted by the softmax gate (reference fused_ec_moe.py:18 — the
    sm75+ fused kernel computes exactly this dense mixture). Weights
    [e, d_model, d_ff] / [e, d_ff, d_model] per the reference layout."""
    if act_type not in ("gelu", "relu"):
        raise ValueError(f"fused_ec_moe: act_type must be gelu|relu, got "
                         f"{act_type!r}")

    def fn(xv, g, w0, b0, w1, b1):
        probs = jax.nn.softmax(g, axis=-1)              # [b, s, e]
        h = jnp.einsum("bsd,edf->bsef", xv, w0) + b0[:, 0]
        h = jax.nn.gelu(h) if act_type == "gelu" else jax.nn.relu(h)
        eo = jnp.einsum("bsef,efd->bsed", h, w1) + b1[:, 0]
        return jnp.einsum("bsed,bse->bsd", eo, probs)

    return apply_op("fused_ec_moe", fn, x, gate, bmm0_weight, bmm0_bias,
                    bmm1_weight, bmm1_bias)


def fused_gate_attention(query, key=None, query_weight=None, key_weight=None,
                         value_weight=None, qkv_weight=None,
                         gate_linear_weight=None, gate_linear_bias=None,
                         out_linear_weight=None, out_linear_bias=None,
                         nonbatched_bias=None, attn_mask=None,
                         has_gating=True, merge_qkv=True,
                         use_flash_attn=False):
    """AlphaFold-style gated attention over [b, msa, res, dim] inputs
    (reference fused_gate_attention.py:19; einsum pseudo-code in its
    docstring is the contract implemented here)."""
    if merge_qkv and qkv_weight is None:
        raise ValueError("fused_gate_attention: merge_qkv=True needs "
                         "qkv_weight")
    if merge_qkv and key is not None:
        raise ValueError(
            "fused_gate_attention: merge_qkv=True is self-attention — "
            "pass key=None (a distinct key needs merge_qkv=False)")
    if not merge_qkv and any(
            w is None for w in (query_weight, key_weight, value_weight)):
        raise ValueError("fused_gate_attention: merge_qkv=False needs "
                         "query_weight, key_weight and value_weight")
    if has_gating and (gate_linear_weight is None
                      or gate_linear_bias is None):
        raise ValueError("fused_gate_attention: has_gating=True needs "
                         "gate_linear_weight and gate_linear_bias")
    if out_linear_weight is None:
        raise ValueError("fused_gate_attention: out_linear_weight is "
                         "required")
    has_key = key is not None
    has_mask = attn_mask is not None
    has_nb = nonbatched_bias is not None
    has_ob = out_linear_bias is not None

    def fn(*args):
        it = iter(args)
        q_data = next(it)
        m_data = next(it) if has_key else q_data
        if merge_qkv:
            qkv_w = next(it)  # [3, h, d, a]: contract over a
            q3 = jnp.einsum("nbqa,chda->cnbqhd", q_data, qkv_w)
            q, k, v = q3[0], q3[1], q3[2]
        else:
            qw, kw, vw = next(it), next(it), next(it)
            q = jnp.einsum("nbqa,ahc->nbqhc", q_data, qw)
            k = jnp.einsum("nbka,ahc->nbkhc", m_data, kw)
            v = jnp.einsum("nbka,ahc->nbkhc", m_data, vw)
        hd = q.shape[-1]
        q = q * (hd ** -0.5)
        logits = jnp.einsum("nbqhc,nbkhc->nbhqk", q, k)
        if has_mask:
            logits = logits + next(it)
        if has_nb:
            nb = next(it)  # [n, h, q, k] (or already [n, 1, h, q, k])
            logits = logits + (nb if nb.ndim == 5 else nb[:, None])
        w = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("nbhqk,nbkhc->nbqhc", w, v)
        if has_gating:
            gw, gb = next(it), next(it)
            gate = jax.nn.sigmoid(
                jnp.einsum("nbqa,ahc->nbqhc", q_data, gw) + gb)
            out = out * gate
        ow = next(it)
        res = jnp.einsum("nbqhc,hco->nbqo", out, ow)
        if has_ob:
            res = res + next(it)
        return res

    args = [query]
    if has_key:
        args.append(key)
    if merge_qkv:
        args.append(qkv_weight)
    else:
        args += [query_weight, key_weight, value_weight]
    if has_mask:
        args.append(attn_mask)
    if has_nb:
        args.append(nonbatched_bias)
    if has_gating:
        args += [gate_linear_weight, gate_linear_bias]
    args.append(out_linear_weight)
    if has_ob:
        args.append(out_linear_bias)
    return apply_op("fused_gate_attention", fn, *args)


def block_multihead_attention(qkv, key_cache, value_cache, seq_lens_encoder,
                              seq_lens_decoder, seq_lens_this_time,
                              padding_offsets, cum_offsets, cu_seqlens_q,
                              cu_seqlens_k, block_tables, pre_key_cache=None,
                              pre_value_cache=None, cache_k_quant_scales=None,
                              cache_v_quant_scales=None,
                              cache_k_dequant_scales=None,
                              cache_v_dequant_scales=None, qkv_out_scale=None,
                              qkv_bias=None, out_shift=None, out_smooth=None,
                              max_enc_len_this_time=None,
                              max_dec_len_this_time=None, rope_emb=None,
                              mask=None, tgt_mask=None, max_seq_len=-1,
                              block_size=64, use_neox_style=False):
    """Paged-KV attention for serving batches (reference
    block_multihead_attention — the vLLM-style paged kernel). TPU-native
    form: the per-sequence block table gathers the paged cache into a
    contiguous view (one XLA gather), then masked attention runs per
    sequence; decode steps append at ``seq_lens_decoder``. The int8
    cache-quant arguments are not supported."""
    if any(a is not None for a in (cache_k_quant_scales, cache_v_quant_scales,
                                   cache_k_dequant_scales,
                                   cache_v_dequant_scales, qkv_out_scale,
                                   out_shift, out_smooth, pre_key_cache,
                                   pre_value_cache)):
        raise NotImplementedError(
            "block_multihead_attention: int8 cache quantization / "
            "pre-caches are not supported on the TPU build")
    if rope_emb is not None or mask is not None or tgt_mask is not None:
        raise NotImplementedError(
            "block_multihead_attention: in-kernel rope_emb/mask/tgt_mask "
            "are not supported on the TPU build — apply rotary before the "
            "call (silently skipping them would corrupt every decode)")
    import math as _m

    import numpy as _np

    nh = key_cache.shape[1]
    hd = key_cache.shape[3]
    # the TPU build handles the uniform-batch packing only: validate
    # EAGERLY against seq_lens_this_time rather than misassigning tokens
    lens_np = _np.asarray(
        seq_lens_this_time._data if hasattr(seq_lens_this_time, "_data")
        else seq_lens_this_time)
    if lens_np.size and not (lens_np == lens_np.reshape(-1)[0]).all():
        raise NotImplementedError(
            "block_multihead_attention: varlen-packed batches (unequal "
            "seq_lens_this_time) are not supported on the TPU build")
    bsz_bt = (block_tables.shape[0] if hasattr(block_tables, "shape")
              else len(block_tables))
    s_decl = int(lens_np.reshape(-1)[0]) if lens_np.size else 0
    tok = qkv.shape[0]
    if s_decl and tok != bsz_bt * s_decl:
        raise ValueError(
            f"block_multihead_attention: qkv packs {tok} tokens but "
            f"seq_lens_this_time declares {s_decl} per sequence x "
            f"{bsz_bt} sequences = {bsz_bt * s_decl}")
    has_qkv_bias = qkv_bias is not None

    def fn(qkv_v, kc, vc, enc_lens, dec_lens, this_lens, bt, *rest):
        bias_v = rest[0] if has_qkv_bias else None
        # qkv_v: [token_num, 3*nh*hd] varlen-packed; this build handles the
        # uniform-batch layout (token_num = bsz * s_this_time)
        bsz = bt.shape[0]
        s = qkv_v.shape[0] // bsz
        q3 = qkv_v.reshape(bsz, s, 3, nh, hd)
        if bias_v is not None:
            q3 = q3 + bias_v.reshape(1, 1, 3, nh, hd)
        q, k_new, v_new = q3[:, :, 0], q3[:, :, 1], q3[:, :, 2]
        # gather each sequence's paged cache into a contiguous view
        max_blocks = bt.shape[1]
        bt_safe = jnp.clip(bt, 0, kc.shape[0] - 1)
        k_pages = kc[bt_safe]          # [bsz, max_blocks, nh, bs, hd]
        v_pages = vc[bt_safe]
        k_lin = k_pages.transpose(0, 2, 1, 3, 4).reshape(
            bsz, nh, max_blocks * block_size, hd)
        v_lin = v_pages.transpose(0, 2, 1, 3, 4).reshape(
            bsz, nh, max_blocks * block_size, hd)
        past = dec_lens.reshape(bsz)  # decode: tokens already cached
        # append the new tokens after the cached prefix
        pos = past[:, None] + jnp.arange(s)[None, :]        # [bsz, s]
        bidx = jnp.arange(bsz)[:, None]
        # separated advanced indices put the broadcast dims first: the
        # selected shape is [bsz, s, nh, hd], matching k_new/v_new
        k_lin = k_lin.at[bidx, :, pos].set(k_new)
        v_lin = v_lin.at[bidx, :, pos].set(v_new)
        total = past + s
        scores = jnp.einsum("bqnd,bnld->bnql",
                            q, k_lin) / _m.sqrt(hd)
        l_ids = jnp.arange(k_lin.shape[2])
        valid = l_ids[None, None, None, :] < total[:, None, None, None]
        causal = (l_ids[None, None, None, :]
                  <= pos[:, None, :, None])
        scores = jnp.where(valid & causal, scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bnql,bnld->bqnd", p, v_lin)
        out = ctx.reshape(bsz * s, nh * hd)
        # write the updated pages back (scatter the linear view into pages)
        k_pages_new = k_lin.reshape(
            bsz, nh, max_blocks, block_size, hd).transpose(0, 2, 1, 3, 4)
        v_pages_new = v_lin.reshape(
            bsz, nh, max_blocks, block_size, hd).transpose(0, 2, 1, 3, 4)
        # padding block-table entries (< 0) must NOT write back: their
        # gathered copy of block 0 is stale, and duplicate scatter indices
        # are nondeterministic — route them out of bounds and drop
        bt_write = jnp.where(bt >= 0, bt, kc.shape[0])
        kc_new = kc.at[bt_write].set(k_pages_new, mode="drop")
        vc_new = vc.at[bt_write].set(v_pages_new, mode="drop")
        return out, kc_new, vc_new

    args = [qkv, key_cache, value_cache, seq_lens_encoder, seq_lens_decoder,
            seq_lens_this_time, block_tables]
    if qkv_bias is not None:
        args.append(qkv_bias)
    return apply_op("block_multihead_attention", fn, *args)
