"""Persistent decode-layer megakernels — the round-16 mega-kernelized hot loop.

The unified serving step (PR 4-9) is ONE jit, but inside it every
transformer layer is still a CHAIN of separate kernels — quant GEMMs,
ragged paged attention, fused MLP — stitched by XLA, with each
intermediate activation round-tripping through HBM between them. At
decode geometry (chunk = 1 token per lane) those tensors are tiny, so
per-kernel dispatch overhead and the activation HBM traffic dominate
device time. Following MPK ("A Compiler and Runtime for Mega-Kernelizing
Tensor Programs", PAPERS.md) and the ragged-blocking discipline of Ragged
Paged Attention (PAPERS.md), this module fuses a FULL layer's decode path
into TWO persistent ``pallas_call``s with the activations pinned in VMEM:

- :func:`mega_attn_layer` — ONE kernel per layer covering
  ``LN1 -> QKV projection (fp or int8 tile-dequant via the quant_matmul
  BlockSpec scale-row machinery) -> inline int8 quantize of the new K/V
  token rows -> ragged paged attention over the block-paged pools (online
  softmax across pages + an in-register causal block over the lane's own
  new tokens) -> output GEMM (per-head partials accumulated in a VMEM-
  revisited block) -> residual add -> LN2``. Grid ``(batch, heads,
  pages)``: weights stream per-head through BlockSpec index maps, the
  activations (x block, softmax state, attention output, the cross-head
  output accumulator) never leave VMEM between stages.
- :func:`mega_mlp` — ONE kernel per layer covering
  ``GEMM1 (+dequant) -> bias + tanh-gelu -> GEMM2 (+dequant) -> residual
  + bias`` with the ffn dim streamed in autotuned ``bn`` tiles and the
  ``[tokens, hidden]`` activation resident across tiles; the 4h-wide
  hidden state NEVER materializes in HBM.

Round 22 generalizes both kernels to the MIXED ragged-chunk geometry:
a lane may feed any ``1..chunk`` new rows per step (``q_lens`` already
drove the per-row causal limits — the in-register new-token block IS
small-chunk prefill), so the unified step routes EVERY round here, not
just all-decode rounds. What stays XLA-stitched (by design, documented
in ARCHITECTURE.md rounds 16/22): the page-pool SCATTER of the
kernel-quantized new K/V rows (pure data movement the donated-buffer
scatter already does optimally — the quantization itself is fused, the
kernel emits int8 + scales), the embedding gather, and the sampling
epilogue.

Contracts shared with the sibling kernels: interpret mode off-TPU (the
CPU suite runs the real kernel bodies), jnp composed references
(:func:`mega_attn_layer_reference` / :func:`mega_mlp_reference`) as the
numerical oracle and the non-TPU fallback, ``(bm, bn, bk)`` geometry on
the shared ``autotune_cache`` (pages-per-block is pinned at 1: the page-
table BlockSpec indirection fetches exactly one pool page per grid step —
a multi-page block would need contiguous pages, which paging exists to
avoid). int4 weights are NOT served here (split-half nibble packing
interleaves the K rows the per-head tiles slice); ``validate_mega_config``
rejects them loudly and the per-op path keeps serving int4.

SPMD (round 22): the kernels compose with the fully-manual ``shard_map``
mp mesh. Head-sharded weight columns and KV pools are already
chip-local; the ONLY mp-sensitive piece was the fused epilogue (residual
add + LN2 / + b2), which must sit AFTER the row-parallel psum. Under
mp > 1 the caller passes ``fuse_epilogue=False``: the kernels emit the
pre-psum output-GEMM partial instead, and ``models/gpt.py`` completes
``psum -> +bias -> residual -> LN2`` with the exact per-op spelling —
one psum per kernel, the same two collectives per layer as the per-op
build. At mesh size 1/None the epilogue stays fused (bit-identical to
round 16).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import autotune_cache as _atc

NEG_INF = -1e30

_MXU = jax.lax.Precision.DEFAULT

# tanh-gelu constants (jax.nn.gelu approximate=True — the GPT activation)
_K0 = 0.7978845608028654  # sqrt(2/pi)
_A = 0.044715


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def use_kernel_default() -> bool:
    return jax.default_backend() == "tpu"


def _dotf32(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=_MXU)


def _ln_f32(x32, g, b, eps):
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    return (x32 - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_f32(u):
    return 0.5 * u * (1.0 + jnp.tanh(_K0 * (u + _A * u * u * u)))


def _deq(w_ref, s_ref, dtype):
    """Widen a weight tile and apply its scale rows: ``s_ref`` is None
    (fp weights), one broadcast row, or ``rows`` dividing the tile's K
    extent (repeated to cover it) — the quant_matmul scale-row contract."""
    w = w_ref[...].astype(jnp.float32)
    if s_ref is None:
        return w.astype(dtype)
    s = s_ref[...].astype(jnp.float32)
    if s.shape[0] not in (1, w.shape[0]):
        s = jnp.repeat(s, w.shape[0] // s.shape[0], axis=0)
    return (w * s).astype(dtype)


def _quantize_rows_f32(x32):
    """Per-row-per-head symmetric int8 — the EXACT
    ``kv_cache.paged_write_packed_quant`` formula, fused in-kernel so the
    new K/V token quantizes inline instead of in a separate XLA pass.
    x32: [rows, hd] fp32. Returns (q int8 [rows, hd], s fp32 [rows, 1])."""
    absmax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    s = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x32 / s), -127, 127).astype(jnp.int8)
    return q, s


# ---------------------------------------------------------------------------
# config validation (the build-time gate)
# ---------------------------------------------------------------------------


def validate_mega_config(weight_dtype, group_size, head_dim, mp=1,
                         moe_experts=0) -> None:
    """Reject geometries the megakernel cannot serve — callers fall back
    to (or stay on) the per-op path with a loud reason instead of
    silently computing something else. ``mp`` is accepted (and ignored)
    since round 22: mp > 1 serves through ``fuse_epilogue=False`` — the
    kernels emit pre-psum partials and the caller's shard_map completes
    the row-parallel reduction, so no mesh size is rejected here."""
    del mp  # round 22: every mp degree is servable (see the docstring)
    if moe_experts:
        raise ValueError(
            "mega_decode is dense-only: the fused MLP kernel has no "
            "routed-expert path (moe_experts="
            f"{moe_experts}) — serve MoE configs through the per-op "
            "unified step (mega_decode=False)")
    if weight_dtype == "int4":
        raise ValueError(
            "mega_decode does not serve int4 weights: split-half nibble "
            "packing interleaves the K rows the per-head wqkv/wo tiles "
            "slice — use weight_dtype='int8' (or the per-op int4 path)")
    if weight_dtype == "int8" and group_size and group_size > 0:
        if head_dim % group_size and group_size % head_dim:
            raise ValueError(
                f"mega_decode needs the weight scale group size "
                f"({group_size}) aligned with head_dim ({head_dim}): the "
                "per-head wo tile must see whole scale groups "
                "(head_dim % group == 0 or group % head_dim == 0)")


# ---------------------------------------------------------------------------
# weight views: per-head BlockSpec plumbing (the scale-row machinery)
# ---------------------------------------------------------------------------


def _split_wq(leaf):
    """(qweight-or-weight, scales-or-None) for a serving weight leaf."""
    if isinstance(leaf, dict):
        return leaf["q"], leaf["s"]
    return leaf, None


def _qkv_views(p, nh, hd, head_major):
    """wqkv reshaped so ONE BlockSpec index map slices a (component,
    head) column tile: eager layout orders columns [3, nh, hd]; the
    mesh layout is head-major [nh, 3, hd]."""
    w, s = _split_wq(p["wqkv"])
    h_in = w.shape[0]
    shape = (h_in, nh, 3, hd) if head_major else (h_in, 3, nh, hd)
    w4 = w.reshape(shape)
    s4 = s.reshape((s.shape[0],) + shape[1:]) if s is not None else None
    bshape = ((1, nh, 3, hd) if head_major else (1, 3, nh, hd))
    b4 = p["bqkv"].reshape(bshape)
    return w4, s4, b4


def _qkv_spec(h_in, hd, c, head_major):
    if head_major:
        return pl.BlockSpec((h_in, None, None, hd),
                            lambda bi, hh, j, *_: (0, hh, c, 0))
    return pl.BlockSpec((h_in, None, None, hd),
                        lambda bi, hh, j, *_: (0, c, hh, 0))


def _kdim_scale_view(s, k, tile, nh):
    """(view, spec) serving a K-sharded weight's scale rows per head tile
    (wo: K = h, tile = head_dim at offset head*tile). Three shapes:
    per-channel broadcast, multiple groups per tile (reshape so the head
    index IS the block index), or one group spanning tiles (index-map
    arithmetic selects the row)."""
    groups, n = s.shape
    if groups == 1:
        return s, pl.BlockSpec((1, n), lambda bi, hh, j, *_: (0, 0))
    gs = k // groups
    if tile % gs == 0:
        view = s.reshape(nh, tile // gs, n)
        return view, pl.BlockSpec((None, tile // gs, n),
                                  lambda bi, hh, j, *_: (hh, 0, 0))
    # gs % tile == 0 (validate_mega_config enforced): one row per tile
    step = gs // tile
    return s, pl.BlockSpec((1, n), lambda bi, hh, j, *_: (hh // step, 0))


# ---------------------------------------------------------------------------
# attention-side megakernel
# ---------------------------------------------------------------------------


def _mega_attn_kernel(ctx_ref, qlen_ref, pt_ref, *refs, page_size, scale,
                      eps, wq_quant, wo_quant, kv_quant, fuse_epilogue):
    """One (lane, head, page) grid step of the fused attention-side layer.

    Stage schedule (all state VMEM-resident across the grid):
    - ``j == 0``: LN1 + this head's QKV column tiles -> q rows saved, the
      new K/V rows quantized inline (int8 KV) and emitted;
    - every ``j``: one pool page through the online softmax (int8 pages'
      per-token scales fold into the dots' fp32 sides as [1, page_size]
      rows — the ragged_paged_attention spelling);
    - ``j == last``: the lane's own new tokens as an in-register causal
      block, then this head's rows of the output GEMM accumulate into the
      cross-head ``yacc`` block;
    - ``(head, j) == last``: residual add + LN2 epilogue emits (y2, s).
    """
    it = iter(refs)
    x_ref = next(it)
    g1_ref, b1g_ref, g2_ref, b2g_ref = (next(it) for _ in range(4))
    wq_ref, wk_ref, wv_ref = (next(it) for _ in range(3))
    sq_ref = sk_ref = sv_ref = None
    if wq_quant:
        sq_ref, sk_ref, sv_ref = (next(it) for _ in range(3))
    bq_ref, bk_ref, bv_ref = (next(it) for _ in range(3))
    wo_ref = next(it)
    so_ref = next(it) if wo_quant else None
    bo_ref = next(it)
    k_ref, v_ref = next(it), next(it)
    ks_ref = vs_ref = None
    if kv_quant:
        ks_ref, vs_ref = next(it), next(it)
    y2_ref, s_ref = next(it), next(it)
    ko_ref, vo_ref = next(it), next(it)
    kso_ref = vso_ref = None
    if kv_quant:
        kso_ref, vso_ref = next(it), next(it)
    yacc_ref, q_ref, m_ref, l_ref, o_ref = (next(it) for _ in range(5))

    b = pl.program_id(0)
    hh = pl.program_id(1)
    j = pl.program_id(2)
    hkv = pl.num_programs(1)
    pps = pl.num_programs(2)
    ctx = ctx_ref[b]       # context length BEFORE this step's tokens
    q_len = qlen_ref[b]    # valid new rows this step (0 = idle lane)
    dtype = x_ref.dtype

    @pl.when((hh == 0) & (j == 0))
    def _init_lane():
        yacc_ref[...] = jnp.zeros_like(yacc_ref)

    @pl.when(j == 0)
    def _init_head():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when((j == 0) & (q_len > 0))
    def _qkv():
        # LN1 + this head's QKV column tiles; rows past q_len are padding
        # whose garbage nothing downstream reads (their K/V scatter drops)
        x32 = x_ref[...].astype(jnp.float32)
        y1 = _ln_f32(x32, g1_ref[...].astype(jnp.float32),
                     b1g_ref[...].astype(jnp.float32), eps).astype(dtype)
        dims = ((1,), (0,))
        q = (_dotf32(y1, _deq(wq_ref, sq_ref, dtype), dims)
             + bq_ref[...].astype(jnp.float32))
        k_new = (_dotf32(y1, _deq(wk_ref, sk_ref, dtype), dims)
                 + bk_ref[...].astype(jnp.float32))
        v_new = (_dotf32(y1, _deq(wv_ref, sv_ref, dtype), dims)
                 + bv_ref[...].astype(jnp.float32))
        q_ref[...] = q.astype(dtype)
        if kv_quant:
            kq, ks = _quantize_rows_f32(k_new)
            vq, vs = _quantize_rows_f32(v_new)
            ko_ref[...] = kq
            vo_ref[...] = vq
            kso_ref[...] = ks
            vso_ref[...] = vs
        else:
            ko_ref[...] = k_new.astype(dtype)
            vo_ref[...] = v_new.astype(dtype)

    @pl.when((j * page_size < ctx) & (q_len > 0))
    def _pages():
        # one pool page through the online softmax (every new row attends
        # the WHOLE prior context — per-row limits only exist inside the
        # new-token block below)
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        if kv_quant:
            k = k.astype(q.dtype)
            v = v.astype(q.dtype)
        s = _dotf32(q, k, ((1,), (1,))) * scale          # [C8, ps] f32
        if kv_quant:
            s = s * ks_ref[pl.ds(hh, 1), :]
        col = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(col < ctx, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        l_next = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        l_safe = jnp.where(l_next == 0.0, 1.0, l_next)
        pw = p * vs_ref[pl.ds(hh, 1), :] if kv_quant else p
        pv = _dotf32(pw.astype(v.dtype), v, ((1,), (0,)))
        o_ref[...] = ((o_ref[...] * (l_prev * alpha) + pv) / l_safe
                      ).astype(o_ref.dtype)
        m_ref[...] = m_next
        l_ref[...] = l_next

    @pl.when((j == pps - 1) & (q_len > 0))
    def _new_block():
        # the lane's OWN new tokens, still in VMEM: row i attends new
        # col c while c <= i (causal within the chunk — exactly the spec
        # verify-row semantics) and c < q_len. int8 KV attends the
        # quantize-dequantize image, matching what later steps will read
        # back from the pool.
        q = q_ref[...]
        kd = ko_ref[...]
        vd = vo_ref[...]
        if kv_quant:
            kd = (kd.astype(jnp.float32) * kso_ref[...]).astype(q.dtype)
            vd = (vd.astype(jnp.float32) * vso_ref[...]).astype(q.dtype)
        s = _dotf32(q, kd, ((1,), (1,))) * scale         # [C8, C8]
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((col <= row) & (col < q_len), s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        l_next = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        l_safe = jnp.where(l_next == 0.0, 1.0, l_next)
        pv = _dotf32(p.astype(vd.dtype), vd, ((1,), (0,)))
        o_ref[...] = ((o_ref[...] * (l_prev * alpha) + pv) / l_safe
                      ).astype(o_ref.dtype)
        m_ref[...] = m_next
        l_ref[...] = l_next

    @pl.when(j == pps - 1)
    def _out_gemm():
        # this head's rows of the output GEMM: o [C8, hd] against wo's
        # [hd, h] row band, accumulated into the cross-head yacc block
        # (idle lanes accumulate zeros — o is init-zero)
        wo_t = _deq(wo_ref, so_ref, dtype)
        yacc_ref[...] += _dotf32(o_ref[...].astype(dtype), wo_t,
                                 ((1,), (0,)))

    @pl.when((hh == hkv - 1) & (j == pps - 1))
    def _epilogue():
        # residual + LN2, still in VMEM: s = x + attn + bo; y2 = LN2(s).
        # s round-trips through the storage dtype before the LN read so
        # the statistics match the per-op path's (which LNs the STORED
        # residual stream). Under mp > 1 (fuse_epilogue=False) the
        # residual/bias/LN2 must sit AFTER the row-parallel psum, so the
        # kernel emits the raw output-GEMM partial instead and the
        # shard_map caller completes the epilogue post-reduction.
        if fuse_epilogue:
            x32 = x_ref[...].astype(jnp.float32)
            s_out = x32 + yacc_ref[...] + bo_ref[...].astype(jnp.float32)
            s_ref[...] = s_out.astype(dtype)
            s32 = s_ref[...].astype(jnp.float32)
            y2 = _ln_f32(s32, g2_ref[...].astype(jnp.float32),
                         b2g_ref[...].astype(jnp.float32), eps)
            y2_ref[...] = y2.astype(dtype)
        else:
            y2_ref[...] = yacc_ref[...].astype(dtype)
            s_ref[...] = jnp.zeros_like(s_ref)


def mega_attn_layer(xb, p, k_pages, v_pages, page_table, ctx_lens, q_lens,
                    *, eps=1e-5, k_scales=None, v_scales=None,
                    head_major=False, use_kernel=None, fuse_epilogue=True):
    """The fused attention-side decode layer over ragged chunk blocks.

    xb: [b, chunk, h] per-lane token blocks (``q_lens[b]`` valid rows —
    any 1..chunk per lane, so mixed prefill+decode rounds serve here);
    p: ONE layer's serving weight dict (``_SRV_LAYER_WEIGHTS`` keys; wqkv
    /wo may be quantized ``{"q", "s"}`` stacks); pages/scales/page_table/
    ctx_lens as in ``ragged_paged_attention`` — ``ctx_lens`` counts
    tokens ALREADY IN THE POOL (this step's tokens are handled
    in-register and emitted for the caller's scatter). Returns
    ``(y2, s, k_new, v_new)`` — y2/s ``[b, chunk, h]`` (LN2 output and
    the residual stream), k_new/v_new ``[b, chunk, kv_heads, head_dim]``
    — plus ``(k_sc, v_sc)`` ``[b, chunk, kv_heads]`` scale rows when the
    pools are int8 (k_new/v_new are then the int8 payloads, quantized
    inline with the ``paged_write_packed_quant`` formula).

    ``fuse_epilogue=False`` (the mp > 1 spelling, round 22): the
    residual add + bo + LN2 must follow the caller's row-parallel psum,
    so the return drops (y2, s) in favor of the single pre-psum partial:
    ``(y_part, k_new, v_new[, k_sc, v_sc])`` with y_part ``[b, chunk,
    h]`` = (this shard's heads' attention output) @ wo — NO residual,
    NO bias, NO LN. Head-sharded callers pass their LOCAL wqkv/wo
    columns and head-sharded pools; q/kv head count derives from the
    pool's head axis.

    ``use_kernel``: None = kernel on TPU / composed jnp reference
    elsewhere; True forces the kernel (interpret off-TPU); False forces
    :func:`mega_attn_layer_reference`.
    """
    if use_kernel is None:
        use_kernel = use_kernel_default()
    if not use_kernel:
        return mega_attn_layer_reference(
            xb, p, k_pages, v_pages, page_table, ctx_lens, q_lens,
            eps=eps, k_scales=k_scales, v_scales=v_scales,
            head_major=head_major, fuse_epilogue=fuse_epilogue)
    b, chunk, h = xb.shape
    num_pages, hkv, page_size, hd = k_pages.shape
    # group-1 attention per shard: q heads == kv heads. The pool's head
    # axis is authoritative — under the mp mesh it carries this shard's
    # LOCAL heads while xb keeps the full (replicated) hidden width.
    nh = hkv
    assert _split_wq(p["wqkv"])[0].shape[1] == 3 * nh * hd, (
        f"mega_attn_layer: wqkv columns "
        f"{_split_wq(p['wqkv'])[0].shape[1]} do not match the pool's "
        f"{nh} heads x {hd} head_dim (group-1: q heads == kv heads)")
    kv_quant = k_scales is not None
    wq, sq, bq4 = _qkv_views(p, nh, hd, head_major)
    wo, so = _split_wq(p["wo"])
    wo3 = wo.reshape(nh, hd, h)
    scale = 1.0 / math.sqrt(hd)
    c8 = max(8, ((chunk + 7) // 8) * 8)
    if c8 != chunk:
        xb = jnp.pad(xb, ((0, 0), (0, c8 - chunk), (0, 0)))
    h_in = wq.shape[0]
    dtype = xb.dtype

    row = lambda: pl.BlockSpec((1, h), lambda bi, hh, j, *_: (0, 0))  # noqa: E731
    lane = pl.BlockSpec((None, c8, h), lambda bi, hh, j, *_: (bi, 0, 0))

    def kv_page(bi, hh, j, ctx_ref, qlen_ref, pt_ref):
        # pages past the last context page re-fetch it (compute skipped);
        # empty/unallocated entries clamp to page 0 — the paged_attention
        # clamping discipline
        ps = jnp.int32(page_size)
        last = jnp.maximum(
            jax.lax.div(ctx_ref[bi] + ps - jnp.int32(1), ps) - jnp.int32(1),
            jnp.int32(0))
        page = pt_ref[bi, jnp.minimum(jnp.int32(j), last)]
        return jnp.clip(page, 0, num_pages - 1)

    kv_spec = pl.BlockSpec((None, None, page_size, hd),
                           lambda bi, hh, j, *r: (kv_page(bi, hh, j, *r),
                                                  hh, 0, 0))
    # all local heads' scale rows of the page (the plane's full last two
    # dims); the kernel slices its own head's [1, page_size] row
    sc_spec = pl.BlockSpec((None, hkv, page_size),
                           lambda bi, hh, j, *r: (kv_page(bi, hh, j, *r),
                                                  0, 0))
    head_rows = pl.BlockSpec((None, c8, hd),
                             lambda bi, hh, j, *_: (bi, 0, 0))

    in_specs = [lane, row(), row(), row(), row()]
    args = [xb, p["ln1_g"].reshape(1, h), p["ln1_b"].reshape(1, h),
            p["ln2_g"].reshape(1, h), p["ln2_b"].reshape(1, h)]
    in_specs += [_qkv_spec(h_in, hd, c, head_major) for c in range(3)]
    args += [wq, wq, wq]
    if sq is not None:
        g_rows = sq.shape[0]
        in_specs += [pl.BlockSpec(
            (g_rows,) + _qkv_spec(h_in, hd, c, head_major).block_shape[1:],
            _qkv_spec(h_in, hd, c, head_major).index_map)
            for c in range(3)]
        args += [sq, sq, sq]
    in_specs += [_qkv_spec(1, hd, c, head_major) for c in range(3)]
    args += [bq4, bq4, bq4]
    in_specs += [pl.BlockSpec((None, hd, h),
                              lambda bi, hh, j, *_: (hh, 0, 0))]
    args += [wo3]
    if so is not None:
        so_view, so_spec = _kdim_scale_view(so, h, hd, nh)
        in_specs += [so_spec]
        args += [so_view]
    in_specs += [row()]
    args += [p["bo"].reshape(1, h)]
    in_specs += [kv_spec, kv_spec]
    args += [k_pages, v_pages]
    if kv_quant:
        in_specs += [sc_spec, sc_spec]
        args += [k_scales.astype(jnp.float32), v_scales.astype(jnp.float32)]

    kv_out_dtype = jnp.int8 if kv_quant else dtype
    out_specs = [lane, lane, head_rows, head_rows]
    out_shape = [
        jax.ShapeDtypeStruct((b, c8, h), dtype),           # y2
        jax.ShapeDtypeStruct((b, c8, h), dtype),           # s
        jax.ShapeDtypeStruct((b, hkv, c8, hd), kv_out_dtype),
        jax.ShapeDtypeStruct((b, hkv, c8, hd), kv_out_dtype),
    ]
    ko_spec = pl.BlockSpec((None, None, c8, hd),
                           lambda bi, hh, j, *_: (bi, hh, 0, 0))
    out_specs[2] = out_specs[3] = ko_spec
    if kv_quant:
        ksc_spec = pl.BlockSpec((None, None, c8, 1),
                                lambda bi, hh, j, *_: (bi, hh, 0, 0))
        out_specs += [ksc_spec, ksc_spec]
        out_shape += [jax.ShapeDtypeStruct((b, hkv, c8, 1), jnp.float32)] * 2
    # VMEM-revisited stages: the cross-head output accumulator, this
    # head's q rows, and the online-softmax state — dropped by the caller
    out_specs += [lane,
                  ko_spec,
                  pl.BlockSpec((None, None, c8, 1),
                               lambda bi, hh, j, *_: (bi, hh, 0, 0)),
                  pl.BlockSpec((None, None, c8, 1),
                               lambda bi, hh, j, *_: (bi, hh, 0, 0)),
                  ko_spec]
    out_shape += [
        jax.ShapeDtypeStruct((b, c8, h), jnp.float32),          # yacc
        jax.ShapeDtypeStruct((b, hkv, c8, hd), dtype),          # q tmp
        jax.ShapeDtypeStruct((b, hkv, c8, 1), jnp.float32),     # m
        jax.ShapeDtypeStruct((b, hkv, c8, 1), jnp.float32),     # l
        jax.ShapeDtypeStruct((b, hkv, c8, hd), jnp.float32),    # o
    ]

    pps = page_table.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv, pps),
        in_specs=in_specs,
        out_specs=out_specs,
    )
    kern = functools.partial(
        _mega_attn_kernel, page_size=page_size, scale=scale,
        eps=float(eps), wq_quant=sq is not None, wo_quant=so is not None,
        kv_quant=kv_quant, fuse_epilogue=fuse_epilogue)
    with _atc.x64_off():
        outs = pl.pallas_call(
            kern, grid_spec=grid_spec, out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary")),
            interpret=_interpret(),
        )(ctx_lens.astype(jnp.int32), q_lens.astype(jnp.int32),
          page_table.astype(jnp.int32), *args)
    y2, s = outs[0][:, :chunk], outs[1][:, :chunk]
    k_new = outs[2].transpose(0, 2, 1, 3)[:, :chunk]   # [b, chunk, hkv, hd]
    v_new = outs[3].transpose(0, 2, 1, 3)[:, :chunk]
    if kv_quant:
        k_sc = outs[4][..., 0].transpose(0, 2, 1)[:, :chunk]
        v_sc = outs[5][..., 0].transpose(0, 2, 1)[:, :chunk]
        if not fuse_epilogue:
            return y2, k_new, v_new, k_sc, v_sc   # y2 slot = y_part
        return y2, s, k_new, v_new, k_sc, v_sc
    if not fuse_epilogue:
        return y2, k_new, v_new
    return y2, s, k_new, v_new


def mega_attn_layer_reference(xb, p, k_pages, v_pages, page_table,
                              ctx_lens, q_lens, *, eps=1e-5, k_scales=None,
                              v_scales=None, head_major=False,
                              fuse_epilogue=True):
    """Composed jnp oracle for :func:`mega_attn_layer`: the existing
    per-op references (dequant matmul, gathered paged attention with the
    in-register new-token semantics, LN) chained in the megakernel's
    exact stage order — the numerical golden AND the non-TPU fallback.
    ``fuse_epilogue=False`` mirrors the kernel's mp spelling: the return
    is the pre-psum output-GEMM partial (no residual/bias/LN2)."""
    from .paged_attention import gather_pages
    from .quant_matmul import dequantize_weight

    b, chunk, h = xb.shape
    num_pages, hkv, page_size, hd = k_pages.shape
    nh = hkv   # pool head axis is authoritative (head-sharded under mp)
    kv_quant = k_scales is not None
    dtype = xb.dtype

    def mm(y, leaf):
        if isinstance(leaf, dict):
            w = dequantize_weight(leaf["q"], leaf["s"],
                                  out_dtype=jnp.float32).astype(dtype)
        else:
            w = leaf
        return y @ w

    x32 = xb.astype(jnp.float32)
    y1 = _ln_f32(x32, p["ln1_g"].astype(jnp.float32),
                 p["ln1_b"].astype(jnp.float32), eps).astype(dtype)
    qkv = mm(y1, p["wqkv"]) + p["bqkv"]                  # [b, c, 3h]
    if head_major:
        q4 = qkv.reshape(b, chunk, nh, 3, hd)
        q, k_new, v_new = q4[..., 0, :], q4[..., 1, :], q4[..., 2, :]
    else:
        q4 = qkv.reshape(b, chunk, 3, nh, hd)
        q, k_new, v_new = (q4[:, :, 0], q4[:, :, 1], q4[:, :, 2])
    q = q.astype(jnp.float32)
    kf, vf = k_new.astype(jnp.float32), v_new.astype(jnp.float32)
    if kv_quant:
        k_q, k_sc = _quantize_rows_f32(kf.reshape(-1, hd))
        v_q, v_sc = _quantize_rows_f32(vf.reshape(-1, hd))
        k_emit = k_q.reshape(b, chunk, hkv, hd)
        v_emit = v_q.reshape(b, chunk, hkv, hd)
        k_scr = k_sc.reshape(b, chunk, hkv)
        v_scr = v_sc.reshape(b, chunk, hkv)
        # attend the quantize-dequantize image — what later steps read
        kf = k_emit.astype(jnp.float32) * k_scr[..., None]
        vf = v_emit.astype(jnp.float32) * v_scr[..., None]
    else:
        k_emit, v_emit = k_new.astype(dtype), v_new.astype(dtype)
    # gathered context (dequantized when the pools are int8)
    pt = jnp.clip(page_table, 0, num_pages - 1)
    pps = page_table.shape[1]
    kc = gather_pages(k_pages, pt)
    vc = gather_pages(v_pages, pt)
    if kv_quant:
        kc = kc.astype(jnp.float32) * gather_pages(k_scales, pt)[..., None]
        vc = vc.astype(jnp.float32) * gather_pages(v_scales, pt)[..., None]
    kc, vc = kc.astype(jnp.float32), vc.astype(jnp.float32)
    scale = 1.0 / math.sqrt(hd)
    s_ctx = jnp.einsum("bcnd,bsnd->bncs", q, kc, precision=_MXU) * scale
    s_new = jnp.einsum("bcnd,bknd->bnck", q, kf, precision=_MXU) * scale
    col = jnp.arange(pps * page_size)[None, None, None, :]
    rowi = jnp.arange(chunk).reshape(1, 1, -1, 1)
    valid_ctx = ((col < ctx_lens.reshape(-1, 1, 1, 1))
                 & (rowi < q_lens.reshape(-1, 1, 1, 1)))
    colk = jnp.arange(chunk)[None, None, None, :]
    valid_new = ((colk <= rowi) & (colk < q_lens.reshape(-1, 1, 1, 1))
                 & (rowi < q_lens.reshape(-1, 1, 1, 1)))
    s_all = jnp.concatenate(
        [jnp.where(valid_ctx, s_ctx, NEG_INF),
         jnp.where(valid_new, s_new, NEG_INF)], axis=-1)
    pr = jax.nn.softmax(s_all, axis=-1)
    valid_any = jnp.concatenate(
        [jnp.broadcast_to(valid_ctx, s_ctx.shape),
         jnp.broadcast_to(valid_new, s_new.shape)], axis=-1)
    pr = jnp.where(valid_any, pr, 0.0)
    v_all = jnp.concatenate([vc, vf.astype(jnp.float32)], axis=1)
    o = jnp.einsum("bncs,bsnd->bcnd", pr, v_all, precision=_MXU)
    a = o.reshape(b, chunk, nh * hd).astype(dtype)
    if not fuse_epilogue:
        # the mp spelling: emit this shard's pre-psum partial; the caller
        # completes psum -> +bo -> residual -> LN2 with the per-op math
        y_part = mm(a, p["wo"]).astype(dtype)
        if kv_quant:
            return y_part, k_emit, v_emit, k_scr, v_scr
        return y_part, k_emit, v_emit
    s_out32 = (xb.astype(jnp.float32)
               + mm(a, p["wo"]).astype(jnp.float32)
               + p["bo"].astype(jnp.float32))
    s_out = s_out32.astype(dtype)
    y2 = _ln_f32(s_out.astype(jnp.float32),
                 p["ln2_g"].astype(jnp.float32),
                 p["ln2_b"].astype(jnp.float32), eps).astype(dtype)
    if kv_quant:
        return y2, s_out, k_emit, v_emit, k_scr, v_scr
    return y2, s_out, k_emit, v_emit


# ---------------------------------------------------------------------------
# MLP-side megakernel
# ---------------------------------------------------------------------------


def _mega_mlp_kernel(y2_ref, s_res_ref, w1_ref, b1_ref, w2_ref, b2_ref,
                     *refs, wq_quant, fuse_epilogue):
    """One ffn tile of the fused MLP: GEMM1 column tile -> bias + tanh
    gelu -> GEMM2 row tile, accumulated into the residual-initialized
    output block (zero-initialized under ``fuse_epilogue=False`` — the
    mp caller adds residual + b2 after its psum). The [rows, 4h] hidden
    state lives only in VMEM."""
    if wq_quant:
        s1_ref, s2_ref, o_ref = refs
    else:
        (o_ref,) = refs
        s1_ref = s2_ref = None
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        if fuse_epilogue:
            o_ref[...] = (s_res_ref[...].astype(jnp.float32)
                          + b2_ref[...].astype(jnp.float32))
        else:
            o_ref[...] = jnp.zeros_like(o_ref)

    y2 = y2_ref[...]
    w1 = _deq(w1_ref, s1_ref, y2.dtype)
    u = _dotf32(y2, w1, ((1,), (0,))) + b1_ref[...].astype(jnp.float32)
    g = _gelu_f32(u).astype(y2.dtype)
    w2 = _deq(w2_ref, s2_ref, y2.dtype)
    o_ref[...] += _dotf32(g, w2, ((1,), (0,)))


BM_DEFAULT = 64
BN_DEFAULT = 512


def _mega_sig(h, f, dtype, chunk=1) -> str:
    """The autotune-cache key for a mega layer geometry. ``chunk`` (round
    22) keys the MIXED ragged-chunk geometry: a chunk-c step runs c times
    the token rows of the decode-only step, so its winning ffn tile can
    differ — the legacy ``chunk == 1`` spelling stays byte-identical so
    every decode-only entry persisted before round 22 still hits."""
    base = f"mega:{h}x{f}"
    if chunk and chunk > 1:
        base += f":c{int(chunk)}"
    return f"{base}:{jnp.dtype(dtype).name}"


def _div_pick(pref: int, dim: int) -> int:
    b = min(pref, dim)
    while dim % b:
        b //= 2
    return max(b, 1)


def preferred_mega_blocks(h, f, dtype=jnp.bfloat16, chunk=1):
    """The autotuned ``(bm, bn, bk)`` for this layer geometry (or the
    defaults): ``bn`` tiles the ffn dim through the MLP megakernel, ``bm``
    /``bk`` are currently whole-extent (the decode token block and the
    hidden contraction both fit VMEM at decode geometry) and pages-per-
    block is pinned at 1 (see the module docstring) — kept in the cached
    tuple so a future sweep can shrink them without a cache migration.
    The signature deliberately omits head_dim: nothing swept today
    depends on it (the attention kernel's tiles are pinned whole-extent),
    and a key the lookup side cannot reconstruct is a cache that never
    hits. ``chunk`` keys the mixed ragged-chunk geometry (round 22); a
    missing chunk-c entry falls back to the chunk-1 entry before the
    defaults (the decode sweep is a better prior than nothing)."""
    hit = _atc.lookup(_mega_sig(h, f, dtype, chunk))
    if not (hit and len(hit) == 3) and chunk and chunk > 1:
        hit = _atc.lookup(_mega_sig(h, f, dtype))
    if hit and len(hit) == 3:
        bm, bn, bk = hit
    else:
        bm, bn, bk = BM_DEFAULT, BN_DEFAULT, h
    return int(bm), int(bn), int(bk)


def _mlp_bn(f, groups, h, dtype, chunk=1) -> int:
    """The ffn tile: the autotuned bn, shrunk to divide the ffn dim and
    align with the w2 scale groups (the quant_matmul whole-groups
    discipline): a tile at least one group wide becomes a MULTIPLE of the
    group size (the kernel reshapes multiple scale rows per tile), a
    smaller tile a divisor of it (one scale row spans several tiles) —
    the autotuned width is preserved, not collapsed to the group size."""
    _, bn, _ = preferred_mega_blocks(h, f, dtype, chunk)
    if groups > 1:
        gs = f // groups
        if bn >= gs:
            return _div_pick(bn // gs, groups) * gs
        return _div_pick(bn, gs)
    return _div_pick(bn, f)


def mega_mlp(y2, s_res, p, *, use_kernel=None, fuse_epilogue=True,
             chunk=1):
    """The fused MLP half of the decode layer on the PACKED token stream:
    ``out = s_res + gelu(y2 @ w1 + b1) @ w2 + b2`` with the ffn dim
    streamed in ``bn`` tiles and the hidden state never touching HBM.
    y2/s_res: [t, h]; returns [t, h] in y2's dtype.

    ``fuse_epilogue=False`` (the mp > 1 spelling): returns the pre-psum
    GEMM2 partial ``gelu(y2 @ w1 + b1) @ w2`` — no residual, no b2; the
    caller completes ``psum -> +b2 -> residual`` after its collective
    (``s_res`` may be None). ``chunk`` only keys the autotune lookup —
    the mixed ragged-chunk geometry may prefer a different ffn tile."""
    if use_kernel is None:
        use_kernel = use_kernel_default()
    if not use_kernel:
        return mega_mlp_reference(y2, s_res, p,
                                  fuse_epilogue=fuse_epilogue)
    t, h = y2.shape
    if s_res is None:
        s_res = jnp.zeros_like(y2)   # never read: fuse_epilogue is False
    w1, s1 = _split_wq(p["w1"])
    w2, s2 = _split_wq(p["w2"])
    f = w1.shape[1]
    groups2 = s2.shape[0] if s2 is not None else 1
    bn = _mlp_bn(f, groups2, h, y2.dtype, chunk)
    t8 = max(8, ((t + 7) // 8) * 8)
    if t8 != t:
        y2 = jnp.pad(y2, ((0, t8 - t), (0, 0)))
        s_res = jnp.pad(s_res, ((0, t8 - t), (0, 0)))
    nf = f // bn
    dtype = y2.dtype

    full = lambda: pl.BlockSpec((t8, h), lambda i: (0, 0))  # noqa: E731
    in_specs = [full(), full(),
                pl.BlockSpec((h, bn), lambda i: (0, i)),
                pl.BlockSpec((1, bn), lambda i: (0, i)),
                pl.BlockSpec((bn, h), lambda i: (i, 0)),
                pl.BlockSpec((1, h), lambda i: (0, 0))]
    args = [y2, s_res, w1, p["b1"].reshape(1, f), w2,
            p["b2"].reshape(1, h)]
    wq_quant = s1 is not None
    if wq_quant:
        in_specs.append(pl.BlockSpec((s1.shape[0], bn), lambda i: (0, i)))
        args.append(s1)
        g2 = s2.shape[0]
        if g2 == 1:
            in_specs.append(pl.BlockSpec((1, h), lambda i: (0, 0)))
            args.append(s2)
        else:
            gs2 = f // g2
            if bn % gs2 == 0:
                in_specs.append(pl.BlockSpec((None, bn // gs2, h),
                                             lambda i: (i, 0, 0)))
                args.append(s2.reshape(nf, bn // gs2, h))
            else:  # gs2 % bn == 0 by the gcd pick
                step = gs2 // bn
                in_specs.append(pl.BlockSpec(
                    (1, h), lambda i, _s=step: (i // _s, 0)))
                args.append(s2)
    kern = functools.partial(_mega_mlp_kernel, wq_quant=wq_quant,
                             fuse_epilogue=fuse_epilogue)
    with _atc.x64_off():
        out = pl.pallas_call(
            kern, grid=(nf,), in_specs=in_specs,
            out_specs=pl.BlockSpec((t8, h), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((t8, h), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=_interpret(),
        )(*args)
    return out[:t].astype(dtype)


def mega_mlp_reference(y2, s_res, p, *, fuse_epilogue=True):
    """Composed jnp oracle for :func:`mega_mlp` (and the non-TPU path).
    ``fuse_epilogue=False`` returns the pre-psum GEMM2 partial (see
    :func:`mega_mlp`)."""
    from .quant_matmul import dequantize_weight

    dtype = y2.dtype

    def mm(y, leaf):
        if isinstance(leaf, dict):
            w = dequantize_weight(leaf["q"], leaf["s"],
                                  out_dtype=jnp.float32).astype(dtype)
        else:
            w = leaf
        return y @ w

    u = (mm(y2, p["w1"]).astype(jnp.float32)
         + p["b1"].astype(jnp.float32))
    g = _gelu_f32(u).astype(dtype)
    if not fuse_epilogue:
        return mm(g, p["w2"]).astype(dtype)
    out = (s_res.astype(jnp.float32)
           + mm(g, p["w2"]).astype(jnp.float32)
           + p["b2"].astype(jnp.float32))
    return out.astype(dtype)


# ---------------------------------------------------------------------------
# geometry autotune (shared persisted cache)
# ---------------------------------------------------------------------------


def autotune_mega_decode(batch, h, f, dtype=jnp.bfloat16,
                         candidates=(256, 512, 1024, 2048), iters=10,
                         chunk=1):
    """Sweep the MLP megakernel's ffn tile (``bn``) for this layer
    geometry on the current device and persist the winning ``(bm, bn,
    bk)`` on the shared autotune cache (``bm``/``bk`` ride along whole-
    extent — see :func:`preferred_mega_blocks`). Candidates collapse to
    their EFFECTIVE tile first (``_div_pick`` shrinks a non-dividing bn
    at serve time, so that is what gets timed AND what gets persisted —
    the cached tuple always describes a program that actually ran) and
    duplicates are timed once. No-op off-TPU. Timing rides the
    observability clock (tpulint AL006: one clock for durations, traces
    and bench windows). ``chunk`` (round 22) sweeps the MIXED ragged-
    chunk geometry: the timed token block scales to ``batch * chunk``
    rows and the result persists under the chunk-keyed signature —
    decode-only (chunk 1) entries are never overwritten."""
    from ...observability import monotonic

    chunk = max(1, int(chunk))
    if _interpret():
        return preferred_mega_blocks(h, f, dtype, chunk)
    _atc.load()
    sig = _mega_sig(h, f, dtype, chunk)
    batch = batch * chunk   # the mixed round's packed token rows
    ky, ks, kw = jax.random.split(jax.random.PRNGKey(0), 3)
    y2 = jax.random.normal(ky, (batch, h), dtype)
    s_res = jax.random.normal(ks, (batch, h), dtype)
    p = {"w1": jax.random.normal(kw, (h, f), dtype) * 0.02,
         "b1": jnp.zeros((f,), dtype),
         "w2": jnp.zeros((f, h), dtype),
         "b2": jnp.zeros((h,), dtype)}
    saved = _atc.CACHE.get(sig)
    best, best_t = None, float("inf")
    tried: set[int] = set()
    for bn in candidates:
        eff = _div_pick(int(bn), f)
        if eff in tried:
            continue
        tried.add(eff)
        _atc.CACHE[sig] = [BM_DEFAULT, eff, int(h)]
        try:
            step = jax.jit(functools.partial(mega_mlp, use_kernel=True,
                                             chunk=chunk))
            step(y2, s_res, p).block_until_ready()
            t0 = monotonic()
            for _ in range(iters):
                out = step(y2, s_res, p)
            out.block_until_ready()
            t = monotonic() - t0
        except Exception:
            continue
        if t < best_t:
            best, best_t = [BM_DEFAULT, eff, int(h)], t
    if best is not None:
        _atc.CACHE[sig] = best
        _atc.save()
    elif saved is None:
        _atc.CACHE.pop(sig, None)
    else:
        _atc.CACHE[sig] = saved
    return preferred_mega_blocks(h, f, dtype, chunk)
