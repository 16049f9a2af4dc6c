"""Flash attention — Pallas TPU kernel with custom VJP.

Parity target: the reference's FlashAttention GPU kernel surface
(paddle/phi/kernels/gpu/flash_attn_kernel.cu:128 FlashAttnKernel, registered
:245, varlen entry :235, backward flash_attn_grad_kernel.cu) which dispatches
to external libflashattn. Here the kernel is implemented directly:
online-softmax tiling (the FlashAttention-2 recurrence) over KV blocks, bf16
MXU matmuls with fp32 accumulators, causal masking, and ONE fused backward
kernel producing dq/dk/dv from the saved (out, lse) residuals (dq lives as a
VMEM-resident accumulator across k-block grid steps) — no S×S materialization
in either direction.

Feature parity with the reference kernel surface:

- **GQA** (flash_attn_kernel.cu num_heads_k < num_heads): kv heads are read
  through the BlockSpec index map (``bh // group``) — no repeat/materialize;
  backward computes per-q-head dk/dv and group-sums outside the kernel.
- **attention mask** (flash_attn_kernel.cu:128 attn_mask): additive
  [b, 1|h, sq, sk] bias streamed block-wise into the scores (fwd and bwd
  recompute); the mask gets no gradient (reference parity).
- **varlen** (flash_attn_kernel.cu:235 FlashAttnUnpaddedKernel): per-batch
  q/kv lengths ride in scalar-prefetch SMEM; masked-out rows produce zeros
  (lse pinned high so backward contributions vanish), and the kv loop upper
  bound is clamped by the actual length, so padding costs no FLOPs. The
  packed (cu_seqlens) public API scatters to the padded layout — TPU wants
  static shapes; see nn/functional/attention.py flash_attn_unpadded.

Layout: public entry takes paddle layout [batch, seq, heads, head_dim] and
computes in [batch*heads, seq, head_dim]. K/V live in VMEM per (batch, head)
program; the fused backward additionally keeps full-seq q, do, and an fp32 dq
accumulator resident (~16.5MB at seq 16k, head_dim 128), so backward bounds
the practical single-kernel length at ~8-12k tokens at head_dim 128; longer
sequences should use the ring/blockwise path (distributed sequence
parallelism) on top.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# stable pallas_call names: they survive into the compiled HLO and the
# device trace, so a check ("did the flash kernel run?") or a trace
# reduction finds the kernels after a refactor
FWD_KERNEL_NAME = "flash_attention_fwd"
BWD_KERNEL_NAME = "flash_attention_bwd"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# Preferred block sizes (upper bounds): swept on the benchmark chip — the
# full GPT train step runs ~25% faster at 256/512 than at 128/128 (fewer
# grid steps amortize per-step overhead; tiles stay MXU-shaped). Actual
# per-call blocks shrink to divide the sequence (see _pick_block).
BLOCK_Q = 256
BLOCK_K = 512


def _pick_block(pref: int, seq: int) -> int:
    b = min(pref, seq)
    while seq % b:
        b //= 2
    return max(b, 1)


# ---------------------------------------------------------------------------
# Block autotune cache: persistence lives in the shared autotune_cache
# module (one JSON file for the whole Pallas kernel family). Keys here are
# (seq_q, seq_k, head_dim, dtype); values are swept (bq, bk). The sweep runs
# only from :func:`autotune` (an explicit eager call — block sizes are
# trace-time constants, so they cannot be switched inside a compiled
# program); `_blocks_for` consults the cache at every trace.
# ---------------------------------------------------------------------------

from . import autotune_cache as _atc


def _sig(seq_q, seq_k, d, dtype, which="fwd") -> str:
    # normalize dtype classes AND array dtypes to one canonical name
    return f"{seq_q}x{seq_k}x{d}:{jnp.dtype(dtype).name}:{which}"


def _blocks_for(seq_q, seq_k, d, dtype, which="fwd"):
    _atc.load()
    hit = _atc.CACHE.get(_sig(seq_q, seq_k, d, dtype, which))
    if hit:
        return _pick_block(hit[0], seq_q), _pick_block(hit[1], seq_k)
    return _pick_block(BLOCK_Q, seq_q), _pick_block(BLOCK_K, seq_k)


def autotune(batch_heads, seq_q, seq_k, d, dtype=jnp.bfloat16,
             causal=True, candidates=(128, 256, 512), iters=3):
    """Sweep (bq, bk) for this shape signature on the current device and
    cache the winner (in process + on disk). Returns (bq, bk).

    The sweep times the FULL fwd+bwd step — the backward kernel has a
    different VMEM profile (full-seq dq accumulator), so a forward-only
    winner could regress training. Run once eagerly before compiling the
    training step; subsequent traces with matching shapes pick the tuned
    blocks.

    Caveat (measured, v5e): an ISOLATED-attention winner can still lose
    inside a full train step where the kernel competes with surrounding
    fusion/remat for VMEM — e.g. GPT-125M's isolated sweep picked
    (256, 128) but the full step runs 12% faster at the hand-swept default
    (256, 512). Treat autotune as a starting point and confirm against the
    end-to-end step; delete the cache file to revert to defaults.
    """
    from ...observability import monotonic

    if _interpret():
        return _blocks_for(seq_q, seq_k, d, dtype)
    _atc.load()
    # one subkey per operand: a shared key makes q/k/v IDENTICAL streams
    # (q == k when seq_q == seq_k), degenerating the softmax the sweep times
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (batch_heads, seq_q, d), dtype)
    k = jax.random.normal(kk, (batch_heads, seq_k, d), dtype)
    v = jax.random.normal(kv, (batch_heads, seq_k, d), dtype)
    sig_f = _sig(seq_q, seq_k, d, dtype, "fwd")
    sig_b = _sig(seq_q, seq_k, d, dtype, "bwd")
    saved = (_atc.CACHE.get(sig_f), _atc.CACHE.get(sig_b))
    best, best_t = None, float("inf")
    scale = 1.0 / math.sqrt(d)
    for bq in candidates:
        if seq_q % min(bq, seq_q):
            continue
        for bk in candidates:
            if seq_k % min(bk, seq_k):
                continue
            cand = [min(bq, seq_q), min(bk, seq_k)]
            _atc.CACHE[sig_f] = cand
            _atc.CACHE[sig_b] = cand
            try:
                # fresh closure per candidate: jit caches on function
                # identity, and the blocks are read from the cache at trace
                step = jax.jit(lambda q, k, v: jax.value_and_grad(
                    lambda q_: jnp.sum(
                        _flash(q_, k, v, None, None, scale, causal, 1)
                        .astype(jnp.float32)))(q))
                loss, g = step(q, k, v)
                g.block_until_ready()  # compile + warmup
                t0 = monotonic()
                for _ in range(iters):
                    loss, g = step(q, k, v)
                g.block_until_ready()
                t = monotonic() - t0
            except Exception:
                continue
            if t < best_t:
                best, best_t = (bq, bk), t
    if best is not None:
        _atc.CACHE[sig_f] = list(best)
        _atc.CACHE[sig_b] = list(best)
        _atc.save()
        return best
    for s, val in zip((sig_f, sig_b), saved):  # no candidate ran: restore
        if val is None:
            _atc.CACHE.pop(s, None)
        else:
            _atc.CACHE[s] = val
    return _blocks_for(seq_q, seq_k, d, dtype)


def autotune_split(batch_heads, seq_q, seq_k, d, dtype=jnp.bfloat16,
                   causal=True, candidates=(128, 256, 512), iters=3):
    """Independent (bq, bk) sweeps for the FORWARD and BACKWARD kernels.

    The joint ``autotune`` ties both signatures to one winner, but the two
    kernels have different VMEM/grid profiles: fwd iterates k-blocks per
    q-block row; bwd grids over k-blocks with a full-seq fp32 dq accumulator
    resident and fori-loops q-blocks (``_bwd_fused_kernel``). Phase 1 times
    the forward alone; phase 2 times fwd+bwd with the forward pinned at its
    winner, so the bwd signature is chosen on its own merits (round-4
    verdict: the backward had no TPU-tuned autotune of its own).
    Returns ((fwd_bq, fwd_bk), (bwd_bq, bwd_bk)).
    """
    from ...observability import monotonic

    if _interpret():
        b = _blocks_for(seq_q, seq_k, d, dtype)
        return b, b
    _atc.load()
    # one subkey per operand: a shared key makes q/k/v IDENTICAL streams
    # (q == k when seq_q == seq_k), degenerating the softmax the sweep times
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (batch_heads, seq_q, d), dtype)
    k = jax.random.normal(kk, (batch_heads, seq_k, d), dtype)
    v = jax.random.normal(kv, (batch_heads, seq_k, d), dtype)
    scale = 1.0 / math.sqrt(d)
    sig_f = _sig(seq_q, seq_k, d, dtype, "fwd")
    sig_b = _sig(seq_q, seq_k, d, dtype, "bwd")

    def _time(fn, *args):
        out = jax.block_until_ready(fn(*args))  # compile + warmup
        t0 = monotonic()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return monotonic() - t0

    def _sweep(sig, make_step):
        saved = _atc.CACHE.get(sig)
        best, best_t = None, float("inf")
        for bq in candidates:
            if seq_q % min(bq, seq_q):
                continue
            for bk in candidates:
                if seq_k % min(bk, seq_k):
                    continue
                _atc.CACHE[sig] = [min(bq, seq_q), min(bk, seq_k)]
                try:
                    t = _time(make_step(), q, k, v)
                except Exception:
                    continue
                if t < best_t:
                    best, best_t = (bq, bk), t
        if best is None:  # no candidate ran: restore prior state
            if saved is None:
                _atc.CACHE.pop(sig, None)
            else:
                _atc.CACHE[sig] = saved
        else:
            _atc.CACHE[sig] = list(best)
        return best

    def fwd_step():
        return jax.jit(lambda q, k, v: _flash(q, k, v, None, None, scale,
                                              causal, 1))

    def full_step():
        return jax.jit(lambda q, k, v: jax.grad(
            lambda q_: jnp.sum(_flash(q_, k, v, None, None, scale, causal, 1)
                               .astype(jnp.float32)))(q))

    best_f = _sweep(sig_f, fwd_step)     # phase 1: forward alone
    best_b = _sweep(sig_b, full_step)    # phase 2: bwd varies, fwd pinned
    _atc.save()
    return (best_f or _blocks_for(seq_q, seq_k, d, dtype, "fwd"),
            best_b or _blocks_for(seq_q, seq_k, d, dtype, "bwd"))


NEG_INF = -1e30
LSE_INVALID = 1e30  # lse for rows with no valid key: exp(s - BIG) == 0 in bwd

# Explicit DEFAULT precision keeps bf16 operands on the native MXU pass
# (f32 accumulate via preferred_element_type). Inheriting the framework's
# global "highest" would force multi-pass fp32 emulation — ~6x slower — and
# this environment's Mosaic toolchain rejects bf16 dots at non-default
# contract precision outright.
_MXU = jax.lax.Precision.DEFAULT


def _dotf32(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=_MXU)


def _fwd_kernel(*refs, scale, causal, bq, bk, hq, has_mask, has_lens, off):
    idx = 0
    if has_lens:
        lens_ref = refs[0]  # SMEM [2, b] int32: (qlens; kvlens)
        idx = 1
    q_ref, k_ref, v_ref = refs[idx:idx + 3]
    idx += 3
    mask_ref = refs[idx] if has_mask else None
    o_ref, lse_ref = refs[-2:]

    i = pl.program_id(1)
    q = q_ref[0]  # [bq, d] kept in input dtype: MXU wants bf16 operands
    seq = k_ref.shape[1]
    num_k = seq // bk
    d = q.shape[1]

    row_ids = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    if has_lens:
        bi = pl.program_id(0) // hq
        qlen = lens_ref[0, bi]
        kvlen = lens_ref[1, bi]
        # Bottom-right causal alignment (FA2 semantics): the LAST query row
        # lines up with the LAST valid key, so row r attends cols
        # <= r + (kvlen - qlen). Per-sequence under varlen.
        coff = kvlen - qlen
    else:
        coff = off  # static: seq_k - seq_q (0 for self-attention)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * bk, bk), :]
        v = v_ref[0, pl.ds(j * bk, bk), :]
        s = _dotf32(q, k, (((1,), (1,)))) * scale  # [bq, bk] f32
        col_ids = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if causal:
            s = jnp.where(row_ids + coff >= col_ids, s, NEG_INF)
        if has_lens:
            s = jnp.where(col_ids < kvlen, s, NEG_INF)
        if has_mask:
            # singleton-sq masks (key-padding [b,1,1,sk]) broadcast over rows
            mrow = mask_ref[0, 0, :, pl.ds(j * bk, bk)].astype(jnp.float32)
            s = s + mrow  # [bq or 1, bk] broadcasts against [bq, bk]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # p cast to the value dtype so the second matmul also rides the MXU
        acc = acc * alpha + _dotf32(p.astype(v.dtype), v, ((1,), (0,)))
        return m_new, l, acc

    # int32 loop bounds: the framework runs with jax_enable_x64, and int64
    # scalars are not lowerable inside Mosaic kernels.
    if causal:
        upper = jnp.clip(
            ((i + 1) * bq + coff + bk - 1) // bk, 0, num_k).astype(jnp.int32)
    else:
        upper = jnp.int32(num_k)
    if has_lens:
        # padding costs no FLOPs: stop at the last block holding a valid key
        upper = jnp.minimum(upper, (kvlen + bk - 1) // bk).astype(jnp.int32)
    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(jnp.int32(0), upper, body, (m0, l0, acc0))
    # Rows whose running max never left NEG_INF saw no valid key (fully
    # causal-masked, e.g. rows before the bottom-right diagonal when
    # qlen > kvlen): their p was exp(NEG_INF - NEG_INF) = 1 garbage — zero
    # them, matching rows the loop never visited (l == 0).
    invalid = (m <= NEG_INF * 0.5) | (l == 0.0)
    l_safe = jnp.where(invalid, 1.0, l)
    out = jnp.where(invalid, 0.0, acc / l_safe)
    lse = jnp.where(invalid[:, 0], LSE_INVALID, (m + jnp.log(l_safe))[:, 0])
    if has_lens:
        rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        out = jnp.where(rows < qlen, out, 0.0)
        lse = jnp.where(rows[:, 0] < qlen, lse, LSE_INVALID)
    o_ref[0] = out.astype(o_ref.dtype)
    lse_ref[0, 0, :] = lse


def _bhsd_specs(seq, d, block: int | None, group: int = 1):
    """BlockSpec for [bh, seq, d] arrays: per-program either one seq-block
    (``block`` rows) or the full sequence (None). ``group`` > 1 maps GQA
    q-head programs onto their shared kv head (bh // group) — no repeat."""
    if block is not None:
        return pl.BlockSpec((1, block, d), lambda bh, i, *_: (bh, i, 0))
    if group > 1:
        return pl.BlockSpec((1, seq, d), lambda bh, i, *_: (bh // group, 0, 0))
    return pl.BlockSpec((1, seq, d), lambda bh, i, *_: (bh, 0, 0))


def _mask_spec_fwd(hq, bm, hm, sqm, bq, seq_k):
    """Mask [bm, hm, sqm, sk] (bm/hm/sqm may be 1 = broadcast): one q-block
    row band per program (the whole singleton row when sqm == 1)."""
    def imap(bh, i, *_):
        return (0 if bm == 1 else bh // hq, 0 if hm == 1 else bh % hq,
                0 if sqm == 1 else i, 0)

    return pl.BlockSpec((1, 1, 1 if sqm == 1 else bq, seq_k), imap)


def _mask_spec_bwd(hq, bm, hm, sqm, seq_q, bkb):
    """Mask [bm, hm, sqm, sk]: one k-block column band per program."""
    def imap(bh, j, *_):
        return (0 if bm == 1 else bh // hq, 0 if hm == 1 else bh % hq, 0, j)

    return pl.BlockSpec((1, 1, 1 if sqm == 1 else seq_q, bkb), imap)


def _flash_fwd_impl(q, k, v, mask, lens, scale, causal, hq, blocks=None):
    bhq, seq, d = q.shape
    group = bhq // k.shape[0]
    bq, bk = blocks or _blocks_for(seq, k.shape[1], d, q.dtype, 'fwd')
    grid = (bhq, seq // bq)
    has_mask = mask is not None
    has_lens = lens is not None
    in_specs = [
        _bhsd_specs(seq, d, bq),
        _bhsd_specs(k.shape[1], d, None, group),
        _bhsd_specs(k.shape[1], d, None, group),
    ]
    args = [q, k, v]
    if has_mask:
        in_specs.append(
            _mask_spec_fwd(hq, mask.shape[0], mask.shape[1], mask.shape[2],
                           bq, k.shape[1]))
        args.append(mask)
    out_specs = [
        _bhsd_specs(seq, d, bq),
        pl.BlockSpec((1, 1, bq), lambda b, i, *_: (b, 0, i)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        jax.ShapeDtypeStruct((bhq, 1, seq), jnp.float32),
    ]
    kern = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, bq=bq, bk=bk, hq=hq,
        has_mask=has_mask, has_lens=has_lens, off=k.shape[1] - seq)
    # Trace kernels in 32-bit mode: the framework enables jax_enable_x64 and
    # int64 scalars are unlowerable in Mosaic.
    with _atc.x64_off():
        if has_lens:
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
                out_specs=out_specs)
            out, lse = pl.pallas_call(
                kern, grid_spec=grid_spec, out_shape=out_shape,
                interpret=_interpret(), name=FWD_KERNEL_NAME,
            )(lens.astype(jnp.int32), *args)
        else:
            out, lse = pl.pallas_call(
                kern, grid=grid, in_specs=in_specs, out_specs=out_specs,
                out_shape=out_shape, interpret=_interpret(),
                name=FWD_KERNEL_NAME,
            )(*args)
    return out, lse


def _bwd_fused_kernel(*refs, scale, causal, bq, bkb, hq, has_mask, has_lens,
                      off):
    """One kernel for dq/dk/dv. Grid (bh, k-block); dq's block is the FULL
    [seq, d] fp32 accumulator, whose index map ignores the k-block dim, so
    Mosaic keeps it VMEM-resident across the inner grid steps and each step
    accumulates its k-block's contribution (classic TPU FA backward layout;
    halves the kernel count AND the s/p recomputation of a split dq/dkv
    pass)."""
    idx = 0
    if has_lens:
        lens_ref = refs[0]
        idx = 1
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[idx:idx + 6]
    idx += 6
    mask_ref = refs[idx] if has_mask else None
    dq_ref, dk_ref, dv_ref = refs[-3:]

    j = pl.program_id(1)
    k = k_ref[0]  # [bkb, d]
    v = v_ref[0]
    seq = q_ref.shape[1]
    num_q = seq // bq
    bk, d = k.shape
    col_ids = j * bkb + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if has_lens:
        bi = pl.program_id(0) // hq
        qlen = lens_ref[0, bi]
        kvlen = lens_ref[1, bi]
        coff = kvlen - qlen  # bottom-right causal alignment (match fwd)
    else:
        coff = off

    @pl.when(j == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * bq, bq), :]
        do = do_ref[0, pl.ds(i * bq, bq), :]
        lse = lse_ref[0, 0, pl.ds(i * bq, bq)][:, None]
        delta = delta_ref[0, 0, pl.ds(i * bq, bq)][:, None]
        s = scale * _dotf32(q, k, ((1,), (1,)))  # [bq, bk] f32
        if causal:
            row_ids = i * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0
            )
            s = jnp.where(row_ids + coff >= col_ids, s, NEG_INF)
        if has_lens:
            s = jnp.where(col_ids < kvlen, s, NEG_INF)
        if has_mask:
            if mask_ref.shape[2] == 1:  # singleton-sq: broadcast over rows
                s = s + mask_ref[0, 0, :, :].astype(jnp.float32)
            else:
                s = s + mask_ref[0, 0, pl.ds(i * bq, bq), :].astype(jnp.float32)
        # invalid q rows carry lse == LSE_INVALID -> p == 0 -> no gradient
        p = jnp.exp(s - lse)
        pc = p.astype(do.dtype)
        dv = dv + _dotf32(pc, do, ((0,), (0,)))
        dp = _dotf32(do, v, ((1,), (1,)))
        ds = (p * (dp - delta)).astype(q.dtype)
        dk = dk + scale * _dotf32(ds, q, ((0,), (0,)))
        dq_blk = dq_ref[0, pl.ds(i * bq, bq), :]
        dq_ref[0, pl.ds(i * bq, bq), :] = (
            dq_blk + scale * _dotf32(ds, k, ((1,), (0,))))
        return dk, dv

    if causal:
        # first q row attending this k block: row >= col - coff
        lower = (jnp.maximum(j * bkb - coff, 0) // bq).astype(jnp.int32)
    else:
        lower = jnp.int32(0)
    z = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(lower, jnp.int32(num_q), body, (z, z))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def flash_bwd_impl(q, k, v, g, lse, delta, scale, causal,
                   mask=None, lens=None, hq=1):
    """Fused dq/dk/dv pallas kernel from explicit (lse, delta) residuals.

    ``lse``/``delta`` are [bh, 1, seq] fp32. Exposed separately so the ring
    (context-parallel) backward can drive the same kernel per KV chunk with
    the *globally* combined lse and delta — the blockwise-attention identity
    p = exp(s - lse_global) makes chunk backward exact without per-chunk
    renormalization.

    GQA: dk/dv are returned at q-head granularity [bhq, sk, d]; the caller
    group-sums them to kv heads (plain XLA reshape+sum).
    """
    bhq, seq, d = q.shape
    group = bhq // k.shape[0]
    seq_k = k.shape[1]
    bq, bkb = _blocks_for(seq, seq_k, d, q.dtype, 'bwd')
    has_mask = mask is not None
    has_lens = lens is not None
    lse_spec_full = pl.BlockSpec((1, 1, seq), lambda b, j, *_: (b, 0, 0))
    kv_block = (
        pl.BlockSpec((1, bkb, d), lambda bh_, j, *_: (bh_ // group, j, 0))
        if group > 1 else
        pl.BlockSpec((1, bkb, d), lambda bh_, j, *_: (bh_, j, 0)))
    dkv_block = pl.BlockSpec((1, bkb, d), lambda bh_, j, *_: (bh_, j, 0))
    q_full = pl.BlockSpec((1, seq, d), lambda bh_, j, *_: (bh_, 0, 0))

    in_specs = [q_full, kv_block, kv_block, q_full, lse_spec_full,
                lse_spec_full]
    args = [q, k, v, g, lse, delta]
    if has_mask:
        in_specs.append(
            _mask_spec_bwd(hq, mask.shape[0], mask.shape[1], mask.shape[2],
                           seq, bkb))
        args.append(mask)
    out_specs = [
        q_full,          # dq accumulator: full seq, j-invariant
        dkv_block,       # per-q-head dk (group-summed by the caller)
        dkv_block,
    ]
    out_shape = [
        jax.ShapeDtypeStruct(q.shape, jnp.float32),
        jax.ShapeDtypeStruct((bhq, seq_k, d), k.dtype),
        jax.ShapeDtypeStruct((bhq, seq_k, d), v.dtype),
    ]
    kern = functools.partial(
        _bwd_fused_kernel, scale=scale, causal=causal, bq=bq, bkb=bkb,
        hq=hq, has_mask=has_mask, has_lens=has_lens, off=seq_k - seq)
    with _atc.x64_off():
        if has_lens:
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(bhq, seq_k // bkb),
                in_specs=in_specs, out_specs=out_specs)
            dq, dk, dv = pl.pallas_call(
                kern, grid_spec=grid_spec, out_shape=out_shape,
                interpret=_interpret(), name=BWD_KERNEL_NAME,
            )(lens.astype(jnp.int32), *args)
        else:
            dq, dk, dv = pl.pallas_call(
                kern, grid=(bhq, seq_k // bkb), in_specs=in_specs,
                out_specs=out_specs, out_shape=out_shape,
                interpret=_interpret(), name=BWD_KERNEL_NAME,
            )(*args)
    if group > 1:
        bkv = k.shape[0]
        dk = dk.reshape(bkv, group, seq_k, d).sum(axis=1).astype(k.dtype)
        dv = dv.reshape(bkv, group, seq_k, d).sum(axis=1).astype(v.dtype)
    return dq.astype(q.dtype), dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash(q, k, v, mask, lens, scale, causal, hq):
    out, _ = _flash_fwd_impl(q, k, v, mask, lens, scale, causal, hq)
    return out


def _flash_fwd(q, k, v, mask, lens, scale, causal, hq):
    out, lse = _flash_fwd_impl(q, k, v, mask, lens, scale, causal, hq)
    # checkpoint_name tags make BOTH residuals saveable under jax.checkpoint
    # (gpt_spmd's remat policy lists "flash_out"): with o and lse stored and
    # q/k/v already saved as weight-GEMM outputs, the rematerialized
    # backward DCEs the forward pallas call instead of re-running it.
    from jax.ad_checkpoint import checkpoint_name

    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_out")
    return out, (q, k, v, mask, lens, out, lse)


def _flash_bwd(scale, causal, hq, res, g):
    q, k, v, mask, lens, out, lse = res
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=False
    )[:, None, :]  # [bh, 1, seq]
    dq, dk, dv = flash_bwd_impl(q, k, v, g, lse, delta, scale, causal,
                                mask=mask, lens=lens, hq=hq)
    dmask = (None if mask is None
             else jnp.zeros_like(mask))  # mask gets no grad (reference parity)
    dlens = (None if lens is None
             else np.zeros(lens.shape, jax.dtypes.float0))
    return dq, dk, dv, dmask, dlens


_flash.defvjp(_flash_fwd, _flash_bwd)


def mask_kernel_compatible(mask_shape, b, hq, sq, sk) -> bool:
    """Whether a (normalized, 4-D) additive mask can stream into the kernel:
    every dim broadcastable (1 or full), except sk which must be full."""
    if len(mask_shape) != 4:
        return False
    mb, mh, msq, msk = mask_shape
    return (mb in (1, b) and mh in (1, hq) and msq in (1, sq) and msk == sk)


def flash_attention(q, k, v, causal: bool = False, scale: float | None = None,
                    mask=None, q_seqlens=None, kv_seqlens=None):
    """Flash attention over paddle-layout arrays [batch, seq, heads, head_dim].

    Raw-array API (used from nn.functional.scaled_dot_product_attention which
    handles the framework tape). Differentiable via the Pallas backward
    kernels.

    - GQA: ``k``/``v`` may have fewer heads than ``q`` (divisible).
    - ``mask``: additive bias [b, 1|hq, sq, sk] streamed into the kernel.
    - ``q_seqlens``/``kv_seqlens``: [b] int per-sequence valid lengths
      (padded varlen); rows past the length produce zeros and no grads.
    No dropout — callers fall back to the reference path for that (matching
    the reference kernel's unsupported-feature fallbacks).
    """
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    assert hq % hkv == 0, f"GQA needs q heads {hq} divisible by kv heads {hkv}"
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    def to_bhsd(x):
        h = x.shape[2]
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, x.shape[1], d)

    qt, kt, vt = to_bhsd(q), to_bhsd(k), to_bhsd(v)
    lens = None
    if q_seqlens is not None or kv_seqlens is not None:
        ql = (jnp.full((b,), sq, jnp.int32) if q_seqlens is None
              else q_seqlens.astype(jnp.int32))
        kl = (jnp.full((b,), k.shape[1], jnp.int32) if kv_seqlens is None
              else kv_seqlens.astype(jnp.int32))
        lens = jnp.stack([ql, kl])  # [2, b]
    if mask is not None:
        if mask.dtype == jnp.bool_:
            mask = jnp.where(mask, 0.0, NEG_INF).astype(q.dtype)
        if mask.ndim == 2:  # [sq, sk]
            mask = mask[None, None]
        elif mask.ndim == 3:  # [b, sq, sk]
            mask = mask[:, None]
        if not mask_kernel_compatible(mask.shape, b, hq, sq, k.shape[1]):
            raise ValueError(
                f"flash_attention: mask shape {mask.shape} not supported "
                f"in-kernel (want broadcastable [{{1|{b}}}, {{1|{hq}}}, "
                f"{{1|{sq}}}, {k.shape[1]}]); use the reference attention "
                "path for other shapes")
    out = _flash(qt, kt, vt, mask, lens, float(scale), bool(causal), hq)
    return jnp.transpose(out.reshape(b, hq, sq, d), (0, 2, 1, 3))
