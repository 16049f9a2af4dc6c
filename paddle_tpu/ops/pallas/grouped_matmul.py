"""Ragged grouped GEMM — the MoE expert-FFN Pallas TPU kernel.

A MoE FFN applies a DIFFERENT weight matrix to each token depending on
which expert the router picked, with a variable (ragged) number of tokens
per expert — including zero. Looping experts through separate XLA dots
pays ``E`` kernel launches and reads every expert's weights even for
empty groups; one dense ``[M, E, K, N]`` einsum materializes all-expert
compute. This kernel is the TPU-native middle path, the same ragged
blocking discipline as ``ragged_paged_attention``:

- tokens arrive PRE-GROUPED (rows sorted by expert) with a
  ``group_offsets [E+1]`` prefix-sum describing the raggedness;
- the caller-side pack pads each group's row range up to a multiple of
  the ``bm`` row tile, so every m tile belongs to exactly ONE group (the
  per-tile group id array rides **scalar prefetch** —
  ``pltpu.PrefetchScalarGridSpec`` — the paged-attention block-table
  trick applied to weights);
- each grid step DMAs that group's ``[bk, bn]`` weight tile into VMEM:
  empty experts stream ZERO weight bytes, and a group's weights are
  fetched only for its own row tiles;
- the float forward (what the serving cells run) moves 1-2 MiB of weights
  a grid step at every contraction width (``_float_fwd_tile``: the tile
  follows ``(k, n, dtype)``; a quarter of a megabyte does not cover a grid
  step's own cost), runs the row tiles that hold rows and no others (the
  grid's first bound is their count, a dynamic grid dimension), and writes
  its output in the rows' dtype: at once where the whole contraction is one
  step, from a float32 scratch on the last of several;
- the int8/int4 tile-dequant scale-row machinery is lifted verbatim from
  ``quant_matmul.py`` — one scale row per k tile, widened and applied on
  the way into the MXU, fp32 accumulation across k tiles.

The jnp segment-matmul reference (:func:`grouped_matmul_reference`) is
the numerical oracle and the non-TPU fallback; interpret mode runs the
real kernel on CPU for the tests. The tiles come from the shape
(``_blocks_for``); :func:`autotune_grouped_matmul` is the sweep behind the
float forward's rule (``PERF.md`` section 6, PR 37) and can still pin a
signature's tiles on the shared ``autotune_cache``
(``gmm:{E}x{K}x{N}:{bits}b:g{gs}:{dtype}``; no packaged entry).

Backward (custom VJP): ``dx`` runs the same grouped tile-dequant
structure with the contraction transposed (weights stay quantized in
HBM); ``dw`` for float weights is the segment outer-product (einsum
against the group one-hot — the training fast path uses the einsum MoE
formulation, so this is a correctness path, not the hot loop); quantized
weights/scales get float0/zero cotangents like ``quant_matmul``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import autotune_cache as _atc
from .quant_matmul import (
    _norm_scales,
    dequantize_weight,
    unpack_int4,
)

_MXU = jax.lax.Precision.DEFAULT

# stable pallas_call name of the forward kernels (survives into the compiled
# HLO and the device trace): how a check or a trace reduction finds them
GROUPED_KERNEL_NAME = "grouped_matmul"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def use_kernel_default() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# ragged layout helpers
# ---------------------------------------------------------------------------


def _round_up(x: int, mult: int) -> int:
    return -(-int(x) // int(mult)) * int(mult)


def token_group_ids(group_offsets, m: int):
    """Per-row group id ``[M] int32`` from a ``[E+1]`` offsets prefix sum
    (rows in ``[offsets[e], offsets[e+1])`` belong to group ``e``)."""
    e = group_offsets.shape[0] - 1
    offs = group_offsets.astype(jnp.int32)
    # compare_all: one dense comparison against the E + 1 offsets; the
    # default binary search is a chain of gathers, slow on the chip
    gid = jnp.searchsorted(offs, jnp.arange(m, dtype=jnp.int32),
                           side="right", method="compare_all") - 1
    return jnp.clip(gid, 0, e - 1).astype(jnp.int32)


def _pack_layout(group_offsets, m: int, e: int, bm: int):
    """Padded-aligned repack plan: each group's rows are shifted so its
    range starts on a ``bm`` boundary (groups padded up to a multiple of
    ``bm``). Returns ``(dest [M], tile_gid [MP/bm], mp, n_live)`` —
    ``dest`` is where row ``i`` lands in the padded buffer, ``tile_gid[t]``
    the ONE group owning row tile ``t``, ``n_live`` (int32 scalar) how many
    leading tiles hold rows: the dead tiles past the ragged end carry the
    last live tile's group (their rows are zero and never gathered back)."""
    offs = group_offsets.astype(jnp.int32)
    counts = offs[1:] - offs[:-1]                                  # [E]
    padded = -(-counts // bm) * bm
    poffs = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(padded)]).astype(jnp.int32)
    # static upper bound: every group pads by < bm rows
    mp = _round_up(m + e * (bm - 1), bm)
    rows = jnp.arange(m, dtype=jnp.int32)
    gid = token_group_ids(group_offsets, m)
    dest = poffs[gid] + (rows - offs[gid])
    n_live = poffs[-1] // bm
    tiles = jnp.arange(mp // bm, dtype=jnp.int32)
    starts = jnp.minimum(tiles, jnp.maximum(n_live - 1, 0)) * bm
    tile_gid = jnp.clip(
        jnp.searchsorted(poffs, starts, side="right") - 1, 0, e - 1
    ).astype(jnp.int32)
    return dest, tile_gid, mp, n_live


def _norm_scales_grouped(scales, e: int, k: int, n: int):
    """Normalize grouped scales to ``[E, groups, N]``; returns
    ``(scales3d, group_size)`` — the per-expert twin of
    ``quant_matmul._norm_scales``."""
    s = scales[:, None, :] if scales.ndim == 2 else scales
    if s.ndim != 3 or s.shape[0] != e:
        raise ValueError(
            f"grouped scales must be [E, N] or [E, groups, N] with E={e}, "
            f"got {scales.shape}")
    if s.shape[-1] != n:
        raise ValueError(f"scales last dim {s.shape[-1]} != out dim {n}")
    groups = s.shape[1]
    if k % groups:
        raise ValueError(f"K={k} not divisible by {groups} scale groups")
    return s, k // groups


def _weight_bits(weights, k: int) -> int:
    """0 = float weights, 8 = int8, 4 = nibble-packed int4 (split-half
    rows, ``[E, K/2, N]`` — the ``quant_matmul.pack_int4`` layout applied
    per expert)."""
    kw = weights.shape[1]
    if weights.dtype == jnp.int8:
        if kw == k:
            return 8
        if kw * 2 == k:
            return 4
        raise ValueError(
            f"grouped quantized weight in-dim {kw} matches neither K={k} "
            f"(int8) nor K/2={k // 2} (packed int4)")
    if kw != k:
        raise ValueError(f"grouped weight in-dim {kw} != K={k}")
    return 0


# ---------------------------------------------------------------------------
# jnp segment-matmul reference (oracle + non-TPU fallback)
# ---------------------------------------------------------------------------


def dequantize_grouped_weight(weights, scales, k=None,
                              out_dtype=jnp.float32):
    """Materialize the full-precision expert stack ``[E, K, N]`` (per-
    expert ``quant_matmul.dequantize_weight``)."""
    if weights.dtype != jnp.int8:
        return weights.astype(out_dtype)
    kk = weights.shape[1] if k is None else k
    s3, _ = _norm_scales_grouped(scales, weights.shape[0], kk,
                                 weights.shape[-1])
    return jax.vmap(
        lambda q, s: dequantize_weight(q, s, k=kk, out_dtype=out_dtype)
    )(weights, s3)


def grouped_matmul_reference(x, weights, group_offsets, scales=None):
    """Segment-matmul oracle: ``out[i] = x[i] @ dequant(weights)[g(i)]``
    spelled as one dense dot per expert plus a row gather — what a
    non-fused XLA implementation does (all-expert outputs materialize
    ``[E, M, N]``). Numerically the golden for the kernel; also the
    non-TPU fallback."""
    m, k = x.shape
    e = weights.shape[0]
    wfp = (dequantize_grouped_weight(weights, scales, k=k, out_dtype=x.dtype)
           if weights.dtype == jnp.int8 else weights.astype(x.dtype))
    acc = jnp.promote_types(x.dtype, jnp.float32)
    gid = token_group_ids(group_offsets, m)

    def one(we):
        return jax.lax.dot_general(
            x, we, (((1,), (0,)), ((), ())),
            preferred_element_type=acc, precision=_MXU)

    ys = jax.lax.map(one, wfp)                       # [E, M, N]
    out = jnp.take_along_axis(ys, gid[None, :, None].astype(jnp.int32),
                              axis=0)[0]
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# kernels (scalar-prefetched group ids; scale-row discipline from qmm)
# ---------------------------------------------------------------------------


def _gmm_kernel(gid_ref, layer_ref, x_ref, w_ref, o_ref, *acc):
    """One [bm, bn] output tile of ONE group: the weight tile is this row
    tile's group's ``[bk, bn]`` slab (the index map reads the prefetched
    group id, and the layer of a stack). With the whole contraction in one
    step the product is written as it comes, rounded once to the block's
    dtype; over several k steps a float32 scratch holds the partial sums and
    the last step writes. The grid runs the row tiles that hold rows and no
    others (its first bound is their count)."""
    del gid_ref, layer_ref  # consumed by the index maps
    x = x_ref[...]
    part = jax.lax.dot_general(
        x, w_ref[0].astype(x.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_MXU)
    if not acc:
        o_ref[...] = part.astype(o_ref.dtype)
        return
    acc_ref, = acc
    kstep = pl.program_id(2)

    @pl.when(kstep == 0)
    def _first():
        acc_ref[...] = part

    @pl.when(kstep > 0)
    def _rest():
        acc_ref[...] += part

    @pl.when(kstep == pl.num_programs(2) - 1)
    def _write():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _gmm_q_kernel(gid_ref, x_ref, w_ref, s_ref, o_ref):
    """int8 expert tile: widen, scale by the ONE group scale row, dot."""
    del gid_ref
    kstep = pl.program_id(2)

    @pl.when(kstep == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    w = w_ref[0].astype(x.dtype) * s_ref[0].astype(x.dtype)
    o_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_MXU)


def _gmm_q4_kernel(gid_ref, xl_ref, xh_ref, p_ref, sl_ref, sh_ref, o_ref):
    """int4 split-half expert tile (``quant_matmul._qmm4_kernel`` with the
    weight/scale tiles selected by the prefetched group id)."""
    del gid_ref
    kstep = pl.program_id(2)

    @pl.when(kstep == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    xl = xl_ref[...]
    p = p_ref[0].astype(jnp.int32)
    lo = (((p & 0xF) ^ 8) - 8).astype(xl.dtype) * sl_ref[0].astype(xl.dtype)
    hi = ((((p >> 4) & 0xF) ^ 8) - 8).astype(xl.dtype) * sh_ref[0].astype(
        xl.dtype)
    dims = (((1,), (0,)), ((), ()))
    o_ref[...] += (
        jax.lax.dot_general(xl, lo, dims,
                            preferred_element_type=jnp.float32,
                            precision=_MXU)
        + jax.lax.dot_general(xh_ref[...], hi, dims,
                              preferred_element_type=jnp.float32,
                              precision=_MXU))


def _gmm_bwd_kernel(gid_ref, dy_ref, w_ref, dx_ref):
    """dx tile [bm, bk] of ONE group accumulating over n tiles
    (``dy @ W_g^T``; weights stay in HBM in their stored dtype)."""
    del gid_ref
    nstep = pl.program_id(2)

    @pl.when(nstep == 0)
    def _init():
        dx_ref[...] = jnp.zeros_like(dx_ref)

    dy = dy_ref[...]
    w = w_ref[0].astype(dy.dtype)
    dx_ref[...] += jax.lax.dot_general(
        dy, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_MXU)


def _gmm_q_bwd_kernel(gid_ref, dy_ref, w_ref, s_ref, dx_ref):
    del gid_ref
    nstep = pl.program_id(2)

    @pl.when(nstep == 0)
    def _init():
        dx_ref[...] = jnp.zeros_like(dx_ref)

    dy = dy_ref[...]
    w = w_ref[0].astype(dy.dtype) * s_ref[0].astype(dy.dtype)
    dx_ref[...] += jax.lax.dot_general(
        dy, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_MXU)


# ---------------------------------------------------------------------------
# tile selection + autotune (shared persisted cache)
# ---------------------------------------------------------------------------

BM_DEFAULT = 32
BN_DEFAULT = 256
BK_DEFAULT = 512
# the float forward's weight tile, a grid step's DMA: the most bytes, and the
# output columns it starts from (the sweep on the chip: PERF.md section 6,
# PR 37). Mosaic's default scoped fast memory is what the tiles must fit.
W_TILE_BYTES = 2 << 20
BN_FLOAT = 512
VMEM_DEFAULT_BYTES = 16 << 20


def _sig(e, k, n, bits, group, dtype) -> str:
    return f"gmm:{e}x{k}x{n}:{bits}b:g{group}:{jnp.dtype(dtype).name}"


def _div_pick(pref: int, dim: int) -> int:
    b = min(pref, dim)
    while dim % b:
        b //= 2
    return max(b, 1)


def _row_tile(e, m):
    """A row tile that holds a mean group, 32 to 128 rows: an expert's
    weights stream once, not once per 32 rows."""
    bm = BM_DEFAULT
    while bm < 128 and bm * e < m:
        bm *= 2
    return bm


def _whole_k_step(k):
    """The whole contraction in one k step where ``[k, 256]`` stays near
    1 MiB (the ``dx`` backward's, and the forward's before PR 37)."""
    return k if k <= 2048 and k % 128 == 0 else BK_DEFAULT


def _float_fwd_tile(k, n, dtype):
    """(bn, bk) of the float forward from the shape: the widest weight tile
    ``[bk, bn]`` within ``W_TILE_BYTES`` whatever ``k`` is. ``bk`` is the
    largest divisor of ``k`` that is a multiple of 128 lanes and fits beside
    ``BN_FLOAT`` columns (the whole contraction where that fits); a narrow
    contraction then widens ``bn`` while the tile stays within the bytes."""
    isz = jnp.dtype(dtype).itemsize
    bn = _div_pick(BN_FLOAT, n)
    fits = [d for d in range(128, k + 1, 128)
            if k % d == 0 and d * bn * isz <= W_TILE_BYTES]
    bk = max(fits) if fits else _div_pick(BK_DEFAULT, k)
    while n % (2 * bn) == 0 and 2 * bn * bk * isz <= W_TILE_BYTES:
        bn *= 2
    return bn, bk


def fwd_vmem_bytes(bm, bn, bk, k, dtype):
    """Fast memory the float forward's pipeline holds: two buffers each of
    the weight tile, the row tile and the output block, and the float32
    scratch where the contraction takes several steps."""
    isz = jnp.dtype(dtype).itemsize
    return (2 * isz * (bk * bn + bm * bk + bm * bn)
            + (bm * bn * 4 if bk < k else 0))


def _blocks_for(e, m, k, n, bits, group_size, dtype, which="fwd"):
    """(bm, bn, bk): bn/bk honor divisibility + scale-group alignment
    exactly like ``quant_matmul``; bm is free because the pack pads every
    group to a bm multiple (it only trades padding waste against MXU
    row occupancy). Without a tuned entry, float weights get tiles from
    the shape: a row tile that holds a mean group, and for the forward
    (``which="fwd"``) a weight tile of 1-2 MiB at every contraction width
    (:func:`_float_fwd_tile`); the ``dx`` backward (``which="bwd"``) keeps
    the whole contraction in one step where ``[k, 256]`` stays near 1 MiB,
    and quantized weights keep the defaults their scale groups tie them
    to."""
    hit = _atc.lookup(_sig(e, k, n, bits, group_size, dtype))
    if hit and len(hit) == 3:
        pm, pn, pk = hit
    else:
        pm, pn, pk = BM_DEFAULT, BN_DEFAULT, BK_DEFAULT
        if bits == 0:
            pm = _row_tile(e, m)
            if which == "fwd":
                pn, pk = _float_fwd_tile(k, n, dtype)
            else:
                pk = _whole_k_step(k)
    bm = max(8, _div_pick(pm, 1024))          # pow2 row tile >= sublane min
    bn = _div_pick(pn, n)
    k_ext = k // 2 if bits == 4 else k
    bk = (pk if pk == k_ext == group_size
          else _div_pick(pk, math.gcd(k_ext, group_size)))
    return bm, bn, bk


def _shape_ok(k, n, bits) -> bool:
    """Kernel eligibility on real TPUs: lane-aligned n, sublane-aligned k
    (int8/int4 weight tiles want 32-row sublanes; float 8). m is always
    fine — the ragged pack pads rows to the tile."""
    k_ext = k // 2 if bits == 4 else k
    return n % 128 == 0 and k_ext % (32 if bits else 8) == 0


def fwd_candidates(e, m, k, n, dtype):
    """The (bm, bn, bk) the float forward's rule ranges over at this shape:
    weight tiles ``[bk, bn]`` of 512 KiB to 4 MiB (``bn`` 256 to 2048 by
    doubling and the widest divisor of ``n`` under that; ``bk`` 512 up by
    doubling and ``k``, ``k / 2``, ``k / 3``) under the rule's row tile; the
    rule's own tile under the row tiles beside its own; and what the rule
    was before PR 37 (``bn`` 256; ``bk`` the whole ``k`` up to 2048, else
    512); all within the default fast memory."""
    isz = jnp.dtype(dtype).itemsize
    bm = _row_tile(e, m)
    widest = max((d for d in range(128, min(n, 2048) + 1, 128)
                  if n % d == 0), default=_div_pick(BN_DEFAULT, n))
    bns = sorted({b for b in (256, 512, 1024, 2048) if n % b == 0}
                 | {widest})
    bks = sorted({b for b in (512, 1024, 2048, 4096) if k % b == 0}
                 | {k // d for d in (1, 2, 3) if k % (128 * d) == 0})
    out = [(bm, BN_DEFAULT, _div_pick(_whole_k_step(k), k))]
    out += [(bm, bn, bk) for bn in bns for bk in bks
            if (512 << 10) <= bk * bn * isz <= (4 << 20)]
    out += [(r, *_float_fwd_tile(k, n, dtype))
            for r in (bm // 2, bm, bm * 2) if 16 <= r <= 256]
    return tuple(c for c in dict.fromkeys(out)
                 if fwd_vmem_bytes(*c, k, dtype) <= VMEM_DEFAULT_BYTES)


def autotune_grouped_matmul(e, m, k, n, bits=8, group_size=-1,
                            dtype=jnp.float32, candidates=None, iters=10,
                            fed=None, layers=None, timings=None):
    """Sweep (bm, bn, bk) for this grouped-GEMM signature and persist the
    winner on the shared cache. No-op off-TPU. ``candidates``: by default
    what the float forward's rule ranges over (:func:`fwd_candidates`), and
    for quantized weights the tiles their scale groups allow. ``fed=(experts,
    rows)``: ragged offsets like a decode step's, ``experts`` of the ``e``
    (evenly spread) hold ``rows`` rows each and the other rows of ``m`` lie
    past the last offset, as a chip's share of an expert-parallel layer
    leaves them; None: ``m // e`` rows in every group. ``layers`` (float
    weights): the stacked ``layer=`` form over that many layers, one after
    the other. ``timings``: a dict filled with ``{candidate: seconds a
    call}`` (a candidate the compiler refuses is left out)."""
    from ...observability import monotonic

    gs = k if group_size in (-1, None, 0) else int(group_size)
    if _interpret():
        return _blocks_for(e, m, k, n, bits, gs, dtype)
    if candidates is None:
        candidates = (fwd_candidates(e, m, k, n, dtype) if bits == 0 else
                      ((32, 256, 512), (8, 256, 512), (128, 256, 512),
                       (32, 512, 256), (16, 256, 1024)))
    _atc.load()
    sig = _sig(e, k, n, bits, gs, dtype)
    kx, kq, kf = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (m, k), dtype)
    if fed is None:
        offs = jnp.arange(e + 1, dtype=jnp.int32) * (m // e)
        offs = offs.at[-1].set(m)
    else:
        experts, rows = fed
        holds = jnp.zeros((e,), jnp.int32).at[
            (jnp.arange(experts) * e) // experts].set(rows)
        offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                jnp.cumsum(holds)]).astype(jnp.int32)
    scales = None
    if bits:
        kext = k // 2 if bits == 4 else k
        w = jax.random.randint(kq, (e, kext, n), -7 if bits == 4 else -127,
                               8 if bits == 4 else 128, jnp.int8)
        scales = jnp.ones((e, k // gs, n), jnp.float32)
    else:
        # one expert's values for every expert: a sweep times, it does not
        # compare, and a gigabyte of normal variates takes its own minute
        w = jnp.broadcast_to(jax.random.normal(kf, (k, n), dtype),
                             ((layers,) if layers else ()) + (e, k, n))
    saved = _atc.CACHE.get(sig)
    best, best_t = None, float("inf")
    for cand in candidates:
        _atc.CACHE[sig] = list(cand)
        try:
            step = jax.jit(functools.partial(grouped_matmul,
                                             use_kernel=True))
            calls = [dict(layer=jnp.int32(i % layers)) if layers else {}
                     for i in range(iters)]
            step(x, w, offs, scales, **calls[0]).block_until_ready()
            t0 = monotonic()
            for kw in calls:
                out = step(x, w, offs, scales, **kw)
            out.block_until_ready()
            t = monotonic() - t0
        except Exception:
            continue
        if timings is not None:
            timings[tuple(cand)] = t / iters
        if t < best_t:
            best, best_t = list(cand), t
    if best is not None:
        _atc.CACHE[sig] = best
        _atc.save()
    elif saved is None:
        _atc.CACHE.pop(sig, None)
    else:
        _atc.CACHE[sig] = saved
    return _blocks_for(e, m, k, n, bits, gs, dtype)


# ---------------------------------------------------------------------------
# fwd/bwd impls + custom VJP
# ---------------------------------------------------------------------------


def _fwd_impl(x2, weights, scales3d, group_offsets, k, bits, group_size,
              layer=None):
    m = x2.shape[0]
    e, _, n = weights.shape[-3:]
    bm, bn, bk = _blocks_for(e, m, k, n, bits, group_size, x2.dtype)
    dest, tile_gid, mp, n_live = _pack_layout(group_offsets, m, e, bm)
    x_pad = jnp.zeros((mp, k), x2.dtype).at[dest].set(x2)
    out_shape = jax.ShapeDtypeStruct((mp, n), jnp.float32)
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, kk, g: (i, j))
    x_spec = pl.BlockSpec((bm, bk), lambda i, j, kk, g: (i, kk))
    semantics = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    if bits == 0:
        # scalar prefetch: the tiles' groups and this call's layer. The
        # first grid bound is the count of row tiles that hold rows (a
        # dynamic grid dimension): the tiles past the ragged end are never
        # visited, and their output rows, which nothing gathers back, stay
        # unwritten
        def w_imap(i, j, kk, g, lay):
            # stacked weights [L, E, K, N]: the leading block index is this
            # call's layer
            return (() if layer is None else (lay[0],)) + (g[i], kk, j)

        w_block = (1, bk, bn) if layer is None else (None, 1, bk, bn)
        nk = k // bk
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_live, n // bn, nk),
            in_specs=[pl.BlockSpec((bm, bk), lambda i, j, kk, g, lay: (i, kk)),
                      pl.BlockSpec(w_block, w_imap)],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, g, lay: (i, j)),
            scratch_shapes=([pltpu.VMEM((bm, bn), jnp.float32)]
                            if nk > 1 else []))
        with _atc.x64_off():
            out = pl.pallas_call(
                _gmm_kernel, grid_spec=grid_spec,
                out_shape=jax.ShapeDtypeStruct((mp, n), x2.dtype),
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("arbitrary", "parallel",
                                         "arbitrary")),
                interpret=_interpret(), name=GROUPED_KERNEL_NAME,
            )(tile_gid,
              jnp.asarray(0 if layer is None else layer, jnp.int32).reshape(1),
              x_pad, weights)
        return out[dest]
    if bits == 8:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(mp // bm, n // bn, k // bk),
            in_specs=[
                x_spec,
                pl.BlockSpec((1, bk, bn),
                             lambda i, j, kk, g: (g[i], kk, j)),
                pl.BlockSpec(
                    (1, 1, bn),
                    lambda i, j, kk, g, _gs=group_size, _bk=bk:
                        (g[i], kk * _bk // _gs, j)),
            ],
            out_specs=o_spec)
        with _atc.x64_off():
            out = pl.pallas_call(
                _gmm_q_kernel, grid_spec=grid_spec, out_shape=out_shape,
                compiler_params=semantics, interpret=_interpret(),
            )(tile_gid, x_pad, weights, scales3d)
        return out[dest]
    k2 = k // 2
    nkb = k2 // bk
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(mp // bm, n // bn, nkb),
        in_specs=[
            x_spec,
            pl.BlockSpec((bm, bk),
                         lambda i, j, kk, g, _nkb=nkb: (i, kk + _nkb)),
            pl.BlockSpec((1, bk, bn), lambda i, j, kk, g: (g[i], kk, j)),
            pl.BlockSpec(
                (1, 1, bn),
                lambda i, j, kk, g, _gs=group_size, _bk=bk:
                    (g[i], kk * _bk // _gs, j)),
            pl.BlockSpec(
                (1, 1, bn),
                lambda i, j, kk, g, _gs=group_size, _bk=bk, _k2=k2:
                    (g[i], (_k2 + kk * _bk) // _gs, j)),
        ],
        out_specs=o_spec)
    with _atc.x64_off():
        out = pl.pallas_call(
            _gmm_q4_kernel, grid_spec=grid_spec, out_shape=out_shape,
            compiler_params=semantics, interpret=_interpret(),
        )(tile_gid, x_pad, x_pad, weights, scales3d, scales3d)
    return out[dest]


def _bwd_dx_impl(dy, weights, scales3d, group_offsets, k, bits, group_size,
                 x_dtype):
    """Grouped ``dx = dy @ W_g^T`` through the same padded-tile machinery
    (int4 falls back to the dequantized reference contraction)."""
    m, n = dy.shape
    e = weights.shape[0]
    if bits == 4:
        wfp = dequantize_grouped_weight(weights, scales3d, k=k,
                                        out_dtype=x_dtype)
        gid = token_group_ids(group_offsets, m)
        dxs = jax.lax.map(
            lambda we: jax.lax.dot_general(
                dy.astype(x_dtype), we, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_MXU),
            wfp)
        return jnp.take_along_axis(
            dxs, gid[None, :, None].astype(jnp.int32), axis=0)[0].astype(
                x_dtype)
    bm, bn, bk = _blocks_for(e, m, k, n, bits, group_size, x_dtype,
                             which="bwd")
    dest, tile_gid, mp, _ = _pack_layout(group_offsets, m, e, bm)
    dy_pad = jnp.zeros((mp, n), x_dtype).at[dest].set(dy.astype(x_dtype))
    out_shape = jax.ShapeDtypeStruct((mp, k), jnp.float32)
    dx_spec = pl.BlockSpec((bm, bk), lambda i, kk, j, g: (i, kk))
    dy_spec = pl.BlockSpec((bm, bn), lambda i, kk, j, g: (i, j))
    semantics = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    if bits == 0:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(mp // bm, k // bk, n // bn),
            in_specs=[
                dy_spec,
                pl.BlockSpec((1, bk, bn),
                             lambda i, kk, j, g: (g[i], kk, j)),
            ],
            out_specs=dx_spec)
        with _atc.x64_off():
            dx = pl.pallas_call(
                _gmm_bwd_kernel, grid_spec=grid_spec, out_shape=out_shape,
                compiler_params=semantics, interpret=_interpret(),
            )(tile_gid, dy_pad, weights)
        return dx[dest].astype(x_dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(mp // bm, k // bk, n // bn),
        in_specs=[
            dy_spec,
            pl.BlockSpec((1, bk, bn), lambda i, kk, j, g: (g[i], kk, j)),
            pl.BlockSpec(
                (1, 1, bn),
                lambda i, kk, j, g, _gs=group_size, _bk=bk:
                    (g[i], kk * _bk // _gs, j)),
        ],
        out_specs=dx_spec)
    with _atc.x64_off():
        dx = pl.pallas_call(
            _gmm_q_bwd_kernel, grid_spec=grid_spec, out_shape=out_shape,
            compiler_params=semantics, interpret=_interpret(),
        )(tile_gid, dy_pad, weights, scales3d)
    return dx[dest].astype(x_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gmm(static, x2, weights, scales3d, group_offsets):
    k, bits, group_size = static
    return _fwd_impl(x2, weights, scales3d, group_offsets, k, bits,
                     group_size)


def _gmm_fwd(static, x2, weights, scales3d, group_offsets):
    k, bits, group_size = static
    out = _fwd_impl(x2, weights, scales3d, group_offsets, k, bits,
                    group_size)
    # 0-size token carries x's dtype through the residuals (qmm trick)
    return out, (x2, weights, scales3d, group_offsets,
                 jnp.zeros((0,), x2.dtype))


def _gmm_bwd(static, res, dy):
    import numpy as np

    k, bits, group_size = static
    x2, weights, scales3d, group_offsets, dtype_tok = res
    dx = _bwd_dx_impl(dy, weights, scales3d, group_offsets, k, bits,
                      group_size, dtype_tok.dtype)
    doffs = np.zeros(group_offsets.shape, jax.dtypes.float0)
    if bits:
        # quantized weights + frozen PTQ scales are program constants
        dw = np.zeros(weights.shape, jax.dtypes.float0)
        ds = jnp.zeros_like(scales3d)
        return dx, dw, ds, doffs
    # segment outer-product: dw[e] = sum_{i in e} x_i^T dy_i
    m = x2.shape[0]
    e = weights.shape[0]
    oh = jax.nn.one_hot(token_group_ids(group_offsets, m), e,
                        dtype=jnp.float32)
    dw = jnp.einsum("me,mk,mn->ekn", oh, x2.astype(jnp.float32),
                    dy.astype(jnp.float32)).astype(weights.dtype)
    ds = jnp.zeros_like(scales3d)
    return dx, dw, ds, doffs


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


def grouped_matmul(x, weights, group_offsets, scales=None,
                   use_kernel: bool | None = None, layer=None):
    """Ragged grouped GEMM: ``out[i] = x[i] @ dequant(weights)[g(i)]``.

    ``layer`` (a traced int32 scalar; float weights, forward only):
    ``weights`` is a STACK ``[L, E, K, N]`` and the call reads layer
    ``layer`` of it through the kernel's block index maps — how a layer
    scan uses its experts without slicing a gigabyte of them out of the
    stack for every layer (the custom call would be handed a copy).

    x: ``[M, K]`` float rows PRE-SORTED by group (ascending group id);
    weights: ``[E, K, N]`` float/int8 or ``[E, K/2, N]`` nibble-packed
    int4 (per-expert :func:`quant_matmul.pack_int4` layout); group_offsets:
    ``[E+1]`` int prefix sum (``offsets[0] == 0``, ``offsets[E] == M``,
    monotone — empty groups allowed); scales: per-expert ``[E, N]``
    per-channel or ``[E, groups, N]`` per-group, required iff weights are
    quantized. ``use_kernel``: None = Pallas kernel on TPU when the shape
    tiles, jnp segment-matmul reference elsewhere; True forces the kernel
    (interpret mode off-TPU — CPU tests); False forces the reference.
    """
    if x.ndim != 2:
        raise ValueError(f"grouped_matmul wants 2D tokens [M, K], got "
                         f"{x.shape}")
    if layer is not None:
        if weights.ndim != 4 or weights.dtype == jnp.int8 \
                or scales is not None:
            raise ValueError(
                f"grouped_matmul(layer=) wants a float stack [L, E, K, N], "
                f"got {weights.dtype}{weights.shape}")
        k = x.shape[1]
        if use_kernel is None:
            use_kernel = use_kernel_default() and _shape_ok(
                k, weights.shape[-1], 0)
        if not use_kernel:
            return grouped_matmul_reference(
                x, jax.lax.dynamic_index_in_dim(weights, layer,
                                                keepdims=False),
                group_offsets)
        return _fwd_impl(x, weights, None, group_offsets.astype(jnp.int32),
                         k, 0, k, layer=layer).astype(x.dtype)
    if weights.ndim != 3:
        raise ValueError(f"grouped_matmul wants stacked weights [E, K, N], "
                         f"got {weights.shape}")
    m, k = x.shape
    e, _, n = weights.shape
    if group_offsets.shape != (e + 1,):
        raise ValueError(
            f"group_offsets must be [E+1]={e + 1}, got "
            f"{group_offsets.shape}")
    bits = _weight_bits(weights, k)
    if bits and scales is None:
        raise ValueError("quantized grouped_matmul needs scales")
    if not bits and scales is not None:
        raise ValueError("float grouped_matmul takes no scales")
    scales3d, group_size = ((None, k) if scales is None
                            else _norm_scales_grouped(scales, e, k, n))
    if use_kernel is None:
        use_kernel = use_kernel_default() and _shape_ok(k, n, bits)
    if not use_kernel:
        return grouped_matmul_reference(x, weights, group_offsets,
                                        scales=scales3d)
    offs = group_offsets.astype(jnp.int32)
    if scales3d is None:
        scales3d = jnp.zeros((e, 1, 0), jnp.float32)  # pytree placeholder
    y = _gmm((k, bits, group_size), x, weights, scales3d, offs)
    return y.astype(x.dtype)
