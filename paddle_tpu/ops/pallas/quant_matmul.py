"""Fused weight-only quantized GEMM — Pallas TPU kernel family.

The serving stack's decode batches are HBM-bandwidth-bound on WEIGHTS: a
decode step reads every layer matmul weight once per token batch, so the
matmul's arithmetic intensity is ~batch — far under the MXU roofline. The
reference's ``weight_only_linear`` (phi cutlass int8/int4 GEMM) buys that
bandwidth back on GPU by keeping weights quantized in memory and
dequantizing inside the GEMM; this module is the TPU-native spelling:

- weights stay **int8** — or **int4, two nibbles packed per byte** (an
  honest 4x over bf16) — in HBM;
- per-channel or per-group scales are applied **inside the kernel,
  tile-by-tile on the way into the MXU**: each grid step DMAs one int8/int4
  weight tile + its one scale row into VMEM, widens to the activation
  dtype, scales, and feeds the MXU — the full-precision weight never
  materializes in HBM;
- fp32 accumulation across k tiles (revisited output block, the flash/
  paged-kernel recurrence pattern), bias + cast epilogue outside (XLA
  fuses it into the copy).

int4 packing is **split-half**: byte ``i`` of the packed ``[K/2, N]`` array
holds original row ``i`` in its low nibble and row ``K/2 + i`` in its high
nibble. Unpacking is then two bit-ops and the contraction splits into
``x_lo @ W_lo + x_hi @ W_hi`` — no sublane interleave inside the kernel
(the packed tile's rows stay contiguous; the two halves ride two MXU dots).

Scales: shape ``[groups, N]`` with ``groups == 1`` meaning per-(output-)
channel; ``group_size = K // groups`` must be a multiple of the k tile so
every tile sees exactly ONE scale row (the BlockSpec index map selects it —
no in-kernel gather).

Backward (custom VJP): ``dx = dy @ dequant(W)^T`` runs the same
tile-dequant structure with the contraction transposed (weights stay
quantized in HBM for the backward too); ``d(quantized weight)`` and
``d(scales)`` are float0/zero — weight-only PTQ treats them as constants.

Interpret-capable on CPU like the other Pallas kernels;
:func:`quant_matmul_reference` (dequantize-then-matmul, what the previous
``nn.quant.weight_only_linear`` did) is the numerical oracle and the
non-TPU fallback. Tile autotune rides the shared ``autotune_cache``
(signatures ``qmm:{K}x{N}:{bits}b:g{gs}:{dtype}``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import autotune_cache as _atc

_MXU = jax.lax.Precision.DEFAULT


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def use_kernel_default() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# int4 nibble packing (split-half layout)
# ---------------------------------------------------------------------------


def pack_int4(q):
    """Pack an int8 array of int4 values (range [-8, 7]) along axis 0:
    ``[K, N] -> [K/2, N]``, byte ``i`` = row ``i`` (low nibble) | row
    ``K/2 + i`` (high nibble). K must be even."""
    k = q.shape[0]
    if k % 2:
        raise ValueError(f"int4 packing needs an even in-dim, got {k}")
    lo = q[: k // 2].astype(jnp.int32) & 0xF
    hi = q[k // 2:].astype(jnp.int32) & 0xF
    byte = (hi << 4) | lo                      # 0..255
    return jnp.where(byte > 127, byte - 256, byte).astype(jnp.int8)


def unpack_int4(packed):
    """Inverse of :func:`pack_int4`: ``[K/2, N] int8 -> [K, N] int8`` with
    values sign-extended from their 4-bit two's complement nibbles."""
    p = packed.astype(jnp.int32)
    lo = ((p & 0xF) ^ 8) - 8
    hi = (((p >> 4) & 0xF) ^ 8) - 8
    return jnp.concatenate([lo, hi], axis=0).astype(jnp.int8)


def _is_packed(qweight, k: int) -> bool:
    if qweight.shape[0] == k:
        return False
    if qweight.shape[0] * 2 == k:
        return True
    raise ValueError(
        f"quantized weight in-dim {qweight.shape[0]} matches neither K={k} "
        f"(int8) nor K/2={k // 2} (packed int4)")


def _norm_scales(scales, k: int, n: int):
    """Normalize scales to [groups, N]; returns (scales2d, group_size)."""
    s = scales.reshape(1, -1) if scales.ndim == 1 else scales
    if s.shape[-1] != n:
        raise ValueError(f"scales last dim {s.shape[-1]} != out dim {n}")
    groups = s.shape[0]
    if k % groups:
        raise ValueError(f"K={k} not divisible by {groups} scale groups")
    return s, k // groups


# ---------------------------------------------------------------------------
# jnp reference (oracle + non-TPU fallback)
# ---------------------------------------------------------------------------


def dequantize_weight(qweight, scales, k=None, out_dtype=jnp.float32):
    """Materialize the full-precision weight ``[K, N]``: widen and scale
    per group row. Packed int4 weights NEED ``k`` (the logical in-dim) to
    be recognized — a ``[K/2, N]`` byte array is indistinguishable from an
    int8 weight by shape alone, so without ``k`` the rows are taken as
    int8 values as-is."""
    if k is not None and _is_packed(qweight, k):
        qweight = unpack_int4(qweight)
    kk, n = qweight.shape
    s, group = _norm_scales(scales, kk, n)
    w = qweight.astype(out_dtype) * jnp.repeat(
        s.astype(out_dtype), group, axis=0)
    return w


def quant_matmul_reference(x, qweight, scales, bias=None):
    """Dequantize-then-matmul oracle: what a non-fused XLA implementation
    does (the full [K, N] weight materializes in the activation dtype).
    Numerically the golden for the kernel; also the non-TPU fallback."""
    k = x.shape[-1]
    w = dequantize_weight(qweight, scales, k=k, out_dtype=x.dtype)
    acc = jnp.promote_types(x.dtype, jnp.float32)   # f64 inputs stay f64
    y = jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=acc, precision=_MXU)
    if bias is not None:
        y = y + bias.astype(acc)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _qmm_kernel(x_ref, w_ref, s_ref, o_ref):
    """One [bm, bn] output tile accumulating over k tiles: widen the int8
    weight tile, scale by its ONE group row, dot on the MXU."""
    kstep = pl.program_id(2)

    @pl.when(kstep == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    w = w_ref[...].astype(x.dtype) * s_ref[...].astype(x.dtype)
    o_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_MXU)


def _qmm4_kernel(xl_ref, xh_ref, p_ref, sl_ref, sh_ref, o_ref):
    """int4 split-half tile: unpack both nibbles of the packed tile and run
    the two half-contractions (lo rows, hi rows) as two MXU dots."""
    kstep = pl.program_id(2)

    @pl.when(kstep == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    xl = xl_ref[...]
    p = p_ref[...].astype(jnp.int32)
    lo = (((p & 0xF) ^ 8) - 8).astype(xl.dtype) * sl_ref[...].astype(xl.dtype)
    hi = ((((p >> 4) & 0xF) ^ 8) - 8).astype(xl.dtype) * sh_ref[...].astype(
        xl.dtype)
    dims = (((1,), (0,)), ((), ()))
    o_ref[...] += (
        jax.lax.dot_general(xl, lo, dims,
                            preferred_element_type=jnp.float32,
                            precision=_MXU)
        + jax.lax.dot_general(xh_ref[...], hi, dims,
                              preferred_element_type=jnp.float32,
                              precision=_MXU))


def _qmm_bwd_kernel(dy_ref, w_ref, s_ref, dx_ref):
    """dx tile [bm, bk] accumulating over n tiles: dequant the weight tile
    and contract dy's n dim against it (dy @ W^T, weights stay int8)."""
    nstep = pl.program_id(2)

    @pl.when(nstep == 0)
    def _init():
        dx_ref[...] = jnp.zeros_like(dx_ref)

    dy = dy_ref[...]
    w = w_ref[...].astype(dy.dtype) * s_ref[...].astype(dy.dtype)
    dx_ref[...] += jax.lax.dot_general(
        dy, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_MXU)


def _qmm4_bwd_kernel(dy_ref, p_ref, sl_ref, sh_ref, dxl_ref, dxh_ref):
    nstep = pl.program_id(2)

    @pl.when(nstep == 0)
    def _init():
        dxl_ref[...] = jnp.zeros_like(dxl_ref)
        dxh_ref[...] = jnp.zeros_like(dxh_ref)

    dy = dy_ref[...]
    p = p_ref[...].astype(jnp.int32)
    lo = (((p & 0xF) ^ 8) - 8).astype(dy.dtype) * sl_ref[...].astype(dy.dtype)
    hi = ((((p >> 4) & 0xF) ^ 8) - 8).astype(dy.dtype) * sh_ref[...].astype(
        dy.dtype)
    dims = (((1,), (1,)), ((), ()))
    dxl_ref[...] += jax.lax.dot_general(
        dy, lo, dims, preferred_element_type=jnp.float32, precision=_MXU)
    dxh_ref[...] += jax.lax.dot_general(
        dy, hi, dims, preferred_element_type=jnp.float32, precision=_MXU)


# ---------------------------------------------------------------------------
# tile selection + autotune (shared persisted cache)
# ---------------------------------------------------------------------------

BM_DEFAULT = 128
BN_DEFAULT = 256
BK_DEFAULT = 512


def _sig(k, n, bits, group, dtype) -> str:
    return f"qmm:{k}x{n}:{bits}b:g{group}:{jnp.dtype(dtype).name}"


def _div_pick(pref: int, dim: int) -> int:
    """Largest block <= pref that divides dim (halving walk, >= 1)."""
    b = min(pref, dim)
    while dim % b:
        b //= 2
    return max(b, 1)


def _blocks_for(m, k, n, bits, group_size, dtype):
    """(bm, bn, bk) honoring divisibility AND group alignment: bk divides
    the (packed-half for int4) k extent and the group size, so each k tile
    sees exactly one scale row."""
    hit = _atc.lookup(_sig(k, n, bits, group_size, dtype))
    pm, pn, pk = (hit if hit and len(hit) == 3
                  else (BM_DEFAULT, BN_DEFAULT, BK_DEFAULT))
    bm = _div_pick(pm, m)
    bn = _div_pick(pn, n)
    # k tiles walk packed rows for int4; a tile must sit inside ONE scale
    # group in original-row units, so bk divides both extents (gcd)
    k_ext = k // 2 if bits == 4 else k
    bk = _div_pick(pk, math.gcd(k_ext, group_size))
    return bm, bn, bk


def _shape_ok(m, k, n, bits) -> bool:
    """Whether the compiled kernel can ride real-TPU tiling: lane-aligned
    n, sublane-aligned m/k (int8 weight tiles want 32-row sublanes)."""
    k_ext = k // 2 if bits == 4 else k
    return n % 128 == 0 and k_ext % 32 == 0 and m % 8 == 0


def autotune_quant_matmul(m, k, n, bits=8, group_size=-1,
                          dtype=jnp.bfloat16,
                          candidates=((128, 256, 512), (128, 512, 256),
                                      (256, 256, 256), (64, 256, 1024)),
                          iters=10):
    """Sweep (bm, bn, bk) for this GEMM signature on the current device and
    persist the winner on the shared autotune cache. No-op off-TPU."""
    from ...observability import monotonic

    if _interpret():
        return _blocks_for(m, k, n, bits, _group(group_size, k), dtype)
    _atc.load()
    gs = _group(group_size, k)
    sig = _sig(k, n, bits, gs, dtype)
    kx, kw4, kw8 = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (m, k), dtype)
    if bits == 4:
        qw = pack_int4(jax.random.randint(kw4, (k, n), -7, 8, jnp.int8))
    else:
        qw = jax.random.randint(kw8, (k, n), -127, 128, jnp.int8)
    s = jnp.ones((k // gs, n), jnp.float32)
    saved = _atc.CACHE.get(sig)
    best, best_t = None, float("inf")
    for cand in candidates:
        _atc.CACHE[sig] = list(cand)
        try:
            step = jax.jit(functools.partial(quant_matmul, use_kernel=True))
            step(x, qw, s).block_until_ready()
            t0 = monotonic()
            for _ in range(iters):
                out = step(x, qw, s)
            out.block_until_ready()
            t = monotonic() - t0
        except Exception:
            continue
        if t < best_t:
            best, best_t = list(cand), t
    if best is not None:
        _atc.CACHE[sig] = best
        _atc.save()
    elif saved is None:
        _atc.CACHE.pop(sig, None)
    else:
        _atc.CACHE[sig] = saved
    return _blocks_for(m, k, n, bits, gs, dtype)


def _group(group_size: int, k: int) -> int:
    return k if group_size in (-1, None, 0) else int(group_size)


# ---------------------------------------------------------------------------
# fwd/bwd impls + custom VJP
# ---------------------------------------------------------------------------


def _fwd_impl(x2, qweight, scales2d):
    m, k = x2.shape
    n = qweight.shape[1]
    packed = _is_packed(qweight, k)
    bits = 4 if packed else 8
    groups = scales2d.shape[0]
    group_size = k // groups
    bm, bn, bk = _blocks_for(m, k, n, bits, group_size, x2.dtype)
    out_shape = jax.ShapeDtypeStruct((m, n), jnp.float32)
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))
    s_lo = pl.BlockSpec(
        (1, bn), lambda i, j, kk: (kk * bk // group_size, j))
    if not packed:
        grid = (m // bm, n // bn, k // bk)
        with _atc.x64_off():
            out = pl.pallas_call(
                _qmm_kernel, grid=grid,
                in_specs=[
                    pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                    pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
                    s_lo,
                ],
                out_specs=o_spec, out_shape=out_shape,
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel", "parallel",
                                         "arbitrary")),
                interpret=_interpret(),
            )(x2, qweight, scales2d)
        return out
    k2 = k // 2
    nkb = k2 // bk                                  # packed-row k blocks
    s_hi = pl.BlockSpec(
        (1, bn), lambda i, j, kk: ((k2 + kk * bk) // group_size, j))
    grid = (m // bm, n // bn, nkb)
    with _atc.x64_off():
        out = pl.pallas_call(
            _qmm4_kernel, grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                pl.BlockSpec((bm, bk),
                             lambda i, j, kk, _nkb=nkb: (i, kk + _nkb)),
                pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
                s_lo, s_hi,
            ],
            out_specs=o_spec, out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=_interpret(),
        )(x2, x2, qweight, scales2d, scales2d)
    return out


def _bwd_impl(dy, qweight, scales2d, k, x_dtype):
    m, n = dy.shape
    packed = _is_packed(qweight, k)
    bits = 4 if packed else 8
    groups = scales2d.shape[0]
    group_size = k // groups
    bm, bn, bk = _blocks_for(m, k, n, bits, group_size, x_dtype)
    dyc = dy.astype(x_dtype)
    s_lo = pl.BlockSpec(
        (1, bn), lambda i, kk, j: (kk * bk // group_size, j))
    if not packed:
        grid = (m // bm, k // bk, n // bn)
        with _atc.x64_off():
            dx = pl.pallas_call(
                _qmm_bwd_kernel, grid=grid,
                in_specs=[
                    pl.BlockSpec((bm, bn), lambda i, kk, j: (i, j)),
                    pl.BlockSpec((bk, bn), lambda i, kk, j: (kk, j)),
                    s_lo,
                ],
                out_specs=pl.BlockSpec((bm, bk), lambda i, kk, j: (i, kk)),
                out_shape=jax.ShapeDtypeStruct((m, k), jnp.float32),
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel", "parallel",
                                         "arbitrary")),
                interpret=_interpret(),
            )(dyc, qweight, scales2d)
        return dx.astype(x_dtype)
    k2 = k // 2
    s_hi = pl.BlockSpec(
        (1, bn), lambda i, kk, j: ((k2 + kk * bk) // group_size, j))
    grid = (m // bm, k2 // bk, n // bn)
    half_spec = pl.BlockSpec((bm, bk), lambda i, kk, j: (i, kk))
    with _atc.x64_off():
        dxl, dxh = pl.pallas_call(
            _qmm4_bwd_kernel, grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bn), lambda i, kk, j: (i, j)),
                pl.BlockSpec((bk, bn), lambda i, kk, j: (kk, j)),
                s_lo, s_hi,
            ],
            out_specs=[half_spec, half_spec],
            out_shape=[jax.ShapeDtypeStruct((m, k2), jnp.float32)] * 2,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=_interpret(),
        )(dyc, qweight, scales2d, scales2d)
    return jnp.concatenate([dxl, dxh], axis=1).astype(x_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _qmm(k, x2, qweight, scales2d):
    return _fwd_impl(x2, qweight, scales2d)


def _qmm_fwd(k, x2, qweight, scales2d):
    # the 0-size token carries x's dtype through the residuals (a raw numpy
    # dtype is not a pytree leaf)
    return _fwd_impl(x2, qweight, scales2d), (qweight, scales2d,
                                              jnp.zeros((0,), x2.dtype))


def _qmm_bwd(k, res, dy):
    import numpy as np

    qweight, scales2d, dtype_tok = res
    dx = _bwd_impl(dy, qweight, scales2d, k, dtype_tok.dtype)
    # quantized weight + frozen PTQ scales are constants of the program
    dq = np.zeros(qweight.shape, jax.dtypes.float0)
    ds = jnp.zeros_like(scales2d)
    return dx, dq, ds


_qmm.defvjp(_qmm_fwd, _qmm_bwd)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


def quant_matmul(x, qweight, scales, bias=None, use_kernel: bool | None = None):
    """Fused weight-only quantized GEMM: ``y = x @ dequant(qweight) + bias``
    with the weight staying int8 (or packed int4) in HBM and scales applied
    in-kernel per tile.

    x: ``[..., K]`` float; qweight: ``[K, N]`` int8 or ``[K/2, N]``
    nibble-packed int4 (see :func:`pack_int4`); scales: ``[N]`` per-channel
    or ``[groups, N]`` per-group (``K % groups == 0``); bias: ``[N]`` or
    None. ``use_kernel``: None = Pallas kernel on TPU when the shape tiles,
    jnp reference elsewhere; True forces the kernel (interpret mode off-TPU
    — CPU tests); False forces the reference.
    """
    k = x.shape[-1]
    n = qweight.shape[-1]
    packed = _is_packed(qweight, k)
    scales2d, _ = _norm_scales(scales, k, n)
    lead = x.shape[:-1]
    m = int(math.prod(lead)) if lead else 1
    if use_kernel is None:
        use_kernel = use_kernel_default() and _shape_ok(
            m, k, n, 4 if packed else 8)
    if not use_kernel:
        return quant_matmul_reference(x, qweight, scales2d, bias=bias)
    x2 = x.reshape(m, k)
    y = _qmm(k, x2, qweight, scales2d)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype).reshape(*lead, n)
