"""Fused MLP-block kernels — Pallas TPU with custom VJP.

The round-5 attribution (PERF_760M_r5_pre.json + mlp_roofline.py) showed the
flagship step's MLP branch carries ~1.3 ms/layer of elementwise overhead
(LN + gelu + residual HBM round-trips) over its pure-GEMM content — traffic
XLA does not fully fuse into the matmul epilogues. These kernels fuse the
ops XLA leaves unfused (the kernel-fusion lever of MPK, PAPERS.md):

- :func:`fused_layer_norm` — single-pass LayerNorm over the last axis:
  mean/var/normalize/scale/shift in ONE kernel, fp32 statistics regardless
  of input dtype, (mean, rstd) saved as residuals so the backward never
  re-reduces the forward. Variants: plain, residual-in (``x + residual`` is
  formed inside the kernel), residual-out (the summed stream is emitted as
  a second output for the next residual add) — the pre-LN transformer block
  pattern ``s = x + branch; y = LN(s)`` costs one HBM round-trip instead of
  three.
- :func:`fused_bias_gelu` / :func:`fused_gelu` — tanh-approximate GELU (the
  GPT activation) with optional bias epilogue; backward recomputes the
  cheap pointwise forward from the saved GEMM output instead of storing
  the activation.

Both directions are Pallas kernels: forward AND a custom-VJP backward that
produces dx plus per-block partial (dgamma, dbeta)/(dbias) reductions —
the cross-row sum is finished in XLA (one [nblocks, H] sum), so the kernel
needs no cross-program accumulation.

Block-size autotune rides the shared persisted cache
(``ops/pallas/autotune_cache.py``, the flash_attention pattern): signatures
``mlp-ln:{rows}x{h}:{dtype}:{fwd|bwd}`` / ``mlp-gelu:...``; an explicit
:func:`autotune_mlp` sweep stores winners in-process and on disk, and
``_rows_for`` consults the cache at every trace. Off-TPU every kernel runs
in interpret mode, so the CPU test suite exercises the real kernel bodies
numerically (``tests/test_fused_mlp.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import autotune_cache as _atc


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# Preferred row-block sizes (rows per grid program over the flattened
# [rows, hidden] view). LN blocks are [br, h]; gelu blocks are [br, 4h] at
# the MLP width, so its default is smaller to keep the fp32 intermediates
# comfortably inside VMEM. Autotune overrides per shape signature.
LN_ROWS = 512
GELU_ROWS = 256
# The gelu backward's Mosaic working set is ~24 B per block element (three
# double-buffered 2-byte bands + fp32 temporaries: a [256, 6144] bf16 block
# asks 38 MB when compiled for a v5e). Blocks are halved until they fit 3/4
# of the 16 MiB scoped-VMEM default.
_GELU_BLOCK_ELEMS = (12 << 20) // 24

_K0 = 0.7978845608028654  # sqrt(2/pi)
_A = 0.044715


def _pick_rows(pref: int, rows: int) -> int:
    b = min(pref, rows)
    while rows % b:
        b //= 2
    return max(b, 1)


def _sig(kind, rows, h, dtype, which) -> str:
    return f"mlp-{kind}:{rows}x{h}:{jnp.dtype(dtype).name}:{which}"


def _rows_for(kind, rows, h, dtype, which="fwd") -> int:
    hit = _atc.lookup(_sig(kind, rows, h, dtype, which))
    pref = hit[0] if hit else (LN_ROWS if kind == "ln" else GELU_ROWS)
    if kind == "gelu":
        while pref * h > _GELU_BLOCK_ELEMS and pref > 8:
            pref //= 2
    return _pick_rows(pref, rows)


def _shape_ok(rows: int, h: int, dtype) -> bool:
    """Whether [rows, h] can ride the compiled kernel on real hardware:
    full-h lane tiles and sublane-aligned row blocks."""
    if h % 128:
        return False
    sub = 16 if jnp.dtype(dtype).itemsize == 2 else 8
    return rows % sub == 0 and rows >= sub


def _use_kernel(use_kernel, rows, h, dtype) -> bool:
    if _interpret():
        # interpret mode has no tiling constraints; default off (CPU users
        # should not pay interpreter dispatch), force honors the caller
        # (model-path flags, tests)
        return bool(use_kernel)
    ok = _shape_ok(rows, h, dtype)
    if use_kernel is None:
        return ok
    return bool(use_kernel) and ok


# ---------------------------------------------------------------------------
# LayerNorm kernels
# ---------------------------------------------------------------------------


def _ln_fwd_kernel(*refs, eps, has_res):
    if has_res:
        x_ref, res_ref, g_ref, b_ref, y_ref, s_ref, mean_ref, rstd_ref = refs
    else:
        x_ref, g_ref, b_ref, y_ref, mean_ref, rstd_ref = refs
    x = x_ref[...].astype(jnp.float32)
    if has_res:
        s = x + res_ref[...].astype(jnp.float32)
        s_ref[...] = s.astype(s_ref.dtype)
    else:
        s = x
    mean = jnp.mean(s, axis=1, keepdims=True)
    c = s - mean
    var = jnp.mean(c * c, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = c * rstd
    g = g_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    y_ref[...] = (xhat * g + b).astype(y_ref.dtype)
    mean_ref[0, :] = mean[:, 0]
    rstd_ref[0, :] = rstd[:, 0]


def _ln_bwd_kernel(*refs, has_dso):
    if has_dso:
        (dy_ref, dso_ref, s_ref, mean_ref, rstd_ref, g_ref,
         dx_ref, dg_ref, db_ref) = refs
    else:
        dy_ref, s_ref, mean_ref, rstd_ref, g_ref, dx_ref, dg_ref, db_ref = refs
    dy = dy_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)
    mean = mean_ref[0, :][:, None]
    rstd = rstd_ref[0, :][:, None]
    g = g_ref[...].astype(jnp.float32)
    xhat = (s - mean) * rstd
    dg_ref[...] = jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_ref[...] = jnp.sum(dy, axis=0, keepdims=True)
    dxhat = dy * g
    m1 = jnp.mean(dxhat, axis=1, keepdims=True)
    m2 = jnp.mean(dxhat * xhat, axis=1, keepdims=True)
    ds = rstd * (dxhat - m1 - xhat * m2)
    if has_dso:
        ds = ds + dso_ref[...].astype(jnp.float32)
    dx_ref[...] = ds.astype(dx_ref.dtype)


def _row_specs(h, br, n):
    """n BlockSpecs of [br, h] row bands."""
    return [pl.BlockSpec((br, h), lambda i: (i, 0)) for _ in range(n)]


def _vec_spec(h):
    """[1, h] broadcast rows (gamma/beta/bias)."""
    return pl.BlockSpec((1, h), lambda i: (0, 0))


def _part_spec(h):
    """One [1, h] partial-sum row per program of a [nblk, 1, h] output: a
    [1, h] block over [nblk, h] breaks Mosaic's (8, 128) rule on the
    second-minor dim; with the unit dim the block's last two dims equal
    the array's."""
    return pl.BlockSpec((None, 1, h), lambda i: (i, 0, 0))


def _stat_spec(br):
    """[1, rows] fp32 per-row statistics, one [1, br] band per program."""
    return pl.BlockSpec((1, br), lambda i: (0, i))


def _ln_fwd_impl(x, res, g, b, eps):
    rows, h = x.shape
    br = _rows_for("ln", rows, h, x.dtype, "fwd")
    has_res = res is not None
    grid = (rows // br,)
    in_specs = _row_specs(h, br, 2 if has_res else 1) + [_vec_spec(h),
                                                         _vec_spec(h)]
    args = ([x, res] if has_res else [x]) + [g.reshape(1, h), b.reshape(1, h)]
    out_specs = _row_specs(h, br, 2 if has_res else 1) + [_stat_spec(br),
                                                          _stat_spec(br)]
    out_shape = ([jax.ShapeDtypeStruct((rows, h), x.dtype)]
                 * (2 if has_res else 1)) + [
        jax.ShapeDtypeStruct((1, rows), jnp.float32),
        jax.ShapeDtypeStruct((1, rows), jnp.float32),
    ]
    kern = functools.partial(_ln_fwd_kernel, eps=eps, has_res=has_res)
    with _atc.x64_off():
        outs = pl.pallas_call(
            kern, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, interpret=_interpret(),
        )(*args)
    if has_res:
        y, s, mean, rstd = outs
        return y, s, mean, rstd
    y, mean, rstd = outs
    return y, mean, rstd


def _ln_bwd_impl(dy, dso, s, mean, rstd, g, x_dtype, eps):
    rows, h = dy.shape
    br = _rows_for("ln", rows, h, dy.dtype, "bwd")
    has_dso = dso is not None
    grid = (rows // br,)
    nblk = rows // br
    in_specs = (_row_specs(h, br, 3 if has_dso else 2)
                + [_stat_spec(br), _stat_spec(br), _vec_spec(h)])
    args = ([dy, dso, s] if has_dso else [dy, s]) + [mean, rstd,
                                                     g.reshape(1, h)]
    out_specs = _row_specs(h, br, 1) + [_part_spec(h), _part_spec(h)]
    out_shape = [
        jax.ShapeDtypeStruct((rows, h), x_dtype),
        jax.ShapeDtypeStruct((nblk, 1, h), jnp.float32),
        jax.ShapeDtypeStruct((nblk, 1, h), jnp.float32),
    ]
    kern = functools.partial(_ln_bwd_kernel, has_dso=has_dso)
    with _atc.x64_off():
        dx, dg_part, db_part = pl.pallas_call(
            kern, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, interpret=_interpret(),
        )(*args)
    return dx, dg_part.sum(axis=(0, 1)), db_part.sum(axis=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ln(x, g, b, eps):
    y, _, _ = _ln_fwd_impl(x, None, g, b, eps)
    return y


def _ln_fwd(x, g, b, eps):
    from jax.ad_checkpoint import checkpoint_name

    y, mean, rstd = _ln_fwd_impl(x, None, g, b, eps)
    # ln_out-tagged residuals: under the train-step remat policy the stats
    # (and y) become saveable, so the rematerialized backward DCEs the
    # forward kernel instead of re-reducing (same contract as flash_out)
    y = checkpoint_name(y, "ln_out")
    mean = checkpoint_name(mean, "ln_out")
    rstd = checkpoint_name(rstd, "ln_out")
    return y, (x, mean, rstd, g)


def _ln_bwd(eps, res, dy):
    x, mean, rstd, g = res
    dx, dg, db = _ln_bwd_impl(dy, None, x, mean, rstd, g, x.dtype, eps)
    return dx, dg.astype(g.dtype), db.astype(g.dtype)


_ln.defvjp(_ln_fwd, _ln_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _ln_res(x, r, g, b, eps):
    y, s, _, _ = _ln_fwd_impl(x, r, g, b, eps)
    return y, s


def _ln_res_fwd(x, r, g, b, eps):
    from jax.ad_checkpoint import checkpoint_name

    y, s, mean, rstd = _ln_fwd_impl(x, r, g, b, eps)
    y = checkpoint_name(y, "ln_out")
    s = checkpoint_name(s, "ln_out")
    mean = checkpoint_name(mean, "ln_out")
    rstd = checkpoint_name(rstd, "ln_out")
    return (y, s), (s, mean, rstd, g)


def _ln_res_bwd(eps, res, cots):
    s, mean, rstd, g = res
    dy, ds_out = cots
    # s = x + r  =>  dL/dx = dL/dr = dLN/ds + ds_out, fused in-kernel
    dx, dg, db = _ln_bwd_impl(dy, ds_out, s, mean, rstd, g, s.dtype, eps)
    return dx, dx, dg.astype(g.dtype), db.astype(g.dtype)


_ln_res.defvjp(_ln_res_fwd, _ln_res_bwd)


# ---------------------------------------------------------------------------
# GELU kernels (tanh approximation — the GPT activation)
# ---------------------------------------------------------------------------


def _gelu_fwd_kernel(*refs, has_bias):
    if has_bias:
        x_ref, b_ref, y_ref = refs
    else:
        x_ref, y_ref = refs
    u = x_ref[...].astype(jnp.float32)
    if has_bias:
        u = u + b_ref[...].astype(jnp.float32)
    t = jnp.tanh(_K0 * (u + _A * u * u * u))
    y_ref[...] = (0.5 * u * (1.0 + t)).astype(y_ref.dtype)


def _gelu_bwd_kernel(*refs, has_bias):
    if has_bias:
        dy_ref, x_ref, b_ref, dx_ref, db_ref = refs
    else:
        dy_ref, x_ref, dx_ref = refs
    dy = dy_ref[...].astype(jnp.float32)
    u = x_ref[...].astype(jnp.float32)
    if has_bias:
        u = u + b_ref[...].astype(jnp.float32)
    u2 = u * u
    t = jnp.tanh(_K0 * (u + _A * u * u2))
    du = dy * (0.5 * (1.0 + t)
               + 0.5 * u * (1.0 - t * t) * _K0 * (1.0 + 3.0 * _A * u2))
    dx_ref[...] = du.astype(dx_ref.dtype)
    if has_bias:
        db_ref[...] = jnp.sum(du, axis=0, keepdims=True)


def _gelu_fwd_impl(x, b):
    rows, h = x.shape
    br = _rows_for("gelu", rows, h, x.dtype, "fwd")
    has_bias = b is not None
    grid = (rows // br,)
    in_specs = _row_specs(h, br, 1) + ([_vec_spec(h)] if has_bias else [])
    args = [x] + ([b.reshape(1, h)] if has_bias else [])
    kern = functools.partial(_gelu_fwd_kernel, has_bias=has_bias)
    with _atc.x64_off():
        y = pl.pallas_call(
            kern, grid=grid, in_specs=in_specs,
            out_specs=_row_specs(h, br, 1)[0],
            out_shape=jax.ShapeDtypeStruct((rows, h), x.dtype),
            interpret=_interpret(),
        )(*args)
    return y


def _gelu_bwd_impl(dy, x, b):
    rows, h = dy.shape
    br = _rows_for("gelu", rows, h, dy.dtype, "bwd")
    has_bias = b is not None
    grid = (rows // br,)
    nblk = rows // br
    in_specs = _row_specs(h, br, 2) + ([_vec_spec(h)] if has_bias else [])
    args = [dy, x] + ([b.reshape(1, h)] if has_bias else [])
    out_specs = _row_specs(h, br, 1)
    out_shape = [jax.ShapeDtypeStruct((rows, h), x.dtype)]
    if has_bias:
        out_specs.append(_part_spec(h))
        out_shape.append(jax.ShapeDtypeStruct((nblk, 1, h), jnp.float32))
    kern = functools.partial(_gelu_bwd_kernel, has_bias=has_bias)
    with _atc.x64_off():
        outs = pl.pallas_call(
            kern, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, interpret=_interpret(),
        )(*args)
    if has_bias:
        dx, db_part = outs
        return dx, db_part.sum(axis=(0, 1))
    return outs[0], None


@jax.custom_vjp
def _gelu(x):
    return _gelu_fwd_impl(x, None)


def _gelu_fwd(x):
    return _gelu_fwd_impl(x, None), (x,)


def _gelu_bwd(res, dy):
    (x,) = res
    dx, _ = _gelu_bwd_impl(dy, x, None)
    return (dx,)


_gelu.defvjp(_gelu_fwd, _gelu_bwd)


@jax.custom_vjp
def _bias_gelu(x, b):
    return _gelu_fwd_impl(x, b)


def _bias_gelu_fwd(x, b):
    # residual is x (the GEMM output the remat policy already saves); the
    # backward recomputes u = x + b in-kernel — one add, no saved activation
    return _gelu_fwd_impl(x, b), (x, b)


def _bias_gelu_bwd(res, dy):
    x, b = res
    dx, db = _gelu_bwd_impl(dy, x, b)
    return dx, db.astype(b.dtype)


_bias_gelu.defvjp(_bias_gelu_fwd, _bias_gelu_bwd)


# ---------------------------------------------------------------------------
# Reference (XLA) implementations — numerical oracle and fallback path
# ---------------------------------------------------------------------------


def ln_reference(x, g, b, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * g.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def gelu_reference(x, b=None):
    u = x if b is None else x + b
    return jax.nn.gelu(u, approximate=True)


# ---------------------------------------------------------------------------
# Public entry points ([..., h] arrays; leading dims flattened to rows)
# ---------------------------------------------------------------------------


def _flat(x):
    h = x.shape[-1]
    return x.reshape(-1, h), x.shape


def fused_layer_norm(x, gamma, beta, eps=1e-5, use_kernel=None):
    """Single-pass fused LayerNorm over the last axis (fp32 statistics).

    ``use_kernel``: None = auto (compiled kernel on TPU when the shape
    tiles, XLA reference otherwise); True forces the kernel (interpret mode
    off-TPU — CPU tests); False forces the reference path.
    """
    x2, shape = _flat(x)
    if not _use_kernel(use_kernel, x2.shape[0], x2.shape[1], x2.dtype):
        return ln_reference(x, gamma, beta, eps)
    return _ln(x2, gamma, beta, float(eps)).reshape(shape)


def fused_ln_residual(x, residual, gamma, beta, eps=1e-5, use_kernel=None):
    """Residual-in/residual-out fused LayerNorm:
    ``s = x + residual; y = LN(s)`` in one kernel. Returns ``(y, s)`` — s is
    the new residual stream for the following branch."""
    x2, shape = _flat(x)
    r2, _ = _flat(residual)
    if not _use_kernel(use_kernel, x2.shape[0], x2.shape[1], x2.dtype):
        s = x + residual
        return ln_reference(s, gamma, beta, eps), s
    y, s = _ln_res(x2, r2, gamma, beta, float(eps))
    return y.reshape(shape), s.reshape(shape)


def fused_gelu(x, use_kernel=None):
    """Fused tanh-approximate GELU."""
    x2, shape = _flat(x)
    if not _use_kernel(use_kernel, x2.shape[0], x2.shape[1], x2.dtype):
        return gelu_reference(x)
    return _gelu(x2).reshape(shape)


def fused_bias_gelu(x, bias, use_kernel=None):
    """Fused ``gelu(x + bias)`` epilogue (tanh approximation) — the GEMM
    epilogue XLA leaves as separate HBM round-trips at large widths."""
    if bias is None:
        return fused_gelu(x, use_kernel=use_kernel)
    x2, shape = _flat(x)
    if not _use_kernel(use_kernel, x2.shape[0], x2.shape[1], x2.dtype):
        return gelu_reference(x, bias)
    return _bias_gelu(x2, bias).reshape(shape)


# ---------------------------------------------------------------------------
# Autotune (shared persisted cache; flash_attention.autotune pattern)
# ---------------------------------------------------------------------------


def autotune_mlp(rows, h, dtype=jnp.bfloat16, kinds=("ln", "gelu"),
                 candidates=(128, 256, 512, 1024), iters=5):
    """Sweep the row-block size for this [rows, h] signature on the current
    device and persist the winners (fwd and bwd share one block — they run
    back-to-back in training and compete for the same VMEM). Returns
    ``{kind: rows_block}``. No-op (returns current choices) off-TPU."""
    from ...observability import monotonic

    out = {}
    if _interpret():
        for kind in kinds:
            out[kind] = _rows_for(kind, rows, h, dtype)
        return out
    _atc.load()
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (rows, h), dtype)
    g = jnp.ones((h,), dtype)
    b = jnp.zeros((h,), dtype)

    def ln_step():
        return jax.jit(lambda x_: jax.grad(
            lambda v: jnp.sum(_ln(v, g, b, 1e-5).astype(jnp.float32)))(x_))

    def gelu_step():
        return jax.jit(lambda x_: jax.grad(
            lambda v: jnp.sum(_bias_gelu(v, b).astype(jnp.float32)))(x_))

    for kind, make_step in (("ln", ln_step), ("gelu", gelu_step)):
        if kind not in kinds:
            continue
        sig_f = _sig(kind, rows, h, dtype, "fwd")
        sig_b = _sig(kind, rows, h, dtype, "bwd")
        saved = (_atc.CACHE.get(sig_f), _atc.CACHE.get(sig_b))
        best, best_t = None, float("inf")
        for br in candidates:
            if rows % min(br, rows):
                continue
            cand = [min(br, rows)]
            _atc.CACHE[sig_f] = cand
            _atc.CACHE[sig_b] = cand
            try:
                step = make_step()  # fresh closure: blocks read at trace
                step(x).block_until_ready()  # compile + warmup
                t0 = monotonic()
                for _ in range(iters):
                    r = step(x)
                r.block_until_ready()
                t = monotonic() - t0
            except Exception:
                continue
            if t < best_t:
                best, best_t = br, t
        if best is not None:
            _atc.CACHE[sig_f] = [best]
            _atc.CACHE[sig_b] = [best]
        else:  # no candidate ran: restore prior state
            for s_, val in zip((sig_f, sig_b), saved):
                if val is None:
                    _atc.CACHE.pop(s_, None)
                else:
                    _atc.CACHE[s_] = val
        out[kind] = _rows_for(kind, rows, h, dtype)
    _atc.save()
    return out
