"""Round-25 ragged grouped GEMM: the MoE expert-FFN Pallas kernel
(interpret mode on CPU) vs the jnp segment-matmul oracle across fp /
int8 / packed-int4 weights and ragged group layouts — empty experts,
all-tokens-one-expert, odd group sizes; the custom VJP; jit replay; and
the incubate surface routing.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.ops.pallas.grouped_matmul import (
    dequantize_grouped_weight, grouped_matmul, grouped_matmul_reference,
    token_group_ids)
from paddle_tpu.ops.pallas.quant_matmul import pack_int4

E, K, N = 4, 64, 128                     # kernel-eligible: n%128, k%32


def _quantize_stack(w, bits=8, group=-1):
    """Per-expert symmetric quantizer ([E, K, N] -> q stack + scales)."""
    qmax = 127.0 if bits == 8 else 7.0
    e, k, n = w.shape
    g = k if group in (-1, None) else group
    absmax = np.maximum(np.abs(w).reshape(e, k // g, g, n).max(2), 1e-8)
    s = (absmax / qmax).astype(np.float32)             # [E, groups, N]
    q = np.clip(np.round(w / np.repeat(s, g, axis=1)),
                -qmax, qmax).astype(np.int8)
    if bits == 4:
        q = np.asarray(jax.vmap(pack_int4)(jnp.asarray(q)))
    return q, (s[:, 0, :] if s.shape[1] == 1 else s)


def _offsets(counts):
    return jnp.asarray(np.concatenate([[0], np.cumsum(counts)]), jnp.int32)


RAGGED_SWEEP = [
    pytest.param([7, 0, 12, 5], id="empty-middle"),
    pytest.param([0, 0, 24, 0], id="all-one-expert"),
    pytest.param([1, 3, 13, 7], id="odd-sizes"),
    pytest.param([0, 0, 0, 0], id="no-tokens"),
    pytest.param([33, 1, 0, 2], id="over-tile"),      # group > bm row tile
]


# -- fp weights -------------------------------------------------------------


@pytest.mark.parametrize("counts", RAGGED_SWEEP)
def test_fp_kernel_matches_oracle(rng, counts):
    m = int(sum(counts))
    x = jnp.asarray(rng.randn(m, K), jnp.float32)
    w = jnp.asarray(rng.randn(E, K, N).astype(np.float32) * 0.1)
    offs = _offsets(counts)
    got = grouped_matmul(x, w, offs, use_kernel=True)
    ref = grouped_matmul_reference(x, w, offs)
    assert got.shape == (m, N)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_oracle_is_segment_matmul(rng):
    """The reference really is out[i] = x[i] @ w[g(i)] row by row."""
    counts = [3, 0, 5, 2]
    m = sum(counts)
    x = rng.randn(m, K).astype(np.float32)
    w = rng.randn(E, K, N).astype(np.float32) * 0.1
    offs = _offsets(counts)
    ref = np.asarray(grouped_matmul_reference(
        jnp.asarray(x), jnp.asarray(w), offs))
    gid = np.asarray(token_group_ids(offs, m))
    for i in range(m):
        np.testing.assert_allclose(ref[i], x[i] @ w[gid[i]],
                                   rtol=1e-5, atol=1e-5)


def test_token_group_ids_raggedness():
    offs = _offsets([2, 0, 3, 1])
    np.testing.assert_array_equal(
        np.asarray(token_group_ids(offs, 6)), [0, 0, 2, 2, 2, 3])


# -- quantized weights ------------------------------------------------------


@pytest.mark.parametrize("counts", RAGGED_SWEEP)
@pytest.mark.parametrize("group", [-1, 32])
def test_int8_kernel_bit_matches_oracle(rng, counts, group):
    """Single-k-tile int8: kernel and oracle share the exact dequant
    arithmetic — bit-identical outputs, not just close."""
    m = int(sum(counts))
    w = rng.randn(E, K, N).astype(np.float32) * 0.1
    q, s = _quantize_stack(w, bits=8, group=group)
    x = jnp.asarray(rng.randn(m, K), jnp.float32)
    offs = _offsets(counts)
    got = grouped_matmul(x, jnp.asarray(q), offs, scales=jnp.asarray(s),
                         use_kernel=True)
    ref = grouped_matmul_reference(x, jnp.asarray(q), offs,
                                   scales=jnp.asarray(s))
    if group == -1:
        # per-channel = one scale row = one dequant spelling: BIT-exact
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    else:
        # per-group scales apply inside the k accumulation — same math,
        # different fp summation order
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=3e-4, atol=2e-6)
    # and both track the fp weights they quantized from
    fp = grouped_matmul_reference(x, jnp.asarray(w), offs)
    if m:
        err = np.abs(np.asarray(got) - np.asarray(fp)).max()
        assert err < 0.5


def test_int4_kernel_matches_oracle(rng):
    counts = [9, 0, 14, 3]
    m = sum(counts)
    w = rng.randn(E, K, N).astype(np.float32) * 0.1
    q, s = _quantize_stack(w, bits=4, group=32)
    x = jnp.asarray(rng.randn(m, K), jnp.float32)
    offs = _offsets(counts)
    got = grouped_matmul(x, jnp.asarray(q), offs, scales=jnp.asarray(s),
                         use_kernel=True)
    ref = grouped_matmul_reference(x, jnp.asarray(q), offs,
                                   scales=jnp.asarray(s))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_dequantize_grouped_roundtrip(rng):
    w = rng.randn(E, K, N).astype(np.float32) * 0.1
    q, s = _quantize_stack(w, bits=8, group=16)
    wd = dequantize_grouped_weight(jnp.asarray(q), jnp.asarray(s), k=K)
    assert wd.shape == (E, K, N)
    assert float(np.abs(np.asarray(wd) - w).max()) < 5e-3


def test_scales_required_iff_quantized(rng):
    x = jnp.zeros((4, K), jnp.float32)
    offs = _offsets([4, 0, 0, 0])
    wq = jnp.zeros((E, K, N), jnp.int8)
    wf = jnp.zeros((E, K, N), jnp.float32)
    with pytest.raises(ValueError):
        grouped_matmul(x, wq, offs)                   # quantized, no scales
    with pytest.raises(ValueError):
        grouped_matmul(x, wf, offs, scales=jnp.ones((E, N)))


# -- custom VJP -------------------------------------------------------------


def test_vjp_dx_matches_oracle_grad(rng):
    counts = [5, 0, 9, 2]
    m = sum(counts)
    w = rng.randn(E, K, N).astype(np.float32) * 0.1
    q, s = _quantize_stack(w, bits=8)
    x = jnp.asarray(rng.randn(m, K), jnp.float32)
    offs = _offsets(counts)
    cot = jnp.asarray(rng.randn(m, N), jnp.float32)

    def loss_k(v):
        return jnp.sum(grouped_matmul(v, jnp.asarray(q), offs,
                                      scales=jnp.asarray(s),
                                      use_kernel=True) * cot)

    def loss_r(v):
        return jnp.sum(grouped_matmul_reference(
            v, jnp.asarray(q), offs, scales=jnp.asarray(s)) * cot)

    np.testing.assert_allclose(np.asarray(jax.grad(loss_k)(x)),
                               np.asarray(jax.grad(loss_r)(x)),
                               rtol=2e-5, atol=2e-5)


def test_vjp_dw_float_weights(rng):
    """Float expert stacks get a real dw (segment outer-product)."""
    counts = [3, 0, 4, 1]
    m = sum(counts)
    x = jnp.asarray(rng.randn(m, K), jnp.float32)
    w = jnp.asarray(rng.randn(E, K, N).astype(np.float32) * 0.1)
    offs = _offsets(counts)

    dw_k = jax.grad(lambda wv: jnp.sum(
        grouped_matmul(x, wv, offs, use_kernel=True) ** 2))(w)
    dw_r = jax.grad(lambda wv: jnp.sum(
        grouped_matmul_reference(x, wv, offs) ** 2))(w)
    np.testing.assert_allclose(np.asarray(dw_k), np.asarray(dw_r),
                               rtol=2e-5, atol=2e-5)
    # empty expert 1 accumulates nothing
    np.testing.assert_array_equal(np.asarray(dw_k[1]), 0.0)


# -- the float forward's tile rule (PR 37) ----------------------------------

# (e held, k, n, rows a fed expert at the decode rung, fed experts) of the
# three routed serving cells' two calls a layer, and each cell's static
# pairs at the rungs of its row ladder (rows x top-k)
CMDAPLUS, GLM, DSV2 = (256, 4352, 8192), (128, 2048), (192, 3264, 6144)
CELL_CALLS = [
    ("cmdaplus.gate_up", 16, 4096, 8192, CMDAPLUS),
    ("cmdaplus.down", 16, 4096, 4096, CMDAPLUS),
    ("glm.gate_up", 16, 6144, 4096, GLM),
    ("glm.down", 16, 2048, 6144, GLM),
    ("dsv2.gate_up", 64, 2048, 2816, DSV2),
    ("dsv2.down", 64, 1408, 2048, DSV2),
]
# the decode rung's offsets: (fed experts, rows each); the grid steps a call
# ran before PR 37 (a static grid over every row tile of the padded bound)
# and runs now (the row tiles that hold rows, on the wider tile)
DECODE = {"cmdaplus": ((14, 2), {8192: (6144, 448), 4096: (3072, 224)}),
          "glm": ((6, 1), {4096: (3840, 144), 6144: (480, 72)}),
          "dsv2": ((64, 3), {2816: (748, 704), 2048: (544, 256)})}


def _pallas_calls(jaxpr):
    for eq in jaxpr.eqns:
        if eq.primitive.name == "pallas_call":
            yield eq
        for v in eq.params.values():
            if hasattr(v, "jaxpr"):
                yield from _pallas_calls(v.jaxpr)


@pytest.mark.parametrize("name,e,k,n,m,decode", [
    pytest.param(name, e, k, n, m, m == rungs[0], id=f"{name}-{m}")
    for name, e, k, n, rungs in CELL_CALLS for m in rungs])
def test_float_forward_tile_rule_at_the_cells_shapes(name, e, k, n, m,
                                                     decode):
    """The rule held to its contract by counting from shapes: divisibility,
    lane and sublane alignment, a weight tile of 1-2 MiB, the reckoned fast
    memory under what the call asks for (nothing: Mosaic's default), the
    grid the traced call really has, and its step count for a decode step's
    offsets."""
    from paddle_tpu.ops.pallas import grouped_matmul as G

    bf = jnp.bfloat16
    bm, bn, bk = G._blocks_for(e, m, k, n, 0, k, bf)
    assert k % bk == 0 and n % bn == 0
    assert bk % 128 == 0 and bn % 128 == 0 and bm % 16 == 0
    tile = bk * bn * 2
    assert (1 << 20) <= tile <= G.W_TILE_BYTES        # k * n * 2 allows it
    assert G.fwd_vmem_bytes(bm, bn, bk, k, bf) <= G.VMEM_DEFAULT_BYTES
    # no smaller than before PR 37 ([k or 512, 256]), the row tile as it was
    pbm, pbn, pbk = G._blocks_for(e, m, k, n, 0, k, bf, which="bwd")
    assert (pbn, pbk) == (256, k if k <= 2048 else 512)
    assert tile >= pbn * pbk * 2 and bm == pbm
    assert (bm, bn, bk) in G.fwd_candidates(e, m, k, n, bf)

    # the call as traced at the cell's shapes (stacked, read by layer index)
    def sds(*shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype)

    jaxpr = jax.make_jaxpr(
        lambda x, w, o, lay: grouped_matmul(x, w, o, use_kernel=True,
                                            layer=lay))(
        sds(m, k), sds(2, e, k, n), sds(e + 1, dtype=jnp.int32),
        sds(dtype=jnp.int32))
    call, = _pallas_calls(jaxpr.jaxpr)
    gm = call.params["grid_mapping"]
    nj, nk = n // bn, k // bk
    assert gm.num_dynamic_grid_bounds == 1            # the live row tiles
    assert tuple(gm.grid[1:]) == (nj, nk)
    out, = call.params["out_avals"]
    assert out.dtype == bf and out.shape[1] == n      # written in bf16
    assert len(gm.scratch_avals) == (1 if nk > 1 else 0)

    # a decode step's offsets: live steps, none dead
    if not decode:
        return
    (fed, rows), steps = DECODE[name.split(".")[0]]
    holds = np.zeros(e, np.int64)
    holds[(np.arange(fed) * e) // fed] = rows
    offs = _offsets(holds)
    _, _, mp, n_live = G._pack_layout(offs, m, e, bm)
    before, now = steps[n]
    assert int(n_live) == fed * -(-rows // bm)
    assert int(n_live) * nj * nk == now
    assert (mp // pbm) * (n // pbn) * (k // pbk) == before


def _ragged_case(rng, e, m, k, n, counts, dtype, layers=None):
    x = jnp.asarray(rng.randn(m, k), dtype)
    w = jnp.asarray(rng.randn(*((layers,) if layers else ()), e, k, n)
                    .astype(np.float32) * 0.1, dtype)
    return x, w, _offsets(counts)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("form", ["stacked", "unstacked"])
@pytest.mark.parametrize("tile_bytes", [128 << 10, 2 << 20],
                         ids=["k-steps-several", "k-steps-1"])
def test_float_forward_matches_oracle_over_k_steps(rng, monkeypatch, dtype,
                                                   form, tile_bytes):
    """Several k steps through the float32 scratch (and one, written at
    once), empty groups, a group over a row tile, a dead tail (rows past the
    last offset, as a chip's share of the experts leaves them: their output
    is never read), rows in bf16 written in bf16, the stacked form."""
    from paddle_tpu.ops.pallas import grouped_matmul as G

    monkeypatch.setattr(G, "W_TILE_BYTES", tile_bytes)
    e, m, k, n = 6, 96, 512, 256
    _, bn, bk = G._blocks_for(e, m, k, n, 0, k, dtype)
    assert (k // bk > 1) == (tile_bytes < 1 << 20)   # bf16 2 steps, f32 4
    counts = [0, 37, 0, 5, 12, 0]                  # 54 rows held, 42 dead
    held = sum(counts)
    layers = 3 if form == "stacked" else None
    x, w, offs = _ragged_case(rng, e, m, k, n, counts, dtype, layers)
    if layers:
        got = jax.jit(lambda x, w, o, lay: grouped_matmul(
            x, w, o, use_kernel=True, layer=lay))(x, w, offs, jnp.int32(2))
        ref = grouped_matmul_reference(x, w[2], offs)
    else:
        got = grouped_matmul(x, w, offs, use_kernel=True)
        ref = grouped_matmul_reference(x, w, offs)
    assert got.dtype == dtype and got.shape == (m, n)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(got[:held], np.float32), np.asarray(ref[:held], np.float32),
        rtol=tol, atol=tol * float(jnp.abs(ref[:held]).max()))


def test_float_forward_k_steps_round_once(rng, monkeypatch):
    """bf16 rows: the partial sums stay float32 until the last k step, so
    two k steps read as the float32 product rounded once, not as two
    rounded halves added."""
    from paddle_tpu.ops.pallas import grouped_matmul as G

    monkeypatch.setattr(G, "W_TILE_BYTES", 128 << 10)
    e, m, k, n = 2, 32, 512, 256
    assert G._blocks_for(e, m, k, n, 0, k, jnp.bfloat16)[2] == 256
    x, w, offs = _ragged_case(rng, e, m, k, n, [20, 12], jnp.bfloat16)
    got = np.asarray(grouped_matmul(x, w, offs, use_kernel=True), np.float32)
    xf, wf = np.asarray(x, np.float32), np.asarray(w, np.float32)
    gid = np.asarray(token_group_ids(offs, m))
    exact = np.stack([xf[i] @ wf[gid[i]] for i in range(m)])
    once = np.asarray(jnp.asarray(exact).astype(jnp.bfloat16), np.float32)
    halves = np.asarray(sum(
        jnp.asarray(np.stack([xf[i, s] @ wf[gid[i], s] for i in range(m)])
                    ).astype(jnp.bfloat16).astype(jnp.float32)
        for s in (slice(0, 256), slice(256, 512))).astype(jnp.bfloat16),
        np.float32)
    assert np.abs(got - once).mean() < 0.5 * np.abs(got - halves).mean()


# -- jit plumbing -----------------------------------------------------------


def test_kernel_inside_jit_no_retrace(rng):
    w = jnp.asarray(rng.randn(E, K, N).astype(np.float32) * 0.1)
    calls = [0]

    @jax.jit
    def f(v, offs):
        calls[0] += 1
        return grouped_matmul(v, w, offs, use_kernel=True)

    x = jnp.asarray(rng.randn(16, K), jnp.float32)
    a = f(x, _offsets([4, 4, 4, 4]))
    b = f(x + 1.0, _offsets([16, 0, 0, 0]))   # different routing, one trace
    assert calls[0] == 1
    assert a.shape == b.shape == (16, N)


def test_autotune_noop_off_tpu():
    from paddle_tpu.ops.pallas.grouped_matmul import autotune_grouped_matmul

    bm, bn, bk = autotune_grouped_matmul(E, 128, K, N)
    assert N % bn == 0 and K % bk == 0 and bm >= 8


# -- incubate surface -------------------------------------------------------


def test_incubate_surface_routes_and_differentiates(rng):
    from paddle_tpu.incubate.nn import functional as F

    counts = [5, 0, 8, 3]
    m = sum(counts)
    x = rng.randn(m, K).astype(np.float32)
    w = rng.randn(E, K, N).astype(np.float32) * 0.1
    offs = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)

    xt = paddle.to_tensor(x, stop_gradient=False)
    out = F.grouped_matmul(xt, paddle.to_tensor(w), paddle.to_tensor(offs))
    ref = grouped_matmul_reference(jnp.asarray(x), jnp.asarray(w),
                                   jnp.asarray(offs))
    np.testing.assert_allclose(out.numpy(), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    out.sum().backward()
    assert xt.grad is not None and xt.grad.shape == [m, K]
