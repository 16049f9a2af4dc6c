"""Paged decode-attention Pallas kernel vs the jnp gather reference
(interpret mode on CPU): ragged lengths, page sizes, GQA groups, bf16 leg,
empty slots, and the incubate.nn.functional surface.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import paged_attention as pa


def _case(rng, b, hq, hkv, d, page_size, pps, dtype=jnp.float32,
          num_extra_pages=3):
    num_pages = b * pps + num_extra_pages

    def t(*shape):
        return jnp.asarray(rng.randn(*shape) * 0.5, dtype)

    q = t(b, hq, d)
    kp = t(num_pages, hkv, page_size, d)
    vp = t(num_pages, hkv, page_size, d)
    # non-trivial page table: a random permutation of the pool, so a bug
    # that reads pages in pool order (ignoring the table) cannot pass
    pt = jnp.asarray(rng.permutation(num_pages)[:b * pps].reshape(b, pps),
                     jnp.int32)
    return q, kp, vp, pt


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2), (16, 1)],
                         ids=["mha", "gqa4", "mqa"])
@pytest.mark.parametrize("page_size", [8, 16, 32])
def test_kernel_matches_reference(rng, hq, hkv, page_size):
    b, d, pps = 4, 64, 5
    q, kp, vp, pt = _case(rng, b, hq, hkv, d, page_size, pps)
    max_len = page_size * pps
    # ragged occupancy: empty slot, single token, mid-page, page-aligned,
    # full — clipped to batch size
    lens_all = [0, 1, page_size + 3, 2 * page_size, max_len]
    lens = jnp.asarray(lens_all[:b], jnp.int32)
    ref = pa.paged_attention_reference(q, kp, vp, pt, lens)
    out = pa.paged_attention(q, kp, vp, pt, lens, use_kernel=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_kernel_matches_reference_bf16(rng):
    b, hq, hkv, d, page_size, pps = 4, 8, 4, 64, 16, 4
    q, kp, vp, pt = _case(rng, b, hq, hkv, d, page_size, pps,
                          dtype=jnp.bfloat16)
    lens = jnp.asarray([5, 64, 33, 17], jnp.int32)
    ref = pa.paged_attention_reference(q, kp, vp, pt, lens)
    out = pa.paged_attention(q, kp, vp, pt, lens, use_kernel=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_empty_slots_produce_zeros(rng):
    b, hq, hkv, d, page_size, pps = 3, 4, 4, 32, 8, 3
    q, kp, vp, pt = _case(rng, b, hq, hkv, d, page_size, pps)
    lens = jnp.asarray([0, 10, 0], jnp.int32)
    for uk in (False, True):
        out = np.asarray(pa.paged_attention(q, kp, vp, pt, lens,
                                            use_kernel=uk))
        assert np.all(out[0] == 0) and np.all(out[2] == 0)
        assert np.any(out[1] != 0)


def test_unallocated_page_entries_are_safe(rng):
    """-1 (unallocated) page-table entries past each length must not read
    out of bounds or poison the output."""
    b, hq, hkv, d, page_size, pps = 2, 4, 4, 32, 8, 4
    q, kp, vp, pt = _case(rng, b, hq, hkv, d, page_size, pps)
    lens = jnp.asarray([9, 3], jnp.int32)  # uses 2 pages / 1 page
    pt = np.asarray(pt).copy()
    pt[0, 2:] = -1
    pt[1, 1:] = -1
    pt = jnp.asarray(pt)
    ref = pa.paged_attention_reference(q, kp, vp, pt, lens)
    out = pa.paged_attention(q, kp, vp, pt, lens, use_kernel=True)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_reference_matches_dense_attention(rng):
    """The gather reference itself vs plain dense softmax attention over
    the linearized cache — anchors both implementations to first
    principles."""
    import math

    b, hq, hkv, d, page_size, pps = 2, 6, 2, 16, 4, 4
    q, kp, vp, pt = _case(rng, b, hq, hkv, d, page_size, pps)
    lens_np = np.asarray([13, 7])
    lens = jnp.asarray(lens_np, jnp.int32)
    out = np.asarray(pa.paged_attention_reference(q, kp, vp, pt, lens))
    group = hq // hkv
    for bi in range(b):
        L = int(lens_np[bi])
        pages = np.asarray(pt)[bi]
        # pool pages are head-major [hkv, page_size, d]
        k_lin = np.asarray(kp)[pages].swapaxes(1, 2).reshape(-1, hkv, d)[:L]
        v_lin = np.asarray(vp)[pages].swapaxes(1, 2).reshape(-1, hkv, d)[:L]
        for h in range(hq):
            kv_h = h // group
            s = (k_lin[:, kv_h] @ np.asarray(q)[bi, h]) / math.sqrt(d)
            p = np.exp(s - s.max())
            p /= p.sum()
            want = p @ v_lin[:, kv_h]
            np.testing.assert_allclose(out[bi, h], want, rtol=1e-5,
                                       atol=1e-5)


def test_incubate_functional_surface(rng):
    """paddle.incubate.nn.functional.paged_attention: Tensor in/out, output
    is non-differentiable (decode-only op)."""
    import paddle_tpu as paddle
    from paddle_tpu.incubate.nn import functional as FI

    b, hq, hkv, d, page_size, pps = 2, 4, 2, 16, 8, 2
    q, kp, vp, pt = _case(rng, b, hq, hkv, d, page_size, pps)
    lens = jnp.asarray([10, 4], jnp.int32)
    out = FI.paged_attention(
        paddle.to_tensor(np.asarray(q)), paddle.to_tensor(np.asarray(kp)),
        paddle.to_tensor(np.asarray(vp)),
        paddle.to_tensor(np.asarray(pt)),
        paddle.to_tensor(np.asarray(lens)))
    assert out.stop_gradient  # registered non-diff
    ref = pa.paged_attention_reference(q, kp, vp, pt, lens)
    np.testing.assert_allclose(out.numpy(), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# -- ragged (unified-step) kernel -------------------------------------------


def _ragged_case(rng, b, c, hq, hkv, d, page_size, pps, dtype=jnp.float32):
    num_pages = b * pps + 3

    def t(*shape):
        return jnp.asarray(rng.randn(*shape) * 0.5, dtype)

    q = t(b, c, hq, d)
    kp = t(num_pages, hkv, page_size, d)
    vp = t(num_pages, hkv, page_size, d)
    pt = jnp.asarray(rng.permutation(num_pages)[:b * pps].reshape(b, pps),
                     jnp.int32)
    return q, kp, vp, pt


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)], ids=["mha", "gqa4"])
def test_ragged_kernel_matches_reference(rng, hq, hkv):
    """Mixed ragged step: decode lane (1 token), full prefill chunk,
    partial chunk, idle lane — kernel == gather oracle on the valid rows."""
    b, c, d, page_size, pps = 4, 8, 32, 8, 4
    q, kp, vp, pt = _ragged_case(rng, b, c, hq, hkv, d, page_size, pps)
    #            decode  full-chunk  partial  idle
    q_lens = jnp.asarray([1, c, 3, 0], jnp.int32)
    kv_lens = jnp.asarray([17, c, 11, 0], jnp.int32)  # lane 1: pure prefill
    ref = pa.ragged_paged_attention_reference(q, kp, vp, pt, kv_lens, q_lens)
    out = pa.ragged_paged_attention(q, kp, vp, pt, kv_lens, q_lens,
                                    use_kernel=True)
    ql = np.asarray(q_lens)
    for bi in range(b):  # rows past q_lens are unspecified for the kernel
        np.testing.assert_allclose(np.asarray(out)[bi, :ql[bi]],
                                   np.asarray(ref)[bi, :ql[bi]],
                                   rtol=2e-5, atol=2e-5)


def test_ragged_causal_within_chunk(rng):
    """Each chunk token must see exactly its own prefix: feeding a context
    in one ragged chunk == feeding it token-by-token (decode shape)."""
    b, c, hq, hkv, d, page_size, pps = 1, 8, 4, 4, 16, 4, 4
    q, kp, vp, pt = _ragged_case(rng, b, c, hq, hkv, d, page_size, pps)
    n = 6
    # one-shot: n tokens in a single chunk over an empty cache; K/V for the
    # chunk already live at positions 0..n-1 (the unified step writes
    # before attending) — emulate by using the pages as-is
    q_lens = jnp.asarray([n], jnp.int32)
    kv_lens = jnp.asarray([n], jnp.int32)
    chunked = pa.ragged_paged_attention(q, kp, vp, pt, kv_lens, q_lens,
                                        use_kernel=True)
    # token-by-token: token t attends positions 0..t
    for t in range(n):
        one = pa.ragged_paged_attention(
            q[:, t:t + 1], kp, vp, pt,
            jnp.asarray([t + 1], jnp.int32), jnp.asarray([1], jnp.int32),
            use_kernel=True)
        np.testing.assert_allclose(np.asarray(chunked)[0, t],
                                   np.asarray(one)[0, 0],
                                   rtol=2e-5, atol=2e-5)


def test_ragged_decode_lane_matches_decode_kernel(rng):
    """A chunk=1 ragged step reproduces the round-7 decode kernel: both
    attend the same ``length`` cached tokens (q_lens=1 makes the in-chunk
    causal limit collapse to kv_lens)."""
    b, hq, hkv, d, page_size, pps = 3, 8, 2, 32, 8, 3
    q, kp, vp, pt = _ragged_case(rng, b, 1, hq, hkv, d, page_size, pps)
    lens = jnp.asarray([9, 1, 20], jnp.int32)
    dec = pa.paged_attention(q[:, 0], kp, vp, pt, lens, use_kernel=True)
    rag = pa.ragged_paged_attention(q, kp, vp, pt, lens,
                                    jnp.ones((b,), jnp.int32),
                                    use_kernel=True)
    np.testing.assert_allclose(np.asarray(rag)[:, 0], np.asarray(dec),
                               rtol=2e-5, atol=2e-5)


def test_ragged_bf16(rng):
    b, c, hq, hkv, d, page_size, pps = 2, 8, 8, 4, 64, 16, 2
    q, kp, vp, pt = _ragged_case(rng, b, c, hq, hkv, d, page_size, pps,
                                 dtype=jnp.bfloat16)
    q_lens = jnp.asarray([5, 1], jnp.int32)
    kv_lens = jnp.asarray([21, 13], jnp.int32)
    ref = pa.ragged_paged_attention_reference(q, kp, vp, pt, kv_lens, q_lens)
    out = pa.ragged_paged_attention(q, kp, vp, pt, kv_lens, q_lens,
                                    use_kernel=True)
    assert out.dtype == jnp.bfloat16
    ql = np.asarray(q_lens)
    for bi in range(b):
        np.testing.assert_allclose(
            np.asarray(out, np.float32)[bi, :ql[bi]],
            np.asarray(ref, np.float32)[bi, :ql[bi]],
            rtol=3e-2, atol=3e-2)


def test_ragged_incubate_functional_surface(rng):
    """paddle.incubate.nn.functional.ragged_paged_attention: Tensor
    in/out, non-differentiable (decode-only serving op)."""
    import paddle_tpu as paddle
    from paddle_tpu.incubate.nn import functional as FI

    b, c, hq, hkv, d, page_size, pps = 2, 4, 4, 2, 16, 8, 2
    q, kp, vp, pt = _ragged_case(rng, b, c, hq, hkv, d, page_size, pps)
    q_lens = jnp.asarray([3, 1], jnp.int32)
    kv_lens = jnp.asarray([10, 4], jnp.int32)
    out = FI.ragged_paged_attention(
        paddle.to_tensor(np.asarray(q)), paddle.to_tensor(np.asarray(kp)),
        paddle.to_tensor(np.asarray(vp)),
        paddle.to_tensor(np.asarray(pt)),
        paddle.to_tensor(np.asarray(kv_lens)),
        paddle.to_tensor(np.asarray(q_lens)))
    assert out.stop_gradient  # registered non-diff
    ref = pa.ragged_paged_attention_reference(q, kp, vp, pt, kv_lens,
                                              q_lens)
    np.testing.assert_allclose(out.numpy(), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_chunk_size_autotune_cache_plumbing(monkeypatch):
    from paddle_tpu.ops.pallas import autotune_cache as atc

    assert pa.preferred_chunk_size(8, 8, 64) == pa.CHUNK_DEFAULT
    sig = pa._chunk_sig(8, 8, 64, jnp.float32)
    atc.load()
    monkeypatch.setitem(atc.CACHE, sig, [32])
    assert pa.preferred_chunk_size(8, 8, 64, jnp.float32) == 32
    assert pa.autotune_chunk_size(2, 8, 8, 64, dtype=jnp.float32) == 32


def test_page_size_autotune_cache_plumbing(tmp_path, monkeypatch):
    """preferred_page_size: default off-cache, cache hit wins; the CPU
    autotune is a no-op returning the preference (sweeps are TPU-only)."""
    from paddle_tpu.ops.pallas import autotune_cache as atc

    assert pa.preferred_page_size(8, 8, 64) == pa.PAGE_SIZE_DEFAULT
    sig = pa._sig(8, 8, 64, jnp.float32)
    atc.load()
    monkeypatch.setitem(atc.CACHE, sig, [32])
    assert pa.preferred_page_size(8, 8, 64, jnp.float32) == 32
    assert pa.autotune_page_size(2, 8, 8, 64, dtype=jnp.float32) == 32


def test_scale_override(rng):
    b, hq, hkv, d, page_size, pps = 2, 4, 4, 16, 8, 2
    q, kp, vp, pt = _case(rng, b, hq, hkv, d, page_size, pps)
    lens = jnp.asarray([9, 12], jnp.int32)
    for uk in (False, True):
        a = np.asarray(pa.paged_attention(q, kp, vp, pt, lens, scale=0.5,
                                          use_kernel=uk))
        b_ = np.asarray(pa.paged_attention(q, kp, vp, pt, lens, scale=0.05,
                                           use_kernel=uk))
        assert np.abs(a - b_).max() > 1e-4  # scale actually flows through
    k_ref = pa.paged_attention_reference(q, kp, vp, pt, lens, scale=0.5)
    k_out = pa.paged_attention(q, kp, vp, pt, lens, scale=0.5,
                               use_kernel=True)
    np.testing.assert_allclose(np.asarray(k_out), np.asarray(k_ref),
                               rtol=2e-5, atol=2e-5)


# -- round 10: int8-KV ragged attention (fused in-kernel dequant) -----------


def _quant_pools(kp, vp):
    """Per-token-per-head symmetric int8 of fp pools + fp32 scale planes
    (the paged_write_packed_quant layout)."""
    def one(p):
        pf = np.asarray(p, np.float32)
        am = np.maximum(np.abs(pf).max(-1), 1e-8)
        s = (am / 127.0).astype(np.float32)
        q = np.clip(np.round(pf / s[..., None]), -127, 127).astype(np.int8)
        return jnp.asarray(q), jnp.asarray(s)

    kq, ks = one(kp)
    vq, vs = one(vp)
    return kq, ks, vq, vs


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)], ids=["mha", "gqa4"])
def test_ragged_int8_kv_kernel_matches_reference(rng, hq, hkv):
    """The int8-KV kernel (scale blocks dequantized in VMEM) against the
    gather-dequant reference, mixed decode/prefill/idle lanes."""
    b, c, d, page_size, pps = 3, 4, 16, 8, 3
    q, kp, vp, pt = _ragged_case(rng, b, c, hq, hkv, d, page_size, pps)
    kq, ks, vq, vs = _quant_pools(kp, vp)
    kv_lens = jnp.asarray([17, 1, 0], jnp.int32)
    q_lens = jnp.asarray([4, 1, 0], jnp.int32)
    ref = pa.ragged_paged_attention_reference(
        q, kq, vq, pt, kv_lens, q_lens, k_scales=ks, v_scales=vs)
    out = pa.ragged_paged_attention(
        q, kq, vq, pt, kv_lens, q_lens, use_kernel=True,
        k_scales=ks, v_scales=vs)
    # rows past q_lens are unspecified kernel garbage: compare valid only
    for i in range(b):
        n = int(q_lens[i])
        np.testing.assert_allclose(np.asarray(out)[i, :n],
                                   np.asarray(ref)[i, :n],
                                   rtol=2e-5, atol=2e-5)


def test_ragged_int8_kv_close_to_fp(rng):
    """int8 quantization error bound vs the fp attention (the serving
    accuracy contract's attention leg)."""
    b, c, hq, hkv, d, page_size, pps = 2, 4, 4, 4, 16, 8, 2
    q, kp, vp, pt = _ragged_case(rng, b, c, hq, hkv, d, page_size, pps)
    kq, ks, vq, vs = _quant_pools(kp, vp)
    kv_lens = jnp.asarray([13, 8], jnp.int32)
    q_lens = jnp.asarray([4, 4], jnp.int32)
    fp = pa.ragged_paged_attention_reference(q, kp, vp, pt, kv_lens, q_lens)
    q8 = pa.ragged_paged_attention(q, kq, vq, pt, kv_lens, q_lens,
                                   use_kernel=True, k_scales=ks,
                                   v_scales=vs)
    assert np.abs(np.asarray(q8) - np.asarray(fp)).max() < 0.05


def test_paged_write_packed_quant_roundtrip(rng):
    """Quantize-on-write: the scattered int8 rows dequantize back to the
    written tokens within the per-head absmax/127 bound; padding and
    unallocated positions drop."""
    from paddle_tpu.inference.kv_cache import paged_write_packed_quant

    num_pages, page_size, h, d = 4, 4, 2, 8
    pages = jnp.zeros((num_pages, h, page_size, d), jnp.int8)
    scales = jnp.zeros((num_pages, h, page_size), jnp.float32)
    pt = jnp.asarray([[0, 2], [3, -1]], jnp.int32)
    toks = jnp.asarray(rng.randn(3, h, d), jnp.float32)
    tok_slot = jnp.asarray([0, 0, -1], jnp.int32)   # last = padding
    tok_pos = jnp.asarray([1, 5, 0], jnp.int32)     # page 0 row 1, page 2 row 1
    pages, scales = paged_write_packed_quant(pages, scales, toks, pt,
                                             tok_slot, tok_pos, page_size)
    got0 = np.asarray(pages)[0, :, 1] * np.asarray(scales)[0, :, 1][:, None]
    got1 = np.asarray(pages)[2, :, 1] * np.asarray(scales)[2, :, 1][:, None]
    for got, want in ((got0, np.asarray(toks)[0]),
                      (got1, np.asarray(toks)[1])):
        bound = np.abs(want).max(-1, keepdims=True) / 127 + 1e-6
        assert (np.abs(got - want) <= bound).all()
    # padding token wrote nowhere: only the two target rows are nonzero
    assert int((np.asarray(scales) != 0).sum()) == 2 * h


# -- PR 27: the stacked pool, addressed by layer index ----------------------

LAYERS = 3


def _stack(rng, pool):
    """LAYERS different pools of ``pool``'s shape and dtype, stacked."""
    noise = rng.randn(LAYERS, *pool.shape) * 0.5
    if jnp.issubdtype(pool.dtype, jnp.integer):
        return jnp.asarray(np.clip(np.round(noise * 60), -127, 127),
                           pool.dtype)
    if pool.ndim == 3:                      # a scale plane: positive
        noise = np.abs(noise) + 0.01
    return jnp.asarray(noise, pool.dtype)


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("kv", ["fp", "int8"])
@pytest.mark.parametrize("use_kernel", [True, False],
                         ids=["kernel", "reference"])
def test_ragged_layer_form_equals_pool_of_that_layer(rng, use_kernel, kv,
                                                     layer):
    """``layer=i`` on the stacked pools reads exactly what the 4-D form
    reads from ``pools[i]``: the same kernel body behind another index map
    (bit-equal), the same oracle behind ``pools[layer]``."""
    b, c, hq, hkv, d, page_size, pps = 3, 4, 8, 2, 16, 8, 3
    q, kp, vp, pt = _ragged_case(rng, b, c, hq, hkv, d, page_size, pps)
    kv_lens = jnp.asarray([17, 4, 0], jnp.int32)
    q_lens = jnp.asarray([1, 4, 0], jnp.int32)
    if kv == "int8":
        kp, ks, vp, vs = _quant_pools(kp, vp)
        stacks = [_stack(rng, p) for p in (kp, vp, ks, vs)]
    else:
        stacks = [_stack(rng, p) for p in (kp, vp)] + [None, None]
    k5, v5, ks4, vs4 = stacks
    one = [None if s is None else s[layer] for s in stacks]
    want = pa.ragged_paged_attention(
        q, one[0], one[1], pt, kv_lens, q_lens, use_kernel=use_kernel,
        k_scales=one[2], v_scales=one[3])
    # a traced index, as the layer scan hands it over
    got = jax.jit(lambda li: pa.ragged_paged_attention(
        q, k5, v5, pt, kv_lens, q_lens, use_kernel=use_kernel,
        k_scales=ks4, v_scales=vs4, layer=li))(jnp.int32(layer))
    ql = np.asarray(q_lens)
    for bi in range(b):  # rows past q_lens are unspecified for the kernel
        np.testing.assert_array_equal(np.asarray(got)[bi, :ql[bi]],
                                      np.asarray(want)[bi, :ql[bi]])


def test_ragged_layer_form_refuses_a_rank_mismatch(rng):
    q, kp, vp, pt = _ragged_case(rng, 2, 4, 4, 4, 16, 8, 2)
    lens = jnp.asarray([4, 4], jnp.int32)
    with pytest.raises(AssertionError, match="rank"):
        pa.ragged_paged_attention(q, kp, vp, pt, lens, lens, layer=0)
    with pytest.raises(AssertionError, match="rank"):
        pa.ragged_paged_attention(q, kp[None], vp[None], pt, lens, lens)


def _write_case(rng):
    """A packed step over 3 slots: slot 0 decodes one row in mid-page, slot
    1 feeds a chunk that crosses a page boundary, slot 2 starts a fresh
    page; one token is padding, one hits an unallocated (-1) page."""
    num_pages, page_size, h, d = 9, 4, 2, 8
    pt = jnp.asarray([[5, 2, -1], [0, 7, 3], [8, -1, -1]], jnp.int32)
    #               slot0  slot1 (pos 2..6: pages 0 and 7)  slot2  pad  -1 page
    tok_slot = jnp.asarray([0, 1, 1, 1, 1, 1, 2, 2, -1, 0], jnp.int32)
    tok_pos = jnp.asarray([6, 2, 3, 4, 5, 6, 0, 1, 0, 9], jnp.int32)
    toks = jnp.asarray(rng.randn(tok_slot.shape[0], h, d), jnp.float32)
    return num_pages, page_size, h, d, pt, tok_slot, tok_pos, toks


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("write", ["fp", "quant", "prequant"])
@pytest.mark.parametrize("form", ["scatter", "kernel"])
def test_packed_write_layer_form_equals_write_on_that_layer(rng, form,
                                                            write, layer):
    """The ``layer=`` form of the three packed writes (the jnp scatter, and
    through ``plan=`` the in-place Pallas kernel) against the old write on
    ``pools[layer]``: that layer equal, the other layers untouched, padding
    and -1 pages dropped."""
    from paddle_tpu.inference import kv_cache as kvc

    num_pages, page_size, h, d, pt, tok_slot, tok_pos, toks = \
        _write_case(rng)
    dest = (pt, tok_slot, tok_pos, page_size)
    plan = (kvc.packed_write_plan(*dest, num_pages) if form == "kernel"
            else None)
    if write == "fp":
        stacks = (_stack(rng, jnp.zeros((num_pages, h, page_size, d),
                                        jnp.float32)),)
        fn, vals = kvc.paged_write_packed, (toks,)
    else:
        stacks = (_stack(rng, jnp.zeros((num_pages, h, page_size, d),
                                        jnp.int8)),
                  _stack(rng, jnp.zeros((num_pages, h, page_size),
                                        jnp.float32)))
        fn, vals = kvc.paged_write_packed_quant, (toks,)
        if write == "prequant":
            q = jnp.asarray(rng.randint(-127, 128, toks.shape), jnp.int8)
            s = jnp.asarray(np.abs(rng.randn(*toks.shape[:2])) + 0.01,
                            jnp.float32)
            fn, vals = kvc.paged_write_packed_prequant, (q, s)
    want = fn(*(s[layer] for s in stacks), *vals, *dest)
    got = jax.jit(lambda li: fn(*stacks, *vals, *dest, layer=li,
                                plan=plan))(jnp.int32(layer))
    if write == "fp":
        want, got = (want,), (got,)
    for before, w, g in zip(stacks, want, got):
        expect = np.array(before)
        expect[layer] = np.asarray(w)
        assert (expect[layer] != np.asarray(before)[layer]).any()
        np.testing.assert_array_equal(np.asarray(g), expect)


def test_packed_write_plan_with_nothing_to_write(rng):
    """A step of padding only: every grid step of the kernel rewrites page
    0 with itself, and the stack comes back as it went in."""
    from paddle_tpu.inference import kv_cache as kvc

    num_pages, page_size, h, d, pt, tok_slot, tok_pos, toks = \
        _write_case(rng)
    dest = (pt, jnp.full_like(tok_slot, -1), tok_pos, page_size)
    plan = kvc.packed_write_plan(*dest, num_pages)
    assert int(jnp.max(plan.hi - plan.lo)) <= 0
    stack = _stack(rng, jnp.zeros((num_pages, h, page_size, d),
                                  jnp.float32))
    got = kvc.paged_write_packed(stack, toks, *dest, layer=1, plan=plan)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(stack))


# -- PR 29: the ragged kernel's grid (all heads and several pages a step) ----


def _assert_valid_rows_match(out, ref, q_lens, tol=2e-5):
    """Rows past ``q_lens`` are unspecified for the kernel."""
    for i, n in enumerate(np.asarray(q_lens)):
        np.testing.assert_allclose(np.asarray(out, np.float32)[i, :n],
                                   np.asarray(ref, np.float32)[i, :n],
                                   rtol=tol, atol=tol)


def _plan_of(q, kp, pt):
    b, c, hq, d = q.shape
    return pa.ragged_grid(b, pt.shape[1], c, hq, kp.shape[-3], kp.shape[-2],
                          d, kp.dtype, q.dtype)


# 8-key pages, 20 page slots: a grid step covers 4 pages = 32 keys
@pytest.mark.parametrize("q_len", [1, 8], ids=["decode", "chunk"])
@pytest.mark.parametrize("kv_len", [21, 31, 32, 33, 64, 65, 160],
                         ids=lambda n: f"ctx{n}")
def test_ragged_context_against_the_key_blocks_edge(rng, kv_len, q_len):
    """A context that ends inside a grid step's block of pages, exactly on
    its edge, one key past it, and at the table's end."""
    b, c, hq, hkv, d, page_size, pps = 2, 8, 4, 4, 16, 8, 20
    q, kp, vp, pt = _ragged_case(rng, b, c, hq, hkv, d, page_size, pps)
    plan = _plan_of(q, kp, pt)
    assert (plan.pages, plan.keys, plan.blocks) == (4, 32, 5)
    kv_lens = jnp.asarray([kv_len, 21], jnp.int32)
    q_lens = jnp.asarray([q_len, 3], jnp.int32)
    ref = pa.ragged_paged_attention_reference(q, kp, vp, pt, kv_lens, q_lens)
    out = pa.ragged_paged_attention(q, kp, vp, pt, kv_lens, q_lens,
                                    use_kernel=True)
    _assert_valid_rows_match(out, ref, q_lens)


@pytest.mark.parametrize("pps", [9, 11, 17])
def test_ragged_page_slots_not_a_multiple_of_the_pages_a_step(rng, pps):
    """The table's last grid step names fewer real slots than operands: a
    context that fills the table to its last key, and one that ends inside
    the table's last page."""
    b, c, hq, hkv, d, page_size = 2, 4, 4, 2, 16, 8
    q, kp, vp, pt = _ragged_case(rng, b, c, hq, hkv, d, page_size, pps)
    assert pps % _plan_of(q, kp, pt).pages
    kv_lens = jnp.asarray([pps * page_size, pps * page_size - 5], jnp.int32)
    q_lens = jnp.asarray([4, 1], jnp.int32)
    ref = pa.ragged_paged_attention_reference(q, kp, vp, pt, kv_lens, q_lens)
    out = pa.ragged_paged_attention(q, kp, vp, pt, kv_lens, q_lens,
                                    use_kernel=True)
    _assert_valid_rows_match(out, ref, q_lens)


def test_ragged_dead_lanes_between_live_ones(rng):
    """An idle lane (``q_len`` 0, a stale context) between two live ones and
    an empty lane (``kv_len`` 0) behind them: their page slots name what the
    grid step before named, the live lanes read their own pages, and the
    dead lanes' rows come back zero."""
    b, c, hq, hkv, d, page_size, pps = 4, 4, 4, 4, 16, 8, 12
    q, kp, vp, pt = _ragged_case(rng, b, c, hq, hkv, d, page_size, pps)
    kv_lens = jnp.asarray([70, 30, 91, 0], jnp.int32)
    q_lens = jnp.asarray([4, 0, 1, 0], jnp.int32)
    ref = pa.ragged_paged_attention_reference(q, kp, vp, pt, kv_lens, q_lens)
    out = pa.ragged_paged_attention(q, kp, vp, pt, kv_lens, q_lens,
                                    use_kernel=True)
    _assert_valid_rows_match(out, ref, q_lens)
    assert not np.asarray(out)[[1, 3]].any()


def test_work_items_visit_live_key_blocks_and_name_the_page_before():
    """Three lanes over 4-key pages, 2 pages a grid step, 4 page slots: lane
    0 holds 3 pages (two key blocks), lane 1 is idle (one item, so that its
    zero rows are written), lane 2 holds one page. Four items run of the six
    the table allows; a dead slot names its operand's page of the item
    before."""
    pt = jnp.asarray([[5, 6, 7, 9], [1, 2, 3, 4], [8, 0, 0, 0]], jnp.int32)
    kv_lens = jnp.asarray([9, 30, 3], jnp.int32)
    q_lens = jnp.asarray([1, 0, 1], jnp.int32)
    plan = pa.ragged_grid(3, 4, 1, 1, 1, 4, 16, jnp.float32, jnp.float32
                          )._replace(pages=2, blocks=2)
    total, lane, block, last, named = pa._work_items(pt, kv_lens, q_lens,
                                                     plan, num_pages=10)
    assert int(total) == 4 == plan.steps([9, 3])
    np.testing.assert_array_equal(np.asarray(lane)[:4], [0, 0, 1, 2])
    np.testing.assert_array_equal(np.asarray(block)[:4], [0, 1, 0, 0])
    np.testing.assert_array_equal(np.asarray(last)[:4], [0, 1, 1, 1])
    np.testing.assert_array_equal(np.asarray(named).reshape(-1, 2)[:4],
                                  [[5, 6], [7, 6], [7, 6], [8, 6]])


def _work_items_before(page_table, kv_lens, q_lens, plan, num_pages):
    """``_work_items`` as PR 29 wrote it, before PR 33 made it the lanes' case
    of the planner both paged kernels share: the oracle of the test below."""
    b, pps = page_table.shape
    pages, i32 = plan.pages, jnp.int32
    n = b * plan.blocks
    live_blocks = jnp.where(q_lens > 0, -(-kv_lens // i32(plan.keys)), 0)
    per_lane = jnp.maximum(live_blocks, 1)
    ends = jnp.cumsum(per_lane)
    item = jnp.arange(n, dtype=i32)
    lane = jnp.minimum(jnp.sum(item[:, None] >= ends[None, :], axis=1),
                       b - 1).astype(i32)
    block = item - (ends - per_lane)[lane]
    last = (block == per_lane[lane] - 1).astype(i32)
    slot = block[:, None] * pages + jnp.arange(pages, dtype=i32)[None, :]
    live = ((slot * plan.page_size < kv_lens[lane][:, None])
            & (q_lens[lane][:, None] > 0) & (slot < pps)
            & (item[:, None] < ends[-1]))
    page = jnp.clip(page_table, 0, num_pages - 1)[
        lane[:, None], jnp.minimum(slot, pps - 1)]
    last_live = jax.lax.cummax(jnp.where(live, item[:, None], 0), axis=0)
    named = jnp.take_along_axis(page, last_live, axis=0)
    return (ends[-1].astype(i32), lane, block.astype(i32), last,
            named.reshape(-1))


# name: (ragged_grid's arguments, kv_lens, q_lens); None: drawn per lane
_PLANNER_CASES = {
    "590m-chat-like": ((24, 32, 64, 12, 12, 64, 128, jnp.bfloat16,
                        jnp.bfloat16), None, "decode"),
    "590m-doc-sat-like": ((24, 32, 64, 12, 12, 64, 128, jnp.bfloat16,
                           jnp.bfloat16), None, "mixed"),
    "590m-int8": ((24, 32, 64, 12, 12, 64, 128, jnp.int8, jnp.bfloat16),
                  None, "mixed"),
    "590m-all-idle": ((24, 32, 64, 12, 12, 64, 128, jnp.bfloat16,
                       jnp.bfloat16), None, "idle"),
    "590m-full-table": ((24, 32, 64, 12, 12, 64, 128, jnp.bfloat16,
                         jnp.bfloat16), [2048] * 24, [64] * 24),
    "dead-lanes-between-live": ((4, 12, 4, 4, 4, 8, 16, jnp.float32,
                                 jnp.float32), [70, 30, 91, 0], [4, 0, 1, 0]),
    "key-block-edges": ((4, 20, 8, 4, 4, 8, 16, jnp.float32, jnp.float32),
                        [31, 32, 33, 160], [1, 8, 8, 1]),
    "slots-not-a-multiple": ((2, 9, 4, 4, 2, 8, 16, jnp.float32,
                              jnp.float32), [72, 67], [4, 1]),
    "three-lanes-two-pages": ((3, 4, 1, 1, 1, 4, 16, jnp.float32,
                               jnp.float32), [9, 30, 3], [1, 0, 1]),
}


@pytest.mark.parametrize("name", list(_PLANNER_CASES))
def test_shared_planner_for_lanes_is_work_items_as_before(rng, name):
    """PR 33: ``_work_items`` is ``work_items`` over lanes (a lane sees its
    whole context, reads its own page-table row and keeps one item when
    idle): every array it hands the kernel equals what PR 29's function
    gave, value for value, at the 590M cells' shapes and on the small ragged
    cases of this file."""
    shape, kv_lens, q_lens = _PLANNER_CASES[name]
    plan = pa.ragged_grid(*shape)
    b, pps, chunk, page_size = shape[0], shape[1], shape[2], shape[5]
    num_pages = b * pps // 2 + 3
    # a table with entries past the pool and below zero, as a scheduler's
    # unset slots are: both functions clip
    pt = jnp.asarray(rng.randint(-1, num_pages + 2, (b, pps)), jnp.int32)
    if kv_lens is None:
        top = pps * page_size
        kv_lens = rng.randint(1, top + 1, b)
        q_lens = {"decode": np.ones(b, int),
                  "mixed": np.where(np.arange(b) % 4 == 0, chunk, 1),
                  "idle": np.zeros(b, int)}[q_lens]
        q_lens[3::7] = 0                       # idle lanes with stale contexts
        kv_lens = np.maximum(kv_lens, q_lens)
    kv_lens = jnp.asarray(kv_lens, jnp.int32)
    q_lens = jnp.asarray(q_lens, jnp.int32)
    want = _work_items_before(pt, kv_lens, q_lens, plan, num_pages)
    got = pa._work_items(pt, kv_lens, q_lens, plan, num_pages)
    assert len(got) == len(want) == 5
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    scheduled = [int(n) for n, q in zip(kv_lens, q_lens) if q]
    assert int(got[0]) == plan.steps(scheduled)


@pytest.mark.parametrize("budget,heads", [(12 << 20, 4), (70_000, 2),
                                          (30_000, 1)])
def test_ragged_fewer_heads_a_step_than_kv_heads(rng, monkeypatch, budget,
                                                 heads):
    """Where a page of every head does not fit the fast-memory budget the
    grid takes the largest divisor of the KV heads that does: the head
    groups become the grid's first axis."""
    b, c, hq, hkv, d, page_size, pps = 2, 4, 8, 4, 16, 8, 10
    q, kp, vp, pt = _ragged_case(rng, b, c, hq, hkv, d, page_size, pps)
    monkeypatch.setattr(pa, "VMEM_BUDGET", budget)
    plan = _plan_of(q, kp, pt)
    assert (plan.heads, plan.groups, plan.blocks) == (
        heads, hkv // heads, 3)
    kv_lens = jnp.asarray([77, 9], jnp.int32)
    q_lens = jnp.asarray([4, 1], jnp.int32)
    ref = pa.ragged_paged_attention_reference(q, kp, vp, pt, kv_lens, q_lens)
    out = pa.ragged_paged_attention(q, kp, vp, pt, kv_lens, q_lens,
                                    use_kernel=True)
    _assert_valid_rows_match(out, ref, q_lens)


def test_ragged_grid_at_the_serving_cells_widths():
    """The 590M cells: all 12 heads and 4 pages a grid step, at most 192
    grid steps a call where the lane x head x page-slot grid had 9,216, and
    only the key blocks that hold keys among them; float32 pages of the same
    shape fit too; 32 heads take 16 a step."""
    plan = pa.ragged_grid(24, 32, 64, 12, 12, 64, 128, jnp.bfloat16,
                          jnp.bfloat16)
    assert (plan.heads, plan.pages, plan.pair, plan.rows, plan.few_rows) == (
        12, 4, 2, 64, 8)
    assert (plan.groups, plan.lanes, plan.blocks, plan.keys) == (
        1, 24, 8, 256)
    assert [plan.live_steps(n) for n in (0, 1, 256, 257, 2048)] == [
        0, 1, 1, 2, 8]
    # 24 idle lanes: one grid step each; every lane at the table's end
    assert plan.steps([]) == 24 and plan.steps([2048] * 24) == 192
    assert plan.steps([300, 64, 1400]) == 21 + 2 + 1 + 6
    int8 = pa.ragged_grid(24, 32, 64, 12, 12, 64, 128, jnp.int8,
                          jnp.bfloat16)
    assert (int8.heads, int8.pair, int8.blocks) == (12, 1, 8)
    # 32 heads do not fit the fast-memory budget whole: two groups of 16
    wide = pa.ragged_grid(24, 32, 64, 32, 32, 64, 128, jnp.bfloat16,
                          jnp.bfloat16)
    assert (wide.heads, wide.groups) == (16, 2)
    assert wide.steps([2048] * 24) == 2 * 192 and wide.live_steps(257) == 4
    # the draft chain's chunk of one: no few-rows form beside the 8-row block
    assert pa.ragged_grid(24, 32, 1, 12, 12, 64, 128, jnp.bfloat16,
                          jnp.bfloat16).few_rows == 0


@pytest.mark.parametrize("kv", ["fp", "int8"])
@pytest.mark.parametrize("group", [1, 2, 4])
def test_ragged_groups_and_int8_pages_over_several_key_blocks(rng, group,
                                                              kv):
    """Grouped-query heads (rows are chunk x group) and int8 pages with
    their scale planes, over contexts of one, two and three grid steps,
    decode lanes beside chunks."""
    b, c, hkv, d, page_size, pps = 4, 8, 2, 16, 8, 20
    q, kp, vp, pt = _ragged_case(rng, b, c, hkv * group, hkv, d, page_size,
                                 pps)
    scales = {}
    if kv == "int8":
        kp, ks, vp, vs = _quant_pools(kp, vp)
        scales = dict(k_scales=ks, v_scales=vs)
    kv_lens = jnp.asarray([150, 64, 9, 131], jnp.int32)
    q_lens = jnp.asarray([8, 1, 5, 1], jnp.int32)
    ref = pa.ragged_paged_attention_reference(q, kp, vp, pt, kv_lens, q_lens,
                                              **scales)
    out = pa.ragged_paged_attention(q, kp, vp, pt, kv_lens, q_lens,
                                    use_kernel=True, **scales)
    _assert_valid_rows_match(out, ref, q_lens)


@pytest.mark.parametrize("kv", ["fp", "int8"])
def test_ragged_layer_form_over_several_key_blocks(rng, kv):
    """``layer=`` on the stacked pools against the 4-D call on that layer's
    pool, with contexts that span grid steps (bit-equal: one body)."""
    b, c, hq, hkv, d, page_size, pps = 3, 4, 4, 2, 16, 8, 11
    q, kp, vp, pt = _ragged_case(rng, b, c, hq, hkv, d, page_size, pps)
    kv_lens = jnp.asarray([88, 65, 0], jnp.int32)
    q_lens = jnp.asarray([1, 4, 0], jnp.int32)
    pools = (kp, vp)
    if kv == "int8":
        kq, ks, vq, vs = _quant_pools(kp, vp)
        pools = (kq, vq, ks, vs)
    stacks = [_stack(rng, p) for p in pools] + [None] * (4 - len(pools))
    layer = 1
    one = [None if s is None else s[layer] for s in stacks]
    want = pa.ragged_paged_attention(
        q, one[0], one[1], pt, kv_lens, q_lens, use_kernel=True,
        k_scales=one[2], v_scales=one[3])
    got = jax.jit(lambda li: pa.ragged_paged_attention(
        q, stacks[0], stacks[1], pt, kv_lens, q_lens, use_kernel=True,
        k_scales=stacks[2], v_scales=stacks[3], layer=li))(jnp.int32(layer))
    for i, n in enumerate(np.asarray(q_lens)):
        np.testing.assert_array_equal(np.asarray(got)[i, :n],
                                      np.asarray(want)[i, :n])


@pytest.mark.parametrize("group", [1, 2, 4])
def test_ragged_few_rows_form_equals_the_chunk_form(rng, monkeypatch, group):
    """Lanes of one row, of exactly as many rows as the few-rows form
    computes, and of one more, against the reference; and the lanes the
    few-rows form took equal to the same lanes through the chunk form (the
    form switched off by asking for more rows than the block has)."""
    c, hkv, d, page_size, pps = 16, 2, 16, 8, 12
    q, kp, vp, pt = _ragged_case(rng, 4, c, hkv * group, hkv, d, page_size,
                                 pps)
    plan = _plan_of(q, kp, pt)
    limit = plan.few_rows // group          # the few-rows form's last q_len
    assert plan.few_rows == 8 and plan.rows == c * group
    q_lens = jnp.asarray([1, limit, limit + 1, c], jnp.int32)
    kv_lens = jnp.asarray([70, 33, 90, 64], jnp.int32)
    ref = pa.ragged_paged_attention_reference(q, kp, vp, pt, kv_lens, q_lens)
    few = pa.ragged_paged_attention(q, kp, vp, pt, kv_lens, q_lens,
                                    use_kernel=True)
    _assert_valid_rows_match(few, ref, q_lens)
    monkeypatch.setattr(pa, "FEW_ROWS", plan.rows)
    assert _plan_of(q, kp, pt).few_rows == 0
    whole = pa.ragged_paged_attention(q, kp, vp, pt, kv_lens, q_lens,
                                      use_kernel=True)
    _assert_valid_rows_match(few, whole, q_lens, tol=1e-6)
