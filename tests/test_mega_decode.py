"""Round-16 megakernelized decode layer: the fused per-layer Pallas
kernels (ops/pallas/mega_decode) against their composed jnp oracles —
the per-op references chained in the megakernel's exact stage order —
across fp/int8-weight/int8-KV geometries, in interpret mode on CPU (the
real kernel bodies run; TPU-compiled parity is the on-chip bench's job).
The serving-level gates (greedy mega == full-forward oracle, mega-off
bit-identity) live in tests/test_serving.py's round-16 block.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu  # noqa: F401  (framework config: x64 off, cpu)
from paddle_tpu.inference.quantize import quantize_weight
from paddle_tpu.ops.pallas.mega_decode import (
    mega_attn_layer, mega_attn_layer_reference, mega_mlp,
    mega_mlp_reference, preferred_mega_blocks, validate_mega_config)

H, HD, F = 32, 8, 64          # 4 heads, 2x ffn — tiny but MXU-shaped
PAGE = 8


def _layer(rng, h=H, f=F, quant=None, group=-1, head_major=False):
    def w(*s):
        return jnp.asarray(rng.randn(*s) * 0.05, jnp.float32)

    wqkv, bqkv = w(h, 3 * h), w(3 * h) * 0.1
    if head_major:
        # the mesh layout: qkv columns permuted [3, nh, hd] -> [nh, 3, hd]
        nh = h // HD
        perm = np.arange(3 * h).reshape(3, nh, HD).transpose(1, 0, 2
                                                             ).reshape(-1)
        wqkv, bqkv = wqkv[:, perm], bqkv[perm]
    p = {
        "ln1_g": jnp.ones((h,), jnp.float32), "ln1_b": w(h) * 0.1,
        "ln2_g": jnp.ones((h,), jnp.float32), "ln2_b": w(h) * 0.1,
        "wqkv": wqkv, "bqkv": bqkv,
        "wo": w(h, h), "bo": w(h) * 0.1,
        "w1": w(h, f), "b1": w(f) * 0.1,
        "w2": w(f, h), "b2": w(h) * 0.1,
    }
    if quant:
        for k in ("wqkv", "wo", "w1", "w2"):
            p[k] = quantize_weight(p[k], quant, group_size=group)
    return p


def _pools(rng, num_pages, nh, kv_quant):
    if kv_quant:
        kq = jnp.asarray(rng.randint(-127, 128,
                                     (num_pages, nh, PAGE, HD)), jnp.int8)
        vq = jnp.asarray(rng.randint(-127, 128,
                                     (num_pages, nh, PAGE, HD)), jnp.int8)
        ks = jnp.asarray(np.abs(rng.randn(num_pages, nh, PAGE)) * 0.01
                         + 1e-3, jnp.float32)
        vs = jnp.asarray(np.abs(rng.randn(num_pages, nh, PAGE)) * 0.01
                         + 1e-3, jnp.float32)
        return kq, vq, ks, vs
    kq = jnp.asarray(rng.randn(num_pages, nh, PAGE, HD), jnp.float32)
    vq = jnp.asarray(rng.randn(num_pages, nh, PAGE, HD), jnp.float32)
    return kq, vq, None, None


def _geometry(rng, b=3, chunk=2, pps=3, kv_quant=False):
    """A ragged decode-round geometry: lane 0 deep-context single token,
    lane 1 idle (q_len 0), lane 2 fresh-context multi-row (the spec
    verify-rows shape) — plus one lane at ctx 0 when b > 3."""
    nh = H // HD
    num_pages = b * pps + 2
    pools = _pools(rng, num_pages, nh, kv_quant)
    pt = np.full((b, pps), -1, np.int32)
    ctx = np.zeros((b,), np.int32)
    qlens = np.zeros((b,), np.int32)
    ctx[0], qlens[0] = 13, 1
    ctx[2], qlens[2] = 5, chunk
    if b > 3:
        ctx[3], qlens[3] = 0, 1        # first-token lane: empty pool ctx
    used = iter(range(num_pages))
    for i in range(b):
        need = -(-int(ctx[i] + qlens[i]) // PAGE) if qlens[i] else 0
        for j in range(need):
            pt[i, j] = next(used)
    xb = jnp.asarray(rng.randn(b, chunk, H), jnp.float32)
    return (xb, pools, jnp.asarray(pt), jnp.asarray(ctx),
            jnp.asarray(qlens))


def _assert_close(ref, ker, qlens, chunk, tol=2e-3):
    valid = np.asarray(qlens)[:, None] > np.arange(chunk)[None]
    for r, k in zip(ref, ker):
        rv, kv = np.asarray(r, np.float32), np.asarray(k, np.float32)
        m = np.broadcast_to(
            valid.reshape(valid.shape + (1,) * (rv.ndim - 2)), rv.shape)
        assert np.abs(np.where(m, rv - kv, 0)).max() <= tol


@pytest.mark.parametrize("quant,group,kv_quant", [
    (None, -1, False),
    ("int8", -1, False),        # per-channel weight scales
    ("int8", 16, False),        # grouped scales (2 groups over h)
    (None, -1, True),           # int8 KV pools, fp weights
    ("int8", 16, True),         # the flagship int8w+int8kv leg
])
def test_mega_attn_kernel_matches_composed_oracle(rng, quant, group,
                                                  kv_quant):
    p = _layer(rng, quant=quant, group=group)
    xb, (kp, vp, ks, vs), pt, ctx, qlens = _geometry(rng, b=4,
                                                     kv_quant=kv_quant)
    ref = mega_attn_layer_reference(xb, p, kp, vp, pt, ctx, qlens,
                                    k_scales=ks, v_scales=vs)
    ker = mega_attn_layer(xb, p, kp, vp, pt, ctx, qlens, k_scales=ks,
                          v_scales=vs, use_kernel=True)
    assert len(ref) == len(ker) == (6 if kv_quant else 4)
    _assert_close(ref, ker, qlens, xb.shape[1])
    if kv_quant:
        # the emitted K/V payloads are int8 and BIT-identical: kernel and
        # oracle share the exact paged_write_packed_quant formula
        assert ker[2].dtype == jnp.int8 and ker[3].dtype == jnp.int8
        q0 = int(qlens[0])
        np.testing.assert_array_equal(np.asarray(ker[2])[0, :q0],
                                      np.asarray(ref[2])[0, :q0])


def test_mega_attn_head_major_layout(rng):
    """The mesh (head-major) qkv column order — same dots, permuted
    columns — must produce the same layer outputs as the eager layout."""
    rng2 = np.random.RandomState(rng.randint(1 << 30))
    p = _layer(rng2, head_major=True)
    xb, (kp, vp, _, _), pt, ctx, qlens = _geometry(rng2)
    ref = mega_attn_layer_reference(xb, p, kp, vp, pt, ctx, qlens,
                                    head_major=True)
    ker = mega_attn_layer(xb, p, kp, vp, pt, ctx, qlens, head_major=True,
                          use_kernel=True)
    _assert_close(ref, ker, qlens, xb.shape[1])


def test_mega_attn_chunk_padding(rng):
    """A chunk that is not a multiple of the 8-row sublane tile pads
    in-kernel; rows past each lane's q_len are never compared (garbage by
    contract — nothing downstream reads them)."""
    p = _layer(rng)
    xb, (kp, vp, _, _), pt, ctx, qlens = _geometry(rng, chunk=5, pps=4)
    ref = mega_attn_layer_reference(xb, p, kp, vp, pt, ctx, qlens)
    ker = mega_attn_layer(xb, p, kp, vp, pt, ctx, qlens, use_kernel=True)
    _assert_close(ref, ker, qlens, 5)


@pytest.mark.parametrize("quant,group", [
    (None, -1), ("int8", -1), ("int8", 16),
])
def test_mega_mlp_matches_composed_oracle(rng, quant, group):
    p = _layer(rng, quant=quant, group=group)
    t = 6
    y2 = jnp.asarray(rng.randn(t, H), jnp.float32)
    sres = jnp.asarray(rng.randn(t, H), jnp.float32)
    ref = mega_mlp_reference(y2, sres, p)
    ker = mega_mlp(y2, sres, p, use_kernel=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               atol=2e-3, rtol=0)


def test_mega_mlp_row_padding(rng):
    """Token counts off the 8-row tile pad and strip transparently."""
    p = _layer(rng)
    for t in (1, 3, 9):
        y2 = jnp.asarray(rng.randn(t, H), jnp.float32)
        sres = jnp.asarray(rng.randn(t, H), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(mega_mlp(y2, sres, p, use_kernel=True)),
            np.asarray(mega_mlp_reference(y2, sres, p)), atol=2e-3)


def test_validate_mega_config_rejections():
    """The build-time gate: int4 weights and head-dim-straddling scale
    groups are rejected LOUDLY (callers stay per-op); servable
    geometries pass silently. Round 22 LIFTED the round-16 mp > 1
    rejection — mega now composes with the shard_map mesh (the
    serving-level equivalence gate lives in test_serving.py) — so mp
    values must pass here."""
    validate_mega_config(None, -1, 16)
    validate_mega_config("int8", -1, 16)
    validate_mega_config("int8", 16, 16)     # group == head_dim
    validate_mega_config("int8", 8, 16)      # two groups per head tile
    validate_mega_config("int8", 32, 16)     # one group spans two tiles
    validate_mega_config(None, -1, 16, mp=2)     # round 22: no raise
    validate_mega_config("int8", 16, 16, mp=4)   # round 22: no raise
    with pytest.raises(ValueError, match="int4"):
        validate_mega_config("int4", -1, 16)
    with pytest.raises(ValueError, match="group"):
        validate_mega_config("int8", 24, 16)  # 16 % 24 and 24 % 16 != 0


def test_mega_mlp_grouped_scale_tile_branches(rng):
    """Both grouped-w2-scale tile shapes stay correct AND the autotuned
    width survives grouping: bn >= group serves MULTIPLE scale rows per
    tile (reshape branch, tile a multiple of the group — not collapsed
    to it), bn < group spans one scale row across tiles (index branch).
    The cache is seeded to force each branch deterministically."""
    from paddle_tpu.ops.pallas import autotune_cache as atc
    from paddle_tpu.ops.pallas.mega_decode import _mega_sig, _mlp_bn

    p = _layer(rng, quant="int8", group=16)   # w2: K=F=64, 4 groups gs=16
    t = 6
    y2 = jnp.asarray(rng.randn(t, H), jnp.float32)
    sres = jnp.asarray(rng.randn(t, H), jnp.float32)
    ref = mega_mlp_reference(y2, sres, p)
    sig = _mega_sig(H, F, jnp.float32)
    saved = atc.CACHE.get(sig)
    try:
        for bn_pref, want_bn in ((32, 32), (8, 8)):
            atc.CACHE[sig] = [64, bn_pref, H]
            assert _mlp_bn(F, 4, H, jnp.float32) == want_bn
            np.testing.assert_allclose(
                np.asarray(mega_mlp(y2, sres, p, use_kernel=True)),
                np.asarray(ref), atol=2e-3, rtol=0)
    finally:
        if saved is None:
            atc.CACHE.pop(sig, None)
        else:
            atc.CACHE[sig] = saved


# -- round 22: ragged mixed-chunk geometry + the unfused (mp) epilogue ------


@pytest.mark.parametrize("chunk", [1, 2, 4])
@pytest.mark.parametrize("quant,group,kv_quant", [
    (None, -1, False),
    ("int8", 16, True),         # the flagship int8w-grouped + int8kv leg
])
def test_mega_attn_ragged_chunk_sweep(rng, chunk, quant, group, kv_quant):
    """The round-22 mixed geometry: every chunk width the unified step's
    packed budget can pack (decode lane + idle lane + a prefill-chunk
    lane + a fresh ctx-0 lane) runs the kernel against the composed
    oracle — the geometries round 16 still routed to the per-op
    fallback."""
    p = _layer(rng, quant=quant, group=group)
    xb, (kp, vp, ks, vs), pt, ctx, qlens = _geometry(
        rng, b=4, chunk=chunk, kv_quant=kv_quant)
    ref = mega_attn_layer_reference(xb, p, kp, vp, pt, ctx, qlens,
                                    k_scales=ks, v_scales=vs)
    ker = mega_attn_layer(xb, p, kp, vp, pt, ctx, qlens, k_scales=ks,
                          v_scales=vs, use_kernel=True)
    _assert_close(ref, ker, qlens, chunk)


def test_mega_attn_single_lane_full_chunk(rng):
    """chunk == the whole token budget (b = 1): a pure prefill-chunk
    round — every row live, in-chunk causal attention carrying most of
    the mass."""
    p = _layer(rng)
    nh, chunk = H // HD, 4
    kp, vp, _, _ = _pools(rng, 3, nh, False)
    pt = jnp.asarray([[0, 1, 2]], jnp.int32)
    ctx = jnp.asarray([5], jnp.int32)
    qlens = jnp.asarray([chunk], jnp.int32)
    xb = jnp.asarray(rng.randn(1, chunk, H), jnp.float32)
    ref = mega_attn_layer_reference(xb, p, kp, vp, pt, ctx, qlens)
    ker = mega_attn_layer(xb, p, kp, vp, pt, ctx, qlens, use_kernel=True)
    _assert_close(ref, ker, qlens, chunk)


@pytest.mark.parametrize("kv_quant", [False, True])
def test_mega_attn_unfused_epilogue(rng, kv_quant):
    """fuse_epilogue=False (the round-22 mp spelling): the kernel's
    pre-psum output-GEMM partial matches the oracle's, AND the caller's
    completion (residual + bo + LN2 in the per-op order) reproduces the
    fused return BIT-exactly — the contract that makes mp > 1 serving
    bit-identical to per-op."""
    from paddle_tpu.ops.pallas.mega_decode import _ln_f32

    p = _layer(rng)
    xb, (kp, vp, ks, vs), pt, ctx, qlens = _geometry(rng, b=4,
                                                     kv_quant=kv_quant)
    ref = mega_attn_layer_reference(xb, p, kp, vp, pt, ctx, qlens,
                                    k_scales=ks, v_scales=vs,
                                    fuse_epilogue=False)
    ker = mega_attn_layer(xb, p, kp, vp, pt, ctx, qlens, k_scales=ks,
                          v_scales=vs, use_kernel=True,
                          fuse_epilogue=False)
    assert len(ref) == len(ker) == (5 if kv_quant else 3)
    _assert_close(ref, ker, qlens, xb.shape[1])
    # manual completion of the unfused oracle == the fused oracle
    fused = mega_attn_layer_reference(xb, p, kp, vp, pt, ctx, qlens,
                                      k_scales=ks, v_scales=vs)
    s = xb + ref[0] + p["bo"]
    y2 = _ln_f32(s, p["ln2_g"], p["ln2_b"], 1e-5)
    valid = np.asarray(qlens)[:, None] > np.arange(xb.shape[1])[None]
    m = valid[..., None]
    np.testing.assert_array_equal(np.where(m, np.asarray(y2), 0),
                                  np.where(m, np.asarray(fused[0]), 0))
    np.testing.assert_array_equal(np.where(m, np.asarray(s), 0),
                                  np.where(m, np.asarray(fused[1]), 0))
    # the emitted K/V payloads are epilogue-independent (unfused index 1
    # == fused index 2: only the (y2, s) head of the tuple changes)
    np.testing.assert_array_equal(np.asarray(ref[1]), np.asarray(fused[2]))
    np.testing.assert_array_equal(np.asarray(ref[2]), np.asarray(fused[3]))


@pytest.mark.parametrize("quant,group", [(None, -1), ("int8", 16)])
def test_mega_mlp_unfused_epilogue(rng, quant, group):
    """The MLP half of the mp spelling: kernel partial vs oracle partial
    (``s_res`` never read — callers pass None), and the caller's
    ``s_res + partial + b2`` completion reproduces the fused oracle
    BIT-exactly."""
    p = _layer(rng, quant=quant, group=group)
    t = 6
    y2 = jnp.asarray(rng.randn(t, H), jnp.float32)
    sres = jnp.asarray(rng.randn(t, H), jnp.float32)
    part_ref = mega_mlp_reference(y2, None, p, fuse_epilogue=False)
    part_ker = mega_mlp(y2, None, p, use_kernel=True, fuse_epilogue=False)
    np.testing.assert_allclose(np.asarray(part_ker), np.asarray(part_ref),
                               atol=2e-3, rtol=0)
    fused = mega_mlp_reference(y2, sres, p)
    done = sres + part_ref + p["b2"]
    np.testing.assert_array_equal(np.asarray(done), np.asarray(fused))


# -- round 22: the single-dispatch draft chain ------------------------------

VOCAB = 97


def _draft_cfg_params(draft_layers=1):
    """A tiny 2-layer target model's serving params, sliced to the
    truncated draft stack — the chain runs the SAME weights the engine
    would."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                       draft_serving_params, serving_params)

    paddle.seed(11)
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=H, num_layers=2,
                    num_heads=H // HD, max_seq_len=64)
    model = GPTForCausalLM(cfg)
    model.eval()
    return cfg, model, draft_serving_params(serving_params(model),
                                            draft_layers)


def _chain_geometry(rng, b=3, pps=2, kv_quant=False):
    """Per-lane draft-pool state: a mid-context lane, a deeper lane, an
    idle lane (steps 0) — page capacity pre-reserved for kv0 + k like the
    engine does."""
    nh = H // HD
    # serving pools carry a leading LAYER axis (the chain's inner scan
    # runs over it); the truncated draft stack has 1 layer
    pools = tuple(None if x is None else x[None]
                  for x in _pools(rng, b * pps, nh, kv_quant))
    pt = np.arange(b * pps, dtype=np.int32).reshape(b, pps)
    kv0 = np.array([5, 9, 0][:b], np.int32)
    first = rng.randint(0, VOCAB, (b,)).astype(np.int32)
    return pools, jnp.asarray(pt), kv0, first


@pytest.mark.parametrize("k", [1, 2, 4])
def test_draft_chain_bit_identical_to_per_step_chain(rng, k):
    """THE round-22 draft-chain contract: the fused k-step chain (one
    dispatch, device-side scan) is BIT-identical — drafts AND pool
    writes — to k separate single-step dispatches chained through the
    host, at ragged per-lane depths (one lane a step behind, one idle)."""
    from paddle_tpu.models.gpt import build_draft_chain

    cfg, _, dparams = _draft_cfg_params()
    (kp0, vp0, _, _), pt, kv0, first = _chain_geometry(rng)
    steps = np.array([k, max(k - 1, 1), 0], np.int32)
    kp_np, vp_np = np.asarray(kp0), np.asarray(vp0)

    fused = build_draft_chain(cfg, 1, PAGE, k, mega=True)
    res = fused(dparams, jnp.asarray(first), jnp.asarray(steps),
                jnp.asarray(kv0), jnp.asarray(kp_np), jnp.asarray(vp_np),
                pt)
    drafts_fused = np.asarray(res[0])

    single = build_draft_chain(cfg, 1, PAGE, 1, mega=True)
    kp, vp = jnp.asarray(kp_np), jnp.asarray(vp_np)
    ids = np.asarray(first)
    per_step = []
    for j in range(k):
        active = steps > j
        r = single(dparams, jnp.asarray(ids),
                   jnp.asarray(active.astype(np.int32)),
                   jnp.asarray(kv0 + j), kp, vp, pt)
        d = np.asarray(r[0])[:, 0]
        per_step.append(np.where(active, d, 0))
        ids = np.where(active, d, ids).astype(np.int32)
        kp, vp = r[1], r[2]
    np.testing.assert_array_equal(drafts_fused, np.stack(per_step, 1))
    np.testing.assert_array_equal(np.asarray(res[1]), np.asarray(kp))
    np.testing.assert_array_equal(np.asarray(res[2]), np.asarray(vp))
    # the idle lane proposed nothing and wrote nothing
    assert not drafts_fused[2].any()
    lane2 = np.asarray(pt)[2]
    np.testing.assert_array_equal(np.asarray(res[1])[0][lane2],
                                  kp_np[0][lane2])


def test_draft_chain_mega_emits_per_op_tokens(rng):
    """Kernel-family independence: the mega-block chain proposes the
    SAME tokens as the per-op chain (pools agree to reference tolerance)
    — mega changes cost, never drafts."""
    from paddle_tpu.models.gpt import build_draft_chain

    cfg, _, dparams = _draft_cfg_params()
    (kp0, vp0, _, _), pt, kv0, first = _chain_geometry(rng)
    steps = np.array([3, 2, 0], np.int32)
    kp_np, vp_np = np.asarray(kp0), np.asarray(vp0)
    out = {}
    for mega in (False, True):
        fn = build_draft_chain(cfg, 1, PAGE, 3, mega=mega)
        out[mega] = fn(dparams, jnp.asarray(first), jnp.asarray(steps),
                       jnp.asarray(kv0), jnp.asarray(kp_np),
                       jnp.asarray(vp_np), pt)
    np.testing.assert_array_equal(np.asarray(out[True][0]),
                                  np.asarray(out[False][0]))
    np.testing.assert_allclose(np.asarray(out[True][1]),
                               np.asarray(out[False][1]), atol=2e-3)


def test_draft_chain_int8kv_payloads_bit_identical(rng):
    """The int8-KV chain: fused vs per-step single dispatches — the
    quantized payloads AND scale rows land bit-identically (both sides
    share the paged_write_packed_quant formula)."""
    from paddle_tpu.models.gpt import build_draft_chain

    cfg, _, dparams = _draft_cfg_params()
    (kp0, vp0, ks0, vs0), pt, kv0, first = _chain_geometry(rng,
                                                           kv_quant=True)
    steps = np.array([2, 2, 0], np.int32)
    raw = tuple(np.asarray(x) for x in (kp0, vp0, ks0, vs0))

    fused = build_draft_chain(cfg, 1, PAGE, 2, kv_quant=True, mega=True)
    res = fused(dparams, jnp.asarray(first), jnp.asarray(steps),
                jnp.asarray(kv0), *(jnp.asarray(x) for x in raw), pt)

    single = build_draft_chain(cfg, 1, PAGE, 1, kv_quant=True, mega=True)
    pools = tuple(jnp.asarray(x) for x in raw)
    ids = np.asarray(first)
    for j in range(2):
        active = steps > j
        r = single(dparams, jnp.asarray(ids),
                   jnp.asarray(active.astype(np.int32)),
                   jnp.asarray(kv0 + j), *pools, pt)
        ids = np.where(active, np.asarray(r[0])[:, 0], ids).astype(np.int32)
        pools = r[1:]
    for got, want in zip(res[1:], pools):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert res[1].dtype == jnp.int8


def test_draft_chain_preemption_replay_self_heals(rng):
    """The engine-level self-heal (round 22, fused chain): after a
    proposal round, a DIVERGED continuation (the target rejected mid-
    draft) and a SHORTER context (preemption replay) must both roll the
    draft KV back to the longest common fed prefix and propose exactly
    what a fresh engine proposes — no commit protocol, one comparison."""
    from paddle_tpu.inference.draft import ModelDraftEngine
    from paddle_tpu.models.gpt import serving_params

    cfg, model, _ = _draft_cfg_params()
    params = serving_params(model)
    kw = dict(page_size=PAGE, chunk=4, max_batch=2, max_seq_len=64,
              max_k=3, mega=True)
    eng = ModelDraftEngine(cfg, params, 1, **kw)
    ctx = rng.randint(0, VOCAB, (9,)).tolist()
    d1 = eng.propose({0: (7, ctx, 3)})[0]
    assert len(d1) == 3

    # diverged continuation: the target accepted d1[0] then emitted its
    # own token — the fed tail past the fork must be rolled back
    ctx2 = ctx + [int(d1[0]), (int(d1[1]) + 1) % VOCAB]
    got = eng.propose({0: (7, ctx2, 3)})[0]
    want = ModelDraftEngine(cfg, params, 1, **kw).propose(
        {0: (7, ctx2, 3)})[0]
    assert got == want and len(got) == 3

    # preemption replay: the request returns with a SHORTER context
    ctx3 = ctx[:5]
    got = eng.propose({0: (7, ctx3, 2)})[0]
    want = ModelDraftEngine(cfg, params, 1, **kw).propose(
        {0: (7, ctx3, 2)})[0]
    assert got == want and len(got) == 2


# -- round 22: chunk-keyed autotune hygiene ---------------------------------


def test_mega_sig_chunk_keying_no_collision():
    """The round-22 cache-key regression gate: chunk-1 signatures stay
    BYTE-identical to the pre-round-22 strings (persisted decode-only
    entries keep hitting), chunk-c signatures are distinct (a mixed-round
    sweep can never clobber the decode winner), the chunk-c lookup falls
    back to the chunk-1 prior, and a seeded chunk-c entry never leaks
    into the chunk-1 lookup."""
    from paddle_tpu.ops.pallas import autotune_cache as atc
    from paddle_tpu.ops.pallas.mega_decode import (BM_DEFAULT, BN_DEFAULT,
                                                   _mega_sig)

    sig1 = _mega_sig(H, F, jnp.float32)
    assert sig1 == _mega_sig(H, F, jnp.float32, chunk=1)   # legacy bytes
    sig4 = _mega_sig(H, F, jnp.float32, chunk=4)
    assert sig4 != sig1 and ":c4" in sig4
    saved = {s: atc.CACHE.get(s) for s in (sig1, sig4)}
    try:
        atc.CACHE.pop(sig1, None)
        atc.CACHE[sig4] = [16, 32, H]
        # the chunk-4 winner serves chunk-4 lookups ONLY; decode-only
        # stays on the defaults
        assert preferred_mega_blocks(H, F, jnp.float32, chunk=4) \
            == (16, 32, H)
        assert preferred_mega_blocks(H, F, jnp.float32) \
            == (BM_DEFAULT, BN_DEFAULT, H)
        # a missing chunk-4 entry falls back to the chunk-1 prior
        atc.CACHE.pop(sig4, None)
        atc.CACHE[sig1] = [32, 64, H]
        assert preferred_mega_blocks(H, F, jnp.float32, chunk=4) \
            == (32, 64, H)
        assert preferred_mega_blocks(H, F, jnp.float32) == (32, 64, H)
    finally:
        for s, v in saved.items():
            if v is None:
                atc.CACHE.pop(s, None)
            else:
                atc.CACHE[s] = v


def test_preferred_mega_blocks_default_and_cache_roundtrip():
    """The sweep's persisted winner must be READ BACK by the serve-time
    lookup — writer and reader derive the SAME signature (a key the
    lookup cannot reconstruct is a cache that never hits)."""
    from paddle_tpu.ops.pallas import autotune_cache as atc
    from paddle_tpu.ops.pallas.mega_decode import _mega_sig

    bm, bn, bk = preferred_mega_blocks(H, F, jnp.float32)
    assert bm > 0 and bn > 0 and bk == H
    sig = _mega_sig(H, F, jnp.float32)
    saved = atc.CACHE.get(sig)
    try:
        atc.CACHE[sig] = [16, 32, H]
        assert preferred_mega_blocks(H, F, jnp.float32) == (16, 32, H)
    finally:
        if saved is None:
            atc.CACHE.pop(sig, None)
        else:
            atc.CACHE[sig] = saved
