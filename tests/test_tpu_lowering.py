"""Lower for the TPU, on the CPU.

Interpret mode has no tiling rule, so a kernel that has only ever run here
can be one Mosaic refuses (PR 21 found three). ``jax.jit(f).trace(*avals)
.lower(lowering_platforms=("tpu",))`` reaches Pallas's Mosaic lowering
checks without a chip: every ``pallas_call`` the two ``chip_smoke.py``
phases reach, plus ``quant_matmul`` / ``grouped_matmul`` / ``fused_mlp``,
is lowered at the smoke's real widths (GPT-3 760M: hidden
1536, 12 heads of 128, ffn 6144; the serving defaults of 8 lanes, chunk 16,
64-token pages) from abstract inputs — nothing is allocated. A lowering
that passes is not a compile that passes (the fast-memory limit and
Mosaic's layout inference are only met by the chip's compiler); this is the
gate that stops an interpret-only kernel from reaching the chip again.
"""
import pytest

import jax
import jax.numpy as jnp

H, HEADS, HD, FFN = 1536, 12, 128, 6144
LANES, CHUNK, PAGE, PAGES_PER_SLOT = 8, 16, 64, 17
POOL = LANES * PAGES_PER_SLOT
BUDGET = LANES + CHUNK
BF16 = jnp.bfloat16


def sds(*shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.fixture(autouse=True)
def _as_on_tpu(monkeypatch):
    """The kernels pick interpret mode and ``use_kernel=None`` from
    ``jax.default_backend()``: answer as the chip would."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def mosaic_calls(fn, *avals) -> int:
    """Lower ``fn`` for the TPU in the hot paths' 32-bit mode; the number
    of Mosaic custom calls in the result."""
    with jax.enable_x64(False):
        lowered = jax.jit(fn).trace(*avals).lower(
            lowering_platforms=("tpu",))
    return lowered.as_text().count("tpu_custom_call")


# -- the serving phase: ragged paged attention ------------------------------


@pytest.mark.parametrize("local_heads", [HEADS, HEADS // 4],
                         ids=["one-chip", "mesh4-shard"])
@pytest.mark.parametrize("kv", ["fp", "int8"])
def test_ragged_paged_attention_lowers(kv, local_heads):
    from paddle_tpu.ops.pallas.paged_attention import ragged_paged_attention

    q = sds(LANES, CHUNK, local_heads, HD)
    table = sds(LANES, PAGES_PER_SLOT, dtype=jnp.int32)
    lens = sds(LANES, dtype=jnp.int32)
    if kv == "fp":
        pool = sds(POOL, local_heads, PAGE, HD)
        n = mosaic_calls(ragged_paged_attention, q, pool, pool, table,
                         lens, lens)
    else:
        pool = sds(POOL, local_heads, PAGE, HD, dtype=jnp.int8)
        scales = sds(POOL, local_heads, PAGE, dtype=jnp.float32)

        def fn(q, k, v, table, kv_lens, q_lens, ks, vs):
            return ragged_paged_attention(q, k, v, table, kv_lens, q_lens,
                                          k_scales=ks, v_scales=vs)

        n = mosaic_calls(fn, q, pool, pool, table, lens, lens, scales,
                         scales)
    assert n == 1


LAYERS = 24


@pytest.mark.parametrize("kv", ["fp", "int8"])
def test_ragged_paged_attention_lowers_on_the_stacked_pools(kv):
    """The unified step's form: the whole ``[layers, ...]`` stack as the
    operand, the layer a traced scalar in the block index maps."""
    from paddle_tpu.ops.pallas.paged_attention import ragged_paged_attention

    q = sds(LANES, CHUNK, HEADS, HD)
    table = sds(LANES, PAGES_PER_SLOT, dtype=jnp.int32)
    lens = sds(LANES, dtype=jnp.int32)
    layer = sds(dtype=jnp.int32)
    pool = sds(LAYERS, POOL, HEADS, PAGE, HD,
               dtype=BF16 if kv == "fp" else jnp.int8)
    scales = (sds(LAYERS, POOL, HEADS, PAGE, dtype=jnp.float32)
              if kv == "int8" else None)

    def fn(q, k, v, table, kv_lens, q_lens, layer, ks=None, vs=None):
        return ragged_paged_attention(q, k, v, table, kv_lens, q_lens,
                                      k_scales=ks, v_scales=vs, layer=layer)

    planes = () if scales is None else (scales, scales)
    assert mosaic_calls(fn, q, pool, pool, table, lens, lens, layer,
                        *planes) == 1


@pytest.mark.parametrize("what", ["bf16-pool", "int8-pool", "scale-plane"])
def test_paged_kv_write_lowers(what):
    """The in-place row write of the unified step, through the packed
    writes' ``plan=`` form: one Mosaic call per pool or plane."""
    from paddle_tpu.inference import kv_cache as kvc

    dtype = {"bf16-pool": BF16, "int8-pool": jnp.int8,
             "scale-plane": jnp.float32}[what]
    tail = () if what == "scale-plane" else (HD,)
    stack = sds(LAYERS, POOL, HEADS, PAGE, *tail, dtype=dtype)
    rows = sds(BUDGET, HEADS, *tail, dtype=dtype)
    table = sds(LANES, PAGES_PER_SLOT, dtype=jnp.int32)
    tok = sds(BUDGET, dtype=jnp.int32)

    def fn(stack, rows, table, tok_slot, tok_pos, layer):
        dest = (table, tok_slot, tok_pos, PAGE)
        plan = kvc.packed_write_plan(*dest, POOL)
        return kvc._write_rows(stack, rows, *dest, layer, plan)

    assert mosaic_calls(fn, stack, rows, table, tok, tok,
                        sds(dtype=jnp.int32)) == 1


def test_paged_decode_attention_lowers():
    from paddle_tpu.ops.pallas.paged_attention import paged_attention

    pool = sds(POOL, HEADS, PAGE, HD)
    n = mosaic_calls(paged_attention, sds(LANES, HEADS, HD), pool, pool,
                     sds(LANES, PAGES_PER_SLOT, dtype=jnp.int32),
                     sds(LANES, dtype=jnp.int32))
    assert n == 1


# -- the training phase: flash attention forward + backward -----------------


def test_flash_attention_fwd_bwd_lowers():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    qkv = sds(8, 1024, HEADS, HD)
    n = mosaic_calls(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                     qkv, qkv, qkv)
    assert n == 2


# -- kernels behind config flags the smoke leaves off -----------------------


@pytest.mark.parametrize("k,n", [(H, 3 * H), (H, H), (H, FFN), (FFN, H)])
def test_quant_matmul_int8_lowers(k, n):
    from paddle_tpu.ops.pallas.quant_matmul import quant_matmul

    calls = mosaic_calls(quant_matmul, sds(BUDGET, k),
                         sds(k, n, dtype=jnp.int8),
                         sds(n, dtype=jnp.float32))
    assert calls == 1


def test_grouped_matmul_lowers():
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul

    experts, rows = 4, 256
    x = sds(rows, H)
    offsets = sds(experts + 1, dtype=jnp.int32)

    def loss(x, w, offsets):
        return grouped_matmul(x, w, offsets).astype(jnp.float32).sum()

    assert mosaic_calls(jax.value_and_grad(loss, argnums=(0, 1)), x,
                        sds(experts, H, FFN), offsets) >= 2

    def int8(x, w, offsets, scales):
        return grouped_matmul(x, w, offsets, scales=scales)

    assert mosaic_calls(int8, x, sds(experts, H, FFN, dtype=jnp.int8),
                        offsets,
                        sds(experts, FFN, dtype=jnp.float32)) == 1


@pytest.mark.parametrize("what", ["latent attention", "latent row write",
                                  "stacked experts"])
def test_latent_moe_kernels_lower_at_deepseek_v2_lite_widths(what):
    """PR 28's kernels at the DeepSeek-V2-Lite cell's widths: 16 heads over
    one 576-value row stored 640 wide, 64-token pages, 256 page slots a lane;
    64 experts of 2048 x 2816 read by layer index out of their stack."""
    from paddle_tpu.inference.kv_cache import (packed_write_plan,
                                               paged_write_packed)
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul
    from paddle_tpu.ops.pallas.mla_paged_attention import (
        mla_ragged_paged_attention)

    layers, pages, row, lanes, budget, slots = 3, 512, 640, 32, 1024, 256
    i32 = jnp.int32
    pool = sds(layers, pages, 1, PAGE, row)
    table = sds(lanes, slots, dtype=i32)
    tok, lane = sds(budget, dtype=i32), sds(lanes, dtype=i32)

    if what == "latent attention":
        def fn(q, pool, table, ctx, q_lens, slot, off, layer):
            return mla_ragged_paged_attention(
                q, pool, table, ctx, q_lens, slot, off, v_dim=512,
                scale=0.1, layer=layer)

        calls = mosaic_calls(fn, sds(budget, 16, row), pool, table, lane,
                             lane, tok, tok, sds(dtype=i32))
    elif what == "latent row write":
        def fn(pool, rows, table, slot, pos, layer):
            plan = packed_write_plan(table, slot, pos, PAGE, pages)
            return paged_write_packed(pool, rows, table, slot, pos, PAGE,
                                      layer=layer, plan=plan)

        calls = mosaic_calls(fn, pool, sds(budget, 1, row), table, tok, tok,
                             sds(dtype=i32))
    else:
        def fn(x, w, offsets, layer):
            return grouped_matmul(x, w, offsets, layer=layer)

        calls = mosaic_calls(fn, sds(6 * budget, 2048),
                             sds(2, 64, 2048, 2816),
                             sds(65, dtype=i32), sds(dtype=i32))
    assert calls == 1


@pytest.mark.parametrize("what", ["index scores", "selection",
                                  "selected latent attention"])
def test_sparse_latent_kernels_lower_at_glm_widths(what):
    """PR 34's kernels at the GLM-5.2 cell's widths: 32 index heads of 128
    over an index plane of 128-value keys, exact top-2048 over 768 page
    slots of 64, 64 query heads over the 640-wide latent row in tiles of 16
    tokens, all from one tile plan."""
    from paddle_tpu.ops.pallas import dsa_index as dsa
    from paddle_tpu.ops.pallas import mla_paged_attention as mla

    pages, lanes, budget, slots, heads = 512, 16, 256, 768, 64
    i32, tile = jnp.int32, mla.tile_for_heads(heads)
    grid = mla.tile_grid(lanes, budget, slots, PAGE, tile)
    table = sds(lanes, slots, dtype=i32)
    tok, lane = sds(budget, dtype=i32), sds(lanes, dtype=i32)

    def planned(fn):
        def with_plan(table, ctx, q_lens, slot, off, *rest):
            plan = mla.tile_plan(slot, off, q_lens, ctx, table,
                                 page_size=PAGE, num_pages=pages, tile=tile)
            return fn(plan, table, ctx, q_lens, slot, off, *rest)
        return with_plan

    scores = sds(grid.tiles * tile, grid.blocks * grid.keys,
                 dtype=jnp.float32)
    if what == "index scores":
        calls = mosaic_calls(
            planned(lambda plan, table, ctx, q_lens, slot, off, q, w, pool:
                    dsa.index_scores(q, w, pool, plan, 1, grid=grid)),
            table, lane, lane, tok, tok, sds(budget, 32, 128),
            sds(budget, 32, dtype=jnp.float32), sds(2, pages, 1, PAGE, 128))
    elif what == "selection":
        calls = mosaic_calls(
            planned(lambda plan, table, ctx, q_lens, slot, off, x:
                    dsa.select_mask(x, plan, grid=grid, k=2048)),
            table, lane, lane, tok, tok, scores)
    else:
        calls = mosaic_calls(
            planned(lambda plan, table, ctx, q_lens, slot, off, q, pool, sel:
                    mla.mla_ragged_paged_attention(
                        q, pool, table, ctx, q_lens, slot, off, v_dim=512,
                        scale=0.06, layer=2, plan=plan, tile=tile,
                        selected=sel, name=mla.SPARSE_MLA_KERNEL_NAME)),
            table, lane, lane, tok, tok, sds(budget, heads, 640),
            sds(5, pages, 1, PAGE, 640),
            sds(*scores.shape, dtype=jnp.bfloat16))
    assert calls == 1


def test_fused_mlp_fwd_bwd_lowers():
    """``bench.py --fused-mlp``'s kernels at the train step's shapes (the
    gelu backward's row block is sized by the fast-memory budget)."""
    from paddle_tpu.ops.pallas import fused_mlp as fm

    def ln(x, res, g, b):
        y, s = fm.fused_ln_residual(x, res, g, b, eps=1e-5,
                                    use_kernel=True)
        return (y.astype(jnp.float32).sum()
                + s.astype(jnp.float32).sum())

    x = sds(8, 1024, H)
    assert mosaic_calls(jax.grad(ln, argnums=(0, 1, 2, 3)), x, x, sds(H),
                        sds(H)) == 2

    def gelu(u, b):
        return fm.fused_bias_gelu(u, b, use_kernel=True).astype(
            jnp.float32).sum()

    assert mosaic_calls(jax.value_and_grad(gelu, argnums=(0, 1)),
                        sds(8, 1024, FFN), sds(FFN)) == 2
