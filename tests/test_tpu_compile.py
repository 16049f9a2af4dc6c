"""Compile for the TPU, on the CPU: the unified serving step.

``tests/test_tpu_lowering.py`` stops at Pallas's lowering; this file hands
the whole step to the chip's own compiler, which is installed here and
compiles for a chip that is described, not attached (the
``on-chip-measurement`` guide, section 2). What only the compiled program
shows is whether the KV pools stay ONE buffer through the step (PR 27): XLA's
layout assignment and copy insertion decide that, not the jaxpr. At the
cells' widths (12 heads of 128, 64-token pages) and two layers, so a compile
takes seconds.

The topology is described inside a fixture, never at import: one process at a
time may load the TPU's library, and every xdist worker imports this file.
Keep such tests in this one file.
"""
import os
import sys

import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LAYERS, H, HEADS, HD, FFN, VOCAB, SEQ = 2, 1536, 12, 128, 6144, 50304, 2048
LANES, PAGE, PAGES, BUDGET, CHUNK = 8, 64, 136, 128, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile is written to the persistent cache and cannot be read
    # back without a chip: keep it out
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cached)
    compilation_cache.reset_cache()


def _step_avals(dev, kv_quant, LANES=LANES, PAGES=PAGES, BUDGET=BUDGET):
    """The unified step's arguments as shapes on ``dev`` (signature in
    ``build_unified_step``'s docstring)."""
    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    i32 = jnp.int32
    layers = {k: sds(LAYERS, *shape) for k, shape in dict(
        ln1_g=(H,), ln1_b=(H,), wqkv=(H, 3 * H), bqkv=(3 * H,), wo=(H, H),
        bo=(H,), ln2_g=(H,), ln2_b=(H,), w1=(H, FFN), b1=(FFN,),
        w2=(FFN, H), b2=(H,)).items()}
    params = dict(tok_emb=sds(VOCAB, H), pos_emb=sds(SEQ, H), lnf_g=sds(H),
                  lnf_b=sds(H), layers=layers)
    pool = sds(LAYERS, PAGES, HEADS, PAGE, HD,
               dtype=jnp.int8 if kv_quant else jnp.bfloat16)
    pools = [pool, pool]
    if kv_quant:
        pools += [sds(LAYERS, PAGES, HEADS, PAGE, dtype=jnp.float32)] * 2
    tok, lane = sds(BUDGET, dtype=i32), sds(LANES, dtype=i32)
    return ([params, tok, tok, tok, lane, lane, lane, tok, lane, lane, lane]
            + pools
            + [sds(LANES, SEQ // PAGE, dtype=i32), lane, lane,
               sds(LANES, 2, dtype=jnp.uint32), sds(LANES, dtype=jnp.float32),
               lane, sds(LANES, dtype=jnp.float32)]), pool


@pytest.mark.parametrize("kv_quant", [False, True], ids=["fp", "int8"])
def test_compiled_step_keeps_the_pools_in_one_buffer(one_chip, monkeypatch,
                                                     kv_quant):
    """No ``copy``, ``dynamic-slice`` or ``dynamic-update-slice`` of a
    pool's or the stack's shape outside the copy-on-write lanes, both
    kernels in the program, and a temp far under one stacked pool (the
    scanned pools cost a second copy of both stacks)."""
    import paddle_tpu  # noqa: F401  framework config (matmul precision)
    from paddle_tpu.models.gpt import GPTConfig, build_unified_step
    from paddle_tpu.ops.pallas.paged_attention import RAGGED_KERNEL_NAME
    from paddle_tpu.ops.pallas.paged_write import KV_WRITE_KERNEL_NAME

    sys.path.insert(0, REPO)
    try:
        from chip_smoke import _is_mosaic_call, pool_copies
    finally:
        sys.path.remove(REPO)

    # the kernels pick interpret mode and ``use_kernel=None`` from
    # ``jax.default_backend()``: answer as the chip would
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=H, num_layers=LAYERS,
                    num_heads=HEADS, max_seq_len=SEQ)
    step = build_unified_step(cfg, PAGE, CHUNK, kv_quant=kv_quant)
    avals, pool = _step_avals(one_chip, kv_quant)
    compiled = step.lower(*avals).compile()
    hlo = compiled.as_text()
    for kernel in (RAGGED_KERNEL_NAME, KV_WRITE_KERNEL_NAME):
        assert any(_is_mosaic_call(line, kernel)
                   for line in hlo.splitlines()), kernel
    assert pool_copies(hlo, pool.shape) == []
    pool_bytes = pool.size * pool.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes


def test_compiled_latent_step_keeps_its_one_pool_in_one_buffer(one_chip,
                                                               monkeypatch):
    """PR 28: the DeepSeek-V2-Lite block through the same step, at the
    cell's widths (16 heads over a 576-value latent row stored 640 wide, 64
    experts of width 1408, a 102,400-word head), one dense and one routed
    layer. The latent kernel, the in-place row write and the grouped GEMM
    are Mosaic calls of the program; nothing of the pool's shape is copied,
    the copy-on-write lanes included (their gather of 640-wide rows would
    slice the whole pool: they go lane by lane); and the expert stacks are
    read by layer index, so the program's temporaries stay far under one
    layer's experts (1.1 GB)."""
    import paddle_tpu  # noqa: F401  framework config (matmul precision)
    from paddle_tpu.models.deepseek_v2 import DeepseekV2Config
    from paddle_tpu.models.gpt import build_unified_step, step_row_ladder
    from paddle_tpu.ops.pallas.mla_paged_attention import (MLA_KERNEL_NAME,
                                                           tile_grid)

    sys.path.insert(0, REPO)
    try:
        from chip_smoke import _is_mosaic_call, pool_copies
        from benchmark.tools.compile_serve_latent_moe_for_v5e import (
            step_avals)
    finally:
        sys.path.remove(REPO)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = DeepseekV2Config(num_layers=2, max_seq_len=4096)
    dep = dict(token_budget=256, max_batch=8, max_seq_len=4096, page_size=64)
    step = build_unified_step(cfg, 64, 64)
    avals = step_avals(cfg, dep, 512, one_chip, jnp.bfloat16)
    compiled = step.lower(*avals).compile()
    hlo = compiled.as_text()
    for kernel in ("mla_ragged_paged_attention", "paged_kv_write",
                   "grouped_matmul"):
        assert any(_is_mosaic_call(line, kernel)
                   for line in hlo.splitlines()), kernel
    # PR 33: exactly one call of the latent kernel in a layer scan's body
    # (the dense layer's scan and the routed layer's: two a step); PR 35: the
    # program holds the step once a rung of its row ladder and runs one
    rungs = len(step_row_ladder(dep["max_batch"], 0, 64,
                                dep["token_budget"]))
    assert rungs == 3
    assert sum(_is_mosaic_call(line, MLA_KERNEL_NAME)
               for line in hlo.splitlines()) == 2 * rungs
    pool = avals[11]
    assert pool.shape == (2, 512, 1, 64, 640)
    assert pool_copies(hlo, pool.shape) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 0.3e9
    # the grid at the cell's shapes (32 lanes, 256 page slots, budget 1,024),
    # by the function the kernel and the scheduler's counters are built from:
    # never more than the static grid it replaces (48 tiles x 32 key blocks
    # of 8 pages = 1,536 steps a call), and the live (tile, key block) pairs
    # alone: a decode lane at 2,048 reads 2,048 keys, a 256-row chunk's tiles
    # each up to their own last row; no lane scheduled, one step
    grid = tile_grid(32, 1024, 256, 64)
    assert grid.tiles == 32 + 1024 // grid.tile
    assert grid.tiles * -(-256 // 8) == 1536
    tiles = 256 // grid.tile
    assert grid.steps([16384] * 32, [1] * 32) == 32 * grid.blocks
    assert (grid.steps([16384] * 32, [1] * 28 + [256] * 4)
            <= grid.tiles * grid.blocks <= 1536)
    assert grid.steps([2048] * 32, [1] * 32) == 32 * -(-2048 // grid.keys)
    assert grid.steps([2048] * 32, [1] * 31 + [256]) == (
        31 * -(-2048 // grid.keys)
        + sum(-(-(1792 + (k + 1) * grid.tile) // grid.keys)
              for k in range(tiles)))
    assert grid.steps([], []) == 1


@pytest.mark.parametrize("kv_quant", [False, True], ids=["fp", "int8"])
def test_compiled_step_at_the_590m_cells_serving_widths(one_chip, monkeypatch,
                                                        kv_quant):
    """PR 29: the step as the 590M serving cells run it (24 lanes, 32 page
    slots of 64 keys, chunk 64, budget 512, 768 pages; two layers). The
    ragged kernel, with all heads and several pages a grid step, is a Mosaic
    call named ``ragged_paged_attention``, exactly one in the layer scan's
    body; passing the stack once per page a step reads provokes no
    pool-shaped copy; and a call launches at most lanes x ceil(page slots /
    pages a step) grid steps (every lane at the table's end; one a lane when
    all are idle), by the function the kernel module exports."""
    import paddle_tpu  # noqa: F401  framework config (matmul precision)
    from paddle_tpu.models.gpt import (GPTConfig, build_unified_step,
                                       step_row_ladder)
    from paddle_tpu.ops.pallas.paged_attention import (RAGGED_KERNEL_NAME,
                                                       ragged_grid)

    sys.path.insert(0, REPO)
    try:
        from chip_smoke import _is_mosaic_call, pool_copies
    finally:
        sys.path.remove(REPO)

    lanes, slots = 24, SEQ // PAGE
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=H, num_layers=LAYERS,
                    num_heads=HEADS, max_seq_len=SEQ)
    step = build_unified_step(cfg, PAGE, CHUNK, kv_quant=kv_quant)
    avals, pool = _step_avals(one_chip, kv_quant, LANES=lanes, PAGES=768,
                              BUDGET=512)
    compiled = step.lower(*avals).compile()
    hlo = compiled.as_text()
    # one call a rung of the row ladder (PR 35: 32 / 160 / 512 rows), one
    # rung a step; the pools stay one buffer through the conditionals
    assert step_row_ladder(lanes, 0, CHUNK, 512) == (32, 160, 512)
    assert sum(_is_mosaic_call(line, RAGGED_KERNEL_NAME)
               for line in hlo.splitlines()) == 3
    assert pool_copies(hlo, pool.shape) == []
    assert compiled.memory_analysis().temp_size_in_bytes < (
        pool.size * pool.dtype.itemsize)
    grid = ragged_grid(lanes, slots, CHUNK, HEADS, HEADS, PAGE, HD,
                       pool.dtype, jnp.bfloat16)
    assert grid.heads == HEADS and grid.pages > 1
    assert grid.steps([SEQ] * lanes) == lanes * -(-slots // grid.pages) == 192
    assert grid.steps([]) == lanes


def test_compiled_sparse_latent_step_keeps_both_planes_in_place(one_chip,
                                                                monkeypatch):
    """PR 34: GLM-5.2's block through the same step at the cell's widths (64
    heads, a low-rank query, 32 index heads, top-2048, 16 of 256 experts
    held), three layers in three runs: dense with an indexer, routed sharing
    its selection, routed with an indexer. The score kernel, the selection
    kernel and the selected form of the latent kernel are Mosaic calls of the
    program (the dense form's name is NOT in it: what is counted for one is
    never applied to the other); neither the latent pool nor the index plane
    is copied; the program's temporaries (scores and selection of 384 tiled
    rows over 49,152 key slots) stay far under one layer's experts."""
    import paddle_tpu  # noqa: F401  framework config (matmul precision)
    from paddle_tpu.models.glm_moe_dsa import GlmMoeDsaConfig
    from paddle_tpu.models.gpt import build_unified_step, step_row_ladder

    sys.path.insert(0, REPO)
    try:
        from chip_smoke import _is_mosaic_call, pool_copies
        from benchmark.tools.compile_serve_sparse_latent_moe_for_v5e import (
            step_avals)
    finally:
        sys.path.remove(REPO)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = GlmMoeDsaConfig(
        vocab_size=19360, num_layers=3, first_k_dense_replace=1,
        n_routed_experts=16, max_seq_len=49152,
        indexer_types=("full", "shared", "full"))
    dep = dict(token_budget=256, max_batch=8, max_seq_len=49152, page_size=64)
    step = build_unified_step(cfg, 64, 128)
    avals, (pool, index) = step_avals(cfg, dep, 512, one_chip, jnp.bfloat16)
    compiled = step.lower(*avals).compile()
    lines = compiled.as_text().splitlines()

    def calls(kernel):
        # by the call's own name: a line also names its operands, and the
        # selection kernel's operand is the score kernel's result
        return sum(_is_mosaic_call(line, f"/{kernel}/pallas_call")
                   for line in lines)

    # one call a layer of the attention kernel, one a layer with an indexer
    # of the other two, in each rung of the row ladder (PR 35; a step runs
    # one rung): the roofline readers count the calls the trace shows
    rungs = len(step_row_ladder(dep["max_batch"], 0, 128,
                                dep["token_budget"]))
    assert rungs == 2
    assert calls("sparse_mla_paged_attention") == 3 * rungs
    assert calls("dsa_index_scores") == calls("dsa_topk_select") == 2 * rungs
    assert calls("grouped_matmul") == 4 * rungs
    assert calls("paged_kv_write") == 5 * rungs
    assert calls("mla_ragged_paged_attention") == 0
    assert pool.shape == (3, 512, 1, 64, 640)
    assert index.shape == (2, 512, 1, 64, 128)
    hlo = "\n".join(lines)
    assert pool_copies(hlo, pool.shape) == []
    assert pool_copies(hlo, index.shape) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 0.4e9


def test_compiled_window_step_keeps_both_groups_in_place(one_chip,
                                                         monkeypatch):
    """PR 36: Command A+'s block through the same step at the cell's widths
    (128 query heads over 8 key-value heads of 128, window 4,096, 16 of 128
    experts held, four shared experts as one MLP), two layers in two runs of
    ONE (a window layer, a full layer: both unstacked, neither scanned), 8
    lanes. At 16 query heads a key-value head the chunk's block is 4,096 rows
    a head and the kernel asks Mosaic for its fast memory; the decode rung's
    block is cut to the rung. Neither group's pools are copied, and both
    kinds of call are Mosaic calls under their own sub-scope."""
    import paddle_tpu  # noqa: F401  framework config (matmul precision)
    from paddle_tpu.models.cohere2_moe import (FULL, WINDOW,
                                               Cohere2MoeConfig)
    from paddle_tpu.models.gpt import build_unified_step, step_row_ladder

    sys.path.insert(0, REPO)
    try:
        from chip_smoke import _is_mosaic_call, pool_copies
        from benchmark.tools.compile_serve_window_moe_for_v5e import (
            step_avals)
    finally:
        sys.path.remove(REPO)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = Cohere2MoeConfig(vocab_size=32768, num_layers=2,
                           n_routed_experts=16, max_seq_len=57344,
                           layer_types=(WINDOW, FULL))
    dep = dict(token_budget=512, max_batch=8, max_seq_len=57344,
               page_size=64, chunk=256)
    step = build_unified_step(cfg, 64, 256)
    avals, (full, win) = step_avals(cfg, dep, 512, one_chip, jnp.bfloat16)
    compiled = step.lower(*avals).compile()
    lines = compiled.as_text().splitlines()

    def calls(kernel, under=""):
        return sum(_is_mosaic_call(line, f"/{kernel}/pallas_call")
                   and under in line for line in lines)

    rungs = len(step_row_ladder(8, 0, 256, 512))
    assert rungs == 2
    assert calls("ragged_paged_attention") == 2 * rungs
    assert calls("ragged_paged_attention", "attn_window") == rungs
    assert calls("ragged_paged_attention", "attn_full") == rungs
    assert calls("grouped_matmul") == 4 * rungs
    assert calls("paged_kv_write") == 4 * rungs
    assert full.shape == (1, 512, 8, 64, 128)
    assert win.shape == (1, 8 * 69, 8, 64, 128)
    hlo = "\n".join(lines)
    assert pool_copies(hlo, full.shape) == []
    assert pool_copies(hlo, win.shape) == []
    # the prefill rung's query blocks ([8, 256, 128, 128] and the kernel's
    # float32 result) are the temporaries; far under one layer's weights
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


@pytest.mark.parametrize("e,m,k,n", [
    pytest.param(16, 256, 4096, 8192, id="cmdaplus-gate-up"),
    pytest.param(16, 256, 4096, 4096, id="cmdaplus-down"),
    pytest.param(16, 128, 6144, 4096, id="glm-gate-up"),
    pytest.param(16, 128, 2048, 6144, id="glm-down"),
    pytest.param(64, 192, 2048, 2816, id="dsv2-gate-up"),
    pytest.param(64, 192, 1408, 2048, id="dsv2-down"),
    pytest.param(64, 6144, 2048, 2816, id="dsv2-gate-up-top-rung"),
    pytest.param(64, 6144, 1408, 2048, id="dsv2-down-top-rung"),
])
def test_grouped_matmul_compiles_alone_at_the_routed_cells_shapes(
        one_chip, monkeypatch, e, m, k, n):
    """PR 37: the KERNEL alone (seconds each; the whole steps above hold the
    call counts) at the three routed cells' six ``(k, n)`` pairs, stacked
    and read by layer index as their scans do: the float forward's tile
    (1-2 MiB of weights a grid step, two buffers of it, the row tile, the
    output block and the float32 scratch) fits Mosaic's default fast memory,
    the grid's dynamic first bound lowers, and the call hands back bf16."""
    import paddle_tpu  # noqa: F401  framework config (matmul precision)
    from paddle_tpu.ops.pallas.grouped_matmul import (
        GROUPED_KERNEL_NAME, VMEM_DEFAULT_BYTES, _blocks_for, fwd_vmem_bytes,
        grouped_matmul)

    sys.path.insert(0, REPO)
    try:
        from chip_smoke import _is_mosaic_call
    finally:
        sys.path.remove(REPO)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bf = jnp.bfloat16

    def sds(*shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(x, w, offsets, layer):
        return grouped_matmul(x, w, offsets, layer=layer)

    with jax.enable_x64(False):
        compiled = jax.jit(fn).lower(
            sds(m, k), sds(2, e, k, n), sds(e + 1, dtype=jnp.int32),
            sds(dtype=jnp.int32)).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if _is_mosaic_call(line, f"/{GROUPED_KERNEL_NAME}/pallas_call")]
    assert len(calls) == 1
    assert calls[0].split("=")[1].strip().startswith("bf16[")
    bm, bn, bk = _blocks_for(e, m, k, n, 0, k, bf)
    assert fwd_vmem_bytes(bm, bn, bk, k, bf) <= VMEM_DEFAULT_BYTES
