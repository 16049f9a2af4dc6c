"""``mla_ragged_paged_attention``: the latent kernel against its gather-based
reference, interpreted on the CPU, and its planner alone (the tiled layout of
a packed step and the work items its grid runs over, made by the one
``work_items`` both paged kernels share), on the same cases."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import mla_paged_attention as mla

HEADS, ROW, V_DIM, PAGE = 2, 48, 32, 4
PAGES = 2                   # a key block is 2 pages of 4 keys: 8 keys
KEYS = PAGES * PAGE
# a tile of no more tokens than the few-rows form's ``FEW_TOKENS`` has no such
# form: every tile runs whole; of larger tiles the decode lanes' (and a
# chunk's one-token tail's) run the few-rows form beside whole ones (with 2
# heads its 8 rows are a tile of 4 tokens whole, and half a tile of 8)
TILES = [mla.FEW_TOKENS, 4, 8]
assert mla.FEW_TOKENS < 4

# name: (page slots a lane, token budget, [(q_len, kv_len) a lane]); kv_len
# is the context INCLUDING this step's rows, (0, n) an idle lane with a stale
# context, (0, 0) an empty one
CASES = {
    "decode-only": (6, 8, [(1, 9), (1, 24), (1, 1)]),
    "prefill-only": (6, 16, [(7, 7), (9, 20)]),
    "mixed": (8, 16, [(1, 17), (6, 30), (1, 8), (5, 5)]),
    "idle-lanes": (6, 8, [(0, 13), (1, 10), (0, 0), (3, 11)]),
    "no-lane-scheduled": (6, 8, [(0, 9), (0, 0), (0, 20)]),
    "chunk-starts-mid-page": (6, 8, [(6, 11), (1, 6)]),
    "context-ends-on-a-page": (6, 8, [(1, 12), (4, 20)]),
    "context-ends-on-a-key-block": (6, 8, [(1, 16), (5, 24)]),
    "one-token-past-a-key-block": (6, 8, [(1, 17), (5, 9)]),
    "slots-not-a-multiple-of-pages": (5, 8, [(1, 20), (6, 19), (1, 17)]),
    "full-budget": (6, 8, [(4, 24), (4, 12)]),
}


def _case(name, seed=0):
    """The operands of one step: distinct pages a lane, every row real."""
    pps, budget, lanes = CASES[name]
    rng = np.random.default_rng(seed)
    b = len(lanes)
    num_pages = b * pps + 3
    pool = jnp.asarray(rng.standard_normal((2, num_pages, 1, PAGE, ROW)),
                       jnp.float32)
    table = jnp.asarray(rng.permutation(num_pages)[:b * pps].reshape(b, pps),
                        jnp.int32)
    q_lens = jnp.asarray([q for q, _ in lanes], jnp.int32)
    kv_lens = jnp.asarray([kv for _, kv in lanes], jnp.int32)
    slot = [i for i, (q, _) in enumerate(lanes) for _ in range(q)]
    off = [o for q, _ in lanes for o in range(q)]
    assert len(slot) <= budget
    pad = budget - len(slot)
    tok_slot = jnp.asarray(slot + [-1] * pad, jnp.int32)
    tok_off = jnp.asarray(off + [0] * pad, jnp.int32)
    q = jnp.asarray(rng.standard_normal((budget, HEADS, ROW)), jnp.float32)
    return (q, pool, table, kv_lens, q_lens, tok_slot, tok_off), lanes


@pytest.mark.parametrize("tile", TILES, ids=lambda n: f"tile{n}")
@pytest.mark.parametrize("name", list(CASES))
def test_interpreted_kernel_against_the_reference(name, tile):
    """Layer 1 of a two-layer pool, float32 so the two agree to rounding;
    padding rows come back zero from both (the interpreter hands back NaN
    where the kernel wrote nothing)."""
    args, lanes = _case(name)
    kw = dict(v_dim=V_DIM, scale=0.3, layer=jnp.int32(1))
    ref = mla.mla_ragged_paged_attention_reference(*args, **kw)
    out = jax.jit(lambda *a: mla.mla_ragged_paged_attention(
        *a, use_kernel=True, tile=tile, pages_per_step=PAGES, **kw))(*args)
    assert out.shape == ref.shape == (args[0].shape[0], HEADS, V_DIM)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)
    fed = sum(q for q, _ in lanes)
    assert not np.asarray(out)[fed:].any()
    assert fed == 0 or np.asarray(out)[:fed].any()


@pytest.mark.parametrize("TILE", TILES, ids=lambda n: f"tile{n}")
@pytest.mark.parametrize("name", list(CASES))
def test_planner_lists_every_live_tile_and_key_block_once_in_order(name,
                                                                   TILE):
    """The work items against the plain enumeration: tile after tile, each
    over the key blocks that hold a key its last real row sees; ``total``
    their count (one item that does nothing where no lane is scheduled), a
    tile's last item marked, every live slot naming its lane's page and a
    dead one the page its operand named on the item before; a few-rows
    tile holds the whole query block of the full tile before it (the first
    one after it where none is before)."""
    (q, pool, table, kv_lens, q_lens, tok_slot, tok_off), lanes = _case(name)
    pps, budget, _ = CASES[name]
    plan = mla.tile_plan(tok_slot, tok_off, q_lens, kv_lens, table,
                         page_size=PAGE, num_pages=pool.shape[1], tile=TILE,
                         pages_per_step=PAGES)
    grid = mla.tile_grid(len(lanes), budget, pps, PAGE, TILE, PAGES)
    assert (grid.keys, grid.tiles, grid.blocks, grid.few) == (
        KEYS, len(lanes) + budget // TILE, -(-pps // PAGES),
        mla.FEW_TOKENS if TILE > mla.FEW_TOKENS else 0)
    plan = jax.tree.map(np.asarray, plan)
    table = np.asarray(table)

    want, tile = [], 0         # (tile, lane, block, horizon, is last)
    for lane, (q_len, kv_len) in enumerate(lanes):
        for start in range(0, q_len, TILE):
            rows = min(TILE, q_len - start)
            assert (plan.rows[tile], plan.first[tile], plan.ctx[tile]) == (
                rows, kv_len - q_len + start, kv_len)
            horizon = kv_len - q_len + start + rows
            blocks = -(-horizon // KEYS)
            want += [(tile, lane, j, horizon, j == blocks - 1)
                     for j in range(blocks)]
            tile += 1
    assert not plan.rows[tile:].any()
    whole = [t for t in range(grid.tiles) if plan.rows[t] > grid.few]
    for t in range(grid.tiles):
        want_full = ([w for w in whole if w <= t][-1:] or whole[:1] or [0])[0]
        assert plan.full[t] == want_full
    assert int(plan.total) == max(len(want), 1) == grid.steps(
        [kv for q, kv in lanes if q], [q for q, _ in lanes if q])
    assert len(want) == sum(grid.live_steps(kv, q) for q, kv in lanes if q)
    assert len(want) <= grid.tiles * grid.blocks == plan.tile.shape[0]
    named = plan.page.reshape(-1, PAGES)
    before = named[0]
    for it, (tile, lane, j, horizon, last) in enumerate(want):
        assert (plan.tile[it], plan.block[it], plan.last[it]) == (
            tile, j, int(last))
        for k in range(PAGES):
            slot = j * PAGES + k
            live = slot < pps and slot * PAGE < horizon
            assert named[it, k] == (table[lane, slot] if live else before[k])
        before = named[it]
    # the rows' places: each real token once, tile by tile
    fed = sum(q for q, _ in lanes)
    assert sorted(plan.dest[:fed]) == [
        t * TILE + r for t in range(grid.tiles) for r in range(plan.rows[t])]
    assert (plan.dest[fed:] == grid.tiles * TILE).all()


def test_tile_grid_counts_a_lanes_tiles_each_to_its_own_horizon():
    """The cell's shapes: 512 keys a grid step. A decode lane reads its
    context once; a 256-row chunk is four tiles, each to its last row."""
    grid = mla.tile_grid(32, 1024, 256, 64)
    assert (grid.tile, grid.pages, grid.keys, grid.tiles, grid.blocks) == (
        mla.TILE_DEFAULT, mla.PAGES_PER_STEP_DEFAULT,
        mla.PAGES_PER_STEP_DEFAULT * 64, 32 + 1024 // mla.TILE_DEFAULT,
        -(-256 // mla.PAGES_PER_STEP_DEFAULT))
    keys, tile = grid.keys, grid.tile
    assert [grid.live_steps(n) for n in (1, keys, keys + 1, 16384)] == [
        1, 1, 2, 16384 // keys]
    chunk = [-(-(2048 - 256 + r) // keys)
             for r in range(tile, 256 + tile, tile)]
    assert grid.live_steps(2048, 256) == sum(chunk)
    assert grid.steps([], []) == 1
    assert grid.steps([2048, 100], [256, 1]) == sum(chunk) + 1
