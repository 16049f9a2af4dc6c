"""Ragged paged attention over a LATENT cache — Pallas TPU kernel.

Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434) caches ONE row
per token and layer, ``[c | k_pe]`` (``kv_lora_rank`` normalised latents, then
the rotated key slice every head shares), in a pool ``[num_layers, num_pages,
1, page_size, row]``: the layout of ``paged_attention.py``'s pools with one
KV head whose "head_dim" is the row. In the ABSORBED form each query head
carries ``[q_nope W^K | q_pe]``, as wide as the row, so attention is
multi-query over the rows themselves: the score is one dot with the row and
the value IS the row's first ``v_dim`` entries (``o_lat = sum p c``; the
caller applies ``W^V`` behind the softmax). One shared 576-wide key and
512-wide value per token serve all 16 heads, so the cache is read once per
query TILE, not once per head.

The grid pays for keys, as ``ragged_paged_attention``'s does (PR 33), but its
query groups are TILES of rows, not lanes: contexts here run to hundreds of
pages and a prefill chunk to hundreds of rows, so

- the query rows of a step arrive as tiles of ``tile`` tokens (all heads of
  a token adjacent: a tile is ``[tile * heads, row]``). :func:`tile_plan`
  lays the packed stream's rows out tile by tile, a lane with ``q_len`` rows
  getting ``ceil(q_len / tile)`` tiles, at most ``lanes + budget // tile``
  in all;
- the grid is ONE axis of WORK ITEMS under a dynamic bound: every tile's key
  blocks that hold a key its last real row sees, in order, tile after tile,
  listed by ``paged_attention.work_items`` (the planner both paged kernels
  share; a group is a tile here, a lane there). An unused tile has no item
  and a page slot past a tile's causal horizon is never visited; a step with
  no lane scheduled runs one item that does nothing. The items depend on
  the step, not on the layer: :func:`tile_plan` makes them once and every
  layer's call takes them as scalar-prefetch tables, in which each block
  index is one lookup;
- each item reads ``pages_per_step`` pages: the pool is passed that many
  times, each operand with its own block index map, so the pipeline fetches
  them together and one item covers ``pages_per_step * page_size`` keys with
  one rescale of the accumulator; two 64-token pages side by side make a
  128-key block (whole vector lanes, a full-width MXU pass); inside a tile's
  last item a slot past the horizon names the page its operand named on the
  item before (nothing is fetched) and its keys are masked;
- a tile moves the rows it holds. One that holds only a decode lane's one
  token runs the few-rows form: it reads the tile's first rows through a
  second, few-rows view of the query buffer (the whole-block view stands
  still on the full tile before it, so nothing of it is fetched), computes
  and initialises those rows alone, and writes them alone: the output stays
  in HBM and each tile's last item copies its rows there. Rows that hold no
  real token are never written; the wrapper reads none of them.

:func:`tile_grid` is that layout as a function of the deployment's shapes,
written once: the plan and the kernel are built from it and the scheduler
counts the grid steps of its lanes' rows and contexts with it.

The pool is addressed by layer index through the block index maps (PR 27's
form): the serving step's scan carries the stack and never slices it.
:func:`mla_ragged_paged_attention_reference` is the gather-based oracle and
the non-TPU path.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import autotune_cache as _atc
from .paged_attention import (NEG_INF, _dotf32, _interpret, gather_pages,
                              use_kernel_default, work_items)

# stable pallas_call name (survives into the compiled HLO and the device
# trace): how a check or a trace reduction finds the kernel
MLA_KERNEL_NAME = "mla_ragged_paged_attention"
# the same kernel reading a SELECTION of the keys (``selected=``; learned
# sparse attention, ``dsa_index.py``) runs under a name of its own: what is
# counted for one is never applied to the other
SPARSE_MLA_KERNEL_NAME = "sparse_mla_paged_attention"

# Chosen on the v5e at the DeepSeek-V2-Lite cell's shapes with no dead grid
# step in the way (PERF.md, PR 33: microseconds a call of the kernel alone over
# hand-made steps of 0-3 prefill chunks beside decode lanes, 1,368 at PR 28's
# static grid). Tiles of 64 tokens and 8 pages a step: 387; tiles of 32 with
# 16 pages 406; 16 pages a step 376 (decode lanes alone 289 against 336, a
# step with three chunks 424 against 406: a step's cost is now its pages'
# fetches plus about 0.05 us an operand); 32 pages a step and tiles of 128 do
# not fit the kernel's 16 MiB of fast memory.
# A few-rows form of ONE token (16 rows for 16 heads, a decode lane's) against
# four: 357 against 387 us a call, 35.54 against 35.89 ms a step in the cell.
TILE_DEFAULT = 64           # query tokens per tile
PAGES_PER_STEP_DEFAULT = 8  # pages one grid step reads
FEW_TOKENS = 1              # a tile with no more real tokens runs the few-rows form


def tile_for_heads(heads: int) -> int:
    """Query tokens a tile holds for ``heads`` query heads: the tile's rows
    (tokens x heads) are what the kernel keeps in fast memory, 1,024 of them
    at the widths it was tuned for (64 tokens of 16 heads); never under the
    16 rows a bf16 tile of a selection mask has."""
    return max(16, min(TILE_DEFAULT, TILE_DEFAULT * 16 // int(heads)))


class TileGrid(NamedTuple):
    """The grid of one ``mla_ragged_paged_attention`` call
    (:func:`tile_grid`): query rows in tiles of ``tile`` tokens, at most
    ``tiles`` of them a step; a grid step reads ``pages`` pages of
    ``page_size`` keys for one tile, and a full page table gives a tile
    ``blocks`` such key blocks. The grid runs over the (tile, key block)
    pairs that hold keys the tile's rows see, tile after tile. A tile with
    at most ``few`` real tokens (a decode lane's one) runs the few-rows form:
    only those tokens' rows are fetched, computed and written (0: no such
    form, where a tile holds no more than that anyway)."""
    tile: int
    pages: int
    page_size: int
    tiles: int
    blocks: int
    few: int

    @property
    def keys(self) -> int:
        """Keys one grid step covers."""
        return self.pages * self.page_size

    def live_steps(self, kv_len: int, q_len: int = 1) -> int:
        """Grid steps that hold keys of a scheduled lane: its ``q_len`` rows
        (the last at position ``kv_len - 1``) tile by tile, each tile over
        the key blocks up to its last row's own position."""
        q_len, first = int(q_len), int(kv_len) - int(q_len)
        return sum(-(-(first + min(start + self.tile, q_len)) // self.keys)
                   for start in range(0, q_len, self.tile))

    def steps(self, contexts, q_lens) -> int:
        """Grid steps one call launches when the scheduled lanes' contexts
        are ``contexts`` and they feed ``q_lens`` rows: the live ones (a step
        with no lane scheduled launches one, which does nothing)."""
        return max(1, sum(map(self.live_steps, contexts, q_lens)))


def tile_grid(lanes, budget, pps, page_size, tile: int = TILE_DEFAULT,
              pages: int = PAGES_PER_STEP_DEFAULT) -> TileGrid:
    """How :func:`mla_ragged_paged_attention` lays its grid over ``lanes``
    lanes of ``pps`` page slots feeding at most ``budget`` packed rows a
    step: written once, so the scheduler's count of grid steps
    (``inference/serving.py``) cannot drift from the kernel."""
    pages = max(1, min(int(pages), pps))
    return TileGrid(tile=tile, pages=pages, page_size=page_size,
                    tiles=lanes + budget // tile, blocks=-(-pps // pages),
                    few=FEW_TOKENS if FEW_TOKENS < tile else 0)


class TilePlan(NamedTuple):
    """One step's query rows, tile by tile, and the grid over them (see
    :func:`tile_plan`). Per tile ``[n]``: ``rows`` how many of its ``tile``
    rows are real (0: an unused tile, which the grid never visits), ``first``
    the position of its row 0 and ``ctx`` its lane's context, this step's
    rows included; ``full`` the tile whose WHOLE query block the kernel holds
    while it serves this one: itself for a tile of more than ``few`` tokens,
    else the full tile before it (the first one after it where none is
    before), so a few-rows tile fetches no whole block. ``dest [t]``: each
    packed token's row in the tiled buffer (``n * tile``: dropped). The work
    items (``work_items`` of ``paged_attention.py`` over tiles): ``total`` of
    them run, item ``i`` being key block ``block[i]`` of tile ``tile[i]``,
    ``last[i]`` where it is the tile's last, and naming the pages
    ``page[i * pages:(i + 1) * pages]``."""
    rows: jax.Array
    first: jax.Array
    ctx: jax.Array
    full: jax.Array
    dest: jax.Array
    total: jax.Array
    tile: jax.Array
    block: jax.Array
    last: jax.Array
    page: jax.Array


def tile_plan(tok_slot, tok_off, q_lens, kv_lens, page_table, *, page_size,
              num_pages, tile: int = TILE_DEFAULT,
              pages_per_step: int = PAGES_PER_STEP_DEFAULT):
    """The tiled layout of a packed step and its work items: made once per
    step from what every layer's call shares. ``tok_slot [t]`` (< 0:
    padding), ``tok_off [t]`` each token's place among its lane's rows of
    this step, ``q_lens [b]``, ``kv_lens [b]`` each lane's context INCLUDING
    this step's rows, ``page_table [b, pps]`` into a pool of ``num_pages``
    pages of ``page_size`` keys."""
    b, t = q_lens.shape[0], tok_slot.shape[0]
    i32 = jnp.int32
    grid = tile_grid(b, t, page_table.shape[1], page_size, tile,
                     pages_per_step)
    n = grid.tiles
    q_lens, kv_lens = q_lens.astype(i32), kv_lens.astype(i32)
    per_lane = -(-q_lens // tile)                                 # [b]
    ends = jnp.cumsum(per_lane)
    base = ends - per_lane
    item = jnp.arange(n, dtype=i32)
    # a tile's lane: the lanes whose tiles end at or before it (a dense
    # comparison; ``searchsorted`` is a loop on the device)
    lane = jnp.minimum(jnp.sum(item[:, None] >= ends[None, :], axis=1),
                       b - 1).astype(i32)
    start = jnp.maximum((item - base[lane]) * tile, 0).astype(i32)
    rows = jnp.where(item < ends[-1],
                     jnp.clip(q_lens[lane] - start, 0, tile), 0).astype(i32)
    slot_c = jnp.clip(tok_slot, 0, b - 1)
    off = jnp.maximum(tok_off, 0)
    valid = (tok_slot >= 0) & (tok_off >= 0) & (tok_off < q_lens[slot_c])
    dest = jnp.where(valid, (base[slot_c] + off // tile) * tile + off % tile,
                     n * tile)
    ctx = kv_lens[lane]
    first = ctx - q_lens[lane] + start        # position of the tile's row 0
    # the keys a tile's last real row sees: the causal horizon of the tile
    horizon = jnp.minimum(first + rows, ctx)
    whole = jnp.where(rows > grid.few, item, -1)
    before = jax.lax.cummax(whole)
    after = jax.lax.cummin(jnp.where(whole < 0, n, whole), reverse=True)
    full = jnp.where(before >= 0, before, jnp.where(after < n, after, 0))
    total, of_tile, block, last, page = work_items(
        page_table.astype(i32), horizon, rows, pages=grid.pages,
        page_size=page_size, blocks=grid.blocks, num_pages=num_pages,
        keep=False, lane=lane)
    return TilePlan(rows=rows, first=first, ctx=ctx, full=full,
                    dest=dest.astype(i32), total=total, tile=of_tile,
                    block=block, last=last, page=page)


def _mla_kernel(tile_ref, blk_ref, last_ref, tbl_ref, first_ref, ctx_ref,
                rows_ref, full_ref, layer_ref, *refs, page_size, pages, heads,
                v_dim, scale, few_tokens, few_rows, selected=False):
    """One work item: ``pages`` pages of one tile's context. The tables are
    :class:`TilePlan`'s (``tbl_ref``, ``full_ref`` and ``layer_ref`` are the
    index maps' business alone). refs: the tile's whole query block, [its
    first ``few_rows`` rows: a second view of the same buffer], [``selected``:
    the item's block ``[tile, keys]`` of the selection mask], the pages,
    the output (left where it lies: a tile's rows go there by a copy of the
    kernel's own), then scratch: the softmax state m, l, acc, the output
    rows on their way out and the copy's semaphore."""
    q_refs, refs = refs[:2 if few_rows else 1], refs[2 if few_rows else 1:]
    if selected:
        sel_ref, refs = refs[0], refs[1:]
    page_refs = refs[:pages]
    o_hbm, m_ref, l_ref, acc_ref, out_ref, sem = refs[pages:]
    it = pl.program_id(0)
    i = tile_ref[it]
    j = blk_ref[it]
    ctx = ctx_ref[i]             # context INCLUDING this step's rows
    rows = rows_ref[i]
    first = first_ref[i]         # position of the tile's row 0
    span = pages * page_size
    whole = q_refs[0].shape[0]

    def by_form(when, part):
        """``part(q_ref, r)`` under ``when``, in the form the tile takes: a
        decode lane's tile holds ONE real token, and the few-rows form
        fetches, computes and writes that token's rows alone; a
        prefill chunk's tile runs whole, so the MXU streams all its rows
        through each page it loads."""
        if few_rows:
            pl.when(when & (rows <= few_tokens))(
                lambda: part(q_refs[1], few_rows))
            pl.when(when & (rows > few_tokens))(
                lambda: part(q_refs[0], whole))
        else:
            pl.when(when)(lambda: part(q_refs[0], whole))

    def init(_, r):
        m_ref[:r, :] = jnp.full((r, 1), NEG_INF, jnp.float32)
        l_ref[:r, :] = jnp.zeros((r, 1), jnp.float32)
        acc_ref[:r, :] = jnp.zeros((r, v_dim), jnp.float32)

    def accumulate(q_ref, r):
        """One online-softmax update of the tile's first ``r`` rows over this
        item's pages."""
        q = q_ref[...]                                    # [r, row]
        # two pages side by side make a 128-key block: whole vector lanes
        # for the scores and a full-width pass of the MXU
        pair = 2 if pages % 2 == 0 else 1
        keys = pair * page_size
        # row i serves query token i // heads, which sees the keys up to and
        # including its own position
        tok = jax.lax.broadcasted_iota(jnp.int32, (r, keys), 0) // heads
        limit = jnp.minimum(first + tok + jnp.int32(1), ctx)
        col0 = jax.lax.broadcasted_iota(jnp.int32, (r, keys), 1)
        m_prev = m_ref[:r, :]                             # [r, 1]
        if selected:
            # row i reads its token's row of the mask: a one-hot product
            # spreads the tile's ``[tokens, keys]`` over its ``[r, keys]``
            tokens = sel_ref.shape[0]
            mine = (jax.lax.broadcasted_iota(jnp.int32, (r, tokens), 0)
                    // heads == jax.lax.broadcasted_iota(
                        jnp.int32, (r, tokens), 1)).astype(sel_ref.dtype)
        blocks, scores, m_next = [], [], m_prev
        for k in range(0, pages, pair):
            block = (page_refs[k][...] if pair == 1 else jnp.concatenate(
                [page_refs[k][...], page_refs[k + 1][...]], axis=0))
            s = _dotf32(q, block, ((1,), (1,))) * scale   # [r, keys]
            col = (j * pages + k) * page_size + col0
            s = jnp.where(col < limit, s, NEG_INF)
            if selected:
                chosen = _dotf32(
                    mine, sel_ref[:, k * page_size:k * page_size + keys],
                    ((1,), (0,)))
                s = jnp.where(chosen > 0.5, s, NEG_INF)
            blocks.append(block)
            scores.append(s)
            m_next = jnp.maximum(m_next, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        l_next = l_ref[:r, :] * alpha
        acc = acc_ref[:r, :] * alpha
        for block, s in zip(blocks, scores):
            p = jnp.exp(s - m_next)
            l_next = l_next + jnp.sum(p, axis=-1, keepdims=True)
            value = block[:, :v_dim]                      # the latents
            acc = acc + _dotf32(p.astype(value.dtype), value, ((1,), (0,)))
        m_ref[:r, :] = m_next
        l_ref[:r, :] = l_next
        acc_ref[:r, :] = acc

    def finish(_, r):
        l = l_ref[:r, :]
        out_ref[:r, :] = (acc_ref[:r, :] / jnp.where(l == 0.0, 1.0, l)
                          ).astype(out_ref.dtype)
        copy = pltpu.make_async_copy(out_ref.at[pl.ds(0, r)],
                                     o_hbm.at[i, pl.ds(0, r)], sem)
        copy.start()
        copy.wait()

    by_form(j == 0, init)
    # every listed item holds keys its tile sees (the one item of a step with
    # no lane scheduled does not)
    by_form((rows > 0) & (j * span < jnp.minimum(first + rows, ctx)),
            accumulate)
    by_form(last_ref[it] == 1, finish)


def _kernel_impl(q3, pool, plan, layer, *, grid, heads, v_dim, scale,
                 selected=None, name=MLA_KERNEL_NAME):
    """q3: ``[n, tile * heads, row]`` tiled queries; returns ``[n, tile *
    heads, v_dim]`` in q3's dtype, of which only a tile's real tokens' rows
    are written (the first ``grid.few`` tokens' of a few-rows tile, none of
    an unused tile). The grid is ``plan.total`` work items; every block
    index is one lookup in the plan's tables. The pool is passed
    ``grid.pages`` times, each operand with its own block index map, so one
    item's pages are fetched together. ``selected [n * tile, blocks * keys]``
    (0/1; ``dsa_index.select_mask``): the keys each tiled row may read
    besides being causal; ``name``: the call's name in the trace."""
    n, r, row = q3.shape
    page_size, pages = pool.shape[3], grid.pages
    # the few-rows form's rows: whole float32 sublane tiles
    few_rows = min(r, -(-heads * grid.few // 8) * 8)
    i32 = jnp.int32

    def few_imap(it, tile_ref, *_):
        return (tile_ref[it], 0, 0)

    def whole_imap(it, tile_ref, blk_ref, last_ref, tbl_ref, first_ref,
                   ctx_ref, rows_ref, full_ref, layer_ref):
        return (full_ref[tile_ref[it]], 0, 0)

    def page_imap(k, it, tile_ref, blk_ref, last_ref, tbl_ref, first_ref,
                  ctx_ref, rows_ref, full_ref, layer_ref):
        return (layer_ref[0], tbl_ref[it * i32(pages) + i32(k)], 0, 0, 0)

    q_specs = [pl.BlockSpec((None, r, row), whole_imap)]
    if few_rows:
        q_specs.append(pl.BlockSpec((None, few_rows, row), few_imap))
    sel_specs = [] if selected is None else [pl.BlockSpec(
        (grid.tile, grid.keys),
        lambda it, tile_ref, blk_ref, *_: (tile_ref[it], blk_ref[it]))]
    page_specs = [pl.BlockSpec((None, None, None, page_size, row),
                               functools.partial(page_imap, k))
                  for k in range(pages)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=9,
        grid=(plan.total,),
        in_specs=q_specs + sel_specs + page_specs,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((r, 1), jnp.float32),
                        pltpu.VMEM((r, 1), jnp.float32),
                        pltpu.VMEM((r, v_dim), jnp.float32),
                        pltpu.VMEM((r, v_dim), q3.dtype),
                        pltpu.SemaphoreType.DMA(())],
    )
    kern = functools.partial(_mla_kernel, page_size=page_size, pages=pages,
                             heads=heads, v_dim=v_dim, scale=scale,
                             few_tokens=grid.few, few_rows=few_rows,
                             selected=selected is not None)
    with _atc.x64_off():
        return pl.pallas_call(
            kern, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n, r, v_dim), q3.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=_interpret(), name=name,
        )(plan.tile, plan.block, plan.last, plan.page, plan.first, plan.ctx,
          plan.rows, plan.full, jnp.asarray(layer, i32).reshape(1),
          *([q3] * len(q_specs)), *([selected] * len(sel_specs)),
          *([pool] * pages))


def mla_ragged_paged_attention_reference(q, pool, page_table, kv_lens,
                                         q_lens, tok_slot, tok_off, *,
                                         v_dim, scale, layer, selected=None):
    """Gather-based oracle (and the non-TPU path): every lane's pages
    gathered into one contiguous view, masked causal softmax per packed
    row. Shapes as :func:`mla_ragged_paged_attention`; ``selected [t, S]``
    (bool) here names the keys each PACKED row may read."""
    b = q_lens.shape[0]
    num_pages = pool.shape[1]
    pt = jnp.clip(page_table, 0, num_pages - 1)
    rows = gather_pages(pool, pt, layer)[:, :, 0]          # [b, S, row]
    slot_c = jnp.clip(tok_slot, 0, b - 1)
    mine = rows[slot_c].astype(jnp.float32)                # [t, S, row]
    s = jnp.einsum("thc,tsc->ths", q.astype(jnp.float32), mine) * scale
    ctx = kv_lens[slot_c]
    pos = ctx - q_lens[slot_c] + tok_off                   # [t]
    col = jnp.arange(mine.shape[1])[None, :]
    seen = (col <= pos[:, None]) & (col < ctx[:, None])    # [t, S]
    if selected is not None:
        seen = seen & selected
    s = jnp.where(seen[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    live = (tok_slot >= 0) & (tok_off >= 0) & (tok_off < q_lens[slot_c])
    p = jnp.where((seen & live[:, None])[:, None, :], p, 0.0)
    out = jnp.einsum("ths,tsc->thc", p, mine[..., :v_dim])
    return out.astype(q.dtype)


def mla_ragged_paged_attention(q, pool, page_table, kv_lens, q_lens,
                               tok_slot, tok_off, *, v_dim: int, scale: float,
                               layer, use_kernel: bool | None = None,
                               plan: TilePlan | None = None,
                               tile: int = TILE_DEFAULT,
                               pages_per_step: int = PAGES_PER_STEP_DEFAULT,
                               selected=None, name: str = MLA_KERNEL_NAME):
    """Absorbed latent attention of one serving step's packed rows.

    q: ``[t, heads, row]`` absorbed queries of the packed token stream;
    pool: the stacked latent pool ``[num_layers, num_pages, 1, page_size,
    row]``, this step's rows already written; ``layer``: which layer of it
    (a traced int32 scalar); page_table ``[b, pps]``; kv_lens ``[b]`` each
    lane's context INCLUDING this step's rows; q_lens ``[b]`` the rows it
    feeds now; tok_slot ``[t]`` each packed row's lane (< 0: padding);
    tok_off ``[t]`` its place among its lane's rows, so it sits at position
    ``kv_lens - q_lens + tok_off`` and sees the keys up to its own. Returns
    ``[t, heads, v_dim]``: the softmax-weighted sums of the rows' first
    ``v_dim`` entries (padding rows: zero). ``plan``: :func:`tile_plan` of
    the same ``tok_slot``, ``tok_off``, ``q_lens``, ``kv_lens``,
    ``page_table``, ``tile`` and ``pages_per_step``, where the caller made it
    once for all layers. ``use_kernel`` as in ``paged_attention``.
    ``selected``: each row reads only the keys it names, of those it sees
    (learned sparse attention, ``ops/pallas/dsa_index.py``): where the
    kernel runs, the 0/1 mask in the tiled layout ``[tiles * tile, blocks *
    keys]`` (``select_mask``); on the jnp path, ``[t, S]`` bool over packed
    rows. ``name``: the kernel call's name in the trace (the selected form
    runs under its own).
    """
    t, heads, row = q.shape
    assert pool.ndim == 5 and pool.shape[2] == 1 and pool.shape[4] == row, (
        f"latent pool {pool.shape} against rows of {row}")
    if use_kernel is None:
        use_kernel = use_kernel_default()
    if not use_kernel:
        return mla_ragged_paged_attention_reference(
            q, pool, page_table, kv_lens, q_lens, tok_slot, tok_off,
            v_dim=v_dim, scale=scale, layer=layer, selected=selected)
    if plan is None:
        plan = tile_plan(tok_slot, tok_off, q_lens, kv_lens, page_table,
                         page_size=pool.shape[3], num_pages=pool.shape[1],
                         tile=tile, pages_per_step=pages_per_step)
    grid = tile_grid(q_lens.shape[0], t, page_table.shape[1], pool.shape[3],
                     tile, pages_per_step)
    n = grid.tiles
    assert plan.rows.shape == (n,) and plan.page.shape == (
        n * grid.blocks * grid.pages,), (
        f"a plan of {plan.rows.shape[0]} tiles and {plan.page.shape[0]} "
        f"page names for {grid}")
    tiled = jnp.zeros((n * tile, heads, row), q.dtype
                      ).at[plan.dest].set(q, mode="drop")
    out = _kernel_impl(tiled.reshape(n, tile * heads, row), pool, plan,
                       layer, grid=grid, heads=heads, v_dim=v_dim,
                       scale=float(scale), selected=selected, name=name)
    out = out.reshape(n * tile, heads, v_dim)
    # a padding row has no place in the tiles (and what holds no real token's
    # row is never written): zero, as the reference gives
    placed = (plan.dest < n * tile)[:, None, None]
    return jnp.where(placed, out[jnp.minimum(plan.dest, n * tile - 1)], 0)
