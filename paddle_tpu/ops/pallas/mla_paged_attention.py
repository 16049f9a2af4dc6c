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

What differs from ``ragged_paged_attention``'s grid (lane x head x every
page slot of the table): contexts here run to hundreds of pages, so

- the query rows of a step arrive as TILES of ``tile`` tokens (all heads of
  a token adjacent: a tile is ``[tile * heads, row]``). :func:`tile_plan`
  lays the packed stream's rows out tile by tile, a lane with ``q_len`` rows
  getting ``ceil(q_len / tile)`` tiles, at most ``lanes + budget // tile``
  in all; the grid's first axis runs over tiles, not over lanes x chunk;
- each grid step reads ``pages_per_step`` pages: the pool is passed that
  many times, each operand with its own block index map, so the pipeline
  fetches them together and one step covers ``pages_per_step * page_size``
  keys with one rescale of the accumulator; two 64-token pages side by side
  make a 128-key block (whole vector lanes, a full-width MXU pass);
- a tile that holds only a decode lane's one token runs its few-rows form;
- steps past a tile's causal horizon name the block they named before
  (nothing is fetched) and compute nothing.

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
                              use_kernel_default)

# stable pallas_call name (survives into the compiled HLO and the device
# trace): how a check or a trace reduction finds the kernel
MLA_KERNEL_NAME = "mla_ragged_paged_attention"

# measured on the v5e at the DeepSeek-V2-Lite cell (PERF.md, PR 28): tiles of
# 16, 32 and 64 tokens served 564, 634 and 657 steps in 30 s (a prefill
# chunk's context is read once a tile); 16 pages a step were no better than 8
TILE_DEFAULT = 64           # query tokens per tile
PAGES_PER_STEP_DEFAULT = 8  # pages one grid step reads
FEW_TOKENS = 4              # a tile with no more real tokens runs its few-rows form


class TilePlan(NamedTuple):
    """One step's query rows, tile by tile (see :func:`tile_plan`):
    ``lane/start/rows [n]`` each tile's lane, its first row's place in the
    lane's rows of this step, and how many of its ``tile`` rows are real (0:
    an unused tile); ``dest [t]`` each packed token's row in the tiled
    buffer (``n * tile``: dropped)."""
    lane: jax.Array
    start: jax.Array
    rows: jax.Array
    dest: jax.Array


def tile_plan(tok_slot, tok_off, q_lens, tile: int = TILE_DEFAULT):
    """The tiled layout of a packed step: made once per step from what every
    layer's call shares. ``tok_slot [t]`` (< 0: padding), ``tok_off [t]``
    each token's place among its lane's rows of this step, ``q_lens [b]``."""
    b, t = q_lens.shape[0], tok_slot.shape[0]
    n = b + t // tile
    per_lane = -(-q_lens.astype(jnp.int32) // tile)               # [b]
    ends = jnp.cumsum(per_lane)
    base = ends - per_lane
    item = jnp.arange(n, dtype=jnp.int32)
    lane = jnp.clip(jnp.searchsorted(ends, item, side="right"), 0, b - 1
                    ).astype(jnp.int32)
    start = (item - base[lane]) * tile
    rows = jnp.where(item < ends[-1],
                     jnp.clip(q_lens[lane] - start, 0, tile), 0)
    slot_c = jnp.clip(tok_slot, 0, b - 1)
    off = jnp.maximum(tok_off, 0)
    valid = (tok_slot >= 0) & (tok_off >= 0) & (tok_off < q_lens[slot_c])
    dest = jnp.where(valid, (base[slot_c] + off // tile) * tile + off % tile,
                     n * tile)
    return TilePlan(lane=lane, start=jnp.maximum(start, 0).astype(jnp.int32),
                    rows=rows.astype(jnp.int32), dest=dest.astype(jnp.int32))


def _mla_kernel(lane_ref, start_ref, rows_ref, ctx_ref, qlen_ref, pt_ref,
                layer_ref, q_ref, *refs, page_size, pages, heads, v_dim,
                scale, few_tokens):
    page_refs = refs[:pages]
    o_ref, m_ref, l_ref, acc_ref = refs[pages:]
    i = pl.program_id(0)
    j = pl.program_id(1)
    lane = lane_ref[i]
    ctx = ctx_ref[lane]          # context INCLUDING this step's rows
    rows = rows_ref[i]
    first = ctx - qlen_ref[lane] + start_ref[i]   # position of the tile's row 0
    horizon = jnp.minimum(first + rows, ctx)      # keys its last real row sees
    span = pages * page_size

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(r):
        """One online-softmax update of the tile's first ``r`` rows over this
        step's pages."""
        q = q_ref[:r, :]                                  # [r, row]
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
        blocks, scores, m_next = [], [], m_prev
        for k in range(0, pages, pair):
            block = (page_refs[k][...] if pair == 1 else jnp.concatenate(
                [page_refs[k][...], page_refs[k + 1][...]], axis=0))
            s = _dotf32(q, block, ((1,), (1,))) * scale   # [r, keys]
            col = (j * pages + k) * page_size + col0
            s = jnp.where(col < limit, s, NEG_INF)
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

    # a decode lane's tile holds ONE real token: the few-rows form does a
    # quarter of the tile's products and exponentials (the rows past it stay
    # zero); a prefill chunk's tile runs whole, so the MXU streams all its
    # rows through each page it loads
    live = (rows > 0) & (j * span < horizon)
    few = min(q_ref.shape[0], max(heads * few_tokens, 8))
    if few < q_ref.shape[0]:
        pl.when(live & (rows <= few_tokens))(lambda: accumulate(few))
        pl.when(live & (rows > few_tokens))(
            lambda: accumulate(q_ref.shape[0]))
    else:
        pl.when(live)(lambda: accumulate(q_ref.shape[0]))

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                      ).astype(o_ref.dtype)


def _kernel_impl(q3, pool, page_table, kv_lens, q_lens, plan, layer, *,
                 heads, v_dim, scale, pages):
    """q3: ``[n, tile * heads, row]`` tiled queries; returns ``[n, tile *
    heads, v_dim]`` in q3's dtype."""
    n, r, row = q3.shape
    num_pages, page_size = pool.shape[1], pool.shape[3]
    pps = page_table.shape[1]
    pages = max(1, min(int(pages), pps))
    nkv = -(-pps // pages)

    def page_imap(k, i, j, lane_ref, start_ref, rows_ref, ctx_ref, qlen_ref,
                  pt_ref, layer_ref):
        lane = lane_ref[i]
        ctx = ctx_ref[lane]
        horizon = jnp.minimum(
            ctx - qlen_ref[lane] + start_ref[i] + rows_ref[i], ctx)
        ps = jnp.int32(page_size)
        last = jnp.maximum(
            jax.lax.div(horizon + ps - jnp.int32(1), ps) - jnp.int32(1),
            jnp.int32(0))
        slot = jnp.minimum(jnp.int32(j) * jnp.int32(pages) + jnp.int32(k),
                           jnp.minimum(last, jnp.int32(pps - 1)))
        page = pt_ref[lane, slot]
        return (layer_ref[0], jnp.clip(page, 0, num_pages - 1), 0, 0, 0)

    q_spec = pl.BlockSpec((None, r, row), lambda i, j, *_: (i, 0, 0))
    page_specs = [pl.BlockSpec((None, None, None, page_size, row),
                               functools.partial(page_imap, k))
                  for k in range(pages)]
    o_spec = pl.BlockSpec((None, r, v_dim), lambda i, j, *_: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(n, nkv),
        in_specs=[q_spec] + page_specs,
        out_specs=o_spec,
        scratch_shapes=[pltpu.VMEM((r, 1), jnp.float32),
                        pltpu.VMEM((r, 1), jnp.float32),
                        pltpu.VMEM((r, v_dim), jnp.float32)],
    )
    kern = functools.partial(_mla_kernel, page_size=page_size, pages=pages,
                             heads=heads, v_dim=v_dim, scale=scale,
                             few_tokens=FEW_TOKENS)
    i32 = jnp.int32
    with _atc.x64_off():
        return pl.pallas_call(
            kern, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n, r, v_dim), q3.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=_interpret(), name=MLA_KERNEL_NAME,
        )(plan.lane, plan.start, plan.rows, kv_lens.astype(i32),
          q_lens.astype(i32), page_table.astype(i32),
          jnp.asarray(layer, i32).reshape(1), q3, *([pool] * pages))


def mla_ragged_paged_attention_reference(q, pool, page_table, kv_lens,
                                         q_lens, tok_slot, tok_off, *,
                                         v_dim, scale, layer):
    """Gather-based oracle (and the non-TPU path): every lane's pages
    gathered into one contiguous view, masked causal softmax per packed
    row. Shapes as :func:`mla_ragged_paged_attention`."""
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
                               pages_per_step: int = PAGES_PER_STEP_DEFAULT):
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
    ``v_dim`` entries (padding rows: garbage nobody reads; the reference
    zeroes them). ``plan``: :func:`tile_plan` of the same ``tok_slot``,
    ``tok_off``, ``q_lens`` and ``tile``, where the caller made it once for
    all layers. ``use_kernel`` as in ``paged_attention``.
    """
    t, heads, row = q.shape
    assert pool.ndim == 5 and pool.shape[2] == 1 and pool.shape[4] == row, (
        f"latent pool {pool.shape} against rows of {row}")
    if use_kernel is None:
        use_kernel = use_kernel_default()
    if not use_kernel:
        return mla_ragged_paged_attention_reference(
            q, pool, page_table, kv_lens, q_lens, tok_slot, tok_off,
            v_dim=v_dim, scale=scale, layer=layer)
    if plan is None:
        plan = tile_plan(tok_slot, tok_off, q_lens, tile)
    n = plan.lane.shape[0]
    assert n == q_lens.shape[0] + t // tile, (
        f"a plan of {n} tiles for {t} rows in tiles of {tile}")
    tiled = jnp.zeros((n * tile, heads, row), q.dtype
                      ).at[plan.dest].set(q, mode="drop")
    out = _kernel_impl(tiled.reshape(n, tile * heads, row), pool, page_table,
                       kv_lens, q_lens, plan, layer, heads=heads,
                       v_dim=v_dim, scale=float(scale), pages=pages_per_step)
    out = out.reshape(n * tile, heads, v_dim)
    return out[jnp.minimum(plan.dest, n * tile - 1)]
