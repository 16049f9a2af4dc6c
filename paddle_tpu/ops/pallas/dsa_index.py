"""Learned sparse attention's indexer over a paged cache — Pallas TPU kernels.

DeepSeek Sparse Attention (DeepSeek-V3.2-Exp; ``models/glm_moe_dsa.py``)
scores every key a query row sees with a small indexer, ``I[t, s] = sum_j
w[t, j] relu(qI[t, j] . kI[s])``, and attention then reads the ``k`` keys
with the largest score alone. The index keys ``kI`` (128 values a token) live
in a second plane of the paged cache, ``[index layers, num_pages, 1,
page_size, dim]``, beside the latent rows and under the same page ids.

Two kernels, both over the TILED layout of a step's rows that
``mla_paged_attention.tile_plan`` makes once a step (a tile is ``tile``
tokens of one lane; a decode lane's tile holds one):

- :data:`INDEX_KERNEL_NAME`: the scores. Its grid is the SAME work-item axis
  the latent attention kernel runs over (every tile's key blocks up to its
  causal horizon, ``plan.page`` naming the pages), each item one ``[tile,
  keys]`` block of the result ``[tiles * tile, key slots]`` float32; a key a
  row does not see scores ``-inf``, blocks past a tile's horizon are never
  written (nor read). A tile of one token computes that token's heads alone.
- :data:`SELECT_KERNEL_NAME`: the exact top-k as a MASK of the same shape
  (bf16 0/1: what the attention kernel reads block by block). Per row the
  k-th largest score is found bit by bit on the scores' order-preserving
  integer image (32 counts over the row), ties at the threshold go to the
  lower positions (another ``log2`` counts), rows that see no more than k
  keys select all of them. A sort would cost the same for every budgeted row
  and every page slot; this costs a tile what its context holds, and an
  unused tile nothing.

The selection stays a mask: the pool's tiling (8 bf16 rows a tile) admits no
DMA of one token's row, so the attention kernel reads whole pages and masks
(``mla_paged_attention`` ``selected=``). :func:`index_scores_reference` and
:func:`select_topk` are the jnp forms (the non-TPU path and the oracle), over
packed rows ``[t, page slots * page_size]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import autotune_cache as _atc
from .paged_attention import _dotf32, _interpret, gather_pages

INDEX_KERNEL_NAME = "dsa_index_scores"
SELECT_KERNEL_NAME = "dsa_topk_select"

_MIN = jnp.iinfo(jnp.int32).min


def keys_of(context: int, fed: int, topk: int):
    """What a scheduled lane's ``fed`` rows (the last at position ``context -
    1``) give one layer: ``(keys their indexer scores, keys their attention
    reads)``: each row its own context, and of it at most ``topk``."""
    first = int(context) - int(fed)
    seen = range(first + 1, int(context) + 1)
    return sum(seen), sum(min(s, int(topk)) for s in seen)


# ---------------------------------------------------------------------------
# jnp forms
# ---------------------------------------------------------------------------


def seen_keys(page_table, kv_lens, q_lens, tok_slot, tok_off, page_size):
    """``[t, S]`` bool: the key slots each packed row sees (its lane's keys
    up to its own position); none for a padding row."""
    b = q_lens.shape[0]
    slot_c = jnp.clip(tok_slot, 0, b - 1)
    ctx = kv_lens[slot_c]
    pos = ctx - q_lens[slot_c] + tok_off
    col = jnp.arange(page_table.shape[1] * page_size)[None, :]
    live = (tok_slot >= 0) & (tok_off >= 0) & (tok_off < q_lens[slot_c])
    return (col <= pos[:, None]) & (col < ctx[:, None]) & live[:, None]


def index_scores_reference(q_idx, w, ipool, page_table, kv_lens, q_lens,
                           tok_slot, tok_off, *, layer):
    """Packed rows' index scores ``[t, S]`` float32, ``-inf`` where a row
    does not see the key. Shapes as :func:`index_scores`."""
    b = q_lens.shape[0]
    pt = jnp.clip(page_table, 0, ipool.shape[1] - 1)
    keys = gather_pages(ipool, pt, layer)[:, :, 0]         # [b, S, d]
    mine = keys[jnp.clip(tok_slot, 0, b - 1)].astype(jnp.float32)
    dots = jnp.einsum("tjd,tsd->tjs", q_idx.astype(jnp.float32), mine)
    scores = jnp.einsum("tjs,tj->ts", jax.nn.relu(dots),
                        w.astype(jnp.float32))
    seen = seen_keys(page_table, kv_lens, q_lens, tok_slot, tok_off,
                     ipool.shape[3])
    return jnp.where(seen, scores, -jnp.inf)


def select_topk(scores, seen, k: int):
    """The selection as a mask: per row the ``k`` seen entries with the
    largest score (every seen entry where there are no more), ties to the
    lower index. ``scores``, ``seen``: ``[..., S]``."""
    s = scores.shape[-1]
    masked = jnp.where(seen, scores.astype(jnp.float32), -jnp.inf)
    _, chosen = jax.lax.top_k(masked, min(int(k), s))
    picked = jnp.zeros(masked.shape, bool)
    picked = jnp.put_along_axis(picked, chosen, True, axis=-1,
                                inplace=False)
    return picked & seen


# ---------------------------------------------------------------------------
# the score kernel
# ---------------------------------------------------------------------------


def _index_kernel(tile_ref, blk_ref, last_ref, tbl_ref, first_ref, ctx_ref,
                  rows_ref, layer_ref, q_ref, w_ref, *refs, page_size, pages,
                  heads, few_tokens):
    """One work item: ``pages`` pages of index keys against one tile's index
    queries ``[tile * heads, dim]`` (a token's heads adjacent), weights ``[tile
    * heads, 1]``; out: the item's ``[tile, pages * page_size]`` scores."""
    page_refs, o_ref = refs[:pages], refs[pages]
    it = pl.program_id(0)
    i, j = tile_ref[it], blk_ref[it]
    ctx, rows, first = ctx_ref[i], rows_ref[i], first_ref[i]
    tile = q_ref.shape[0] // heads
    pair = 2 if pages % 2 == 0 else 1
    keys = pair * page_size

    def part(tokens):
        r = tokens * heads
        q, w = q_ref[:r, :], w_ref[:r, :]
        tok = jax.lax.broadcasted_iota(jnp.int32, (tokens, keys), 0)
        limit = jnp.where(tok < rows,
                          jnp.minimum(first + tok + jnp.int32(1), ctx), 0)
        col0 = jax.lax.broadcasted_iota(jnp.int32, (tokens, keys), 1)
        for k in range(0, pages, pair):
            block = (page_refs[k][...] if pair == 1 else jnp.concatenate(
                [page_refs[k][...], page_refs[k + 1][...]], axis=0))
            s = jnp.maximum(_dotf32(q, block, ((1,), (1,))), 0.0) * w
            score = s.reshape(tokens, heads, keys).sum(axis=1)
            col = (j * pages + k) * page_size + col0
            o_ref[:tokens, k * page_size:k * page_size + keys] = jnp.where(
                col < limit, score, -jnp.inf)

    if 0 < few_tokens < tile:
        pl.when(rows <= few_tokens)(lambda: part(few_tokens))
        pl.when(rows > few_tokens)(lambda: part(tile))
    else:
        part(tile)


def _index_call(qt, wt, ipool, plan, layer, *, grid, heads):
    """``qt [n, tile * heads, dim]``, ``wt [n, tile * heads, 1]`` float32:
    the tiled index queries and weights; returns ``[n * tile, blocks *
    keys]`` float32."""
    n, r, dim = qt.shape
    page_size, pages = ipool.shape[3], grid.pages
    i32 = jnp.int32

    def tile_imap(it, tile_ref, *_):
        return (tile_ref[it], 0, 0)

    def page_imap(k, it, tile_ref, blk_ref, last_ref, tbl_ref, first_ref,
                  ctx_ref, rows_ref, layer_ref):
        return (layer_ref[0], tbl_ref[it * i32(pages) + i32(k)], 0, 0, 0)

    def out_imap(it, tile_ref, blk_ref, *_):
        return (tile_ref[it], blk_ref[it])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(plan.total,),
        in_specs=[pl.BlockSpec((None, r, dim), tile_imap),
                  pl.BlockSpec((None, r, 1), tile_imap)]
        + [pl.BlockSpec((None, None, None, page_size, dim),
                        functools.partial(page_imap, k))
           for k in range(pages)],
        out_specs=pl.BlockSpec((grid.tile, grid.keys), out_imap),
    )
    kern = functools.partial(_index_kernel, page_size=page_size, pages=pages,
                             heads=heads, few_tokens=grid.few)
    with _atc.x64_off():
        return pl.pallas_call(
            kern, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(
                (n * grid.tile, grid.blocks * grid.keys), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=_interpret(), name=INDEX_KERNEL_NAME,
        )(plan.tile, plan.block, plan.last, plan.page, plan.first, plan.ctx,
          plan.rows, jnp.asarray(layer, i32).reshape(1), qt, wt,
          *([ipool] * pages))


def index_scores(q_idx, w, ipool, plan, layer, *, grid):
    """The step's index scores in the TILED layout. ``q_idx [t, heads,
    dim]`` the packed rows' index queries, ``w [t, heads]`` their head
    weights; ``ipool`` the index-key plane ``[index layers, num_pages, 1,
    page_size, dim]``, this step's keys already written; ``layer`` which
    layer of it; ``plan`` / ``grid``: ``tile_plan`` / ``tile_grid`` of the
    step. Returns ``[tiles * tile, blocks * keys]`` float32: row ``plan.dest[
    r]`` is packed row ``r``'s, ``-inf`` where it does not see the key;
    blocks past a tile's horizon are not written."""
    n, tile = grid.tiles, grid.tile
    heads, dim = q_idx.shape[1:]
    qt = jnp.zeros((n * tile, heads, dim), q_idx.dtype
                   ).at[plan.dest].set(q_idx, mode="drop")
    wt = jnp.zeros((n * tile, heads), jnp.float32
                   ).at[plan.dest].set(w.astype(jnp.float32), mode="drop")
    return _index_call(qt.reshape(n, tile * heads, dim),
                       wt.reshape(n, tile * heads, 1), ipool, plan, layer,
                       grid=grid, heads=heads)


# ---------------------------------------------------------------------------
# the selection kernel
# ---------------------------------------------------------------------------


def _select_kernel(first_ref, ctx_ref, rows_ref, x_ref, o_ref, key_ref, *,
                   k, chunk, few_rows):
    """One tile: its rows' scores ``x [tile, S]`` to the mask ``o [tile, S]``
    of each row's ``k`` largest seen scores. ``key_ref [tile, S]`` int32
    holds the scores' order-preserving image."""
    i = pl.program_id(0)
    ctx, rows, first = ctx_ref[i], rows_ref[i], first_ref[i]
    tile, s = x_ref.shape
    horizon = jnp.minimum(first + rows, ctx)
    live = (horizon + (chunk - 1)) // chunk       # chunks that hold a key
    kf = jnp.float32(k)

    def part(r):
        """Select for the tile's first ``r`` rows."""
        tok = jax.lax.broadcasted_iota(jnp.int32, (r, 1), 0)
        limit = jnp.where(tok < rows,
                          jnp.minimum(first + tok + jnp.int32(1), ctx), 0)
        col0 = jax.lax.broadcasted_iota(jnp.int32, (r, chunk), 1)

        def at(c):
            return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)

        def image(c, carry):
            x = jnp.where(c * chunk + col0 < limit, x_ref[:r, at(c)],
                          -jnp.inf)
            bits = jax.lax.bitcast_convert_type(x, jnp.int32)
            # float order as signed integer order
            key_ref[:r, at(c)] = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
            return carry

        jax.lax.fori_loop(0, live, image, 0)

        def count(pred):
            """``[r, 1]`` float32 (exact: a row is far under 2**24 long):
            how many of a row's live entries ``pred(keys, columns)``
            holds for."""
            def body(c, acc):
                hit = pred(key_ref[:r, at(c)], c * chunk + col0)
                return acc + jnp.sum(hit.astype(jnp.float32), axis=1,
                                     keepdims=True)
            return jax.lax.fori_loop(0, live, body,
                                     jnp.zeros((r, 1), jnp.float32))

        # the k-th largest key, built from its top bit down in the unsigned
        # order (signed keys with the sign bit flipped): the largest value
        # that at least k entries reach
        def bit(b, ans):
            cand = ans | jnp.left_shift(jnp.int32(1), jnp.int32(31) - b)
            reach = count(lambda key, col: key >= (cand ^ _MIN))
            return jnp.where(reach >= kf, cand, ans)

        kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros((r, 1), jnp.int32)
                                ) ^ _MIN
        # entries above it are in; of those equal to it, the first ``need``
        # by position: the largest p that fewer than ``need`` of them lie
        # before is the position of the last one in
        need = kf - count(lambda key, col: key > kth)
        nbits = max(int(s - 1).bit_length(), 1)

        def pbit(b, p):
            cand = p | jnp.left_shift(jnp.int32(1), jnp.int32(nbits - 1) - b)
            before = count(lambda key, col: (key == kth) & (col < cand))
            return jnp.where(before < need, cand, p)

        cut = jax.lax.fori_loop(0, nbits, pbit, jnp.zeros((r, 1), jnp.int32))

        def write(c, carry):
            key, col = key_ref[:r, at(c)], c * chunk + col0
            chosen = ((key > kth) | ((key == kth) & (col <= cut))) \
                & (col < limit)
            o_ref[:r, at(c)] = chosen.astype(o_ref.dtype)
            if r < tile:
                # the attention kernel spreads the whole block over its rows
                # by a product: what it does not use must still be a number
                o_ref[r:, at(c)] = jnp.zeros((tile - r, chunk), o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, s // chunk, write, 0)

    if 0 < few_rows < tile:
        pl.when(rows <= 1)(lambda: part(few_rows))
        pl.when(rows > 1)(lambda: part(tile))
    else:
        part(tile)


def _chunk_of(s, k):
    """Columns one pass of the selection kernel's loops covers: the largest
    of a few sizes that divides the row and holds ``k`` entries."""
    for chunk in (4096, 2048, 1024, 512, 256, 128):
        if s % chunk == 0 and chunk >= k:
            return chunk
    return s


def select_mask(scores, plan, *, grid, k: int):
    """The exact top-``k`` of every row of the tiled ``scores [tiles * tile,
    S]`` (:func:`index_scores`) as a bf16 0/1 mask of the same shape; ties
    to the lower position; a row that sees no more than ``k`` keys selects
    them all. Rows of a tile past its real ones select nothing."""
    rows_all, s = scores.shape
    tile = grid.tile
    assert rows_all == grid.tiles * tile and tile % 16 == 0, (scores.shape,
                                                              grid)
    chunk = _chunk_of(s, k)
    assert chunk >= min(k, s), (s, k)
    used = jnp.maximum(jnp.sum(plan.rows > 0), 1).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(used,),
        in_specs=[pl.BlockSpec((tile, s), lambda i, *_: (i, 0))],
        out_specs=pl.BlockSpec((tile, s), lambda i, *_: (i, 0)),
        scratch_shapes=[pltpu.VMEM((tile, s), jnp.int32)],
    )
    kern = functools.partial(_select_kernel, k=int(k), chunk=chunk,
                             few_rows=8 if grid.few else 0)
    with _atc.x64_off():
        return pl.pallas_call(
            kern, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(scores.shape, jnp.bfloat16),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=64 * 1024 * 1024),
            interpret=_interpret(), name=SELECT_KERNEL_NAME,
        )(plan.first, plan.ctx, plan.rows, scores)
