"""Paged attention — the Pallas TPU kernel over a block-paged KV cache.

The serving-side sibling of ``flash_attention.py``: a sequence's query
tokens attend over that sequence's K/V prefix, which lives in a POOL of
fixed-size pages (``[num_pages, kv_heads, page_size, head_dim]`` — head-major
inside a page, so one (page, head) block is a contiguous ``[page_size,
head_dim]`` tile that satisfies Mosaic's (8, 128) rule on a block's last
two dims) indexed by a per-sequence page table — the
vLLM/Ragged-Paged-Attention memory layout (arxiv 2604.15464) that lets a
continuous-batching scheduler admit/evict sequences without copying or
fragmenting the cache. The page table and the ragged per-sequence lengths
ride in scalar-prefetch SMEM, and the K/V BlockSpec index maps read them to
DMA exactly the pages each sequence owns — the page-table indirection costs
no gather/materialization.

ONE kernel, :func:`ragged_paged_attention` (Ragged Paged Attention, arxiv
2604.15464): each sequence contributes 1..chunk query tokens per step
(decode lanes feed 1, prefill chunks feed up to ``chunk``), causal within
the chunk, online softmax across that sequence's pages. Query rows for one
(sequence, kv-head) pair are laid out ``[chunk * group, head_dim]``
(chunk-major, GQA group minor) so one MXU dot serves the whole chunk; the
per-row causal limit is ``kv_start + row // group + 1``. The per-step chunk
size is a trace-time constant autotuned on the shared cache
(:func:`preferred_chunk_size` / :func:`autotune_chunk_size`). ``length ==
0`` marks an empty slot (output rows zero) — the scheduler parks evicted
slots that way. The decode op :func:`paged_attention` (one query token per
sequence; the Paddle surface ``incubate.nn.functional.paged_attention``) is
a shape adapter onto it: a chunk of one row.

Inference-only: no VJP (the ops register as non-differentiable).
Interpret-capable on CPU like the other Pallas kernels; the jnp
gather-based references are both the numerical oracles and the non-TPU
fallback. Page-size autotune rides the shared ``autotune_cache`` (the page
size IS the kernel's kv block size, fixed at cache construction — see
:func:`autotune_page_size`).

The kernel's grid (PR 29) pays for keys, not for page slots:

- one grid step serves ALL local KV heads of a lane (a page's block for
  every head is one contiguous ``[kv_heads, page_size, head_dim]`` tile;
  where that does not fit the fast-memory budget, the largest divisor of the
  head count that does, and the head groups lead the grid) over SEVERAL
  pages: the pool is passed once per page a step reads, each operand with
  its own block index map, so the pipeline fetches them together. Two
  64-key pages side by side make a 128-key score tile; the accumulator is
  rescaled once a grid step and divided by ``l`` once a lane; ``m``, ``l``
  and the float32 accumulator live in VMEM scratch;
- the grid's second axis runs over WORK ITEMS, not over lanes x page slots:
  each lane's key blocks that hold keys, lane after lane, listed by the
  wrapper from ``kv_lens`` / ``q_lens`` (``_work_items``) and handed over as
  scalar-prefetch operands, the grid's bound being their count (a dynamic
  grid dimension). A page slot past a lane's context is never visited; an
  idle lane keeps one item that writes its zero rows; inside a live item a
  page slot past the context names the page its operand named before
  (nothing is fetched) and its key block computes nothing;
- a lane that feeds at most a few rows (a decode lane's one token, a verify
  lane's drafts) takes the few-rows form of the same body, chosen per grid
  step from ``q_lens``: 8 query rows a head, not ``chunk * group``.

:func:`ragged_grid` is that layout as a function of the operands' shapes and
dtypes, written once: the kernel is built from it and the scheduler counts
the grid steps of its lanes' contexts with it.

SPMD contract (round 11): under the multi-chip serving mesh these kernels
run PER CHIP inside a fully-manual ``shard_map`` over ``Mesh(("mp",))`` —
the caller hands in its chip's head shard of q and the head-sharded page
pools / scale planes, and the grid's ``kv_heads`` dim is simply the local
head count. Heads are embarrassingly parallel in paged attention (each
(slot, head) program reads only its own pages), so no collectives exist
at this level and GSPMD never has to partition the ``pallas_call`` — the
same per-shard discipline as the flash kernel under TP training.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import autotune_cache as _atc

NEG_INF = -1e30

# MXU note (see flash_attention.py): explicit DEFAULT precision keeps bf16
# operands on the native MXU pass under the framework's "highest" default.
_MXU = jax.lax.Precision.DEFAULT


# stable pallas_call name (survives into the compiled HLO and the device
# trace): how a check or a trace reduction finds the unified step's kernel
RAGGED_KERNEL_NAME = "ragged_paged_attention"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _dotf32(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=_MXU)


# ---------------------------------------------------------------------------
# jnp gather-based reference (oracle + non-TPU fallback + bench baseline)
# ---------------------------------------------------------------------------


def gather_pages(pool, pt, layer=None):
    """A page pool ``[num_pages, kv_heads, page_size, ...]`` gathered by the
    (clipped) page table ``pt [b, pps]`` into each sequence's contiguous
    token-major view ``[b, pps * page_size, kv_heads, ...]``. With ``layer``
    the pool is stacked ``[num_layers, num_pages, ...]`` and the one gather
    reads that layer's pages."""
    g = pool[pt] if layer is None else pool[layer, pt]
    g = jnp.swapaxes(g, 2, 3)                 # [b, pps, ps, hkv, ...]
    return g.reshape(g.shape[0], -1, *g.shape[3:])


def paged_attention_reference(q, k_pages, v_pages, page_table, lengths,
                              scale=None):
    """Gather the paged cache into a contiguous view and run masked decode
    attention — what a non-paged XLA implementation would do (one gather of
    ``pages_per_seq * page_size`` positions per sequence, materialized in
    HBM). Numerically the oracle for the kernel; also the measured baseline
    ``bench_serve.py`` compares the kernel against.

    q: [b, num_q_heads, d]; k/v_pages: [num_pages, kv_heads, page_size, d];
    page_table: [b, pages_per_seq] int; lengths: [b] int (0 = empty slot).
    Returns [b, num_q_heads, d] in q's dtype.
    """
    b, hq, d = q.shape
    num_pages, hkv, page_size, _ = k_pages.shape
    pps = page_table.shape[1]
    group = hq // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    pt = jnp.clip(page_table, 0, num_pages - 1)
    k = gather_pages(k_pages, pt)
    v = gather_pages(v_pages, pt)
    qg = q.reshape(b, hkv, group, d)
    s = jnp.einsum("bhgd,bshd->bhgs", qg.astype(jnp.float32),
                   k.astype(jnp.float32),
                   precision=_MXU) * scale
    valid = (jnp.arange(pps * page_size)[None, :]
             < lengths.reshape(-1, 1))            # [b, S]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # empty slots (length 0): all-masked softmax is uniform garbage — zero it
    p = jnp.where((lengths > 0).reshape(-1, 1, 1, 1), p, 0.0)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32),
                     precision=_MXU)
    return out.reshape(b, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


def use_kernel_default() -> bool:
    return jax.default_backend() == "tpu"


def paged_attention(q, k_pages, v_pages, page_table, lengths, scale=None,
                    use_kernel: bool | None = None):
    """Decode attention over the paged KV cache: one query token per
    sequence, as a one-row chunk of :func:`ragged_paged_attention`.

    ``use_kernel``: None = Pallas kernel on TPU, jnp reference elsewhere;
    True forces the kernel (interpret mode off-TPU — CPU tests); False
    forces the reference. See :func:`paged_attention_reference` for shapes.
    """
    b, hq, d = q.shape
    hkv = k_pages.shape[1]
    assert hq % hkv == 0, f"GQA needs q heads {hq} divisible by kv {hkv}"
    assert k_pages.shape == v_pages.shape
    assert page_table.shape[0] == b and lengths.shape == (b,)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if use_kernel is None:
        use_kernel = use_kernel_default()
    if not use_kernel:
        return paged_attention_reference(q, k_pages, v_pages, page_table,
                                         lengths, scale=scale)
    # a chunk of ONE query row a lane; an empty slot feeds none
    lengths = lengths.astype(jnp.int32)
    out = ragged_paged_attention(q[:, None], k_pages, v_pages, page_table,
                                 lengths, (lengths > 0).astype(jnp.int32),
                                 scale=scale, use_kernel=True)
    return out[:, 0]


# ---------------------------------------------------------------------------
# ragged kernel: 1..chunk query tokens per sequence, causal within the chunk
# ---------------------------------------------------------------------------


# What one grid step of the ragged kernel covers (measured on the v5e at the
# 590M serving cells, PERF.md, PR 29: with no dead grid steps 2, 4, 6 and 8
# pages a step are within 4% of each other; 4 needs half the operands of 8):
# the pages one step reads, the fast memory its blocks may take of Mosaic's
# 16 MiB scoped default (double buffers counted; the rest is the compiler's
# own), and the query rows of the few-rows form (one f32 sublane tile).
PAGES_PER_STEP = 4
VMEM_BUDGET = 12 << 20
FEW_ROWS = 8


class RaggedGrid(NamedTuple):
    """The grid of one ``ragged_paged_attention`` call (:func:`ragged_grid`):
    ``heads`` KV heads and ``pages`` pages a grid step, ``pair`` pages side by
    side in one score tile, the padded query ``rows`` of a lane and head, how
    many of them the few-rows form computes (``few_rows``: 0 where the block
    has no more), and what the grid runs over: ``groups`` of heads x the key
    blocks that hold keys, lane after lane (an idle lane has one grid step,
    which writes its zero rows), at most ``lanes * blocks`` a group."""
    heads: int
    pages: int
    pair: int
    rows: int
    few_rows: int
    page_size: int
    groups: int
    lanes: int
    blocks: int
    #: fast memory one grid step's blocks and scratch take where that is
    #: more than :data:`VMEM_BUDGET` even with one head a step (a wide
    #: query group's whole chunk), else 0: the call then asks Mosaic for it
    vmem: int = 0

    @property
    def keys(self) -> int:
        """Keys one grid step covers."""
        return self.pages * self.page_size

    def live_steps(self, kv_len: int, q_len: int = 1, window=None) -> int:
        """Grid steps that hold keys of a scheduled lane's context (however
        many rows it feeds: the signature is ``TileGrid.live_steps``'s).
        ``window``: the layer's rows see their last ``window`` positions
        alone, and the steps are listed from the block of the first key the
        lane's first row sees (:func:`first_live_key`; ``kv_len`` in the
        page table's own coordinates)."""
        first = first_live_key(int(kv_len), int(q_len), window)
        return self.groups * (-(-int(kv_len) // self.keys)
                              - first // self.keys)

    def steps(self, contexts, q_lens=None, window=None) -> int:
        """Grid steps one call launches when the scheduled lanes' contexts
        are ``contexts`` (the lanes not named are idle)."""
        fed = q_lens if q_lens is not None else [1] * len(contexts)
        live = [max(self.groups, self.live_steps(n, q, window))
                for n, q in zip(contexts, fed)]
        return self.groups * (self.lanes - len(live)) + sum(live)


def first_live_key(kv_len, q_len, window):
    """The first key position any of a lane's ``q_len`` new rows sees, its
    context ``kv_len`` long with them: 0, or under a ``window`` the lower
    edge of its FIRST new row, ``(kv_len - q_len) - window + 1`` (a row at
    position p sees keys ``p - window + 1 .. p``, itself counted). One
    spelling for the host's count, the planner and the kernel (ints or
    traced int32)."""
    if window is None:
        return 0
    lo = kv_len - q_len - (int(window) - 1)
    return max(lo, 0) if isinstance(lo, int) else jnp.maximum(lo, 0)


def lane_block_rows(chunk, rung, hq, hkv) -> int:
    """Query rows of a lane's block (the ``chunk`` :func:`ragged_grid` and
    the kernel are handed) in a step of ``rung`` rows. A lane feeds at most
    the rung's rows. Where a KV head serves a GROUP of query heads the lanes'
    blocks are that many times a row, and are cut to what the rung can hold;
    at one query head a KV head they have one shape at every rung (the
    kernel's body, a second to trace, is traced for the first). One spelling
    for the step (``models/gpt.py`` ``mha``) and the scheduler's count of its
    grid steps (``inference/serving.py``)."""
    return chunk if hkv == hq else min(chunk, rung)


def ragged_grid(lanes, pps, chunk, hq, hkv, page_size, head_dim, kv_dtype,
                q_dtype) -> RaggedGrid:
    """How :func:`ragged_paged_attention` lays its grid over operands of
    these shapes: written once, so the scheduler's count of live and launched
    grid steps (``inference/serving.py``) cannot drift from the kernel.

    A grid step reads ``min(PAGES_PER_STEP, pps)`` pages of as many local KV
    heads as fit :data:`VMEM_BUDGET` (the largest divisor of ``hkv``): K and
    V blocks double-buffered, int8 pools with their scale planes (whose block
    is all heads of a page whatever the step takes), the query block, the
    float32 output block and the softmax state in scratch."""
    group = hq // hkv
    rows = max(8, -(-chunk * group // 8) * 8)
    kv_bytes, q_bytes = jnp.dtype(kv_dtype).itemsize, jnp.dtype(q_dtype).itemsize
    quant = jnp.dtype(kv_dtype) == jnp.int8
    pages = max(1, min(PAGES_PER_STEP, pps))
    # pages side by side in one score tile, up to 128 keys (whole vector
    # lanes, a full-width MXU pass); int8 pages stay apart: their scale rows
    # would have to be joined along the lanes
    wide = 1 if quant else max(1, 128 // page_size)
    pair = max(p for p in range(1, pages + 1) if pages % p == 0 and p <= wide)
    score_lanes = -(-pair * page_size // 128) * 128   # a tile's last dim
    per_head = (2 * 2 * pages * page_size * head_dim * kv_bytes     # K, V
                + rows * head_dim * (2 * q_bytes + 2 * 4 + 4)       # q, o, acc
                + 3 * rows * 128 * 4                                # m, l, top
                + pages // pair * rows * score_lanes * 4)           # scores
    fixed = 2 * 2 * pages * hkv * page_size * 4 if quant else 0
    heads = max([h for h in range(1, hkv + 1) if hkv % h == 0
                 and fixed + h * per_head <= VMEM_BUDGET] or [1])
    few = max(FEW_ROWS, -(-group // 8) * 8)
    # one head's blocks alone can be past the budget (16 query heads a KV
    # head x a chunk's 256 rows): the call then asks Mosaic for what they
    # take, twice over for the compiler's own values (scores, exponentials)
    over = fixed + heads * per_head > VMEM_BUDGET
    return RaggedGrid(heads=heads, pages=pages, pair=pair, rows=rows,
                      few_rows=few if few < rows else 0, page_size=page_size,
                      groups=hkv // heads, lanes=lanes,
                      blocks=-(-pps // pages),
                      vmem=2 * (fixed + heads * per_head) if over else 0)


def _ragged_kernel(lens_ref, qlens_ref, lane_ref, blk_ref, last_ref, tbl_ref,
                   *refs, plan, group, scale, quant, stacked, window=None):
    """One grid step: ``plan.heads`` KV heads of one lane over ``plan.pages``
    pages. refs: [layer (stacked pools only: the index maps' business alone)]
    q, K pages x P, V pages x P, [K scale rows x P, V scale rows x P], o,
    then scratch: the softmax state m, l, acc, and within a step the key
    blocks' scores and their running row maxima.

    ``quant`` (round-10 int8 KV): the page tiles arrive int8 with the page's
    scale rows ([kv_heads, page_size] blocks of the scale plane). The int8
    values are exact in the compute dtype, so the per-token scales fold into
    the two dots' fp32 sides as [1, page_size] lane vectors: ``(q . kq) *
    ks`` and ``(p * vs) . vq``; the online-softmax recurrence is IDENTICAL
    (one body, so the paths cannot drift).

    ``window``: a row sees its last ``window`` positions alone. The lane's
    items then start at the block of the first key its first row sees
    (``blk_ref`` counts from that block), and the scores have a lower edge
    beside the causal one; blocks and edge in the page table's own
    coordinates (a window group's table starts at its first held page)."""
    pages, pair, page_size = plan.pages, plan.pair, plan.page_size
    refs = refs[1:] if stacked else refs
    q_ref, refs = refs[0], refs[1:]
    k_refs, v_refs, refs = refs[:pages], refs[pages:2 * pages], refs[2 * pages:]
    if quant:
        ks_refs, vs_refs, refs = (refs[:pages], refs[pages:2 * pages],
                                  refs[2 * pages:])
    o_ref, m_ref, l_ref, acc_ref, top_ref, s_ref = refs
    g = pl.program_id(0)
    i = pl.program_id(1)
    b = lane_ref[i]
    j = blk_ref[i]
    kv_len = lens_ref[b]     # context INCLUDING this chunk's tokens
    q_len = qlens_ref[b]     # valid query tokens this step (0 = idle lane)
    keys = pair * page_size
    first_key = j * plan.keys
    if window is not None:
        first_key += (first_live_key(kv_len, q_len, window)
                      // jnp.int32(plan.keys)) * jnp.int32(plan.keys)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(page_refs, c, h):
        """Key block ``c`` of this step for head ``h``: ``pair`` pages side
        by side, ``[keys, d]``."""
        parts = [page_refs[c * pair + t][h] for t in range(pair)]
        return parts[0] if pair == 1 else jnp.concatenate(parts, axis=0)

    def accumulate(rows):
        """One online-softmax update of every head's first ``rows`` query
        rows over this step's pages: the scores of all its key blocks first
        (kept in ``s_ref``, their running row maxima in ``top_ref``), ONE
        rescale of the accumulator, then the weighted values. A key block
        past the context computes nothing; inside a block's region the heads
        are independent straight-line code, so their products overlap."""
        # row r serves query token r // group: it may attend every key up
        # to and including its own position kv_start + r // group
        qi = jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 0) // group
        limit = jnp.minimum((kv_len - q_len) + qi + jnp.int32(1), kv_len)
        col = first_key + jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 1)
        if window is not None:
            # the row at position p sees keys p - window + 1 .. p
            lower = (kv_len - q_len) + qi + jnp.int32(1 - window)

        def scores(c):
            seen = col + jnp.int32(c * keys) < limit
            if window is not None:
                seen &= col + jnp.int32(c * keys) >= lower
            for h in range(plan.heads):
                q = q_ref[h, :rows, :]                       # [rows, d]
                k = tile(k_refs, c, h)
                if quant:
                    k = k.astype(q.dtype)
                s = _dotf32(q, k, ((1,), (1,))) * scale      # [rows, keys]
                if quant:
                    s = s * ks_refs[c][pl.ds(g * plan.heads + h, 1), :]
                s = jnp.where(seen, s, NEG_INF)
                s_ref[c, h, :rows, :] = s
                top = (m_ref if c == 0 else top_ref)[h, :rows, :]
                top_ref[h, :rows, :] = jnp.maximum(
                    top, jnp.max(s, axis=-1, keepdims=True))

        def values(c):
            for h in range(plan.heads):
                m_next = top_ref[h, :rows, :]                # [rows, 1]
                p = jnp.exp(s_ref[c, h, :rows, :] - m_next)
                v = tile(v_refs, c, h)
                if quant:
                    v = v.astype(q_ref.dtype)
                    pw = p * vs_refs[c][pl.ds(g * plan.heads + h, 1), :]
                else:
                    pw = p
                l_add = jnp.sum(p, axis=-1, keepdims=True)
                pv = _dotf32(pw.astype(v.dtype), v, ((1,), (0,)))
                if c == 0:
                    # the step's one rescale of what the blocks before gave
                    alpha = jnp.exp(m_ref[h, :rows, :] - m_next)
                    l_ref[h, :rows, :] = l_ref[h, :rows, :] * alpha + l_add
                    acc_ref[h, :rows, :] = acc_ref[h, :rows, :] * alpha + pv
                    m_ref[h, :rows, :] = m_next
                else:
                    l_ref[h, :rows, :] += l_add
                    acc_ref[h, :rows, :] += pv

        # the step's first key block holds keys wherever the step is live
        for part in (scores, values):
            part(0)
            for c in range(1, pages // pair):
                pl.when(first_key + jnp.int32(c * keys) < kv_len)(
                    functools.partial(part, c))

    # a decode lane feeds ONE token (a verify lane a few): the few-rows form
    # spares it the chunk's other rows' products and exponentials; a prefill
    # chunk runs whole. Chosen per grid step from q_lens.
    live = (q_len > 0) & (first_key < kv_len)
    if plan.few_rows:
        few = q_len * group <= plan.few_rows
        pl.when(live & few)(lambda: accumulate(plan.few_rows))
        pl.when(live & jnp.logical_not(few))(lambda: accumulate(plan.rows))
    else:
        pl.when(live)(lambda: accumulate(plan.rows))

    @pl.when(last_ref[i] == 1)
    def _finish():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                      ).astype(o_ref.dtype)


def work_items(page_table, seen, fed, *, pages, page_size, blocks, num_pages,
               keep, lane=None, first=None):
    """The work-item axis both paged kernels' grids run over, item by item:
    every query GROUP's live key blocks in order, group after group. A group
    is what one output block serves: a lane for ``ragged_paged_attention``, a
    tile of a lane's rows for ``mla_ragged_paged_attention``. ``seen [g]``:
    the keys a group's rows see (a key block is ``pages`` pages of
    ``page_size`` keys and live while it holds one of them); ``fed [g]``: the
    rows it feeds (0: no key block is live); ``lane [g]``: the page-table row
    it reads (None: group ``i`` reads row ``i``); ``keep``: whether a group
    with no live block keeps ONE item (a lane does, so that its zero rows are
    written; an unused tile, whose rows nobody reads, does not); ``blocks``:
    the key blocks a full table gives a group, so ``g * blocks`` items bound
    the axis; ``first [g]``: the first key position a group's rows see (None:
    0), its items then start at that key's block and ``block`` counts from
    it. Returns ``total`` (the items this call runs: the grid's dynamic
    bound, at least one), each item's ``group`` and key ``block``, whether it
    is its group's ``last`` (``[n]``, n the static bound), and the page every
    (item, operand) names (``[n * pages]``): a slot past what the group sees
    names the page its operand named on the item BEFORE, across groups too,
    so the pipeline fetches nothing for it."""
    g, pps = seen.shape[0], page_table.shape[1]
    i32 = jnp.int32
    n = g * blocks
    live_blocks = jnp.where(fed > 0, -(-seen // i32(pages * page_size)), 0)
    if first is not None:
        # the blocks before the first live key's are not listed
        before = jnp.where(fed > 0, first // i32(pages * page_size), 0)
        live_blocks = live_blocks - before
    per_group = jnp.maximum(live_blocks, 1) if keep else live_blocks
    ends = jnp.cumsum(per_group)
    item = jnp.arange(n, dtype=i32)
    group = jnp.minimum(jnp.sum(item[:, None] >= ends[None, :], axis=1),
                        g - 1).astype(i32)
    block = item - (ends - per_group)[group]
    last = (block == per_group[group] - 1).astype(i32)
    # operand k's slot of a group's key block j holds keys while j * pages +
    # k is one of the group's live page slots: in its first ``reach[g, k]``
    # blocks. Past them it names what it named in the last of them, or where
    # the group has none, in the last group before that has one (``held``: a
    # dense comparison over groups, not a scan over items; -1: nothing named
    # yet, so what item 0 would name). Which page that is, is worked out per
    # group and operand; the items only pick rows of these small tables and
    # ``pages`` adjacent entries of the page table (an element-by-element
    # gather over items x pages costs the chip 9 ns an element)
    assert pages <= pps <= blocks * pages, (pages, pps, blocks)
    k = jnp.arange(pages, dtype=i32)
    slots = jnp.where(fed > 0, jnp.minimum(-(-seen // i32(page_size)), pps),
                      0)
    reach = jnp.maximum(-(-(slots[:, None] - k[None, :]) // i32(pages)), 0)
    of = jnp.arange(g, dtype=i32)
    # an operand holds keys in one of a group's LISTED blocks
    has = reach > 0 if first is None else reach > before[:, None]
    held = jnp.max(jnp.where(has[None] & (of[None, :] <= of[:, None]
                                          )[:, :, None],
                             of[None, :, None], -1), axis=1)      # [g, pages]
    table = jnp.pad(jnp.clip(page_table, 0, num_pages - 1),
                    ((0, 0), (0, blocks * pages - pps))
                    ).reshape(-1, blocks, pages)
    rows = of if lane is None else lane
    held_in = jnp.where(held < 0, group[0], held)
    held_block = jnp.where(held < 0, 0, reach[held_in, k[None, :]] - 1)
    held_page = table[rows[held_in], held_block, k[None, :]]      # [g, pages]
    # an item's key block among the table's: counted from the group's first
    at = block if first is None else block + before[group]
    own = table[rows[group], jnp.minimum(at, blocks - 1)]         # [n, pages]
    named = jnp.where(at[:, None] < reach[group], own, held_page[group])
    total = ends[-1] if keep else jnp.maximum(ends[-1], 1)
    return total.astype(i32), group, block.astype(i32), last, named.reshape(-1)


def _work_items(page_table, kv_lens, q_lens, plan, num_pages, window=None):
    """``ragged_paged_attention``'s items (:func:`work_items`): a group is a
    lane, which sees its whole context (under a ``window``: from the first
    key its first row sees) and keeps one item when idle."""
    return work_items(page_table, kv_lens, q_lens, pages=plan.pages,
                      page_size=plan.page_size, blocks=plan.blocks,
                      num_pages=num_pages, keep=True,
                      first=None if window is None
                      else first_live_key(kv_lens, q_lens, window))


def _ragged_kernel_impl(q4, k_pages, v_pages, page_table, kv_lens, q_lens,
                        plan, group, scale, k_scales=None, v_scales=None,
                        layer=None, window=None):
    """q4: [b, kv_heads, R, d] with R = ``plan.rows``; returns [b, kv_heads,
    R, d] fp32. ``k_scales``/``v_scales`` ([num_pages, kv_heads, page_size]
    or None) flip the int8-KV kernel. ``layer`` (an int32 scalar, or None):
    the pools and scale planes are stacked ``[num_layers, ...]`` and the
    block index maps lead with it, one more scalar-prefetch operand; the
    body never sees the stack. The pools are passed ``plan.pages`` times,
    each operand with its own block index map, so one grid step's pages
    are fetched together."""
    b, hkv, rows, d = q4.shape
    stacked = layer is not None
    num_pages, page_size = k_pages.shape[-4], k_pages.shape[-2]
    quant = k_scales is not None
    hs, pages = plan.heads, plan.pages
    i32 = jnp.int32

    def kv_page(k, g, i, lens_ref, qlens_ref, lane_ref, blk_ref, last_ref,
                tbl_ref, *layer_ref):
        # the stacked forms' leading block index: this call's layer
        return (tuple(ref[0] for ref in layer_ref)
                + (tbl_ref[i * i32(pages) + i32(k)],))

    def kv_imap(k, g, i, *refs):
        return kv_page(k, g, i, *refs) + (g, 0, 0)

    def scale_imap(k, g, i, *refs):
        return kv_page(k, g, i, *refs) + (0, 0)

    def q_imap(g, i, lens_ref, qlens_ref, lane_ref, *_):
        return (lane_ref[i], g, 0, 0)

    lead = (None,) if stacked else ()
    q_spec = pl.BlockSpec((None, hs, rows, d), q_imap)
    kv_specs = [pl.BlockSpec(lead + (None, hs, page_size, d),
                             functools.partial(kv_imap, k))
                for k in range(pages)]
    in_specs = [q_spec] + kv_specs + kv_specs
    args = [q4] + [k_pages] * pages + [v_pages] * pages
    if quant:
        # a page's scales for ALL local heads ([kv_heads, page_size]: the
        # plane's full last two dims, which is what the (8, 128) block rule
        # admits); the kernel slices each head's row
        sc_specs = [pl.BlockSpec(lead + (None, hkv, page_size),
                                 functools.partial(scale_imap, k))
                    for k in range(pages)]
        in_specs += sc_specs + sc_specs
        args += ([k_scales.astype(jnp.float32)] * pages
                 + [v_scales.astype(jnp.float32)] * pages)
    kv_lens, q_lens = kv_lens.astype(i32), q_lens.astype(i32)
    total, *items = _work_items(page_table.astype(i32), kv_lens, q_lens,
                                plan, num_pages, window)
    prefetch = [kv_lens, q_lens, *items]
    if stacked:
        prefetch.append(jnp.asarray(layer, i32).reshape(1))
    kern = functools.partial(_ragged_kernel, plan=plan, group=group,
                             scale=scale, quant=quant, stacked=stacked,
                             window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(plan.groups, total),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, hs, rows, d), q_imap),
        scratch_shapes=[pltpu.VMEM((hs, rows, 1), jnp.float32),
                        pltpu.VMEM((hs, rows, 1), jnp.float32),
                        pltpu.VMEM((hs, rows, d), jnp.float32),
                        pltpu.VMEM((hs, rows, 1), jnp.float32),
                        pltpu.VMEM((pages // plan.pair, hs, rows,
                                    plan.pair * page_size), jnp.float32)],
    )
    with _atc.x64_off():
        return pl.pallas_call(
            kern, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, hkv, rows, d), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                **({"vmem_limit_bytes": plan.vmem} if plan.vmem else {})),
            interpret=_interpret(), name=RAGGED_KERNEL_NAME,
        )(*prefetch, *args)


def ragged_paged_attention_reference(q, k_pages, v_pages, page_table,
                                     kv_lens, q_lens, scale=None,
                                     k_scales=None, v_scales=None,
                                     layer=None, window=None):
    """Gather-based oracle for the ragged kernel (and the non-TPU path).

    q: [b, chunk, num_q_heads, d] right-padded query chunks; kv_lens: [b]
    context length per slot INCLUDING this chunk; q_lens: [b] valid query
    rows (0 = idle lane — its output rows are zero). Query token t of slot
    b sits at absolute position ``kv_lens[b] - q_lens[b] + t`` and attends
    all keys at positions <= its own. With ``k_scales``/``v_scales``
    ([num_pages, kv_heads, page_size]) the pages are int8 and dequantize
    after the gather. ``layer``: the pools and scale planes are stacked
    ``[num_layers, ...]`` and the gather reads that layer of them.
    ``window``: a token at position p attends keys ``p - window + 1 .. p``
    alone (positions in the page table's own coordinates). Returns
    [b, chunk, num_q_heads, d].
    """
    b, c, hq, d = q.shape
    num_pages, hkv, page_size, _ = k_pages.shape[-4:]
    pps = page_table.shape[1]
    group = hq // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    pt = jnp.clip(page_table, 0, num_pages - 1)
    k = gather_pages(k_pages, pt, layer)
    v = gather_pages(v_pages, pt, layer)
    if k_scales is not None:
        k = k.astype(jnp.float32) * gather_pages(k_scales, pt,
                                                 layer)[..., None]
        v = v.astype(jnp.float32) * gather_pages(v_scales, pt,
                                                 layer)[..., None]
    qg = q.reshape(b, c, hkv, group, d)
    s = jnp.einsum("bchgd,bshd->bhgcs", qg.astype(jnp.float32),
                   k.astype(jnp.float32), precision=_MXU) * scale
    kv_start = (kv_lens - q_lens).reshape(-1, 1, 1)              # [b,1,1]
    limit = kv_start + jnp.arange(c).reshape(1, -1, 1) + 1       # [b,c,1]
    col = jnp.arange(pps * page_size).reshape(1, 1, -1)
    valid = ((col < jnp.minimum(limit, kv_lens.reshape(-1, 1, 1)))
             & (jnp.arange(c).reshape(1, -1, 1) < q_lens.reshape(-1, 1, 1)))
    if window is not None:
        valid &= col >= limit - int(window)
    s = jnp.where(valid[:, None, None], s, NEG_INF)              # [b,h,g,c,s]
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows (idle lanes / padding past q_lens): softmax is
    # uniform garbage — zero them
    p = jnp.where(valid[:, None, None], p, 0.0)
    out = jnp.einsum("bhgcs,bshd->bchgd", p, v.astype(jnp.float32),
                     precision=_MXU)
    return out.reshape(b, c, hq, d).astype(q.dtype)


def ragged_paged_attention(q, k_pages, v_pages, page_table, kv_lens, q_lens,
                           scale=None, use_kernel: bool | None = None,
                           k_scales=None, v_scales=None, layer=None,
                           window=None):
    """Ragged prefill+decode attention over the paged KV cache.

    The unified-step entry: each slot contributes ``q_lens[b]`` (0..chunk)
    query tokens this step, causal within the chunk, attending that slot's
    whole paged context (``kv_lens[b]`` tokens, chunk included — the
    chunk's K/V must already be written to the pages). ``use_kernel`` as in
    :func:`paged_attention`. Rows past ``q_lens`` are garbage the caller
    must ignore (their page writes drop; the reference zeroes them).
    ``k_scales``/``v_scales`` ([num_pages, kv_heads, page_size]) mark the
    pools int8 (round-10 quantized KV); dequantization fuses into the
    kernel's page loop (or the gathered reference) — pages stay int8
    end-to-end in HBM.

    Where the pools live: the unified step keeps every layer's pool in ONE
    stacked buffer ``[num_layers, num_pages, kv_heads, page_size, d]`` (scale
    planes ``[num_layers, num_pages, kv_heads, page_size]``) for the whole
    step and passes it here whole, with ``layer`` (a traced int32 scalar)
    naming the layer to read: the kernel's block index maps lead with it, so
    no layer's pool is ever sliced out of the stack (151 MB a layer and pool
    at the 590M deployment). Without ``layer`` the pools are one layer's,
    4-D, as the decode op, the draft chain and the autotuners pass them.

    ``window`` (a static int): sliding-window attention, a token at position
    p attends keys ``p - window + 1 .. p``. The grid lists a lane's key
    blocks from the first one any of its rows sees and the scores get a lower
    edge. Positions are those of the page table handed in: a cache group that
    keeps a window's pages alone hands in a table that starts at its first
    held page and ``kv_lens`` counted from that page's first position
    (causality and the window are both differences of positions). With no
    window the plan and the kernel are what they were.
    """
    b, c, hq, d = q.shape
    hkv = k_pages.shape[-3]
    assert hq % hkv == 0, f"GQA needs q heads {hq} divisible by kv {hkv}"
    assert k_pages.shape == v_pages.shape
    assert k_pages.ndim == (4 if layer is None else 5), (
        f"pools of rank {k_pages.ndim} with layer={layer!r}: a stacked "
        "[num_layers, ...] pool needs layer=, one layer's pool must not "
        "have it")
    assert page_table.shape[0] == b
    assert kv_lens.shape == (b,) and q_lens.shape == (b,)
    assert (k_scales is None) == (v_scales is None)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if use_kernel is None:
        use_kernel = use_kernel_default()
    if not use_kernel:
        return ragged_paged_attention_reference(
            q, k_pages, v_pages, page_table, kv_lens, q_lens, scale=scale,
            k_scales=k_scales, v_scales=v_scales, layer=layer, window=window)
    group = hq // hkv
    plan = ragged_grid(b, page_table.shape[1], c, hq, hkv,
                       k_pages.shape[-2], d, k_pages.dtype, q.dtype)
    # rows = chunk-major, group-minor: [b, c, hkv, g, d] -> [b, hkv, c*g, d]
    q4 = q.reshape(b, c, hkv, group, d).transpose(0, 2, 1, 3, 4)
    q4 = q4.reshape(b, hkv, c * group, d)
    if plan.rows != c * group:
        q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, plan.rows - c * group),
                          (0, 0)))
    out = _ragged_kernel_impl(q4, k_pages, v_pages, page_table, kv_lens,
                              q_lens, plan, group, float(scale),
                              k_scales=k_scales, v_scales=v_scales,
                              layer=layer, window=window)
    out = out[:, :, :c * group, :].reshape(b, hkv, c, group, d)
    out = out.transpose(0, 2, 1, 3, 4).reshape(b, c, hq, d)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# page-size autotune (rides the shared autotune cache)
# ---------------------------------------------------------------------------

PAGE_SIZE_DEFAULT = 64


def _sig(hq, hkv, d, dtype) -> str:
    return f"paged:{hq}h{hkv}x{d}:{jnp.dtype(dtype).name}:page_size"


def preferred_page_size(hq, hkv, d, dtype=jnp.bfloat16) -> int:
    """The autotuned page size for this head geometry (or the default).
    ``KVCacheManager(page_size=None)`` consults this, so a swept winner
    changes the cache layout the next time a cache is built."""
    hit = _atc.lookup(_sig(hq, hkv, d, dtype))
    return int(hit[0]) if hit else PAGE_SIZE_DEFAULT


def autotune_page_size(batch, hq, hkv, d, max_len=2048, dtype=jnp.bfloat16,
                       candidates=(16, 32, 64, 128), iters=5):
    """Sweep the cache page size on the current device and persist the
    winner (process + disk via the shared autotune cache).

    Page size is a TRACE-TIME cache-layout constant (it shapes the page
    pool and the kernel's kv block), so like the flash block sweep this is
    an explicit eager call to run once before building caches; the winner
    then flows through :func:`preferred_page_size`. Returns the page size.
    """
    from ...observability import monotonic

    if _interpret():
        return preferred_page_size(hq, hkv, d, dtype)
    _atc.load()
    sig = _sig(hq, hkv, d, dtype)
    # one subkey per operand: a shared key makes q/k/v correlated streams,
    # degenerating the softmax the sweep times
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (batch, hq, d), dtype)
    best, best_t = None, float("inf")
    for ps in candidates:
        pps = (max_len + ps - 1) // ps
        num_pages = batch * pps + 1
        kp = jax.random.normal(kk, (num_pages, hkv, ps, d), dtype)
        vp = jax.random.normal(kv, (num_pages, hkv, ps, d), dtype)
        pt = jnp.arange(batch * pps, dtype=jnp.int32).reshape(batch, pps)
        lens = jnp.full((batch,), max_len, jnp.int32)
        try:
            step = jax.jit(functools.partial(paged_attention,
                                             use_kernel=True))
            step(q, kp, vp, pt, lens).block_until_ready()  # compile+warmup
            t0 = monotonic()
            for _ in range(iters):
                out = step(q, kp, vp, pt, lens)
            out.block_until_ready()
            t = monotonic() - t0
        except Exception:
            continue
        if t < best_t:
            best, best_t = ps, t
    if best is not None:
        _atc.CACHE[sig] = [int(best)]
        _atc.save()
        return best
    return preferred_page_size(hq, hkv, d, dtype)


# ---------------------------------------------------------------------------
# chunk-size autotune (the unified step's per-slot query-chunk width)
# ---------------------------------------------------------------------------

CHUNK_DEFAULT = 16


def _chunk_sig(hq, hkv, d, dtype) -> str:
    return f"ragged:{hq}h{hkv}x{d}:{jnp.dtype(dtype).name}:chunk"


def preferred_chunk_size(hq, hkv, d, dtype=jnp.bfloat16) -> int:
    """The autotuned unified-step chunk size for this head geometry (or the
    default). Chunk is a TRACE-TIME shape constant of the unified step jit
    (its [batch, chunk] query block), so like the page size it is consulted
    once when the serving step is built."""
    hit = _atc.lookup(_chunk_sig(hq, hkv, d, dtype))
    return int(hit[0]) if hit else CHUNK_DEFAULT


def autotune_chunk_size(batch, hq, hkv, d, max_len=2048, page_size=None,
                        dtype=jnp.bfloat16, candidates=(8, 16, 32, 64),
                        iters=5):
    """Sweep the ragged kernel's chunk width on the current device and
    persist the winner on the shared autotune cache. The sweep times a
    mixed step (half the lanes decode 1 token, half prefill a full chunk —
    the steady-state unified-step shape). Returns the chunk size."""
    from ...observability import monotonic

    if _interpret():
        return preferred_chunk_size(hq, hkv, d, dtype)
    _atc.load()
    sig = _chunk_sig(hq, hkv, d, dtype)
    ps = page_size or preferred_page_size(hq, hkv, d, dtype)
    pps = (max_len + ps - 1) // ps
    num_pages = batch * pps + 1
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    kp = jax.random.normal(kk, (num_pages, hkv, ps, d), dtype)
    vp = jax.random.normal(kv, (num_pages, hkv, ps, d), dtype)
    pt = jnp.arange(batch * pps, dtype=jnp.int32).reshape(batch, pps)
    best, best_t = None, float("inf")
    for chunk in candidates:
        q = jax.random.normal(kq, (batch, chunk, hq, d), dtype)
        # mixed ragged step: even lanes decode (1 token), odd lanes carry a
        # full prefill chunk
        q_lens = jnp.where(jnp.arange(batch) % 2 == 0, 1, chunk
                           ).astype(jnp.int32)
        kv_lens = jnp.full((batch,), max_len, jnp.int32)
        try:
            step = jax.jit(functools.partial(ragged_paged_attention,
                                             use_kernel=True))
            step(q, kp, vp, pt, kv_lens, q_lens).block_until_ready()
            t0 = monotonic()
            for _ in range(iters):
                out = step(q, kp, vp, pt, kv_lens, q_lens)
            out.block_until_ready()
            # normalize per useful token: bigger chunks do more work/step
            t = (monotonic() - t0) / float(q_lens.sum())
        except Exception:
            continue
        if t < best_t:
            best, best_t = chunk, t
    if best is not None:
        _atc.CACHE[sig] = [int(best)]
        _atc.save()
        return best
    return preferred_chunk_size(hq, hkv, d, dtype)
