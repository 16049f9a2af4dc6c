"""Paged KV cache manager — the serving cache behind paged decode attention.

Reference shape: the vLLM-style block manager behind the reference's
``block_multihead_attention`` serving path, TPU-native: the cache is a POOL
of fixed-size pages ``[num_layers, num_pages, kv_heads, page_size,
head_dim]`` (one stacked array per K and V so the decode jit sees ONE
pytree leaf each; HEAD-MAJOR inside a page, so the attention kernels'
per-(page, head) block is a contiguous ``[page_size, head_dim]`` tile —
the (8, 128) block rule Mosaic enforces on the last two dims), and each
admitted sequence owns a list of pages through a per-slot page table.
Admission/eviction move pages between the free list and slots without
copying K/V — fragmentation-free continuous batching.

Split of responsibilities:

- **host side (this class)**: page free list, slot free list, admission
  (can the prompt + headroom fit?), per-step growth (allocate a page when a
  sequence crosses a page boundary), eviction. All O(pages) numpy/python —
  never inside a compiled program.
- **device side (pure functions below)**: the scatters that write a step's
  packed K/V rows into the page pool. They are shape-stable jnp functions
  traced INTO the serving step's jit (models/gpt.py), so the cache arrays
  never round-trip through the host.

Page-table convention (shared with ops/pallas/paged_attention):
``page_table[slot, i]`` is the pool index of the slot's i-th page, ``-1``
when unallocated; ``seq_lens[slot]`` counts tokens already written (0 =
empty slot). Writes to unallocated/out-of-range positions are routed out of
bounds and dropped (``mode="drop"``) rather than corrupting page 0.

Round 9 adds PREFIX CACHING (vLLM automatic-prefix-caching shape, page
granularity): prompt pages are registered under a content CHAIN HASH
(page i's key folds page i-1's key, so a key names the whole prefix up to
and including that page) once their prefill lands. A later admission walks
its prompt's chain and attaches every matching page read-only
(refcount += 1) instead of re-prefilling it; the final page may match a
registered PARTIAL fill (the key records the token count). Refcounted
pages are PINNED (never reallocated); a registered page whose refcount
drops to 0 parks on an LRU and keeps serving hits until the free list runs
dry, at which point the LRU tail is evicted (unregistered) and reused.
Divergence is handled copy-on-write: a slot about to write into a page
with refcount >= 2 gets a fresh copy via :meth:`prepare_write` — the
device-side page copy is traced into the unified step (cow_src/cow_dst
lanes), so shared immutable pages are never mutated.

Round 21 adds the HOST TIER: a bounded host-DRAM buffer UNDER the HBM
pool. A zero-ref prefix page falling off the LRU no longer just drops —
its payload (K/V rows, int8 scale planes, partial tails included)
spills to the host keyed by the SAME sha1 chain key, checksummed at
spill time. A later admission (or export walk) whose chain breaks on
the device registry but continues in the host tier re-admits the
missing links through the batched import landing zone
(:meth:`KVCacheManager.import_prefix_pages` — ONE donated scatter per
K/V/scale plane per restore round, not a full pool copy per page) and
the normal match walk then pins them like never-evicted pages. Eviction
ordering is HBM -> host -> drop: the host tier runs its own LRU under
its byte budget, and a tier entry whose checksum fails at restore is
DETECTED, dropped and counted — degrading to a recompute, never
scattering corrupt bytes into the pool.
"""
from __future__ import annotations

import math
import zlib
from collections import OrderedDict
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from .faults import fault_point


def pages_needed(length: int, page_size: int) -> int:
    """Pages a ``length``-token sequence occupies (>= 1) — the ONE spelling
    of the ceil-div every pool-sizing site shares."""
    return math.ceil(max(length, 1) / page_size)


def chain_key(prev: bytes, tokens) -> bytes:
    """The prefix cache's sha1 content chain key: page i's key folds page
    i-1's, so one key names the whole prefix up to and including this
    page's tokens (count included — a 4-token partial and an 8-token full
    fill hash differently). Module-level because the key is a CONTRACT
    shared beyond one manager: the fleet router's prefix-affinity map
    (``inference/fleet_serving.py``) hashes prompts with the SAME chain so
    shared-prefix traffic lands on the replica whose pool already holds
    those pages — which is only sound because independently constructed
    managers (different replicas, different processes) derive identical
    keys from identical tokens (locked by tests/test_prefix_cache.py)."""
    import hashlib

    h = hashlib.sha1(prev)
    h.update(np.asarray(tokens, np.int64).tobytes())
    return h.digest()


def prompt_chain_keys(tokens, page_size: int) -> list[bytes]:
    """The chain keys of every FULL page of ``tokens``, shallowest first —
    the fleet router's affinity walk (deepest registered key wins, so the
    longest shared prefix decides the replica). Prompts shorter than one
    page have no stable page-granular identity: empty list."""
    keys: list[bytes] = []
    h = b""
    for i in range(0, len(tokens) - len(tokens) % int(page_size),
                   int(page_size)):
        h = chain_key(h, tokens[i:i + page_size])
        keys.append(h)
    return keys


def kv_cache_quantized(kv_cache_dtype) -> bool:
    """Map a ``kv_cache_dtype`` config value to the pool-quantization flag
    — the ONE validation every consumer (generate_paged, ServingPredictor)
    shares, so an unsupported value fails loudly instead of silently
    serving a full-precision cache."""
    if kv_cache_dtype in (None, "none"):
        return False
    if kv_cache_dtype == "int8":
        return True
    raise ValueError(
        f"kv_cache_dtype must be None or 'int8', got {kv_cache_dtype!r} "
        "(int4 KV is not supported — sub-byte pages would halve the "
        "scatter granularity; weight_dtype='int4' is the 4x lever)")


# ---------------------------------------------------------------------------
# device-side pure scatter helpers (traced into the serving step's jit)
# ---------------------------------------------------------------------------


def _packed_dest(page_table, tok_slot, tok_pos, page_size, num_pages):
    """The packed-write scatter destination shared by the fp and quantized
    writes: per-token (page, row) with padding (< 0 slot/pos) and
    unallocated (-1) entries routed to the out-of-bounds ``num_pages``
    sentinel (``mode="drop"``). Returns ``(pg, row)``."""
    b = page_table.shape[0]
    slot_c = jnp.clip(tok_slot, 0, b - 1)
    pos = jnp.maximum(tok_pos, 0)
    pg = page_table[slot_c,
                    jnp.clip(pos // page_size, 0, page_table.shape[1] - 1)]
    valid = (tok_slot >= 0) & (tok_pos >= 0) & (pg >= 0)
    return jnp.where(valid, pg, num_pages), pos % page_size


class PackedWritePlan(NamedTuple):
    """One step's packed write, page by page (see
    :func:`packed_write_plan`): ``order/page/lo/hi [n]`` in the write
    kernel's grid order, ``src [n, page_size]`` the packed token whose row
    lands at each row of each touched page."""
    order: jax.Array
    page: jax.Array
    lo: jax.Array
    hi: jax.Array
    src: jax.Array


def packed_write_plan(page_table, tok_slot, tok_pos, page_size, num_pages):
    """The packed write's destinations regrouped by TOUCHED PAGE, for the
    in-place kernel (``ops/pallas/paged_write``): made once per step from
    the arguments every packed write of the step shares, used by each
    layer's writes (``plan=``).

    A row is narrower than a tile of the pool, so the kernel rewrites whole
    pages: item ``i`` is one touched page, ``lo[i] .. hi[i]`` its new rows.
    The bound on touched pages, ``n = 2 * batch + budget // page_size``,
    holds because a slot's rows of one step are consecutive positions (the
    same contract ragged attention's ``kv_lens``/``q_lens`` state), and for
    the same reason a page's new rows are one run. Grid steps past the last
    touched page repeat it (the kernel's no-stale-read rule); with nothing
    to write every step rewrites page 0 with itself.
    """
    b, t = page_table.shape[0], tok_slot.shape[0]
    n = 2 * b + t // page_size
    pg, row = _packed_dest(page_table, tok_slot, tok_pos, page_size,
                           num_pages)
    # ascending, so the dropped tokens' sentinel (num_pages) sorts last
    touched = jnp.unique(pg, size=n, fill_value=num_pages)
    item = jnp.where(pg < num_pages, jnp.searchsorted(touched, pg), n)
    src = jnp.zeros((n, page_size), jnp.int32).at[item, row].set(
        jnp.arange(t, dtype=jnp.int32), mode="drop")
    lo = jnp.full((n,), page_size, jnp.int32).at[item].min(row, mode="drop")
    hi = jnp.zeros((n,), jnp.int32).at[item].max(row + 1, mode="drop")
    last = jnp.maximum(jnp.sum(touched < num_pages) - 1, 0)
    order = jnp.minimum(jnp.arange(n), last).astype(jnp.int32)
    return PackedWritePlan(
        order=order, page=jnp.clip(touched[order], 0, num_pages - 1),
        lo=lo[order], hi=hi[order], src=src)


def _write_rows(pages, vals, page_table, tok_slot, tok_pos, page_size,
                layer, plan):
    """The one row write behind the three packed writes. ``vals [budget,
    kv_heads(, head_dim)]`` land in ``pages`` (a pool or a scale plane) at
    ``[pg, :, row]``; with ``layer``, at ``[layer, pg, :, row]`` of the
    STACKED ``[num_layers, num_pages, ...]`` pool, the other layers
    untouched; with ``plan`` too, through the in-place Pallas kernel."""
    vals = vals.astype(pages.dtype)
    if plan is not None:
        from ..ops.pallas.paged_write import paged_write_pages

        # page-aligned new rows, [n, kv_heads, page_size(, head_dim)]
        new = jnp.swapaxes(vals[plan.src], 1, 2)
        return paged_write_pages(pages, new, plan.order, plan.page,
                                 plan.lo, plan.hi, layer)
    pg, row = _packed_dest(page_table, tok_slot, tok_pos, page_size,
                           pages.shape[0 if layer is None else 1])
    dest = (pg, slice(None), row)
    if layer is not None:
        dest = (layer,) + dest
    return pages.at[dest].set(vals, mode="drop")


def paged_write_packed(pages, toks, page_table, tok_slot, tok_pos,
                       page_size, layer=None, plan=None):
    """Write a PACKED token stream into the page pool in one scatter (the
    unified-step write: the step's dense dims run over the flat token
    budget, each token carrying its owning slot + absolute position).

    pages: [num_pages, kv_heads, page_size, head_dim]; toks: [budget,
    kv_heads, head_dim]; page_table: [batch, pages_per_slot] int32;
    tok_slot: [budget] int32 owning slot (< 0 = padding, dropped);
    tok_pos: [budget] int32 absolute write position. Returns the pool.

    ``layer`` (a traced scalar; all three packed writes take it): ``pages``
    is the STACKED pool ``[num_layers, num_pages, ...]`` and the rows land
    in that layer of it — how the unified step's layer scan writes into the
    one buffer it carries, without slicing a layer's pool out of the stack.
    ``plan`` (:func:`packed_write_plan` of the same ``page_table``,
    ``tok_slot``, ``tok_pos``; needs ``layer``) writes through the Pallas
    kernel that updates the stack in place: on the chip XLA's scatter
    copies its whole operand into a layout of its own and back.
    """
    return _write_rows(pages, toks, page_table, tok_slot, tok_pos,
                       page_size, layer, plan)


def paged_write_packed_quant(pages, scales, toks, page_table, tok_slot,
                             tok_pos, page_size, layer=None, plan=None):
    """Quantize-on-write for the int8 KV cache: the packed write
    (:func:`paged_write_packed`) with a per-token-per-head symmetric int8
    quantization fused in front of the scatter.

    pages: [num_pages, kv_heads, page_size, head_dim] **int8**; scales:
    [num_pages, kv_heads, page_size] fp32 (the per-page scale plane — page
    granularity keeps it travelling with the page through CoW copies,
    prefix sharing and eviction); toks: [budget, kv_heads, head_dim] float.
    Each token row quantizes against its own per-head absmax
    (``scale = absmax / 127``), so pages never need rescaling as later
    tokens land. Returns ``(pages, scales)``.
    """
    tf = toks.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(tf), axis=-1)           # [budget, kv_heads]
    s = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(tf / s[..., None]), -127, 127).astype(jnp.int8)
    return paged_write_packed_prequant(pages, scales, q, s, page_table,
                                       tok_slot, tok_pos, page_size,
                                       layer, plan)


def paged_write_packed_prequant(pages, scales, q_toks, s_toks, page_table,
                                tok_slot, tok_pos, page_size, layer=None,
                                plan=None):
    """The scatter half of :func:`paged_write_packed_quant`: int8 payloads
    ``q_toks [budget, kv_heads, head_dim]`` and their per-row-per-head
    scales ``s_toks [budget, kv_heads]`` into the int8 pool and its scale
    plane. The budget packs 1..chunk rows per lane (pad rows ``tok_slot ==
    -1``); the drop-mode scatter is position-addressed. Returns ``(pages,
    scales)``.
    """
    dest = (page_table, tok_slot, tok_pos, page_size, layer, plan)
    return (_write_rows(pages, q_toks, *dest),
            _write_rows(scales, s_toks, *dest))


def paged_copy_pages(pages, src, dst, lane_by_lane=False):
    """Copy-on-write page copies, traced into the unified step.

    pages: [num_layers, num_pages, kv_heads, page_size, head_dim] (the
    stacked pool as the jits see it); src/dst: [batch] int32 pool indices,
    ``dst == num_pages`` (the host's no-op sentinel) drops the copy. Each
    active lane duplicates one page across every layer.

    ``lane_by_lane``: one page at a time, each a dynamic slice written back
    in place, instead of one gather and scatter over all lanes. What a pool
    whose rows are wider than 512 values needs (a latent cache's): compiled
    for the chip, the gather of such rows is split by slicing the WHOLE pool
    into narrower ones first, 4.7 GB of temporaries and a pass over the pool
    every step at the DeepSeek-V2-Lite deployment.
    """
    num_pages = pages.shape[1]
    src_c = jnp.clip(src, 0, num_pages - 1)
    if not lane_by_lane:
        return pages.at[:, dst].set(pages[:, src_c], mode="drop")

    def one(i, pages):
        to = jnp.clip(dst[i], 0, num_pages - 1)
        page = jax.lax.dynamic_slice_in_dim(pages, src_c[i], 1, axis=1)
        kept = jax.lax.dynamic_slice_in_dim(pages, to, 1, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(
            pages, jnp.where(dst[i] < num_pages, page, kept), to, axis=1)

    return jax.lax.fori_loop(0, src.shape[0], one, pages)


def batched_import_rows(pages, vals, pg, row):
    """Land one restore round's token rows in ONE scatter — the round-21
    batched import/restore write (tpulint flagship: ``serving-tiered``).

    pages: ``[L, P, kv_heads, page_size, head_dim]`` (or a 4-D scale
    plane ``[L, P, kv_heads, page_size]``); vals: ``[L, R, kv_heads,
    head_dim]`` (resp. ``[L, R, kv_heads]``, the token-row-major payload
    layout) — flat row ``r`` lands at ``pages[:, pg[r], :, row[r]]``.
    Padding rows carry ``pg == P`` (the out-of-bounds sentinel) and drop,
    so one power-of-two-padded trace serves every restore round of that
    width.
    """
    # pg/row are split by the head slice, so the indexed view leads with
    # the row axis: [R, L, kv_heads(, head_dim)]
    return pages.at[:, pg, :, row].set(jnp.moveaxis(vals, 1, 0),
                                       mode="drop")


#: the jitted batched-import entry point: the pool argument is DONATED —
#: a restore round updates the (potentially multi-GiB) pool in place
#: instead of materializing a second copy per plane. The 5-D K/V pools
#: and the 4-D scale planes each trace once per padded row width.
_batched_import_rows_jit = jax.jit(batched_import_rows,
                                   donate_argnums=(0,))


#: payload plane name -> the manager attribute holding that pool (the scale
#: planes exist on int8 pools only)
_PLANE_ATTRS = {"k": "k_pages", "v": "v_pages",
                "ks": "k_scales", "vs": "v_scales"}


def _payload_crc(planes: dict) -> int:
    """The host-tier integrity checksum: one crc32 over every plane's
    bytes in plane-name order — computed at spill time, verified at
    restore (a corrupt stored payload must be DETECTED, never scattered
    into the device pool)."""
    crc = 0
    for name in sorted(planes):
        crc = zlib.crc32(planes[name].tobytes(), crc)
    return crc


# ---------------------------------------------------------------------------
# host-side manager
# ---------------------------------------------------------------------------


class _WindowGroup:
    """The cache GROUP of the layers that attend a WINDOW (sliding-window
    attention): their K and V pools ``[layers, num_pages, kv_heads,
    page_size, head_dim]``, a free list and a page table of their own. A lane
    holds only the pages its next rows can still see: before a step that
    feeds ``n <= chunk`` rows from position ``written`` the scheduler releases
    the pages wholly behind ``written - tokens + 1`` (the lower edge of the
    step's first row) and grows the lane to ``written + n``, so a lane never
    holds more than ``pages_per_slot`` pages, whatever its context. Row ``k``
    of a lane's table is the page of positions ``(first + k) * page_size
    ..``: the table starts at the lane's first HELD page, and the step is
    handed ``first * page_size`` beside it (:meth:`device`), the position its
    coordinates count from.

    A released page goes back to the free list at once, while steps
    dispatched before may still be in flight, and a row of such a step (its
    lower edge lies further back) may still READ the page. What keeps that
    safe is order, not visibility: every step was dispatched with a private
    copy of the table as it stood, the device runs steps in dispatch order
    (each takes the pools the one before returns), and a page is REWRITTEN
    only by a step dispatched after the release (the earliest is the step
    being packed, whose own rows no longer see it), so a new owner's first
    write lands after every read of the old rows."""

    def __init__(self, layers, tokens, chunk, *, max_batch, num_kv_heads,
                 head_dim, page_size, dtype):
        self.layers, self.tokens = int(layers), int(tokens)
        self.page_size = int(page_size)
        # tokens - 1 positions behind the first new row, chunk new rows, at
        # any alignment to the pages
        self.pages_per_slot = -(-(self.tokens + int(chunk) - 1)
                                // self.page_size) + 1
        self.num_pages = int(max_batch) * self.pages_per_slot
        shape = (self.layers, self.num_pages, num_kv_heads, self.page_size,
                 head_dim)
        self.k_pages = jnp.zeros(shape, dtype)
        self.v_pages = jnp.zeros(shape, dtype)
        self.table = np.full((max_batch, self.pages_per_slot), -1, np.int32)
        self.first = np.zeros((max_batch,), np.int32)
        self.free = list(range(self.num_pages - 1, -1, -1))   # pop()
        self.released = 0
        self._rev = 0
        self._dev = (-1, None)

    def held(self, slot=None) -> int:
        """Pages a lane holds (None: all lanes)."""
        if slot is None:
            return self.num_pages - len(self.free)
        return int((self.table[slot] >= 0).sum())

    def release_behind(self, slot: int, written: int) -> int:
        """Release ``slot``'s pages wholly behind the lower edge of a row at
        position ``written``. Returns how many."""
        edge = max(0, int(written) - self.tokens + 1) // self.page_size
        drop = min(edge - int(self.first[slot]), self.pages_per_slot)
        if drop <= 0:
            return 0
        gone = [int(p) for p in self.table[slot, :drop] if p >= 0]
        self.free.extend(reversed(gone))
        self.table[slot] = np.concatenate(
            [self.table[slot, drop:], np.full((drop,), -1, np.int32)])
        self.first[slot] = edge
        self.released += len(gone)
        self._rev += 1
        return len(gone)

    def need(self, slot: int, new_len: int) -> int:
        """Pages ``slot`` still has to claim to hold ``new_len`` tokens."""
        want = pages_needed(new_len, self.page_size) - int(self.first[slot])
        return max(0, want - self.held(slot))

    def grow(self, slot: int, new_len: int) -> bool:
        """Claim the pages ``slot`` lacks to hold ``new_len`` tokens; whether
        it claimed any."""
        have = self.held(slot)
        want = pages_needed(new_len, self.page_size) - int(self.first[slot])
        if want > self.pages_per_slot:
            raise RuntimeError(
                f"slot {slot}: {want} window pages for {new_len} tokens from "
                f"page {int(self.first[slot])}, over the lane's bound "
                f"{self.pages_per_slot}: release_behind was not called, or "
                "the step feeds more rows than the group was built for")
        for k in range(have, want):
            self.table[slot, k] = self.free.pop()
        if want > have:
            self._rev += 1
        return want > have

    def free_slot(self, slot: int) -> None:
        gone = [int(p) for p in self.table[slot] if p >= 0]
        self.free.extend(reversed(gone))
        self.table[slot] = -1
        self.first[slot] = 0
        self._rev += 1

    def device(self):
        """``(table [batch, pages_per_slot], base [batch])`` on the device:
        the group's page table and the position each lane's table starts at.
        Uploaded from private copies when stale (as the full table)."""
        rev, dev = self._dev
        if rev != self._rev:
            dev = (jnp.asarray(self.table.copy()),
                   jnp.asarray(self.first * np.int32(self.page_size)))
            self._dev = (self._rev, dev)
        return dev


class KVCacheManager:
    """Owns the page pool + page table + free lists for one model.

    ``num_pages`` bounds total cached tokens (``num_pages * page_size``);
    ``max_batch`` bounds concurrent sequences (decode-step batch — the
    FIXED jit shape); ``max_seq_len`` bounds per-sequence length (page-table
    width). ``page_size=None`` consults the autotuned
    :func:`~paddle_tpu.ops.pallas.paged_attention.preferred_page_size`.
    """

    def __init__(self, num_layers, num_kv_heads, head_dim, *, num_pages,
                 max_batch, max_seq_len, page_size=None, num_q_heads=None,
                 dtype=jnp.float32, enable_prefix_cache=False,
                 quantize_kv=False, mesh=None, metrics=None,
                 host_tier_bytes=0, latent=False, index_plane=None,
                 window=None):
        from ..ops.pallas.paged_attention import preferred_page_size

        # layers that attend a WINDOW: ``window=(layers, tokens, chunk)``,
        # ``layers`` of the ``num_layers`` keep their last ``tokens``
        # positions alone (a step feeds a lane at most ``chunk`` rows). They
        # are a cache GROUP of their own (:class:`_WindowGroup`); the pools,
        # table and free list below are the full layers'. What is not built
        # over two groups fails here.
        if window is not None:
            unsupported = [name for name, on in (
                ("enable_prefix_cache (a hit needs the last window's pages "
                 "alive in the window group)", enable_prefix_cache),
                ("quantize_kv", quantize_kv), ("mesh", mesh is not None),
                ("host_tier_bytes", host_tier_bytes),
                ("latent", latent)) if on]
            if unsupported or not 0 < int(window[0]) < num_layers:
                raise NotImplementedError(
                    f"a cache with a window group ({window[0]} of "
                    f"{num_layers} layers) does not take "
                    f"{', '.join(unsupported) or 'that split of layers'} "
                    "yet")

        # a LATENT cache (multi-head latent attention): ONE pool, one row
        # per token shared by every head — ``num_kv_heads`` 1, ``head_dim``
        # the row's width. Page table, prefix cache, CoW and the packed
        # write serve it as they serve a K pool; there is no V pool.
        self.latent = bool(latent)
        if self.latent:
            unsupported = [name for name, on in (
                ("quantize_kv", quantize_kv), ("mesh", mesh is not None),
                ("host_tier_bytes", host_tier_bytes)) if on]
            if unsupported or num_kv_heads != 1:
                raise NotImplementedError(
                    f"a latent cache is one pool of one shared row per "
                    f"token (num_kv_heads 1, got {num_kv_heads}) and does "
                    f"not take {', '.join(unsupported) or 'more heads'} yet")

        if page_size is None:
            page_size = preferred_page_size(
                num_q_heads or num_kv_heads, num_kv_heads, head_dim, dtype)
        if mesh is not None and num_kv_heads % int(mesh.shape["mp"]):
            raise ValueError(
                f"the mp mesh size {int(mesh.shape['mp'])} must divide "
                f"kv heads {num_kv_heads} (pages shard by whole head)")
        self.window = None if window is None else _WindowGroup(
            *window, max_batch=max_batch, num_kv_heads=num_kv_heads,
            head_dim=head_dim, page_size=page_size, dtype=dtype)
        if self.window is not None:
            num_layers -= self.window.layers     # the full group's
        self.num_layers = num_layers
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.max_batch = int(max_batch)
        self.max_seq_len = int(max_seq_len)
        self.pages_per_slot = math.ceil(self.max_seq_len / self.page_size)
        shape = (num_layers, self.num_pages, num_kv_heads,
                 self.page_size, head_dim)
        # int8 KV (round 10): pages store int8 with a per-page fp32 scale
        # plane [L, P, kv_heads, page_size] — the scale travels WITH its
        # page (CoW copies, prefix sharing, eviction all stay page-local).
        # ``dtype`` remains the COMPUTE dtype (page-size autotune key).
        self.quantize_kv = bool(quantize_kv)
        pool_dtype = jnp.int8 if self.quantize_kv else dtype
        self.k_pages = jnp.zeros(shape, pool_dtype)
        self.v_pages = None if self.latent else jnp.zeros(shape, pool_dtype)
        # a latent cache whose model has a learned INDEXER (learned sparse
        # attention, models/glm_moe_dsa.py): ``index_plane=(layers, width)``,
        # a second plane ``[layers, num_pages, 1, page_size, width]`` for the
        # indexer layers' keys alone. It is keyed by the SAME page ids, so a
        # page's index keys are allocated, freed, shared by the prefix cache
        # and copied on write with the page they belong to (the step copies
        # every pool it is handed); nothing below knows of it
        self.index_pages = None
        if index_plane is not None:
            if not self.latent:
                raise NotImplementedError(
                    "an index plane belongs to a latent cache")
            self.index_pages = jnp.zeros(
                (int(index_plane[0]), self.num_pages, 1, self.page_size,
                 int(index_plane[1])), pool_dtype)
        if self.quantize_kv:
            sshape = shape[:4]
            self.k_scales = jnp.zeros(sshape, jnp.float32)
            self.v_scales = jnp.zeros(sshape, jnp.float32)
        else:
            self.k_scales = self.v_scales = None
        # round 11: under a serving mesh the pools (and scale planes) live
        # SHARDED on the head axis — each chip owns its heads' pages end
        # to end; the sharded serving jits return them sharded, so the
        # pool never materializes whole on one chip
        self.mesh = mesh
        if mesh is not None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            kv_sh = NamedSharding(mesh, P(None, None, "mp", None, None))
            self.k_pages = jax.device_put(self.k_pages, kv_sh)
            self.v_pages = jax.device_put(self.v_pages, kv_sh)
            if self.quantize_kv:
                sc_sh = NamedSharding(mesh, P(None, None, "mp", None))
                self.k_scales = jax.device_put(self.k_scales, sc_sh)
                self.v_scales = jax.device_put(self.v_scales, sc_sh)
        # host-side bookkeeping (numpy; uploaded per step as small arrays).
        # the device views are REVISION-CACHED: every mutator bumps its
        # revision and the upload happens only when a view is stale — a
        # steady decode step whose lanes stay inside their pages re-serves
        # the same device page table with zero H2D traffic (round 13)
        self._page_table = np.full(
            (self.max_batch, self.pages_per_slot), -1, np.int32)
        self._seq_lens = np.zeros((self.max_batch,), np.int32)
        self._pt_rev = 0
        self._sl_rev = 0
        self._pt_dev: tuple[int, jnp.ndarray | None] = (-1, None)
        self._sl_dev: tuple[int, jnp.ndarray | None] = (-1, None)
        self._free_pages = list(range(self.num_pages - 1, -1, -1))  # pop()
        self._free_slots = list(range(self.max_batch - 1, -1, -1))
        # round 17: pages temporarily withheld from circulation (fault
        # injection's pool-pressure squeeze / reserved headroom) — out of
        # every free/available count until restored
        self._withheld: list[int] = []
        # prefix cache state: per-page slot refcounts, the content-key
        # registry, and the LRU of zero-ref registered pages (evictable,
        # still serving hits until reused)
        self.enable_prefix_cache = bool(enable_prefix_cache)
        self._refcount = np.zeros((self.num_pages,), np.int32)
        self._page_key: dict[int, bytes] = {}    # page -> chain key
        self._prefix_pages: dict[bytes, int] = {}  # chain key -> page
        self._lru: OrderedDict[int, None] = OrderedDict()
        # round 21: the HOST TIER under the HBM pool — spilled page
        # payloads keyed by chain key, LRU-ordered under a byte budget
        # (0 disables: evictions drop exactly like pre-21). Entries are
        # (ntok, planes dict of host numpy arrays, nbytes, crc32).
        self.host_tier_limit = int(host_tier_bytes or 0)
        if self.host_tier_limit < 0:
            raise ValueError(
                f"host_tier_bytes must be >= 0, got {host_tier_bytes}")
        self._host_tier: OrderedDict[
            bytes, tuple[int, dict, int, int]] = OrderedDict()
        self._host_tier_nbytes = 0
        # per-page registered token count — the spill path must know how
        # many rows of a page are REAL prefix payload (partial tails
        # spill exactly their fill, never padding rows)
        self._page_ntok: dict[int, int] = {}
        # round 15: pool telemetry — occupancy gauges + prefix/eviction/
        # CoW counters on the observability registry (the serving
        # predictor shares its registry so one snapshot covers the stack)
        from ..observability import MetricsRegistry

        self.metrics = metrics if metrics is not None else MetricsRegistry()
        if not self.metrics.enabled:
            # prefix_hit_tokens/prefix_query_tokens read through these
            # counters — a disabled registry silently zeroes them
            raise ValueError(
                "KVCacheManager requires an enabled metrics registry; "
                "the one passed is disabled")
        m = self.metrics
        self._m_pages_free = m.gauge(
            "kv_pages_free", "strictly-free pool pages")
        self._m_pages_evictable = m.gauge(
            "kv_pages_evictable", "zero-ref registered pages on the LRU")
        self._m_slots_free = m.gauge(
            "kv_slots_free", "unoccupied decode slots")
        self._m_prefix_hit = m.counter(
            "kv_prefix_hit_tokens", "admitted tokens served from the cache")
        self._m_prefix_query = m.counter(
            "kv_prefix_query_tokens", "admitted tokens queried")
        self._m_evictions = m.counter(
            "kv_prefix_evictions", "registered pages evicted off the LRU")
        self._m_cow = m.counter(
            "kv_cow_copies", "copy-on-write page copies prepared")
        self._m_trimmed = m.counter(
            "kv_pages_trimmed", "pages released by draft rollback")
        self._m_withheld = m.gauge(
            "kv_pages_withheld", "pages withheld from circulation")
        # round 21: host-tier instruments — registered unconditionally
        # (a disabled tier reads zeros) so the flat-snapshot schema is
        # identical with and without a tier
        self._m_tier_pages = m.gauge(
            "kv_tier_pages", "page payloads held in the host tier")
        self._m_tier_bytes = m.gauge(
            "kv_tier_bytes", "host-tier bytes in use")
        self._m_tier_spills = m.counter(
            "kv_tier_spills", "evicted pages spilled to the host tier")
        self._m_tier_spill_bytes = m.counter(
            "kv_tier_spill_bytes", "payload bytes written to the host "
            "tier by spills")
        self._m_tier_restores = m.counter(
            "kv_tier_restores", "host-tier pages re-admitted to the pool")
        self._m_tier_restore_bytes = m.counter(
            "kv_tier_restore_bytes", "payload bytes restored from the "
            "host tier")
        self._m_tier_lookups = m.counter(
            "kv_tier_lookups", "chain links probed against the host tier")
        self._m_tier_hits = m.counter(
            "kv_tier_hits", "host-tier probes that returned a verified "
            "payload")
        self._m_tier_evictions = m.counter(
            "kv_tier_evictions", "host-tier entries dropped by its own "
            "LRU (the HBM -> host -> drop ladder's last rung)")
        self._m_tier_spill_drops = m.counter(
            "kv_tier_spill_drops", "spills lost at the host_spill_drop "
            "seam")
        self._m_tier_corrupt = m.counter(
            "kv_tier_restore_corrupt", "host-tier payloads rejected by "
            "the restore checksum (detected, dropped, recomputed)")
        self._m_restore_scatters = m.counter(
            "kv_tier_restore_device_calls", "device scatter calls issued "
            "by batched imports (one per plane per round)")
        if self.window is not None:
            self._m_window_held = m.gauge(
                "kv_window_pages_held", "pages the window group's lanes "
                "hold (each over the group's layers)")
            self._m_window_released = m.counter(
                "kv_window_pages_released", "window-group pages released "
                "behind a lane's window")
            self._m_full_held = m.gauge(
                "kv_full_pages_held", "pages the full group's lanes hold")
        self._note_occupancy()

    def _note_occupancy(self) -> None:
        """Refresh the pool-occupancy gauges (called by every public
        mutator — page events per step are few, so three gauge sets are
        noise next to the allocation work itself)."""
        self._m_pages_free.set(len(self._free_pages))
        self._m_pages_evictable.set(len(self._lru))
        self._m_slots_free.set(len(self._free_slots))
        self._m_withheld.set(len(self._withheld))
        self._m_tier_pages.set(len(self._host_tier))
        self._m_tier_bytes.set(self._host_tier_nbytes)
        if self.window is not None:
            # (no page of such a cache is shared or registered: what is
            # not free or withheld is held by one lane)
            self._m_window_held.set(self.window.held())
            self._m_full_held.set(self.num_pages - self.available_page_count
                                  - len(self._withheld))

    # -- back-compat metric reads (pre-round-15 attribute surface) ---------

    @property
    def prefix_hit_tokens(self) -> int:
        return int(self._m_prefix_hit.value)

    @property
    def prefix_query_tokens(self) -> int:
        return int(self._m_prefix_query.value)

    # -- capacity ----------------------------------------------------------

    @property
    def free_page_count(self) -> int:
        return len(self._free_pages)

    @property
    def available_page_count(self) -> int:
        """Pages an allocation may claim: truly free + evictable (zero-ref
        registered prefix pages on the LRU)."""
        return len(self._free_pages) + len(self._lru)

    @property
    def free_slot_count(self) -> int:
        return len(self._free_slots)

    @property
    def withheld_page_count(self) -> int:
        return len(self._withheld)

    def pages_needed(self, length: int) -> int:
        return pages_needed(length, self.page_size)

    def can_admit(self, prompt_len: int) -> bool:
        return (bool(self._free_slots)
                and prompt_len <= self.max_seq_len
                and self.pages_needed(prompt_len)
                <= self.available_page_count)

    def _alloc_page(self) -> int:
        """Claim one page: the free list first, then evict the LRU tail of
        the zero-ref registered pages (unregistering it — round 21: its
        payload spills to the host tier first instead of dropping)."""
        if self._free_pages:
            return self._free_pages.pop()
        if self._lru:
            page, _ = self._lru.popitem(last=False)   # oldest
            key = self._page_key.pop(page)
            del self._prefix_pages[key]
            ntok = self._page_ntok.pop(page, 0)
            self._spill_page(key, page, ntok)
            self._m_evictions.inc()
            return page
        raise RuntimeError("cache exhausted: no free or evictable pages")

    # -- host tier (round 21) ----------------------------------------------

    def _spill_page(self, key: bytes, page: int, ntok: int) -> bool:
        """Spill one evicted page's payload to the host tier (the middle
        rung of the HBM -> host -> drop eviction ladder). Content-
        addressed: a key already resident only refreshes its recency —
        identical tokens hash to identical keys, so the stored payload
        is already the right bytes. Host pressure evicts the tier's own
        LRU head (the final drop). Returns True when the payload is
        resident after the call."""
        if not self.host_tier_limit or not ntok \
                or not self.enable_prefix_cache:
            return False
        if key in self._host_tier:
            self._host_tier.move_to_end(key)
            return True
        if fault_point("host_spill_drop"):
            # the seam models a lost spill DMA / reclaimed host buffer:
            # the eviction proceeds, the tier just never sees the bytes
            # — a cache-effectiveness loss, counted, never an error
            self._m_tier_spill_drops.inc()
            return False
        planes = {name: np.array(a) for name, a in
                  self.read_page_payload(page, int(ntok)).items()}
        nbytes = sum(a.nbytes for a in planes.values())
        if nbytes > self.host_tier_limit:
            return False
        while self._host_tier_nbytes + nbytes > self.host_tier_limit:
            self._drop_tier_entry(next(iter(self._host_tier)))
            self._m_tier_evictions.inc()
        self._host_tier[key] = (int(ntok), planes, nbytes,
                                _payload_crc(planes))
        self._host_tier_nbytes += nbytes
        self._m_tier_spills.inc()
        self._m_tier_spill_bytes.inc(nbytes)
        return True

    def _drop_tier_entry(self, key: bytes) -> None:
        _, _, nbytes, _ = self._host_tier.pop(key)
        self._host_tier_nbytes -= nbytes

    def reserve_import_room(self, npages: int) -> bool:
        """Replenish the strictly-free list to ``npages`` by evicting
        LRU-tail zero-ref pages down the normal ladder (each one spills
        to the host tier before its slot frees — content-addressed, so
        a payload already resident costs a recency touch, not a copy).
        The import landing zones themselves NEVER evict (the locked
        round-20 contract: pressure returns None); this is the explicit
        room-making step the restore round and the pull destination run
        first. ``available_page_count`` is unchanged — pages move from
        the evictable rung to the free rung — so a reservation inside a
        soft admission probe mutates nothing the scheduler accounts.
        Returns True when the room exists after the call."""
        npages = int(npages)
        while len(self._free_pages) < npages and self._lru:
            page, _ = self._lru.popitem(last=False)   # oldest
            key = self._page_key.pop(page)
            del self._prefix_pages[key]
            ntok = self._page_ntok.pop(page, 0)
            self._spill_page(key, page, ntok)
            self._m_evictions.inc()
            self._free_pages.append(page)
        self._note_occupancy()
        return len(self._free_pages) >= npages

    def _tier_lookup(self, key: bytes):
        """Probe the host tier for one chain key, verifying the stored
        checksum before handing the payload out. The
        ``tier_restore_corrupt`` seam flips a stored byte first — the
        mismatch is DETECTED, the entry dropped and counted, and the
        probe degrades to a miss (the admission recomputes; corrupt
        bytes never reach the device pool). Returns ``(ntok, planes)``
        or None."""
        self._m_tier_lookups.inc()
        ent = self._host_tier.get(key)
        if ent is None:
            return None
        ntok, planes, nbytes, crc = ent
        if fault_point("tier_restore_corrupt"):
            flat = planes[min(planes)].reshape(-1).view(np.uint8)
            flat[flat.shape[0] // 2] ^= 0xFF
        if _payload_crc(planes) != crc:
            self._drop_tier_entry(key)
            self._m_tier_corrupt.inc()
            return None
        self._host_tier.move_to_end(key)
        self._m_tier_hits.inc()
        return ntok, planes

    def _tier_restore(self, tokens) -> int:
        """Walk ``tokens``'s chain and re-admit every link the device
        registry lost but the host tier still holds, so the match/export
        walk that follows sees them as ordinary registered pages. The
        walk mirrors :meth:`_match_prefix` exactly — full pages in chain
        order, then the longest partial tail at the stop position — and
        collects the WHOLE round's tier hits before landing them through
        :meth:`import_prefix_pages` (one donated scatter per plane).
        The round makes its own room first (:meth:`reserve_import_room`:
        LRU-tail pages evict DOWN the ladder — they spill to the tier,
        so room-making loses nothing — while this chain's resident
        links are touched to the MRU end so they are never the
        victims); the landing zone itself still claims strictly-free
        pages only, and under true pressure the round lands a prefix of
        itself with the rest staying resident in the tier. Restored
        entries STAY in
        the tier (content-addressed: a later re-eviction refreshes
        recency instead of re-copying). Returns pages restored."""
        if not self.host_tier_limit or not self._host_tier:
            return 0
        ps = self.page_size
        n = len(tokens)
        entries: list[tuple[bytes, int, dict]] = []
        pos = 0
        h = b""
        while pos + ps <= n:
            nxt = self._chain_key(h, tokens[pos:pos + ps])
            if nxt in self._prefix_pages:
                # touch the resident link: the room-making below evicts
                # from the LRU tail, and this chain's own device-held
                # links must not be the victims
                page = self._prefix_pages[nxt]
                if page in self._lru:
                    self._lru.move_to_end(page)
            else:
                ent = self._tier_lookup(nxt)
                if ent is None:
                    break
                entries.append((nxt, ent[0], ent[1]))
            pos += ps
            h = nxt
        for t in range(min(ps - 1, n - pos), 0, -1):
            nxt = self._chain_key(h, tokens[pos:pos + t])
            if nxt in self._prefix_pages:
                break                      # a deeper device tail wins
            ent = self._tier_lookup(nxt)
            if ent is not None:
                entries.append((nxt, ent[0], ent[1]))
                break
        if not entries:
            return 0
        # make room down the eviction ladder (colder pages spill to the
        # tier; best-effort — under true pressure the round lands a
        # prefix of itself and the rest stays resident in the tier)
        self.reserve_import_room(len(entries))
        restored = 0
        for (key, ntok, planes), status in zip(
                entries, self.import_prefix_pages(entries)):
            if status == "imported":
                restored += 1
                self._m_tier_restores.inc()
                self._m_tier_restore_bytes.inc(
                    sum(a.nbytes for a in planes.values()))
        return restored

    @property
    def host_tier_page_count(self) -> int:
        return len(self._host_tier)

    @property
    def host_tier_bytes_used(self) -> int:
        return int(self._host_tier_nbytes)

    @property
    def host_tier_occupancy(self) -> float:
        """Host-tier byte budget in use, 0..1 (0.0 when disabled)."""
        if not self.host_tier_limit:
            return 0.0
        return self._host_tier_nbytes / self.host_tier_limit

    @property
    def tier_hit_rate(self) -> float:
        """Fraction of host-tier probes that returned a verified
        payload (0.0 before any probe)."""
        lookups = int(self._m_tier_lookups.value)
        if not lookups:
            return 0.0
        return int(self._m_tier_hits.value) / lookups

    def _release_page(self, page: int) -> None:
        """Drop one slot's reference; a zero-ref page parks on the LRU if
        registered (it keeps serving prefix hits), else frees."""
        self._refcount[page] -= 1
        assert self._refcount[page] >= 0, f"refcount underflow on {page}"
        if self._refcount[page] == 0:
            if page in self._page_key:
                self._lru[page] = None        # MRU end
            else:
                self._free_pages.append(page)

    # -- admission / growth / eviction ------------------------------------

    def admit(self, prompt_len: int) -> int:
        """Claim a slot + the pages the prompt needs; returns the slot id.
        Raises RuntimeError when out of slots/pages (the scheduler checks
        :meth:`can_admit` and queues instead)."""
        if prompt_len > self.max_seq_len:
            raise RuntimeError(
                f"prompt of {prompt_len} tokens exceeds max_seq_len "
                f"{self.max_seq_len}")
        if not self._free_slots:
            raise RuntimeError("no free decode slots")
        need = self.pages_needed(prompt_len)
        if need > self.available_page_count:
            raise RuntimeError(
                f"cache exhausted: need {need} pages, "
                f"{self.available_page_count} free")
        slot = self._free_slots.pop()
        for i in range(need):
            page = self._alloc_page()
            self._page_table[slot, i] = page
            self._refcount[page] = 1
        self._seq_lens[slot] = prompt_len
        self._pt_rev += 1
        self._sl_rev += 1
        self._note_occupancy()
        return slot

    def ensure_capacity(self, slot: int, new_len: int) -> bool:
        """Allocate pages so ``slot`` can hold ``new_len`` tokens. Returns
        False (allocating nothing) when the pool cannot satisfy it — the
        scheduler then evicts or stalls the sequence."""
        if new_len > self.max_seq_len:
            return False
        if self.window is not None:
            # both groups or neither (the window group is sized for every
            # lane's bound, so after release_window it has the pages)
            if self.window.need(slot, new_len) > len(self.window.free):
                return False
        have = int((self._page_table[slot] >= 0).sum())
        need = self.pages_needed(new_len)
        if need - have > self.available_page_count:
            return False
        if self.window is not None and self.window.grow(slot, new_len) \
                and need <= have:
            self._note_occupancy()
        if need <= have:
            return True
        for i in range(have, need):
            page = self._alloc_page()
            self._page_table[slot, i] = page
            self._refcount[page] = 1
        self._pt_rev += 1
        self._note_occupancy()
        return True

    def release_window(self, slot: int) -> int:
        """Return to the window group's free list the pages of ``slot`` that
        no row from its written length on can see (a cache without a window
        group: nothing). The scheduler calls it when it packs a step, before
        it grows the lane. Returns the pages released."""
        if self.window is None:
            return 0
        n = self.window.release_behind(slot, int(self._seq_lens[slot]))
        if n:
            self._m_window_released.inc(n)
        return n

    def advance(self, slot: int, n: int = 1) -> None:
        self._seq_lens[slot] += n
        self._sl_rev += 1

    def draft_allowance(self, slot: int, reserve: int = 0) -> int:
        """Draft tokens ``slot`` may feed this step beyond its base
        decode token using only its own pages plus strictly-FREE pages,
        AFTER reserving the base token's own growth page, (when the
        write position is shared) its CoW destination, and ``reserve``
        further pages the caller has promised elsewhere (the scheduler
        passes the plain-token page needs of every OTHER slot still
        scheduled this step). Speculation is opportunistic: a rejected
        draft must never cost a registered prefix page its spot (LRU
        eviction) or preempt a running request — this is the claim the
        scheduler re-checks in its capacity loop right before
        allocating, so slots consuming the free list in the same step
        shrink the drafts instead of pushing ANY slot's allocation into
        the eviction/preemption paths a plain step would never enter.
        (Drafts inside already-reserved pages are always free: they
        cost no extra page.)"""
        written = int(self._seq_lens[slot])
        if written >= self.max_seq_len:
            return 0     # at the ceiling: the truncation-stop owns it
        have = int((self._page_table[slot] >= 0).sum())
        base_need = max(0, self.pages_needed(written + 1) - have)
        cow_need = 1 if self.needs_cow(slot, written) else 0
        spare = max(0, len(self._free_pages) - base_need - cow_need
                    - max(0, int(reserve)))
        cap = min((have + base_need + spare) * self.page_size,
                  self.max_seq_len)
        return max(0, cap - written - 1)

    def plain_step_page_need(self, slot: int, n_tokens: int) -> int:
        """Pages ``slot`` will claim this step to write ``n_tokens``
        plain (non-draft) tokens from its current length: growth pages
        plus a CoW destination when the first write position is shared —
        the per-slot reservation the scheduler charges against other
        slots' draft allowances."""
        written = int(self._seq_lens[slot])
        if written >= self.max_seq_len:
            return 0     # at the ceiling: the truncation-stop owns it
        have = int((self._page_table[slot] >= 0).sum())
        grow = max(0, self.pages_needed(
            min(written + max(1, n_tokens), self.max_seq_len)) - have)
        return grow + (1 if self.needs_cow(slot, written) else 0)

    def withhold_pages(self, n: int) -> int:
        """Take up to ``n`` strictly-FREE pages out of circulation (they
        leave every free/available count until :meth:`restore_withheld`)
        — the fault-injection pool-pressure squeeze. SINGLE-HOLDER: there
        is one withheld set and ``restore_withheld`` returns all of it,
        so two concurrent holders (e.g. a router headroom reservation
        alongside an armed squeeze) would release each other's pages.
        Never touches referenced or prefix-LRU pages, so sequence and
        registry state are unaffected. Returns how many were actually
        withheld."""
        take = min(max(0, int(n)), len(self._free_pages))
        for _ in range(take):
            self._withheld.append(self._free_pages.pop())
        if take:
            self._note_occupancy()
        return take

    def restore_withheld(self) -> int:
        """Return every withheld page to the free list (LIFO, restoring
        the pre-withhold pop order). Returns how many came back."""
        n = len(self._withheld)
        while self._withheld:
            self._free_pages.append(self._withheld.pop())
        if n:
            self._note_occupancy()
        return n

    def trim_pages(self, slot: int) -> int:
        """Release ``slot``'s pages beyond what ``seq_len`` needs — the
        host half of speculative-draft rollback. A spec step allocates for
        ``written + 1 + k`` tokens up front; when only ``m < k`` drafts
        are accepted, ``advance(slot, 1 + m)`` moves the valid watermark
        and this returns the over-allocated tail pages to the pool, so the
        page accounting is IDENTICAL to a never-speculated run (trimmed
        pages are always fresh refcount-1 allocations: shared/registered
        prefix pages live below the watermark by construction, and a
        shared tail was CoW'd by ``prepare_write`` before any draft KV
        landed in it). Rejected-draft K/V left INSIDE kept pages sits
        above ``seq_len`` — never read (the ragged kernel masks by
        context length) and overwritten by the next step's writes.
        Returns the number of pages released."""
        keep = self.pages_needed(int(self._seq_lens[slot]))
        have = int((self._page_table[slot] >= 0).sum())
        freed = 0
        # release high indices first: alloc pops the free-list tail, so
        # reverse-order release restores the exact pre-speculation order
        # (allocation is index-contiguous, so the scan is bounded by the
        # pages actually held — not the full page-table width)
        for i in range(have - 1, keep - 1, -1):
            page = int(self._page_table[slot, i])
            if page < 0:
                continue
            self._page_table[slot, i] = -1
            self._release_page(page)
            freed += 1
        if freed:
            self._pt_rev += 1
            self._m_trimmed.inc(freed)
            self._note_occupancy()
        return freed

    def rollback(self, slot: int, new_len: int) -> int:
        """Shrink ``slot``'s valid watermark to ``new_len`` tokens and
        release the pages beyond it (round 19: the draft KV pool's
        self-heal — a rejected draft's K/V, or a whole stale tail after a
        preemption replay diverged the context, rolls back to the longest
        still-valid prefix). ``new_len`` may be 0 (slot keeps its first
        page — the admission invariant every sequence holds). Only ever
        valid on pools whose pages are refcount-1 owned (the draft pool
        never shares/registers pages); returns the pages released."""
        new_len = max(0, int(new_len))
        if new_len > int(self._seq_lens[slot]):
            raise ValueError(
                f"rollback to {new_len} tokens past slot {slot}'s "
                f"watermark {int(self._seq_lens[slot])}")
        if new_len != int(self._seq_lens[slot]):
            self._seq_lens[slot] = new_len
            self._sl_rev += 1
        return self.trim_pages(slot)

    def free(self, slot: int) -> None:
        """Evict: drop the slot's page references (shared pages survive in
        other slots / the prefix LRU), park the slot."""
        for i in range(self.pages_per_slot):
            pg = int(self._page_table[slot, i])
            if pg >= 0:
                self._release_page(pg)
            self._page_table[slot, i] = -1
        if self.window is not None:
            self.window.free_slot(slot)
        self._seq_lens[slot] = 0
        self._pt_rev += 1
        self._sl_rev += 1
        self._free_slots.append(slot)
        self._note_occupancy()

    # -- prefix cache ------------------------------------------------------

    def _chain_key(self, prev: bytes, tokens) -> bytes:
        """Content chain key — delegates to the module-level
        :func:`chain_key` so the registry and the fleet router's affinity
        map hash the SAME chain (see that function's contract)."""
        return chain_key(prev, tokens)

    def _match_prefix(self, tokens):
        """Longest registered prefix of ``tokens`` at page granularity
        (the final page may match a partial fill). The returned
        ``matched_len`` is capped at ``len(tokens)-1`` so at least one
        token is left to feed (the cache stores K/V, not logits) — on a
        full-prompt hit the re-fed token overwrites its own identical K/V
        (deterministic in token+position), CoW-guarded when the page is
        shared. Returns (pages, matched_len)."""
        ps = self.page_size
        n = len(tokens)
        pages: list[int] = []
        matched = 0
        h = b""
        while matched + ps <= n:
            nxt = self._chain_key(h, tokens[matched:matched + ps])
            page = self._prefix_pages.get(nxt)
            if page is None:
                break
            pages.append(page)
            matched += ps
            h = nxt
        # partial tail: longest registered partial fill of the next page
        for t in range(min(ps - 1, n - matched), 0, -1):
            nxt = self._chain_key(h, tokens[matched:matched + t])
            page = self._prefix_pages.get(nxt)
            if page is not None:
                pages.append(page)
                matched += t
                break
        return pages, min(matched, n - 1)

    def admit_prefix(self, tokens, *, headroom=0, soft=False):
        """Admit a sequence whose context is ``tokens``: attach every
        registered prefix page read-only (refcount += 1), allocate fresh
        pages for the rest of the context, set the slot's written length to
        the matched token count. Returns ``(slot, cached_len)`` — the
        scheduler feeds ``tokens[cached_len:]`` through prefill chunks.

        ``headroom`` demands that many extra allocatable pages beyond the
        admission's own need (the scheduler's growth watermark). On
        pressure (or no free slot), ``soft=True`` returns None with
        NOTHING mutated instead of raising — the one owner of the
        can-this-fit accounting, so the check can never diverge from the
        allocation it guards. (Round 21: the host-tier restore that runs
        first is CACHE state, not admission state — it moves strictly-
        free pages onto the evictable LRU, leaving every availability
        count and the admission decision unchanged, so a soft None after
        a restore still mutated nothing the scheduler accounts.)
        """
        n = len(tokens)
        if n > self.max_seq_len:
            raise RuntimeError(
                f"prompt of {n} tokens exceeds max_seq_len "
                f"{self.max_seq_len}")
        if not self._free_slots:
            if soft:
                return None
            raise RuntimeError("no free decode slots")
        if self.enable_prefix_cache:
            # round 21: restore-aware admission — pull the chain's
            # host-tier survivors back into the registry so the match
            # walk below pins them like never-evicted pages
            self._tier_restore(tokens)
        shared, matched = (self._match_prefix(tokens)
                           if self.enable_prefix_cache else ([], 0))
        need_total = self.pages_needed(n)
        need_fresh = need_total - len(shared)
        # matched pages sitting on the LRU are about to be re-pinned by
        # THIS admission: they cannot also serve the fresh allocations
        lru_matched = sum(1 for p in shared if p in self._lru)
        available = self.available_page_count - lru_matched
        if need_fresh + headroom > available:
            if soft:
                return None
            raise RuntimeError(
                f"cache exhausted: need {need_fresh} pages, "
                f"{available} free")
        self._m_prefix_query.inc(n)
        self._m_prefix_hit.inc(matched)
        slot = self._free_slots.pop()
        for i, page in enumerate(shared):
            self._page_table[slot, i] = page
            if self._refcount[page] == 0:
                self._lru.pop(page, None)     # re-pinned off the LRU
            self._refcount[page] += 1
        for i in range(len(shared), need_total):
            page = self._alloc_page()
            self._page_table[slot, i] = page
            self._refcount[page] = 1
        self._seq_lens[slot] = matched
        self._pt_rev += 1
        self._sl_rev += 1
        self._note_occupancy()
        return slot, matched

    def register_prefix(self, slot: int, tokens, include_tail=True) -> None:
        """Register ``slot``'s pages holding ``tokens`` (a prefilled
        prompt, or its prefilled-so-far prefix) in the prefix registry:
        every full page, plus the partial tail when ``include_tail`` (only
        pass True once the WHOLE prompt has landed — a mid-prompt partial
        key would pin the page's one key slot on a transient fill). Pages
        already registered (or whose key another page already serves) are
        skipped — one page, one key — so progressive per-step calls are
        idempotent."""
        if not self.enable_prefix_cache:
            return
        ps = self.page_size
        h = b""
        pos = 0
        i = 0
        while pos < len(tokens):
            t = min(ps, len(tokens) - pos)
            if t < ps and not include_tail:
                break
            h = self._chain_key(h, tokens[pos:pos + t])
            page = int(self._page_table[slot, i])
            if page < 0:
                break
            if page not in self._page_key and h not in self._prefix_pages:
                self._page_key[page] = h
                self._prefix_pages[h] = page
                # the spill path needs the page's REAL fill (a partial
                # tail spills t rows, never page_size)
                self._page_ntok[page] = t
            pos += t
            i += 1

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of admitted context tokens served from the prefix
        cache (0.0 when nothing was admitted)."""
        if not self.prefix_query_tokens:
            return 0.0
        return self.prefix_hit_tokens / self.prefix_query_tokens

    # -- KV-page transfer surface (round 20) -------------------------------
    #
    # The export/import half of disaggregated prefill/decode
    # (inference/kv_transfer.py): a prefill replica's registered prompt
    # pages stream to the decode replica addressed by the SAME sha1
    # chain keys, land here as zero-ref registered LRU pages, and the
    # next admission's ``admit_prefix`` walk pins them exactly like
    # locally-prefilled pages — transferred pages serve hits
    # immediately, and a failed transfer unwinds to an accounting state
    # indistinguishable from a colocated run.

    def prefix_page_records(self, tokens):
        """The chain-keyed export walk: every REGISTERED page holding a
        prefix of ``tokens`` — full pages first, then the longest
        registered partial tail — as ``(chain_key, page, ntok)``
        records in chain order. Unlike :meth:`_match_prefix` there is
        no ``n - 1`` feed cap: the exporter ships every page it has
        (the RECEIVER's admission walk re-applies the cap). Stops at
        the first unregistered link (a partially-evicted chain exports
        its surviving prefix — the rest re-prefills colocated). Round
        21: the walk is restore-aware — links the HBM registry lost but
        the host tier kept are re-admitted first, so a cross-replica
        pull reaches THROUGH this replica's host tier with no transfer-
        layer changes."""
        if self.enable_prefix_cache:
            self._tier_restore(tokens)
        ps = self.page_size
        n = len(tokens)
        recs: list[tuple[bytes, int, int]] = []
        pos = 0
        h = b""
        while pos + ps <= n:
            nxt = self._chain_key(h, tokens[pos:pos + ps])
            page = self._prefix_pages.get(nxt)
            if page is None:
                break
            recs.append((nxt, page, ps))
            pos += ps
            h = nxt
        for t in range(min(ps - 1, n - pos), 0, -1):
            nxt = self._chain_key(h, tokens[pos:pos + t])
            page = self._prefix_pages.get(nxt)
            if page is not None:
                recs.append((nxt, page, t))
                break
        return recs

    def pin_page(self, page: int) -> None:
        """Take one extra reference on ``page`` (an in-flight transfer's
        eviction guard — a registered source page must stay put while
        its frames stream). Balanced by :meth:`unpin_page`."""
        if self._refcount[page] == 0:
            self._lru.pop(page, None)
        self._refcount[page] += 1
        self._note_occupancy()

    def unpin_page(self, page: int) -> None:
        self._release_page(page)
        self._note_occupancy()

    def _planes(self) -> dict:
        """Payload plane name -> this manager's pool array."""
        if self.latent or self.window is not None:
            raise NotImplementedError(
                "page payloads (kv_transfer frames, host-tier entries, "
                "imported prefix pages) are not defined for a latent cache "
                "or for a cache with a window group yet")
        return {name: getattr(self, attr)
                for name, attr in _PLANE_ATTRS.items()
                if self.quantize_kv or name in ("k", "v")}

    def read_page_payload(self, page: int, ntok: int) -> dict:
        """One page's transferable payload: the first ``ntok`` token
        rows of every layer's K/V (+ the int8 scale planes when the
        pool is quantized) as host numpy arrays — exactly the bytes a
        decode replica needs to serve this page bit-identically."""
        # pool pages are head-major; the payload (wire + host tier
        # contract) is token-row-major [L, ntok, kv_heads(, head_dim)]
        return {name: np.ascontiguousarray(
                    np.asarray(pool[:, page, :, :ntok]).swapaxes(1, 2))
                for name, pool in self._planes().items()}

    def import_prefix_page(self, key: bytes, ntok: int, payload: dict):
        """Land one transferred page: allocate a pool page, write the
        payload rows, register ``key`` and park the page zero-ref on
        the LRU (it serves prefix hits immediately; the admission that
        consumes it pins it like any locally-prefilled page).

        Returns ``"imported"``, ``"present"`` (idempotent re-delivery:
        the key is already registered — a retransmitted frame is a
        no-op), or ``None`` when the pool has no allocatable page (the
        receiver's pressure signal — the transfer aborts and the router
        falls back to colocated prefill). Geometry/dtype mismatches are
        CONFIG errors between identically-built replicas: they raise.

        Cost note: each ``.at[].set`` below is an eager functional
        update — a full pool copy per plane per frame. It stays as the
        reference landing path (and the batched path's bit-identity
        oracle); round 21's :meth:`import_prefix_pages` is the batched
        spelling restore rounds and transfer ticks should ride."""
        if not self.enable_prefix_cache:
            raise RuntimeError(
                "import_prefix_page needs enable_prefix_cache=True "
                "(transferred pages land in the prefix registry)")
        if key in self._prefix_pages:
            return "present"
        self._validate_import(ntok, payload)
        if not self._free_pages:
            # transfers claim strictly-FREE pages only: an imported page
            # must never evict a registered page off the LRU (same
            # contract as draft allowances — opportunistic work never
            # costs a warm prefix its spot), which also keeps the
            # failed-transfer unwind exactly reversible
            return None
        page = self._free_pages.pop()
        self._refcount[page] = 0
        for name, pool in self._planes().items():
            # token-row-major payload -> the pool's head-major page
            setattr(self, _PLANE_ATTRS[name], pool.at[
                :, page, :, :ntok].set(payload[name].swapaxes(1, 2)))
        self._page_key[page] = key
        self._prefix_pages[key] = page
        self._page_ntok[page] = int(ntok)
        self._lru[page] = None                 # MRU end, zero-ref
        self._note_occupancy()
        return "imported"

    def _validate_import(self, ntok: int, payload: dict) -> None:
        """The import landing zone's geometry/dtype gate, shared by the
        per-page and batched paths. Mismatches are CONFIG errors
        between identically-built replicas: they raise."""
        if not (0 < int(ntok) <= self.page_size):
            raise ValueError(
                f"ntok must be in (0, {self.page_size}], got {ntok}")
        want = {"k", "v"} | ({"ks", "vs"} if self.quantize_kv else set())
        if set(payload) != want:
            raise ValueError(
                f"payload planes {sorted(payload)} do not match this "
                f"pool's {sorted(want)} (fp vs int8-KV replicas must be "
                "built identically)")
        shape = (self.num_layers, int(ntok), self.num_kv_heads,
                 self.head_dim)
        for name in ("k", "v"):
            a = payload[name]
            if tuple(a.shape) != shape or a.dtype != self.k_pages.dtype:
                raise ValueError(
                    f"plane '{name}' is {a.dtype}{tuple(a.shape)}, "
                    f"expected {self.k_pages.dtype}{shape}")
        if self.quantize_kv:
            for name in ("ks", "vs"):
                a = payload[name]
                if tuple(a.shape) != shape[:3] \
                        or a.dtype != self.k_scales.dtype:
                    raise ValueError(
                        f"plane '{name}' is {a.dtype}{tuple(a.shape)}, "
                        f"expected {self.k_scales.dtype}{shape[:3]}")

    def import_prefix_pages(self, entries):
        """The BATCHED landing zone (round 21): land a whole restore
        round / transfer tick of ``(key, ntok, payload)`` entries with
        ONE donated scatter per (K, V, scale) plane
        (:func:`batched_import_rows`) instead of the per-page path's
        eager full-pool copies. Registration semantics are exactly
        :meth:`import_prefix_page`'s — zero-ref LRU parking, strictly-
        free allocation, idempotent re-delivery — and the landed
        payloads are bit-identical to the per-page path (locked by
        tests/test_prefix_cache.py). Validation runs for EVERY entry
        before anything mutates. Returns a per-entry status list
        aligned with ``entries``: ``"imported"`` / ``"present"`` /
        ``None`` (pool pressure — once the free list dries mid-round,
        every later entry reads None)."""
        if not self.enable_prefix_cache:
            raise RuntimeError(
                "import_prefix_pages needs enable_prefix_cache=True "
                "(transferred pages land in the prefix registry)")
        entries = list(entries)
        for _, ntok, payload in entries:
            self._validate_import(ntok, payload)
        statuses: list = [None] * len(entries)
        landings = []       # (entry idx, key, ntok, payload, page)
        claimed: set[bytes] = set()
        for i, (key, ntok, payload) in enumerate(entries):
            if key in self._prefix_pages or key in claimed:
                statuses[i] = "present"
                continue
            if not self._free_pages:
                continue                       # stays None: pressure
            landings.append((i, key, int(ntok), payload,
                             self._free_pages.pop()))
            claimed.add(key)
        if not landings:
            return statuses
        self._scatter_landings(landings)
        for i, key, ntok, _, page in landings:
            self._refcount[page] = 0
            self._page_key[page] = key
            self._prefix_pages[key] = page
            self._page_ntok[page] = ntok
            self._lru[page] = None             # MRU end, zero-ref
            statuses[i] = "imported"
        self._note_occupancy()
        return statuses

    def _scatter_landings(self, landings) -> None:
        """Flatten one batch's (page, row) destinations and land every
        plane with a single donated device scatter. The flat row axis
        pads to a power of two (padding rows route to the ``num_pages``
        out-of-bounds sentinel and drop), so the jit traces per padded
        WIDTH, not per exact row count."""
        total = sum(ntok for _, _, ntok, _, _ in landings)
        cap = 1
        while cap < total:
            cap *= 2
        pg = np.full((cap,), self.num_pages, np.int32)
        row = np.zeros((cap,), np.int32)
        kv_shape = (self.num_layers, cap, self.num_kv_heads,
                    self.head_dim)
        vals = {"k": np.zeros(kv_shape, self.k_pages.dtype),
                "v": np.zeros(kv_shape, self.k_pages.dtype)}
        if self.quantize_kv:
            s_shape = kv_shape[:3]
            vals["ks"] = np.zeros(s_shape, self.k_scales.dtype)
            vals["vs"] = np.zeros(s_shape, self.k_scales.dtype)
        off = 0
        for _, _, ntok, payload, page in landings:
            pg[off:off + ntok] = page
            row[off:off + ntok] = np.arange(ntok, dtype=np.int32)
            for name in vals:
                vals[name][:, off:off + ntok] = payload[name]
            off += ntok
        pg = jnp.asarray(pg)
        row = jnp.asarray(row)
        for name, pool in self._planes().items():
            setattr(self, _PLANE_ATTRS[name], _batched_import_rows_jit(
                pool, jnp.asarray(vals[name]), pg, row))
            self._m_restore_scatters.inc()

    def discard_imported_prefix(self, keys) -> int:
        """Unwind a failed transfer: unregister + free every page in
        ``keys`` that is still zero-ref (a page an admission already
        pinned is serving real traffic and stays). Pass the keys in
        REVERSE import order so the free list recovers its exact
        pre-transfer pop order — after the unwind the pool accounting
        is indistinguishable from a run where the transfer never
        happened. (Round 21: deliberately NO host-tier spill here — an
        unwind must leave no trace, and a half-transferred chain in the
        tier would be exactly such a trace.) Returns the pages freed."""
        dropped = 0
        for key in keys:
            page = self._prefix_pages.get(key)
            if page is None or int(self._refcount[page]) != 0:
                continue
            del self._prefix_pages[key]
            del self._page_key[page]
            self._page_ntok.pop(page, None)
            self._lru.pop(page, None)
            self._free_pages.append(page)
            dropped += 1
        if dropped:
            self._note_occupancy()
        return dropped

    # -- copy-on-write -----------------------------------------------------

    def needs_cow(self, slot: int, pos: int) -> bool:
        """True when writing position ``pos`` would touch a page some
        OTHER reference also holds (refcount >= 2) — the write must go to
        a private copy."""
        page = int(self._page_table[slot, pos // self.page_size])
        return page >= 0 and int(self._refcount[page]) >= 2

    def prepare_write(self, slot: int, pos: int):
        """Make ``slot``'s page at ``pos`` privately writable. Returns
        ``None`` when it already is, else ``(src, dst)`` pool indices for
        the device-side copy (:func:`paged_copy_pages`) the caller must
        thread through its next step. The shared source page keeps its
        registration and remaining references; the copy is owned."""
        i = pos // self.page_size
        page = int(self._page_table[slot, i])
        if page < 0 or int(self._refcount[page]) < 2:
            return None
        dst = self._alloc_page()
        self._refcount[dst] = 1
        self._page_table[slot, i] = dst
        self._pt_rev += 1
        self._refcount[page] -= 1   # >= 1 left: stays pinned, registered
        self._m_cow.inc()
        self._note_occupancy()
        return page, dst

    # -- device views ------------------------------------------------------

    def page_table_device(self) -> jnp.ndarray:
        # upload from a PRIVATE copy: the async engine mutates the live
        # numpy bookkeeping (advance/growth) right after dispatch, while
        # the dispatched step's H2D transfer may still be in flight — an
        # aliased buffer would race the device read
        rev, dev = self._pt_dev
        if rev != self._pt_rev:
            dev = jnp.asarray(self._page_table.copy())
            self._pt_dev = (self._pt_rev, dev)
        return dev

    def seq_lens_device(self) -> jnp.ndarray:
        rev, dev = self._sl_dev
        if rev != self._sl_rev:
            dev = jnp.asarray(self._seq_lens.copy())
            self._sl_dev = (self._sl_rev, dev)
        return dev

    def seq_len(self, slot: int) -> int:
        return int(self._seq_lens[slot])

    def slot_pages(self, slot: int) -> jnp.ndarray:
        return jnp.asarray(self._page_table[slot])

    def pools(self) -> tuple:
        """The donated pools in the order the unified step takes and
        returns them: ``(k, v[, k_scales, v_scales])``, or the one latent
        pool [and its index plane]."""
        if self.index_pages is not None:
            return (self.k_pages, self.index_pages)
        if self.latent:
            return (self.k_pages,)
        if self.window is not None:
            return (self.k_pages, self.v_pages, self.window.k_pages,
                    self.window.v_pages)
        if self.quantize_kv:
            return (self.k_pages, self.v_pages, self.k_scales, self.v_scales)
        return (self.k_pages, self.v_pages)

    def update_pages(self, *pools) -> None:
        """Adopt the pools returned by a jitted serving step, in
        :meth:`pools`'s order (scale planes too on the int8-KV path; a latent
        cache's one pool [and its index plane]; the full group's pair and
        then the window group's)."""
        if self.window is not None:
            (self.k_pages, self.v_pages, self.window.k_pages,
             self.window.v_pages) = pools
            return
        k_pages, v_pages, k_scales, v_scales = (pools + (None,) * 3)[:4]
        self.k_pages = k_pages
        if self.index_pages is not None:
            self.index_pages = v_pages   # the latent pool's second plane
        elif v_pages is not None:
            self.v_pages = v_pages
        if k_scales is not None:
            self.k_scales = k_scales
        if v_scales is not None:
            self.v_scales = v_scales
