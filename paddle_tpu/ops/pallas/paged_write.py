"""In-place row writes into the stacked paged KV pool — Pallas TPU kernel.

The unified serving step keeps every layer's pool in ONE donated buffer
``[num_layers, num_pages, kv_heads, page_size, head_dim]`` (scale planes
``[num_layers, num_pages, kv_heads, page_size]``) and each layer writes the
step's new K/V rows into its slice of it. XLA's scatter cannot do that in
place on the chip: it wants the rows' ``[kv_heads, head_dim]`` window
minor-most, so it gives the whole operand another layout and copies the
stack to and from it (what the compiled step showed, twice 2.7 GB a layer at
the 590M deployment). This kernel aliases the stack instead
(``input_output_aliases``): the output IS the input buffer, and only the
pages a step touches cross VMEM.

A row is narrower than a tile of the pool (bf16 packs two rows a sublane),
so the unit of work is a PAGE: each grid step reads one touched page
``[kv_heads, page_size, ...]``, replaces rows ``lo .. hi`` by the new rows
(laid out page-aligned by the caller, one block per touched page) and writes
the page back. The grid is the static bound on touched pages; steps past the
last touched page REPEAT the last one (same blocks, same result — Pallas
neither fetches nor writes back a block whose index did not change), so no
step ever rewrites a page from a stale read.

:func:`paged_write_pages` is the raw entry; ``inference/kv_cache.py``
derives its plan (:func:`~paddle_tpu.inference.kv_cache.packed_write_plan`)
from the same per-token destinations the jnp scatter uses, which stays the
reference and the non-TPU path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import autotune_cache as _atc
from .paged_attention import _interpret

# stable pallas_call name (survives into the compiled HLO and the device
# trace): how a check or a trace reduction finds the step's KV write
KV_WRITE_KERNEL_NAME = "paged_kv_write"


def _write_kernel(order_ref, page_ref, lo_ref, hi_ref, layer_ref,
                  new_ref, old_ref, out_ref):
    i = pl.program_id(0)
    row = jax.lax.broadcasted_iota(jnp.int32, old_ref.shape, 1)
    take = (row >= lo_ref[i]) & (row < hi_ref[i])
    # select in 32 bits: every pool dtype (bf16, int8, fp32 scales) widens
    # and narrows back exactly, and the mask keeps the iota's layout
    out_ref[...] = jnp.where(take, new_ref[...].astype(jnp.float32),
                             old_ref[...].astype(jnp.float32)
                             ).astype(out_ref.dtype)


def paged_write_pages(stack, new_pages, order, page, lo, hi, layer):
    """Write rows ``lo[i] .. hi[i]`` of ``new_pages[order[i]]`` over page
    ``page[i]`` of layer ``layer`` of ``stack``, for every grid step ``i``,
    in place.

    stack: ``[num_layers, num_pages, kv_heads, page_size(, head_dim)]``;
    new_pages: ``[n, kv_heads, page_size(, head_dim)]`` page-aligned new
    rows, any float or int dtype the stack's dtype holds; order, page, lo,
    hi: ``[n]`` int32, already in grid order; layer: int32 scalar. Two grid
    steps may name the same page only as exact repeats of each other (same
    ``order``, ``lo``, ``hi``), and repeats must be adjacent. Returns the
    stack (the same buffer under jit donation).
    """
    n = order.shape[0]
    block = tuple(stack.shape[2:])
    tail = (0,) * len(block)
    assert new_pages.shape == (new_pages.shape[0],) + block, (
        new_pages.shape, stack.shape)

    def new_imap(i, order_ref, *_):
        return (order_ref[i],) + tail

    def page_imap(i, order_ref, page_ref, lo_ref, hi_ref, layer_ref):
        return (layer_ref[0], page_ref[i]) + tail

    page_spec = pl.BlockSpec((None, None) + block, page_imap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n,),
        in_specs=[pl.BlockSpec((None,) + block, new_imap), page_spec],
        out_specs=page_spec,
    )
    i32 = jnp.int32
    with _atc.x64_off():
        return pl.pallas_call(
            _write_kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(stack.shape, stack.dtype),
            # operand 6 (the stack, after the five scalar-prefetch arrays
            # and the new rows) IS output 0
            input_output_aliases={6: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=_interpret(), name=KV_WRITE_KERNEL_NAME,
        )(order.astype(i32), page.astype(i32), lo.astype(i32),
          hi.astype(i32), jnp.asarray(layer, i32).reshape(1),
          new_pages, stack)
