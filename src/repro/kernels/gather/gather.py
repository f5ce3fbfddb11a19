"""Row-table gather kernel (Indirect Access unit, paper §3.2) for TPU.

Mapping (DESIGN.md §2): each grid step serves one plan tile — up to ``lanes``
words from ONE table block. The scalar-prefetched ``tile_block`` array *is*
the Row Table: it drives ``BlockSpec.index_map`` so Mosaic issues one
HBM->VMEM DMA per opened block ("row activate"), and — because Pallas keeps a
block resident while consecutive grid steps map to the same index — all
subsequent tiles of that block are served from VMEM ("row-buffer hits").
Word offsets (the Word Table) index within the open block; each tile's
offsets are a ``(None, 1, lanes)`` block in scalar memory (a ``(1, lanes)``
VMEM block would break the (8, 128) tiling rule).

VMEM per step: a ``(block_rows, D)`` table block and a ``(lanes, D)`` output
block, both double-buffered; ``kernels.common.tile_shape`` sizes them.
16-bit tables read the aligned 16-row group around each row and select the
row in float32; their output block is float32 and is cast back outside
the kernel (exact: every value came from the table).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common


def _gather_kernel(tile_block_ref, offs_ref, table_ref, out_ref, *,
                   lanes: int, group: int):
    """One grid step: serve `lanes` rows from the open block."""
    def body(l, carry):
        off = offs_ref[0, l]
        if group == 1:
            out_ref[pl.ds(l, 1), :] = table_ref[pl.ds(off, 1), :]
        else:
            base = pl.multiple_of((off // group) * group, group)
            rows = table_ref[pl.ds(base, group), :].astype(jnp.float32)
            pick = jax.lax.broadcasted_iota(
                jnp.int32, rows.shape, 0) == off - base
            out_ref[pl.ds(l, 1), :] = jnp.max(
                jnp.where(pick, rows, -jnp.inf), axis=0, keepdims=True)
        return carry
    jax.lax.fori_loop(0, lanes, body, 0)


@functools.partial(jax.jit, static_argnames=("block_rows", "lanes"))
def row_table_gather(table: jax.Array, tile_block: jax.Array,
                     offsets: jax.Array, *, block_rows: int,
                     lanes: int) -> jax.Array:
    """Gather planned by a row table.

    Args:
      table:      (N, D) — N % block_rows == 0 after padding by the wrapper.
      tile_block: (num_tiles,) int32 block id per plan tile (scalar prefetch).
      offsets:    (num_tiles, lanes) int32 word offsets within the block.
    Returns:
      (num_tiles * lanes, D) packed rows in plan order.
    """
    num_tiles = tile_block.shape[0]
    n, d = table.shape
    assert n % block_rows == 0, (n, block_rows)
    assert offsets.shape == (num_tiles, lanes)
    group = common.row_group(table.dtype)
    out_dtype = table.dtype if group == 1 else jnp.float32

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((None, 1, lanes), lambda i, blk: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, d), lambda i, blk: (blk[i], 0)),
        ],
        out_specs=pl.BlockSpec((lanes, d), lambda i, blk: (i, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_gather_kernel, lanes=lanes, group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_tiles * lanes, d), out_dtype),
        interpret=common.interpret(),
        name="row_table_gather",
    )(tile_block.astype(jnp.int32),
      offsets.astype(jnp.int32).reshape(num_tiles, 1, lanes), table)
    return out.astype(table.dtype)
