"""Row-table scatter-RMW kernel (Indirect Access unit, store/RMW path).

Dual of the gather kernel: destinations are pre-sorted & pre-reduced (the
engine's coalesce stage leaves at most one update per row), so each table
block ("DRAM row") is opened once, receives all its updates in VMEM, and is
written back once — the paper's exclusive-writer bulk-store pipeline.

The output aliases the table (in-place semantics at the XLA level): blocks
never touched by the plan pass through untouched; a touched block stays
resident in VMEM across the consecutive grid steps that map to it (Pallas
revisiting), is initialised from the table on its first visit (`tile_first`)
and accumulated into by later visits.

Padded lanes carry the RMW identity (op-neutral), so no masking is needed
in-kernel. 16-bit tables update the aligned 16-row group around each row
in float32 (their updates arrive as float32) and store the group back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.isa import alu_apply
from repro.kernels import common


def _rmw_kernel(tile_block_ref, tile_first_ref, offs_ref, table_ref,
                vals_ref, out_ref, *, lanes: int, op: str, group: int):
    i = pl.program_id(0)

    @pl.when(tile_first_ref[i] != 0)
    def _init():  # open the row: load current contents
        out_ref[...] = table_ref[...]

    def body(l, carry):
        off = offs_ref[0, l]
        upd = vals_ref[pl.ds(l, 1), :]
        if group == 1:
            cur = out_ref[pl.ds(off, 1), :]
            out_ref[pl.ds(off, 1), :] = alu_apply(op, cur, upd)
        else:
            base = pl.multiple_of((off // group) * group, group)
            rows = out_ref[pl.ds(base, group), :]
            cur = rows.astype(jnp.float32)
            pick = jax.lax.broadcasted_iota(
                jnp.int32, cur.shape, 0) == off - base
            out_ref[pl.ds(base, group), :] = jnp.where(
                pick, alu_apply(op, cur, upd), cur).astype(rows.dtype)
        return carry
    jax.lax.fori_loop(0, lanes, body, 0)


@functools.partial(jax.jit, static_argnames=("block_rows", "lanes", "op"))
def row_table_rmw(table: jax.Array, tile_block: jax.Array,
                  tile_first: jax.Array, offsets: jax.Array,
                  vals: jax.Array, *, block_rows: int, lanes: int,
                  op: str = "ADD") -> jax.Array:
    """Apply planned RMW updates block-by-block.

    Args:
      table:      (N, D), N % block_rows == 0.
      tile_block: (num_tiles,) int32 — scalar prefetch row table.
      tile_first: (num_tiles,) int32 — 1 where a tile opens its block.
      offsets:    (num_tiles, lanes) int32 within-block destinations
                  (unique within each block's run).
      vals:       (num_tiles * lanes, D) update rows in plan order; padded
                  lanes must hold the RMW identity.
    Returns:
      (N, D) updated table.
    """
    num_tiles = tile_block.shape[0]
    n, d = table.shape
    assert n % block_rows == 0
    group = common.row_group(table.dtype)
    if group != 1:
        vals = vals.astype(jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((None, 1, lanes), lambda i, blk, first: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, d), lambda i, blk, first: (blk[i], 0)),
            pl.BlockSpec((lanes, d), lambda i, blk, first: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d),
                               lambda i, blk, first: (blk[i], 0)),
    )
    return pl.pallas_call(
        functools.partial(_rmw_kernel, lanes=lanes, op=op, group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, d), table.dtype),
        input_output_aliases={3: 0},  # table (arg index incl. 2 scalars) -> out
        interpret=common.interpret(),
        name="row_table_rmw",
    )(tile_block.astype(jnp.int32), tile_first.astype(jnp.int32),
      offsets.astype(jnp.int32).reshape(num_tiles, 1, lanes), table, vals)
