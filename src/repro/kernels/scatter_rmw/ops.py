"""Jitted wrapper: coalesced (sorted-unique) RMW -> row-table kernel."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.isa import rmw_identity
from repro.core.reorder import make_row_table_plan
from repro.kernels.common import check_tile, tile_shape
from repro.kernels.scatter_rmw import ref as _ref
from repro.kernels.scatter_rmw import scatter_rmw as _k


@partial(jax.jit, static_argnames=("op", "block_rows", "lanes", "use_ref"))
def row_table_rmw(table: jax.Array, dest: jax.Array, vals: jax.Array, *,
                  op: str = "ADD", block_rows: Optional[int] = None,
                  lanes: Optional[int] = None,
                  use_ref: bool = False) -> jax.Array:
    """table[dest[u]] op= vals[u] for unique, *sorted* dest.

    Stores drop (the repo-wide OOB policy): entries with dest outside
    ``[0, n)`` — scatter padding, empty-segment markers, negative or
    overshooting destinations — are neutralised with the RMW identity.
    ``block_rows``/``lanes`` default to ``kernels.common.tile_shape``.
    Returns the updated table.
    """
    n = table.shape[0]
    d_rows, d_lanes = tile_shape(table.shape[1], table.dtype)
    block_rows = block_rows or d_rows
    lanes = lanes or d_lanes
    ident = rmw_identity(op, table.dtype)
    ok = (dest >= 0) & (dest < n)
    vals = jnp.where(ok.reshape((-1,) + (1,) * (vals.ndim - 1)), vals, ident)
    # neutralised lanes keep the stream sorted: negatives (stream head)
    # clamp to row 0, pads/overshoots (stream tail) to the last row
    dest_c = jnp.where(dest < 0, 0, jnp.where(dest < n, dest, n - 1))

    n_pad = -(-n // block_rows) * block_rows
    padded = jnp.pad(table, ((0, n_pad - n),) + ((0, 0),) * (table.ndim - 1))
    plan = make_row_table_plan(dest_c, n_rows=n_pad, block_rows=block_rows,
                               lanes=lanes)
    # vals in plan order; invalid lanes -> identity
    v_planned = vals[plan.src_pos.reshape(-1)]
    v_planned = jnp.where(
        plan.valid.reshape((-1,) + (1,) * (vals.ndim - 1)), v_planned, ident)
    if use_ref:
        fn = _ref.row_table_rmw_ref
    else:
        check_tile(block_rows, lanes, table.dtype)
        fn = _k.row_table_rmw
    out = fn(padded, plan.tile_block, plan.tile_first.astype(jnp.int32),
             plan.offsets, v_planned, block_rows=block_rows, lanes=lanes,
             op=op)
    return out[:n]
