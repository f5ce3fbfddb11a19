"""Jitted wrapper: RowTablePlan -> kernel call (+ padding management)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.reorder import RowTablePlan
from repro.kernels.common import check_tile
from repro.kernels.gather import gather as _k
from repro.kernels.gather import ref as _ref


def _pad_table(table: jax.Array, block_rows: int) -> jax.Array:
    n = table.shape[0]
    rem = (-n) % block_rows
    if rem:
        table = jnp.pad(table, ((0, rem),) + ((0, 0),) * (table.ndim - 1))
    return table


def row_table_gather(table: jax.Array, plan: RowTablePlan, *,
                     use_ref: bool = False) -> jax.Array:
    """Execute a planned gather. Returns (num_tiles*lanes, D) packed rows."""
    table = _pad_table(table, plan.block_rows)
    if use_ref:
        return _ref.row_table_gather_ref(
            table, plan.tile_block, plan.offsets,
            block_rows=plan.block_rows, lanes=plan.lanes)
    check_tile(plan.block_rows, plan.lanes, table.dtype)
    return _k.row_table_gather(
        table, plan.tile_block, plan.offsets,
        block_rows=plan.block_rows, lanes=plan.lanes)
