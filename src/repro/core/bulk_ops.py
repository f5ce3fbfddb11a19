"""High-level bulk access ops: the functional API models use directly.

Each op applies the paper's pipeline — reorder (sort), coalesce (dedup),
interleave (block-sequential DMA / sharded routing) — before touching memory:

  bulk_gather       C[i] = A[B[i]]          (ILD)
  bulk_scatter      A[B[i]] = C[i]          (IST; duplicate policy = last)
  bulk_rmw          A[B[i]] op= C[i]        (IRMW; op in RMW_OPS)

Tables may be 1-D (engine/scalar use) or 2-D row tables (embeddings, KV
pages, expert buffers). 2-D paths use the Pallas row-table kernels when the
caller asks (``use_kernel=True``; off by default), compiled on a TPU and
interpreted on the CPU; every other path is fused XLA. All fall back to
reference behaviour under ``optimize=False`` so every paper baseline is
runnable.

Out-of-range index policy (DESIGN.md §"OOB policy"): **loads clamp, stores
drop**. ``bulk_gather`` clamps every index into ``[0, n-1)`` — negatives to
row 0, overshoots to the last row — on every path (optimize on/off, kernel
on/off), so a gather can never fault and never wraps Python-style.
``bulk_scatter``/``bulk_rmw`` route negative and ``>= n`` destinations out
of range and drop them (``mode="drop"``), on every path. The NumPy oracle
and the Pallas kernel refs implement the same policy, so OOB streams are
parity-checked, not UB.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import reorder
from repro.core.isa import alu_apply, rmw_identity
from repro.kernels.common import tile_shape

_SEG_OPS = {
    "ADD": jax.ops.segment_sum,
    "MAX": jax.ops.segment_max,
    "MIN": jax.ops.segment_min,
    "MUL": jax.ops.segment_prod,
}

_BITWISE_OPS = ("AND", "OR", "XOR")


def _segment_bitwise(vals, seg, num_segments: int, op: str):
    """Per-bit segment reduction for AND/OR/XOR (integer dtypes only).

    AND per bit is a segment-min, OR a segment-max, XOR a parity sum; empty
    segments come out as the op identity, mirroring ``rmw_identity``.
    """
    dt = jnp.dtype(vals.dtype)
    if not jnp.issubdtype(dt, jnp.integer):
        raise ValueError(f"bitwise RMW {op} requires an integer table, "
                         f"got {dt}")
    nbits = jnp.iinfo(dt).bits
    udt = jnp.dtype(f"uint{nbits}")
    u = vals.astype(udt)
    out = jnp.zeros((num_segments,) + vals.shape[1:], udt)
    for b in range(nbits):
        bit = (u >> b) & jnp.asarray(1, udt)
        if op == "AND":
            rb = jnp.minimum(jax.ops.segment_min(
                bit, seg, num_segments=num_segments), 1)  # empty -> 1
        elif op == "OR":
            rb = jax.ops.segment_max(bit, seg, num_segments=num_segments)
        else:  # XOR: parity of set bits
            rb = jax.ops.segment_sum(
                bit.astype(jnp.uint32), seg,
                num_segments=num_segments) & 1
        out = out | (rb.astype(udt) << b)
    return out.astype(dt)


def segment_combine(vals, seg, *, num_segments: int, op: str):
    """Combine same-segment lanes with ``op`` (any RMW_OPS member): the
    reorder-safe segment reduction ``bulk_rmw`` applies at the table,
    exposed for callers that must merge duplicates *before* the table —
    the sharded engine's pre-exchange combine (one update per distinct
    destination crosses the fabric). Empty segments read the op identity
    for ADD/MUL and the dtype extremum for MIN/MAX (callers mask them)."""
    if op in _SEG_OPS:
        return _SEG_OPS[op](vals, seg, num_segments=num_segments)
    if op in _BITWISE_OPS:
        return _segment_bitwise(vals, seg, num_segments, op)
    raise ValueError(f"op {op!r} has no segment reduction (RMW_OPS only)")


# ---------------------------------------------------------------------------
# gather
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("sort", "dedup", "use_kernel",
                                   "block_rows", "lanes"))
def bulk_gather(table: jax.Array, idx: jax.Array, *, sort: bool = True,
                dedup: bool = True, use_kernel: bool = False,
                block_rows: Optional[int] = None,
                lanes: Optional[int] = None) -> jax.Array:
    """C = A[B] with reorder+coalesce. Works for (N,) or (N, D) tables.

    use_kernel: route the packed fetch of a 2-D table through the Pallas
    row-table kernel. ``block_rows``/``lanes`` default to the tile
    ``kernels.common.tile_shape`` derives from the row width and dtype.
    """
    idx = idx.astype(jnp.int32)
    # loads clamp (policy): negatives to row 0, >= n to the last row — on
    # every path, so optimize on/off cannot disagree about OOB streams
    flat_idx = jnp.clip(idx.reshape(-1), 0, table.shape[0] - 1)
    if not sort and not dedup:
        out = table[flat_idx]
        return out.reshape(idx.shape + table.shape[1:])

    if dedup:
        uniq, inv, _ = reorder.coalesce(flat_idx)
        if use_kernel and table.ndim == 2:
            from repro.kernels.gather import ops as gops
            d_rows, d_lanes = tile_shape(table.shape[1], table.dtype)
            plan = reorder.make_row_table_plan(
                uniq, n_rows=table.shape[0],
                block_rows=block_rows or d_rows, lanes=lanes or d_lanes)
            packed_tiles = gops.row_table_gather(table, plan)
            # packed_tiles: (num_tiles*lanes, D) in plan order; scatter into
            # sorted-unique order via src_pos, then expand through inverse.
            packed = jnp.zeros((uniq.shape[0],) + table.shape[1:],
                               table.dtype)
            dest = jnp.where(plan.valid, plan.src_pos,
                             uniq.shape[0]).reshape(-1)
            packed = packed.at[dest].set(packed_tiles, mode="drop",
                                         unique_indices=True)
            out = packed[inv]
        else:
            packed = table[uniq]          # sorted unique fetch ("scratchpad")
            out = packed[inv]             # cores read packed data
        return out.reshape(idx.shape + table.shape[1:])

    # sort-only path (no dedup): fetch in sorted order, unsort.
    sorted_idx, perm = reorder.sort_indices(flat_idx)
    fetched = table[sorted_idx]
    out = jnp.zeros_like(fetched).at[perm].set(fetched)
    return out.reshape(idx.shape + table.shape[1:])


# ---------------------------------------------------------------------------
# scatter (IST): duplicate destinations resolved to the *last* write in
# program order, matching sequential-loop semantics of A[B[i]] = C[i].
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("optimize",))
def bulk_scatter(table: jax.Array, idx: jax.Array, values: jax.Array, *,
                 cond: jax.Array | None = None,
                 optimize: bool = True) -> jax.Array:
    idx = idx.astype(jnp.int32).reshape(-1)
    if idx.shape[0] == 0:
        return table
    values = values.reshape((idx.shape[0],) + table.shape[1:])
    # stores drop (policy): negative and >= n destinations are routed to the
    # one-past-the-end row that mode="drop" discards (negatives would
    # otherwise wrap Python-style inside jnp scatters)
    idx = jnp.where((idx >= 0) & (idx < table.shape[0]), idx,
                    table.shape[0])
    if cond is not None:
        cond = cond.reshape(-1)
        # route masked lanes out of range; mode="drop" discards them.
        idx = jnp.where(cond, idx, table.shape[0])
    if not optimize:
        return table.at[idx].set(values, mode="drop")
    # reorder+coalesce: keep only the last write per destination. Sort by
    # (idx, position) ascending, keep the final entry of each run — every
    # surviving write has a unique destination => single-writer, no
    # serialization (the paper's exclusive-write guarantee).
    order = jnp.argsort(idx, stable=True)  # stable: program order kept in runs
    sidx = idx[order]
    last_of_run = jnp.concatenate(
        [sidx[1:] != sidx[:-1], jnp.ones((1,), bool)])
    dest = jnp.where(last_of_run, sidx, table.shape[0])  # drop non-last
    return table.at[dest].set(values[order], mode="drop",
                              unique_indices=True)


# ---------------------------------------------------------------------------
# RMW (IRMW): sort-by-destination -> combine runs -> write each row once.
# ---------------------------------------------------------------------------

def rmw_combine(ndim: int, op: str, optimize: bool) -> str:
    """How ``bulk_rmw`` combines a table of ``ndim`` dimensions:

    ``"scan"``     1-D tables: one sort carries the values, a segmented
                   scan totals each run, one unique scatter writes the
                   totals (a TPU scatters and gathers single elements one
                   by one; this keeps one of the segment path's five
                   lane-length scatters and gathers)
    ``"segment"``  2-D row tables: sort, segment-reduce, then one unique
                   scatter of rows (each lane moves a whole row, so it
                   vectorises; ``lax.sort`` carries no row payload)
    ``"scatter"``  the naive baseline (``optimize=False``): XLA's
                   duplicate-index scatter; bitwise ops have no such mode
                   and take a combine path either way
    """
    if not optimize and op not in _BITWISE_OPS:
        return "scatter"
    return "scan" if ndim == 1 else "segment"


def _segmented_scan(vals, keys, op: str):
    """Inclusive scan of ``op`` within each run of equal sorted ``keys``:
    log2(L) passes, each combining a lane with the one 2^k lanes before it
    where both hold one key (sorted, so no other run lies between them).
    (``lax.associative_scan`` over 2^25 lanes crashed XLA's TPU compiler
    on a v5e; these passes compile in seconds.)"""
    d = 1
    while d < vals.shape[0]:
        vals = jnp.concatenate([vals[:d], jnp.where(
            keys[d:] == keys[:-d], alu_apply(op, vals[:-d], vals[d:]),
            vals[d:])])
        # one pass at a time: otherwise XLA keeps a third lane-length
        # buffer live (537 against 404 MB of scratch at 2^25 lanes, v5e)
        vals, keys = jax.lax.optimization_barrier((vals, keys))
        d *= 2
    return vals


def _scan_rmw(table, idx, values, op: str):
    """``table[idx] op= values`` for a 1-D table; ``idx`` already routes
    dropped lanes past the end.

    One sort carries the values; a segmented inclusive scan leaves each
    run's total on its last lane, combining only lanes of one run as
    ``segment_sum`` does (a global prefix differenced at run boundaries
    would stop being exact once a float32 prefix passes 2^24); one unique
    scatter writes the run totals, every other lane routed past the end."""
    sidx, svals = jax.lax.sort((idx, values), num_keys=1)
    runs = _segmented_scan(svals, sidx, op)
    last = jnp.concatenate([sidx[1:] != sidx[:-1], jnp.ones((1,), bool)])
    return _write_unique(table, jnp.where(last, sidx, table.shape[0]),
                         runs, op)


def _write_unique(table, dest, packed, op: str):
    """``table[dest] op= packed`` where no two in-range ``dest`` are equal;
    out-of-range destinations drop."""
    if op in _BITWISE_OPS:
        # no bitwise scatter mode in XLA: gather-modify-set (dests unique)
        cur = table[jnp.clip(dest, 0, table.shape[0] - 1)]
        new = alu_apply(op, cur, packed)
        return table.at[dest].set(new, mode="drop", unique_indices=True)
    if op == "ADD":
        return table.at[dest].add(packed, mode="drop", unique_indices=True)
    if op == "MAX":
        return table.at[dest].max(packed, mode="drop", unique_indices=True)
    if op == "MIN":
        return table.at[dest].min(packed, mode="drop", unique_indices=True)
    if op == "MUL":
        return table.at[dest].multiply(packed, mode="drop",
                                       unique_indices=True)
    raise ValueError(op)


@partial(jax.jit, static_argnames=("op", "optimize", "use_kernel",
                                   "block_rows", "lanes"))
def bulk_rmw(table: jax.Array, idx: jax.Array, values: jax.Array, *,
             op: str = "ADD", cond: jax.Array | None = None,
             optimize: bool = True, use_kernel: bool = False,
             block_rows: Optional[int] = None,
             lanes: Optional[int] = None) -> jax.Array:
    """A[B[i]] op= C[i]; op must be associative+commutative (RMW_OPS).

    use_kernel / block_rows / lanes: as for ``bulk_gather``, for the final
    unique scatter of a 2-D table."""
    idx = idx.astype(jnp.int32).reshape(-1)
    if idx.shape[0] == 0:
        return table
    if op in _BITWISE_OPS and not jnp.issubdtype(table.dtype, jnp.integer):
        raise ValueError(f"bitwise RMW {op} requires an integer table, "
                         f"got {table.dtype}")
    values = values.reshape((idx.shape[0],) + table.shape[1:])
    ident = rmw_identity(op, table.dtype)
    # stores drop (policy): route negative/OOB destinations past the end so
    # every path below discards them (XLA would wrap negatives instead)
    idx = jnp.where((idx >= 0) & (idx < table.shape[0]), idx,
                    table.shape[0])
    if cond is not None:
        cond = cond.reshape(-1)
        cshape = (-1,) + (1,) * (values.ndim - 1)
        values = jnp.where(cond.reshape(cshape), values, ident)
    path = rmw_combine(table.ndim, op, optimize)
    if path == "scatter":
        # naive baseline: XLA scatter with duplicate indices (serialized on
        # real hardware; the paper's RMW-Atomic analogue).
        if op == "ADD":
            return table.at[idx].add(values, mode="drop")
        if op == "MAX":
            return table.at[idx].max(values, mode="drop")
        if op == "MIN":
            return table.at[idx].min(values, mode="drop")
        if op == "MUL":
            return table.at[idx].multiply(values, mode="drop")
        raise ValueError(op)
    # Bitwise ops have no XLA scatter mode, so both optimize settings take
    # a combine path below — exact either way (associative + commutative).
    if path == "scan":
        return _scan_rmw(table, idx, values, op)

    # (1) reorder: sort by destination
    sidx, perm = reorder.sort_indices(idx)
    svals = values[perm]
    # (2) coalesce: segment-reduce runs of equal destinations to one value
    seg = jnp.cumsum(jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         (sidx[1:] != sidx[:-1]).astype(jnp.int32)]))
    nseg = idx.shape[0]  # static bound
    if op in _SEG_OPS:
        packed = _SEG_OPS[op](svals, seg, num_segments=nseg)
    else:  # AND / OR / XOR via per-bit segment reductions
        packed = _segment_bitwise(svals, seg, nseg, op)
    # destination row of each segment (empty segments -> dtype-min -> routed
    # out of range and dropped by the scatter).
    seg_dest = jax.ops.segment_max(sidx, seg, num_segments=nseg)
    seg_dest = jnp.where(seg_dest < 0, table.shape[0], seg_dest)

    if use_kernel and table.ndim == 2:
        from repro.kernels.scatter_rmw import ops as sops
        return sops.row_table_rmw(table, seg_dest.astype(jnp.int32), packed,
                                  op=op, block_rows=block_rows, lanes=lanes)
    # (3) unique scatter — every destination written exactly once.
    return _write_unique(table, seg_dest, packed, op)
