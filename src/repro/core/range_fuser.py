"""Range Fuser unit (paper §3.4, Fig. 5).

Flattens many short range loops — ``for i: for j in [lo[i], hi[i])`` — into
one bulk (i, j) stream so the Indirect unit sees a full tile of future
accesses. This is CSR row expansion: graph frontiers (GAP), UME zone->point
ranges, and NAS CG row loops are all this shape (Table 1).

JAX adaptation: static output capacity (the tile size) + a validity count,
implemented with cumsum + searchsorted; fully jittable and differentiable-
free (integer only). A fused stream longer than the capacity is issued as
successive tiles: ``start`` (a traced scalar, so moving it never
recompiles) is the first fused position of the tile, and the uncapped
total of the whole stream comes back, so a caller knows how many tiles
remain. Lanes past a tile's capacity are never dropped: they are the next
tile's.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnames=("capacity",))
def fuse_ranges(lo: jax.Array, hi: jax.Array, *, capacity: int,
                start=0, cond: jax.Array | None = None):
    """Fuse range loops into bulk (outer_i, inner_j) streams.

    Args:
      lo, hi: (n,) integer range boundaries per outer iteration
              (e.g. H[K[i]] and H[K[i]+1]).
      capacity: static output tile capacity; entries past the stream's end
                are invalid (zeros, masked by ``fused_valid_mask``).
      start: first fused position of this tile (0 for the first tile; a
             traced scalar).
      cond: optional (n,) bool condition tile (TC operand).

    Returns:
      (outer, inner, total): each (capacity,) int32, plus the scalar count
      of the whole fused stream, uncapped. For 0 <= p < capacity with
      start + p < total: outer[p] = i of fused iteration start + p,
      inner[p] = its j value.
    """
    start = jnp.asarray(start, jnp.int32)
    if lo.shape[0] == 0:
        # zero outer iterations (an empty BFS frontier is a legal Table-1
        # input): all-invalid output with total == 0, matching
        # reorder.coalesce's empty-stream handling. The general path below
        # would die on lo[outer] (zero-size slice).
        z = jnp.zeros((capacity,), jnp.int32)
        return z, z, jnp.zeros((), jnp.int32)
    lo = lo.astype(jnp.int32)
    hi = hi.astype(jnp.int32)
    lens = jnp.maximum(hi - lo, 0)
    if cond is not None:
        lens = jnp.where(cond, lens, 0)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(lens)]).astype(jnp.int32)  # (n+1,)
    total = offs[-1]
    p = start + jnp.arange(capacity, dtype=jnp.int32)
    # the queries are sorted: one merge-sort beats a binary search's
    # log2(n) rounds of dependent gathers
    outer = jnp.searchsorted(offs, p, side="right",
                             method="sort").astype(jnp.int32) - 1
    outer = jnp.clip(outer, 0, lo.shape[0] - 1)
    inner = lo[outer] + (p - offs[outer])
    valid = p < total
    return (jnp.where(valid, outer, 0),
            jnp.where(valid, inner, 0),
            total)


def fused_valid_mask(total: jax.Array, capacity: int,
                     start=0) -> jax.Array:
    """Valid lanes of the tile that begins at fused position ``start`` of a
    stream of ``total`` iterations."""
    return jnp.arange(capacity, dtype=jnp.int32) < total - start
