"""Edge cases for reorder.coalesce / fuse_ranges / make_row_table_plan:
empty streams, all-duplicates, partial last blocks, n_unique when the max
value is itself duplicated, static-size truncation overflow, and the
empty-frontier range loop."""
import jax
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (bulk_gather, bulk_rmw, bulk_scatter, coalesce,
                        fuse_ranges, make_row_table_plan)
from repro.core.isa import RMW_OPS
from repro.kernels.gather import ops as gops


class TestCoalesceEdges:
    def test_empty_stream(self):
        uniq, inv, n_u = coalesce(jnp.zeros((0,), jnp.int32))
        assert uniq.shape == (0,)
        assert inv.shape == (0,)
        assert int(n_u) == 0

    def test_empty_stream_padded(self):
        uniq, inv, n_u = coalesce(jnp.zeros((0,), jnp.int32), size=4)
        assert uniq.shape == (4,)
        assert int(n_u) == 0

    def test_all_duplicates(self):
        idx = jnp.full((16,), 7, jnp.int32)
        uniq, inv, n_u = coalesce(idx)
        assert int(n_u) == 1
        np.testing.assert_array_equal(np.asarray(uniq), [7] * 16)
        np.testing.assert_array_equal(np.asarray(uniq)[np.asarray(inv)],
                                      np.asarray(idx))

    def test_n_unique_with_duplicated_max(self):
        # the pad uses the max value; a duplicated max must not inflate n_u
        idx = jnp.asarray([5, 3, 5, 5, 1], jnp.int32)
        uniq, inv, n_u = coalesce(idx)
        assert int(n_u) == 3
        u = np.asarray(uniq)
        assert (np.diff(u) >= 0).all()
        np.testing.assert_array_equal(u[np.asarray(inv)], np.asarray(idx))

    def test_single_element(self):
        uniq, inv, n_u = coalesce(jnp.asarray([9], jnp.int32))
        assert int(n_u) == 1
        np.testing.assert_array_equal(np.asarray(uniq), [9])


class TestCoalesceTruncation:
    """size < n_unique used to silently truncate: jnp.unique(..., size=k)
    keeps inverse positions into the *untruncated* unique array, so
    entries >= k indexed past the result and JAX's clamping gather
    misread the last row with no error."""

    def test_overflow_raises_eagerly(self):
        idx = jnp.asarray([0, 1, 2, 3, 4], jnp.int32)   # 5 unique
        with pytest.raises(ValueError, match="do not fit"):
            coalesce(idx, size=3)

    def test_overflow_clamps_under_trace(self):
        # inside jit we cannot raise on data: inverse must stay in range
        idx = jnp.asarray([10, 20, 30, 40, 50], jnp.int32)
        uniq, inv, n_u = jax.jit(lambda x: coalesce(x, size=3))(idx)
        assert uniq.shape == (3,)
        assert int(jnp.max(inv)) <= 2 and int(jnp.min(inv)) >= 0
        assert int(n_u) <= 3

    def test_exact_fit_still_works(self):
        idx = jnp.asarray([7, 7, 7, 2, 2, 7], jnp.int32)  # 2 unique, size 2
        uniq, inv, n_u = coalesce(idx, size=2)
        assert int(n_u) == 2
        np.testing.assert_array_equal(
            np.asarray(uniq)[np.asarray(inv)], np.asarray(idx))

    def test_pad_value_invariants_size_gt_n(self):
        # padding must use the max value (keeps the array sorted for the
        # row-table plan) and must not inflate n_unique
        idx = jnp.asarray([5, 3, 5, 1], jnp.int32)
        uniq, inv, n_u = coalesce(idx, size=9)
        u = np.asarray(uniq)
        assert u.shape == (9,)
        assert int(n_u) == 3
        assert (np.diff(u) >= 0).all()
        np.testing.assert_array_equal(u[3:], [5] * 6)   # max-value padding
        np.testing.assert_array_equal(u[np.asarray(inv)], np.asarray(idx))


class TestFuseRangesEmpty:
    def test_empty_frontier(self):
        # zero outer iterations (drained BFS frontier) used to raise
        # TypeError ("Slice size ... out of range") from lo[outer]
        e = jnp.zeros((0,), jnp.int32)
        outer, inner, total = fuse_ranges(e, e, capacity=16)
        assert outer.shape == inner.shape == (16,)
        assert int(total) == 0
        np.testing.assert_array_equal(np.asarray(outer), 0)
        np.testing.assert_array_equal(np.asarray(inner), 0)

    def test_empty_frontier_with_cond(self):
        e = jnp.zeros((0,), jnp.int32)
        _, _, total = fuse_ranges(e, e, capacity=4,
                                  cond=jnp.zeros((0,), bool))
        assert int(total) == 0

    def test_all_zero_length_ranges_nonempty_frontier(self):
        # the neighbouring case: n > 0 outer iterations, every range empty
        lo = jnp.asarray([3, 5, 9], jnp.int32)
        outer, inner, total = fuse_ranges(lo, lo, capacity=8)
        assert int(total) == 0
        np.testing.assert_array_equal(np.asarray(outer), 0)


class TestEmptyBulkOps:
    def test_empty_scatter_is_identity(self):
        t = jnp.arange(4.0)
        e = jnp.zeros((0,), jnp.int32)
        for optimize in (True, False):
            out = bulk_scatter(t, e, jnp.zeros((0,), jnp.float32),
                               optimize=optimize)
            np.testing.assert_array_equal(np.asarray(out), np.arange(4.0))

    def test_empty_rmw_is_identity_all_ops(self):
        t = jnp.arange(8, dtype=jnp.int32)
        e = jnp.zeros((0,), jnp.int32)
        for op in RMW_OPS:
            for optimize in (True, False):
                out = bulk_rmw(t, e, e, op=op, optimize=optimize)
                np.testing.assert_array_equal(np.asarray(out),
                                              np.arange(8)), (op, optimize)


class TestRowTablePlanEdges:
    def test_empty_stream_plan(self):
        plan = make_row_table_plan(jnp.zeros((0,), jnp.int32), n_rows=128,
                                   block_rows=32, lanes=8)
        assert plan.num_tiles == 0
        assert int(plan.n_tiles) == 0

    def test_empty_stream_gather(self):
        table = jnp.arange(64, dtype=jnp.float32).reshape(16, 4)
        out = bulk_gather(table, jnp.zeros((0,), jnp.int32),
                          use_kernel=False)
        assert out.shape == (0, 4)

    def test_all_duplicates_single_tile(self):
        idx = jnp.full((10,), 3, jnp.int32)
        plan = make_row_table_plan(idx, n_rows=64, block_rows=16, lanes=16)
        assert int(plan.n_tiles) == 1
        assert int(plan.tile_block[0]) == 0
        offs = np.asarray(plan.offsets)[0][np.asarray(plan.valid)[0]]
        np.testing.assert_array_equal(offs, [3] * 10)

    def test_last_partial_block(self):
        # n_rows=70, block_rows=32 -> last block holds rows [64, 70)
        idx = jnp.asarray([64, 65, 69, 69], jnp.int32)
        plan = make_row_table_plan(idx, n_rows=70, block_rows=32, lanes=4)
        assert int(plan.n_tiles) == 1
        assert int(plan.tile_block[0]) == 2
        offs = np.asarray(plan.offsets)[0][np.asarray(plan.valid)[0]]
        np.testing.assert_array_equal(offs, [0, 1, 5, 5])

    def test_partial_block_kernel_gather_matches(self):
        rng = np.random.default_rng(0)
        table = rng.normal(size=(70, 4)).astype(np.float32)
        idx = np.sort(rng.integers(60, 70, size=12)).astype(np.int32)
        plan = make_row_table_plan(jnp.asarray(idx), n_rows=70,
                                   block_rows=32, lanes=4)
        packed = gops.row_table_gather(jnp.asarray(table), plan)
        got = np.asarray(packed)[np.asarray(plan.valid).reshape(-1)]
        np.testing.assert_allclose(got, table[idx], rtol=1e-6)

    def test_plan_serves_every_position(self):
        rng = np.random.default_rng(1)
        idx = np.sort(rng.integers(0, 100, size=57)).astype(np.int32)
        plan = make_row_table_plan(jnp.asarray(idx), n_rows=100,
                                   block_rows=16, lanes=8)
        src = np.asarray(plan.src_pos)[np.asarray(plan.valid)]
        np.testing.assert_array_equal(np.sort(src), np.arange(57))
