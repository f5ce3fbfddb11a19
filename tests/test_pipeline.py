"""Decoupled access/execute pipeline: flush windows, the RMW fast path,
DecoupledLoop drivers, and report-lifetime hygiene (thunks and shard
stats must release what they closed over)."""
import gc
import weakref

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import Scheduler
from repro.core.engine import Engine
from repro.core.scheduler import FlushHandle
from repro.pipeline import AccessWindow, DecoupledLoop, run_sequential
from repro.serve import AccessService

TILE = 256


@pytest.fixture
def rng():
    return np.random.default_rng(5)


# ---------------------------------------------------------------------------
# flush_async / FlushHandle
# ---------------------------------------------------------------------------

class TestFlushAsync:
    def test_handle_poll_and_result(self, rng):
        sched = Scheduler(engine=Engine(tile_size=TILE))
        table = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
        idx = rng.integers(0, 64, size=32).astype(np.int32)
        t = sched.submit_gather(table, idx)
        h = sched.flush_async()
        assert isinstance(h, FlushHandle)
        rep = h.result()             # blocks until retired
        assert h.poll() is True
        assert rep.n_gathers == 1
        np.testing.assert_array_equal(np.asarray(sched.result(t)),
                                      np.asarray(table)[idx])

    def test_blocking_flush_is_a_wrapper(self, rng):
        sched = Scheduler(engine=Engine(tile_size=TILE))
        t = sched.submit_gather(jnp.arange(8.0),
                                jnp.asarray([1, 2], jnp.int32))
        rep = sched.flush()          # returns the report, not a handle
        assert rep.n_gathers == 1
        np.testing.assert_array_equal(np.asarray(sched.result(t)), [1., 2.])

    def test_service_flush_async_sets_last_report(self, rng):
        svc = AccessService(tile_size=TILE, auto_flush=0)
        svc.submit_gather(jnp.arange(16.0), jnp.asarray([3], jnp.int32))
        h = svc.flush_async()
        assert svc.last_report is h.report
        h.result()

    def test_empty_flush(self):
        sched = Scheduler(engine=Engine(tile_size=TILE))
        h = sched.flush_async()
        assert h.poll() is True
        assert h.result().n_programs == 0

    def test_result_is_idempotent(self, rng):
        """Second result() hands back the materialized report without
        re-syncing (the leaves are dropped on first materialization)."""
        sched = Scheduler(engine=Engine(tile_size=TILE))
        sched.submit_gather(jnp.arange(64.0),
                            rng.integers(0, 64, size=32, dtype=np.int32))
        h = sched.flush_async()
        rep = h.result()
        assert h._leaves == () and h.done
        assert h.result() is rep                 # no leaves to block on
        assert h.poll() is True

    def test_flush_while_inflight_raises(self, rng):
        """A second flush while the previous async window is unresolved
        is a clear error — not undefined interleaving — unless the caller
        opts into overlap (inflight_ok, the decoupled pipeline's mode)."""

        class _InFlight:                         # leaf that never retires
            def is_ready(self):
                return False

            def block_until_ready(self):
                return self

        sched = Scheduler(engine=Engine(tile_size=TILE))
        t0 = sched.submit_gather(jnp.arange(8.0),
                                 jnp.asarray([1], jnp.int32))
        h = sched.flush_async()
        h._leaves += (_InFlight(),)              # pin the window in flight
        h._done = False
        sched.submit_gather(jnp.arange(8.0), jnp.asarray([2], jnp.int32))
        with pytest.raises(RuntimeError, match="still in flight"):
            sched.flush_async()
        with pytest.raises(RuntimeError, match="still in flight"):
            sched.flush()
        h2 = sched.flush_async(inflight_ok=True)   # deliberate overlap
        h2.result()
        h.result()                               # resolves the pin
        assert h.done
        sched.submit_gather(jnp.arange(8.0), jnp.asarray([3], jnp.int32))
        sched.flush()                            # resolved -> no error
        np.testing.assert_array_equal(np.asarray(sched.result(t0)), [1.0])

    def test_abandoned_handle_does_not_pin_or_block(self, rng):
        """The in-flight guard holds the last handle by weakref: a caller
        that drops an unresolved handle neither pins its window's report/
        leaves on the scheduler nor blocks future flushes."""
        sched = Scheduler(engine=Engine(tile_size=TILE))
        t = sched.submit_gather(jnp.arange(8.0),
                                jnp.asarray([1], jnp.int32))
        h = sched.flush_async()
        ref = weakref.ref(h.report)
        del h
        gc.collect()
        assert ref() is None, "scheduler pinned an abandoned flush window"
        sched.submit_gather(jnp.arange(8.0), jnp.asarray([2], jnp.int32))
        sched.flush()                            # guard lifted, no error
        np.testing.assert_array_equal(np.asarray(sched.result(t)), [1.0])

    def test_polled_to_retirement_allows_next_flush(self, rng):
        sched = Scheduler(engine=Engine(tile_size=TILE))
        sched.submit_gather(jnp.arange(8.0), jnp.asarray([1], jnp.int32))
        h = sched.flush_async()
        while not h.poll():                      # observe retirement
            pass
        sched.submit_gather(jnp.arange(8.0), jnp.asarray([2], jnp.int32))
        sched.flush()                            # no error, no result() call


# ---------------------------------------------------------------------------
# submit_rmw fast path
# ---------------------------------------------------------------------------

class TestSubmitRmw:
    def test_cross_tenant_fusion_same_op(self, rng):
        sched = Scheduler(engine=Engine(tile_size=TILE))
        table = np.zeros(32, np.int32)
        i1 = rng.integers(0, 32, size=40).astype(np.int32)
        i2 = rng.integers(0, 32, size=24).astype(np.int32)
        t1 = sched.submit_rmw(table, i1, np.ones(40, np.int32), op="ADD",
                              tenant="a")
        t2 = sched.submit_rmw(table, i2, np.ones(24, np.int32), op="ADD",
                              tenant="b")
        rep = sched.flush()
        assert rep.n_rmws == 2
        want = np.zeros(32, np.int64)
        np.add.at(want, i1, 1)
        np.add.at(want, i2, 1)
        # both tickets observe the fused end-of-window state
        for t in (t1, t2):
            np.testing.assert_array_equal(np.asarray(sched.result(t)), want)
        ((gain, per, fused),) = rep.rmw_coalescing.values()
        assert gain >= 1.0 and fused <= per

    @pytest.mark.parametrize("row_shape,scans", [((), 1), ((4,), 0)])
    def test_scan_combine_counted_for_1d_tables(self, rng, row_shape, scans):
        """A fused window of RMWs into a 1-D table takes the scan combine
        and counts it; a 2-D table's window keeps the row scatter."""
        sched = Scheduler(engine=Engine(tile_size=TILE))
        table = np.zeros((32,) + row_shape, np.float32)
        i1 = rng.integers(0, 32, size=40).astype(np.int32)
        i2 = rng.integers(0, 32, size=24).astype(np.int32)
        t1 = sched.submit_rmw(table, i1, np.ones((40,) + row_shape,
                                                 np.float32), tenant="a")
        sched.submit_rmw(table, i2, np.ones((24,) + row_shape, np.float32),
                         tenant="b")
        sched.flush()
        assert sched.stats["rmw_scan_combines"] == scans
        want = np.zeros_like(table)
        np.add.at(want, np.concatenate([i1, i2]), 1)
        np.testing.assert_array_equal(np.asarray(sched.result(t1)), want)

    def test_different_ops_chain_in_order(self):
        # mixed ops on one table is exactly the DX010 hazard; this test
        # pins the submission-order chaining the scheduler guarantees
        # when the window is allowed to run (strict=False)
        sched = Scheduler(engine=Engine(tile_size=TILE), strict=False)
        table = np.zeros(8, np.int32)
        idx = np.asarray([2, 2, 5], np.int32)
        t1 = sched.submit_rmw(table, idx, np.asarray([3, 4, 9], np.int32),
                              op="ADD")
        t2 = sched.submit_rmw(table, np.asarray([2], np.int32),
                              np.asarray([100], np.int32), op="MAX")
        report = sched.flush()
        assert any(d.code == "DX010" for d in report.diagnostics)
        want = np.zeros(8, np.int32)
        want[2], want[5] = 7, 9            # ADD first
        want[2] = max(want[2], 100)        # then MAX
        np.testing.assert_array_equal(np.asarray(sched.result(t1)), want)
        np.testing.assert_array_equal(np.asarray(sched.result(t2)), want)

    def test_cond_and_oob_lanes_drop(self):
        sched = Scheduler(engine=Engine(tile_size=TILE))
        table = np.zeros(8, np.float32)
        idx = np.asarray([1, -4, 20, 3], np.int32)
        cond = np.asarray([True, True, True, False])
        t = sched.submit_rmw(table, idx, np.ones(4, np.float32), op="ADD",
                             cond=cond)
        sched.flush()
        want = np.zeros(8, np.float32)
        want[1] = 1.0                      # -4/20 OOB-drop, lane 3 masked
        np.testing.assert_array_equal(np.asarray(sched.result(t)), want)

    def test_rejects_non_rmw_op(self):
        sched = Scheduler(engine=Engine(tile_size=TILE))
        with pytest.raises(ValueError, match="RMW_OPS"):
            sched.submit_rmw(np.zeros(4), np.zeros(2, np.int32),
                             np.zeros(2), op="SUB")

    def test_result_autoflushes_rmw_ticket(self):
        """result() on a queued-but-unflushed RMW ticket must flush, like
        program and gather tickets do."""
        sched = Scheduler(engine=Engine(tile_size=TILE))
        t = sched.submit_rmw(np.zeros(4, np.int32),
                             np.asarray([1, 1], np.int32),
                             np.ones(2, np.int32), op="ADD")
        np.testing.assert_array_equal(np.asarray(sched.result(t)),
                                      [0, 2, 0, 0])


# ---------------------------------------------------------------------------
# DecoupledLoop drivers
# ---------------------------------------------------------------------------

class TestDecoupledLoop:
    def test_dependent_run_matches_sequential(self, rng):
        """x_{k+1} = gather(x_k, perm) * 1: a pure dependence chain."""
        perm = rng.permutation(64).astype(np.int32)
        x0 = jnp.asarray(rng.integers(0, 100, size=64).astype(np.int32))

        def access(loop, k, state):
            return loop.submit_gather(state, perm)

        def compute(k, state, xg):
            return xg + 1

        svc1 = AccessService(tile_size=TILE, auto_flush=0)
        got_p = DecoupledLoop(svc1).run(x0, 5, access, compute)
        svc2 = AccessService(tile_size=TILE, auto_flush=0)
        got_s = run_sequential(svc2, x0, 5, access, compute)
        x = np.asarray(x0)
        for _ in range(5):
            x = x[perm] + 1
        np.testing.assert_array_equal(np.asarray(got_p), x)
        np.testing.assert_array_equal(np.asarray(got_s), x)
        assert DecoupledLoop(svc1).stats["windows"] == 0  # fresh loop
        assert svc1.scheduler.stats["flushes"] == 5

    def test_run_windows_order_and_depth(self, rng):
        table = jnp.asarray(rng.normal(size=(128,)).astype(np.float32))
        streams = [rng.integers(0, 128, size=16).astype(np.int32)
                   for _ in range(7)]

        def access(loop, k, item):
            return loop.submit_gather(table, item)

        def compute(k, item, res):
            return np.asarray(res)

        svc = AccessService(tile_size=TILE, auto_flush=0)
        loop = DecoupledLoop(svc, depth=3)
        outs = loop.run_windows(streams, access, compute)
        assert len(outs) == 7
        for s, o in zip(streams, outs):
            np.testing.assert_array_equal(o, np.asarray(table)[s])
        assert loop.stats["windows"] == 7
        assert loop.stats["iterations"] == 7

    def test_zero_iterations(self):
        svc = AccessService(tile_size=TILE, auto_flush=0)
        state = object()
        assert DecoupledLoop(svc).run(state, 0, None, None) is state
        assert DecoupledLoop(svc).run_windows([], None, None) == []

    def test_bad_depth_rejected(self):
        with pytest.raises(ValueError, match="depth"):
            DecoupledLoop(AccessService(auto_flush=0), depth=0)

    def test_access_window_redeem_structure(self, rng):
        sched = Scheduler(engine=Engine(tile_size=TILE))
        table = jnp.arange(32.0)
        t1 = sched.submit_gather(table, jnp.asarray([1], jnp.int32))
        t2 = sched.submit_gather(table, jnp.asarray([2, 3], jnp.int32))
        h = sched.flush_async()
        win = AccessWindow(sched, {"a": t1, "b": [t2]}, h)
        res = win.redeem()
        np.testing.assert_array_equal(np.asarray(res["a"]), [1.0])
        np.testing.assert_array_equal(np.asarray(res["b"][0]), [2.0, 3.0])
        assert win.wait() is win and win.ready


# ---------------------------------------------------------------------------
# report lifetime: thunks and stats release what they closed over
# ---------------------------------------------------------------------------

class TestReportLifetime:
    def test_group_report_drops_thunk_after_materialization(self, rng):
        from repro.core import compile_pattern
        from repro.core.compiler import Access, Load, Pattern, Var
        sched = Scheduler(engine=Engine(tile_size=TILE))
        pat = Pattern([Access("LD", "A", Load("B", Var("i")), dtype="f32")],
                      name="g")
        prog, _ = compile_pattern(pat, tile_size=TILE)
        table = rng.normal(size=(64,)).astype(np.float32)
        iota = np.arange(TILE, dtype=np.int32)
        regs = {"tile_base": 0, "N": 32, "tile_end": 32}
        for tenant in ("a", "b"):
            idx = rng.integers(0, 64, size=TILE).astype(np.int32)
            sched.submit(prog, {"A": table, "B": idx, "__iota__": iota},
                         regs, tenant=tenant)
        rep = sched.flush()
        g = rep.groups[0]
        assert g._coalescing_thunk is not None
        first = g.cross_coalescing
        assert g._coalescing_thunk is None          # released
        assert g.cross_coalescing is first          # still cached

    def test_flush_report_releases_gather_streams(self, rng):
        """The lazy coalescing thunk must not pin the window's device
        arrays once materialized."""
        sched = Scheduler(engine=Engine(tile_size=TILE))
        table = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
        sched.submit_gather(table, rng.integers(0, 64, size=32,
                                                dtype=np.int32))
        rep = sched.flush()
        streams = rep._gather_thunk.__defaults__[0]
        ref = weakref.ref(next(iter(streams.values()))[0])
        del streams
        assert ref() is not None
        assert rep.gather_coalescing               # materialize
        assert rep._gather_thunk is None
        gc.collect()
        assert ref() is None, "closed-over gather stream not released"

    def test_flush_report_releases_rmw_streams(self, rng):
        sched = Scheduler(engine=Engine(tile_size=TILE))
        idx = jnp.asarray(rng.integers(0, 16, size=8, dtype=np.int32))
        sched.submit_rmw(np.zeros(16, np.int32), idx,
                         np.ones(8, np.int32), op="ADD")
        rep = sched.flush()
        del idx        # the queued stream may alias the caller's array
        ref = weakref.ref(
            next(iter(rep._rmw_thunk.__defaults__[0].values()))[0])
        assert rep.rmw_coalescing
        gc.collect()
        assert ref() is None, "closed-over RMW stream not released"

    def test_shard_stats_release_device_arrays(self, rng):
        pytest.importorskip("jax")
        from repro.distributed import ShardedEngine
        eng = ShardedEngine(mesh=1)
        idx = rng.integers(0, 32, size=16, dtype=np.int32)
        eng.sharded_gather(jnp.arange(32.0), jnp.asarray(idx))
        st = eng.last_shard_stats
        assert st._device is not None and st._host is None
        ref = weakref.ref(st._device[0])
        assert st.sent.shape == (1, 1)             # materialize
        assert st._device is None and st._host is not None
        gc.collect()
        assert ref() is None, "ShardStats kept its device buffers"
        # post-dedup accounting: lanes count distinct requested rows
        assert int(st.received.sum()) == np.unique(idx).shape[0]
