"""Distributed bulk-access engine: exchange units, oracle parity across
mesh sizes, and the Scheduler/serve integration.

Mesh sizes above the visible device count are skipped — run the full
matrix with XLA_FLAGS=--xla_force_host_platform_device_count=8 (the CI
``sharded`` job does)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Scheduler
from repro.core.compiler import Access, Load, Pattern, Var
from repro.distributed import (ShardedEngine, as_mesh, device_mesh,
                               masked_unique_count, partition_by_owner)
from repro.distributed.exchange import pack_payload, unpack_result
from repro.serve.access_service import AccessService
from repro.testing import harness

N_DEV = len(jax.devices())
MESH_SIZES = [m for m in (1, 2, 4, 8) if m <= N_DEV]
multidev = pytest.mark.skipif(
    N_DEV < 2, reason="single-device host: set "
    "XLA_FLAGS=--xla_force_host_platform_device_count=8")


# ---------------------------------------------------------------------------
# exchange primitives (collective-free: run on any host)
# ---------------------------------------------------------------------------

class TestPartitionByOwner:
    def test_buckets_are_owner_pure_and_ordered(self):
        idx = jnp.asarray([7, 0, 12, 3, 9, 15, 1], jnp.int32)
        valid = jnp.ones((7,), bool)
        send_idx, send_valid, order, slot, sent = partition_by_owner(
            idx, valid, rows_per=4, num_shards=4)
        L = 7
        si, sv = np.asarray(send_idx), np.asarray(send_valid)
        for o in range(4):
            bucket = si[o * L:(o + 1) * L][sv[o * L:(o + 1) * L]]
            assert (bucket // 4 == o).all()
        # every valid index lands exactly once
        np.testing.assert_array_equal(np.sort(si[sv]), np.sort(np.asarray(idx)))
        np.testing.assert_array_equal(np.asarray(sent), [3, 1, 1, 2])

    def test_invalid_lanes_drop(self):
        idx = jnp.asarray([5, 99, 2, 99], jnp.int32)
        valid = jnp.asarray([True, False, True, False])
        send_idx, send_valid, _, _, sent = partition_by_owner(
            idx, valid, rows_per=8, num_shards=2)
        assert int(jnp.sum(send_valid)) == 2
        assert int(jnp.sum(sent)) == 2

    def test_payload_roundtrip(self):
        rng = np.random.default_rng(0)
        idx = jnp.asarray(rng.integers(0, 64, size=33), jnp.int32)
        valid = jnp.asarray(rng.random(33) < 0.8)
        _, send_valid, order, slot, _ = partition_by_owner(
            idx, valid, rows_per=16, num_shards=4)
        payload = jnp.asarray(rng.normal(size=33).astype(np.float32))
        bucket = pack_payload(payload, order, slot, num_shards=4)
        back = unpack_result(bucket, order, slot, valid)
        want = np.where(np.asarray(valid), np.asarray(payload), 0)
        np.testing.assert_array_equal(np.asarray(back), want)

    def test_masked_unique_count(self):
        idx = jnp.asarray([4, 4, 7, 2, 7, 9], jnp.int32)
        valid = jnp.asarray([True, True, True, True, True, False])
        assert int(masked_unique_count(idx, valid)) == 3
        assert int(masked_unique_count(idx, jnp.zeros(6, bool))) == 0


class TestMesh:
    def test_device_mesh_too_big_raises(self):
        with pytest.raises(ValueError, match="xla_force_host_platform"):
            device_mesh(N_DEV + 1)

    def test_as_mesh_accepts_int_none_mesh(self):
        m = device_mesh(1)
        assert as_mesh(m) is m
        assert as_mesh(1).shape == {"shards": 1}
        assert as_mesh(None).shape["shards"] == N_DEV
        with pytest.raises(TypeError):
            as_mesh("shards")


# ---------------------------------------------------------------------------
# oracle parity across mesh sizes (the acceptance criterion)
# ---------------------------------------------------------------------------

class TestShardedParity:
    def test_gather_rmw_parity_all_mesh_sizes(self):
        checked, ran = harness.check_sharded_parity(mesh_sizes=MESH_SIZES)
        assert ran == MESH_SIZES
        assert checked == len(harness.default_sharded_cases(0)) * len(ran)

    @pytest.mark.parametrize("seed", [1, 2])
    def test_fuzzed_streams(self, seed):
        checked, _ = harness.check_sharded_parity(
            cases=harness.default_sharded_cases(seed),
            mesh_sizes=MESH_SIZES, seed=seed)
        assert checked > 0

    def test_empty_stream_and_stats(self):
        eng = ShardedEngine(mesh=MESH_SIZES[-1])
        table = jnp.arange(32.0)
        out = eng.sharded_gather(table, jnp.zeros((0,), jnp.int32))
        assert out.shape == (0,)
        assert eng.last_shard_stats is None

    def test_shard_stats_accounting(self):
        """Stats are **post-dedup** (DESIGN.md §5): ``sent[i, j]`` counts
        distinct rows per (source slice, owner), every sent lane lands
        (the measured capacity is exact), and ``unique[j]`` — the global
        distinct rows owned by ``j`` — is placement-invariant."""
        m = MESH_SIZES[-1]
        rng = np.random.default_rng(3)
        idx = rng.integers(0, 96, size=200).astype(np.int32)
        rows_per = -(-96 // m)
        want_uniq = [np.unique(idx[idx // rows_per == o]).shape[0]
                     for o in range(m)]
        for placement in ("block", "owner"):
            eng = ShardedEngine(mesh=m)
            eng.sharded_gather(jnp.arange(96.0), jnp.asarray(idx),
                               placement=placement)
            st = eng.last_shard_stats
            assert st.placement == placement
            assert st.sent.shape == (m, m)
            # dedup-before-fabric: at most the distinct rows ship, and
            # nothing drops on the measured-capacity exchange
            assert int(st.sent.sum()) <= 200
            assert int(st.sent.sum()) == int(st.received.sum())
            assert int(st.sent.sum()) >= np.unique(idx).shape[0]
            np.testing.assert_array_equal(st.unique, want_uniq)
            assert (st.coalescing_gain >= 1).all()
            assert 0 <= st.local_fraction <= 1
            assert st.bytes_on_wire >= 0 and st.compression_ratio >= 1.0

    def test_owner_placement_raises_local_fraction(self):
        """The locality lever: on a blocked per-shard mix, owner-major
        placement keeps nearly every post-dedup lane on its owner while
        block placement scatters them."""
        m = MESH_SIZES[-1]
        if m < 2:
            pytest.skip("needs a real mesh")
        rng = np.random.default_rng(11)
        rows = 1 << 10
        idx = jnp.asarray(rng.integers(0, rows, size=2048).astype(np.int32))
        table = jnp.arange(float(rows))
        eng = ShardedEngine(mesh=m)
        out_b = eng.sharded_gather(table, idx, placement="block")
        lf_block = eng.last_shard_stats.local_fraction
        out_o = eng.sharded_gather(table, idx, placement="owner")
        lf_owner = eng.last_shard_stats.local_fraction
        np.testing.assert_array_equal(np.asarray(out_b), np.asarray(out_o))
        assert lf_owner >= 0.9 > lf_block

    @pytest.mark.parametrize("codec", ["raw", "bitmap", "delta"])
    def test_codec_paths_bit_exact(self, codec):
        """Compressed exchange is bit-exact vs raw at every mesh size,
        for gathers and RMWs, including OOB and duplicate-heavy lanes."""
        rng = np.random.default_rng(7)
        rows = 96
        idx = rng.integers(-8, rows + 8, size=300).astype(np.int32)
        vals = rng.integers(0, 32, size=300).astype(np.int32)
        table = jnp.asarray(rng.normal(size=(rows, 3)).astype(np.float32))
        itab = jnp.asarray(rng.integers(0, 99, size=rows).astype(np.int32))
        want_g = np.asarray(table)[np.clip(idx, 0, rows - 1)]
        want_r = np.asarray(itab).copy()
        ok = (idx >= 0) & (idx < rows)
        np.add.at(want_r, idx[ok], vals[ok])
        for m in MESH_SIZES:
            eng = ShardedEngine(mesh=m)
            out = eng.sharded_gather(table, jnp.asarray(idx), codec=codec)
            np.testing.assert_array_equal(np.asarray(out), want_g)
            new = eng.sharded_rmw(itab, jnp.asarray(idx),
                                  jnp.asarray(vals), op="ADD", codec=codec)
            np.testing.assert_array_equal(np.asarray(new), want_r)

    def test_split_route_exec_matches_fused(self):
        """gather_start/finish and rmw_start/finish (the emit stage's
        overlap path) produce exactly the fused single-dispatch result
        and record an overlap fraction."""
        m = MESH_SIZES[-1]
        rng = np.random.default_rng(13)
        rows = 128
        idx = jnp.asarray(rng.integers(0, rows, size=256).astype(np.int32))
        vals = jnp.asarray(rng.integers(0, 9, size=256).astype(np.int32))
        table = jnp.asarray(rng.normal(size=(rows, 2)).astype(np.float32))
        itab = jnp.asarray(rng.integers(0, 9, size=rows).astype(np.int32))
        eng = ShardedEngine(mesh=m)
        fused = eng.sharded_gather(table, idx)
        assert eng.last_shard_stats.overlap_fraction is None
        fl = eng.gather_start(table, idx)
        split = eng.gather_finish(table, fl)
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(split))
        assert eng.last_shard_stats.overlap_fraction in (0.0, 1.0)
        fused_r = eng.sharded_rmw(itab, idx, vals, op="ADD")
        fl = eng.rmw_start(itab, idx, vals, op="ADD")
        split_r = eng.rmw_finish(itab, fl)
        np.testing.assert_array_equal(np.asarray(fused_r),
                                      np.asarray(split_r))

    def test_rejects_non_rmw_op(self):
        eng = ShardedEngine(mesh=1)
        with pytest.raises(ValueError, match="RMW_OPS"):
            eng.sharded_rmw(jnp.arange(8), jnp.zeros(4, jnp.int32),
                            jnp.zeros(4), op="SUB")


# ---------------------------------------------------------------------------
# scheduler / serve integration
# ---------------------------------------------------------------------------

class TestSchedulerIntegration:
    @pytest.mark.parametrize("m", MESH_SIZES)
    def test_submit_gather_spans_mesh(self, m):
        sched = Scheduler(engine=ShardedEngine(mesh=m, tile_size=256))
        rng = np.random.default_rng(m)
        table = jnp.asarray(rng.normal(size=(128, 4)).astype(np.float32))
        streams = [rng.integers(0, 128, size=64).astype(np.int32)
                   for _ in range(5)]
        tickets = [sched.submit_gather(table, s, tenant=f"c{i}")
                   for i, s in enumerate(streams)]
        report = sched.flush()
        for t, s in zip(tickets, streams):
            np.testing.assert_array_equal(np.asarray(sched.result(t)),
                                          np.asarray(table)[s])
        # per-shard stats rolled into the flush report
        assert len(report.shard_stats) == 1
        (st,) = report.shard_stats.values()
        assert st.sent.shape == (m, m)
        assert (st.coalescing_gain >= 1).all()
        # the exchange carries the deduped fetch, not the coalesce padding:
        # lanes on the fabric == truly unique rows across all tenants
        n_uniq = np.unique(np.concatenate(streams)).shape[0]
        assert int(np.asarray(st.received).sum()) == n_uniq

    def test_single_device_engine_has_no_shard_stats(self):
        sched = Scheduler()
        t = sched.submit_gather(jnp.arange(16.0),
                                jnp.asarray([3, 3, 1], jnp.int32))
        report = sched.flush()
        np.testing.assert_array_equal(np.asarray(sched.result(t)),
                                      [3.0, 3.0, 1.0])
        assert report.shard_stats == {}

    @pytest.mark.parametrize("m", MESH_SIZES)
    def test_batched_program_groups_on_mesh(self, m):
        """Grouped program execution through the sharded engine's lane
        fan-out agrees with the per-program oracle (vmapped group of 8 =
        num_shards * local sub-batches)."""
        tile = 128
        cases = []
        rng = np.random.default_rng(0)
        for k in range(8):
            pat = Pattern([Access("LD", "A", Load("B", Var("i")),
                                  dtype="f32")], name=f"lane{k}")
            env = {"A": rng.normal(size=200).astype(np.float32),
                   "B": rng.integers(0, 200, size=256).astype(np.int32)}
            cases.append((pat, env, 100))
        sched = Scheduler(engine=ShardedEngine(mesh=m, tile_size=tile))
        checked, report = harness.check_scheduler_parity(
            cases, tile_size=tile, scheduler=sched)
        assert checked > 0
        assert any(g.vmapped for g in report.groups)


class TestAccessServiceMesh:
    def test_service_mesh_kwarg(self):
        svc = AccessService(mesh=MESH_SIZES[-1], tile_size=256,
                            auto_flush=0)
        assert isinstance(svc.scheduler.engine, ShardedEngine)
        core = svc.connect("c0")
        table = jnp.arange(64.0)
        t = core.submit_gather(table, jnp.asarray([5, 9, 5], jnp.int32))
        np.testing.assert_array_equal(np.asarray(core.wait(t)),
                                      [5.0, 9.0, 5.0])
        assert svc.last_report.shard_stats

    def test_failed_exchange_measurement_is_counted(self, monkeypatch):
        # the shard pass measures first; only that measurement fails
        measure, calls = ShardedEngine._measure_padded, []

        def fails_once(self, *a, **k):
            calls.append(1)
            if len(calls) == 1:
                raise RuntimeError("injected measurement failure")
            return measure(self, *a, **k)
        monkeypatch.setattr(ShardedEngine, "_measure_padded", fails_once)
        svc = AccessService(mesh=MESH_SIZES[-1], tile_size=256,
                            auto_flush=0)
        table = jnp.arange(64.0)
        tickets = [svc.connect(f"c{k}").submit_gather(
            table, jnp.asarray([k, 9, 5], jnp.int32)) for k in range(2)]
        svc.flush()
        for k, t in enumerate(tickets):     # the unmeasured plan is correct
            np.testing.assert_array_equal(np.asarray(svc.wait(t)),
                                          [k, 9.0, 5.0])
        assert svc.stats()["engine"]["exchange_measure_errors"] > 0

    def test_mesh_plus_scheduler_rejected(self):
        with pytest.raises(ValueError, match="not both"):
            AccessService(scheduler=Scheduler(), mesh=1)
