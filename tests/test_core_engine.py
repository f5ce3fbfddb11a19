"""Unit tests: ISA semantics, engine execution, Table-1 workload patterns."""
import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (Access, BinOp, Compare, Engine, LegalityError, Load,
                        Pattern, RangeLoop, Var, bulk_gather, bulk_rmw,
                        bulk_scatter, compile_pattern, fuse_ranges, isa,
                        run_tiled, structural_signature)
from repro.core.range_fuser import fused_valid_mask


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(42)


# ---------------------------------------------------------------------------
# bulk ops vs numpy loop semantics
# ---------------------------------------------------------------------------

class TestBulkOps:
    def test_gather_matches_loop(self, rng):
        A = rng.normal(size=(513,)).astype(np.float32)
        B = rng.integers(0, 513, size=(257,)).astype(np.int32)
        out = bulk_gather(jnp.asarray(A), jnp.asarray(B))
        np.testing.assert_allclose(np.asarray(out), A[B])

    def test_gather_2d_dedup_off(self, rng):
        A = rng.normal(size=(64, 16)).astype(np.float32)
        B = rng.integers(0, 64, size=(40,)).astype(np.int32)
        out = bulk_gather(jnp.asarray(A), jnp.asarray(B), dedup=False)
        np.testing.assert_allclose(np.asarray(out), A[B])

    def test_scatter_last_write_wins(self):
        table = jnp.zeros((8,), jnp.float32)
        idx = jnp.asarray([1, 1, 2, 1], jnp.int32)
        vals = jnp.asarray([10., 20., 30., 40.], jnp.float32)
        out = bulk_scatter(table, idx, vals)
        ref = np.zeros(8, np.float32)
        for i, v in [(1, 10.), (1, 20.), (2, 30.), (1, 40.)]:
            ref[i] = v
        np.testing.assert_allclose(np.asarray(out), ref)

    def test_scatter_conditional(self):
        table = jnp.zeros((8,), jnp.float32)
        idx = jnp.asarray([1, 2, 3], jnp.int32)
        vals = jnp.asarray([1., 2., 3.], jnp.float32)
        cond = jnp.asarray([True, False, True])
        out = bulk_scatter(table, idx, vals, cond=cond)
        np.testing.assert_allclose(np.asarray(out),
                                   [0, 1, 0, 3, 0, 0, 0, 0])

    @pytest.mark.parametrize("op", ["ADD", "MAX", "MIN", "MUL"])
    def test_rmw_matches_naive(self, rng, op):
        A = rng.normal(size=(100,)).astype(np.float32)
        B = rng.integers(0, 100, size=(500,)).astype(np.int32)
        C = rng.normal(size=(500,)).astype(np.float32)
        opt = bulk_rmw(jnp.asarray(A), jnp.asarray(B), jnp.asarray(C), op=op)
        naive = bulk_rmw(jnp.asarray(A), jnp.asarray(B), jnp.asarray(C),
                         op=op, optimize=False)
        np.testing.assert_allclose(np.asarray(opt), np.asarray(naive),
                                   rtol=1e-5, atol=1e-5)

    def test_rmw_conditional(self):
        A = jnp.zeros((4,), jnp.float32)
        out = bulk_rmw(A, jnp.asarray([0, 1, 0]),
                       jnp.asarray([1., 2., 4.]),
                       cond=jnp.asarray([True, True, False]))
        np.testing.assert_allclose(np.asarray(out), [1., 2., 0., 0.])

    def test_rmw_rejects_non_commutative(self):
        with pytest.raises(ValueError):
            isa.IRMW("f32", "A", "SUB", "t0", "t1")


# ---------------------------------------------------------------------------
# range fuser (paper Fig. 5)
# ---------------------------------------------------------------------------

class TestRangeFuser:
    def test_fig5_semantics(self):
        lo = jnp.asarray([2, 0, 7], jnp.int32)
        hi = jnp.asarray([5, 0, 9], jnp.int32)
        outer, inner, total = fuse_ranges(lo, hi, capacity=8)
        assert int(total) == 5
        np.testing.assert_array_equal(np.asarray(outer)[:5], [0, 0, 0, 2, 2])
        np.testing.assert_array_equal(np.asarray(inner)[:5], [2, 3, 4, 7, 8])

    def test_condition_tile(self):
        lo = jnp.asarray([0, 0], jnp.int32)
        hi = jnp.asarray([3, 3], jnp.int32)
        _, _, total = fuse_ranges(lo, hi, capacity=8,
                                  cond=jnp.asarray([True, False]))
        assert int(total) == 3

    def test_capacity_clamp(self):
        # the tile holds 16 lanes; the total is the whole stream's, so the
        # 384 lanes past the capacity are left for later tiles, not lost
        lo = jnp.zeros((4,), jnp.int32)
        hi = jnp.full((4,), 100, jnp.int32)
        _, _, total = fuse_ranges(lo, hi, capacity=16)
        assert int(total) == 400
        assert int(fused_valid_mask(total, 16).sum()) == 16


# ---------------------------------------------------------------------------
# compiled Table-1 patterns vs python loop references
# ---------------------------------------------------------------------------

def _loop_gather(A, B):
    out = np.zeros(len(B), A.dtype)
    for i in range(len(B)):
        out[i] = A[B[i]]
    return out


class TestCompiledPatterns:
    def test_simple_gather_fig7(self, rng):
        """for i: v = A[B[i]] — the running example of Fig. 7."""
        N = 3000
        A = rng.normal(size=(4096,)).astype(np.float32)
        B = rng.integers(0, 4096, size=(N,)).astype(np.int32)
        pat = Pattern([Access("LD", "A", Load("B", Var("i")), dtype="f32")],
                      name="gather")
        eng = Engine(tile_size=1024)
        env, spd, info = run_tiled(eng, pat,
                                   {"A": jnp.asarray(A), "B": jnp.asarray(B)},
                                   n=N)
        # last tile result: positions [2048, 3000)
        tile = np.asarray(spd[info["loads"]["A"]])
        np.testing.assert_allclose(tile[:N - 2048], _loop_gather(A, B)[2048:])

    def test_hash_join_pattern(self, rng):
        """PRH: A[B[(C[i] & F) >> G]] = payload (Table 1, Hash-Join)."""
        n = 512
        C = rng.integers(0, 2**16, size=(n,)).astype(np.int32)
        Bk = rng.permutation(256).astype(np.int32)
        A = np.zeros(256, np.float32)
        payload = rng.normal(size=(n,)).astype(np.float32)
        F, G = 0xFF0, 4
        pat = Pattern([Access(
            "ST", "A",
            Load("B", BinOp("SHR", BinOp("AND", Load("C", Var("i")), F), G)),
            value=Load("P", Var("i")), dtype="f32")], name="hashjoin")
        eng = Engine(tile_size=n)
        env, _, _ = run_tiled(
            eng, pat,
            {"A": jnp.asarray(A), "B": jnp.asarray(Bk),
             "C": jnp.asarray(C), "P": jnp.asarray(payload)}, n=n)
        ref = A.copy()
        for i in range(n):
            ref[Bk[(C[i] & F) >> G]] = payload[i]
        np.testing.assert_allclose(np.asarray(env["A"]), ref)

    def test_conditional_rmw_ume(self, rng):
        """UME GZ: if (D[i] >= F): A[B[i]] += V[i] (Table 1)."""
        n = 1000
        A = np.zeros(128, np.float32)
        B = rng.integers(0, 128, size=(n,)).astype(np.int32)
        D = rng.normal(size=(n,)).astype(np.float32)
        V = rng.normal(size=(n,)).astype(np.float32)
        pat = Pattern([Access(
            "RMW", "A", Load("B", Var("i")), value=Load("V", Var("i")),
            op="ADD", dtype="f32",
            cond=Compare("GE", Load("D", Var("i")), 0.0))], name="ume_gz")
        eng = Engine(tile_size=256)
        env, _, _ = run_tiled(
            eng, pat, {"A": jnp.asarray(A), "B": jnp.asarray(B),
                       "D": jnp.asarray(D), "V": jnp.asarray(V)}, n=n)
        ref = A.copy()
        for i in range(n):
            if D[i] >= 0:
                ref[B[i]] += V[i]
        np.testing.assert_allclose(np.asarray(env["A"]), ref, rtol=1e-5,
                                   atol=1e-5)

    def test_csr_range_loop_cg(self, rng):
        """NAS CG: for i: for j in [H[i], H[i+1]): out += A[B[j]] * X[j].

        We check the fused (i, j) stream + gather path: LD A[B[j]].
        """
        rows, nnz = 64, 1024
        H = np.zeros(rows + 1, np.int32)
        H[1:] = np.cumsum(rng.multinomial(nnz, [1 / rows] * rows))
        B = rng.integers(0, 512, size=(nnz,)).astype(np.int32)
        A = rng.normal(size=(512,)).astype(np.float32)
        pat = Pattern(
            [Access("LD", "A", Load("B", Var("j")), dtype="f32")],
            range_loop=RangeLoop("j", Load("H", Var("i")),
                                 Load("H", BinOp("ADD", Var("i"), 1))),
            name="cg")
        eng = Engine(tile_size=2048)
        env, spd, info = run_tiled(
            eng, pat, {"A": jnp.asarray(A), "B": jnp.asarray(B),
                       "H": jnp.asarray(H)}, n=rows)
        got = np.asarray(spd[info["loads"]["A"]])[:nnz]
        np.testing.assert_allclose(got, A[B])

    def test_legality_gauss_seidel_rejected(self):
        """§4.2: loads and stores aliasing the same region must be rejected."""
        pat = Pattern([
            Access("LD", "X", Load("B", Var("i")), dtype="f32"),
            Access("ST", "X", Load("C", Var("i")),
                   value=Load("V", Var("i")), dtype="f32"),
        ], name="gauss_seidel")
        with pytest.raises(LegalityError):
            compile_pattern(pat)

    def test_program_level_legality(self):
        with pytest.raises(ValueError):
            isa.AccessProgram((
                isa.IST("f32", "A", "t_idx", "t_val"),
                isa.ILD("f32", "A", "t_out", "t_idx2"),
            ))


# ---------------------------------------------------------------------------
# compile cache: repeat submissions of identical structure must not re-trace
# ---------------------------------------------------------------------------

class TestCompileCache:
    def _prog(self, name="g", tile=128):
        pat = Pattern([Access("LD", "A", Load("B", Var("i")), dtype="f32")],
                      name=name)
        return compile_pattern(pat, tile_size=tile)

    def _env(self, rng, tile=128):
        return {"A": jnp.asarray(rng.normal(size=(256,)).astype(np.float32)),
                "B": jnp.asarray(rng.integers(0, 256, size=(tile,))
                                 .astype(np.int32)),
                "__iota__": jnp.arange(tile, dtype=jnp.int32)}

    def test_executable_is_cached(self, rng):
        eng = Engine(tile_size=128)
        prog, _ = self._prog()
        exe1 = eng.executable(prog)
        exe2 = eng.executable(prog)
        assert exe1 is exe2
        assert eng.stats == {"trace_requests": 2, "trace_misses": 1}
        assert eng.cache_hits == 1

    def test_name_is_not_part_of_identity(self):
        eng = Engine(tile_size=128)
        p1, _ = self._prog("one")
        p2, _ = self._prog("two")
        assert structural_signature(p1) == structural_signature(p2)
        assert eng.executable(p1) is eng.executable(p2)

    def test_repeat_calls_trace_once(self, rng):
        """The satellite fix: N calls through jit_run == exactly 1 trace."""
        eng = Engine(tile_size=128)
        prog, info = self._prog()
        regs = {"tile_base": 0, "N": 128, "tile_end": 128}
        for k in range(6):
            exe = eng.jit_run(prog)
            env = self._env(np.random.default_rng(k))
            _, spd = exe(env, regs, {})
            np.testing.assert_allclose(
                np.asarray(spd[info["loads"]["A"]]),
                np.asarray(env["A"])[np.asarray(env["B"])])
        exe = eng.jit_run(prog)
        assert exe.traces == 1          # python side effect: 1 per retrace
        assert exe.calls == 6
        assert eng.stats["trace_misses"] == 1
        assert eng.stats["trace_requests"] == 7  # 6 loop + 1 re-fetch

    def test_engine_knobs_split_cache_entries(self):
        e1 = Engine(tile_size=128, optimize=True)
        prog, _ = self._prog()
        a = e1.executable(prog)
        e1.optimize = False
        b = e1.executable(prog)
        assert a is not b               # optimize flag changes lowering

    def test_batched_executable_separate_entry(self):
        eng = Engine(tile_size=128)
        prog, _ = self._prog()
        assert eng.executable(prog) is not eng.executable(prog, batch=4)
        assert eng.executable(prog, batch=4) is eng.executable(prog, batch=4)

    def test_structural_signature_covers_immediates(self):
        pat1 = Pattern([Access("ST", "A",
                               Load("B", BinOp("AND", Load("C", Var("i")),
                                               0xFF)),
                               value=Load("P", Var("i")), dtype="f32")])
        pat2 = Pattern([Access("ST", "A",
                               Load("B", BinOp("AND", Load("C", Var("i")),
                                               0xF0)),
                               value=Load("P", Var("i")), dtype="f32")])
        p1, _ = compile_pattern(pat1, tile_size=64)
        p2, _ = compile_pattern(pat2, tile_size=64)
        assert structural_signature(p1) != structural_signature(p2)

    def test_frozen_program_replace_shares_entry(self):
        eng = Engine(tile_size=128)
        prog, _ = self._prog()
        renamed = dataclasses.replace(prog, name="renamed")
        assert eng.executable(prog) is eng.executable(renamed)
