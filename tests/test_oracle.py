"""Unit tests for the NumPy oracle itself (repro.testing.oracle).

The oracle is ground truth for everything else, so it gets its own
known-answer tests, plus meta-tests showing the parity harness actually
*detects* seeded divergence (a differential tester that can't fail is
worthless).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import isa
from repro.core.compiler import Access, Load, Pattern, RangeLoop, Var
from repro.core.engine import Engine
from repro.testing import harness, oracle
from repro.testing.oracle import OracleEngine


def _prog(*instrs, tile_size=8):
    return isa.AccessProgram(tuple(instrs), tile_size=tile_size)


class TestOracleInstructions:
    def test_sld_strided(self):
        p = _prog(isa.SLD("i32", "A", "t", rs1=2, rs3=3))
        env = {"A": np.arange(100, dtype=np.int32)}
        _, spd = OracleEngine(8).run(p, env)
        np.testing.assert_array_equal(spd["t"], 2 + 3 * np.arange(8))

    def test_sld_clips_at_region_end(self):
        p = _prog(isa.SLD("i32", "A", "t", rs1=0, rs3=1))
        env = {"A": np.arange(5, dtype=np.int32)}
        _, spd = OracleEngine(8).run(p, env)
        np.testing.assert_array_equal(spd["t"], [0, 1, 2, 3, 4, 4, 4, 4])

    def test_ild_gather_and_cond(self):
        p = _prog(isa.SLD("i32", "B", "idx", rs1=0),
                  isa.ILD("f32", "A", "out", "idx", tc="mask"))
        env = {"A": np.arange(8, dtype=np.float32) * 2.0,
               "B": np.asarray([3, 1, 0, 2, 7, 6, 5, 4], np.int32)}
        spd0 = {"mask": np.asarray([1, 1, 0, 1, 1, 1, 1, 0], np.int32)}
        _, spd = OracleEngine(8).run(p, env, spd=spd0)
        want = np.asarray([6, 2, 0, 4, 14, 12, 10, 0], np.float32)
        want[2] = 0.0
        np.testing.assert_array_equal(spd["out"], want)

    def test_ist_last_write_wins(self):
        p = _prog(isa.IST("f32", "A", "idx", "val"))
        env = {"A": np.zeros(8, np.float32)}
        spd0 = {"idx": np.asarray([1, 1, 2, 1, 0, 0, 3, 3], np.int32),
                "val": np.arange(8, dtype=np.float32) + 1}
        env2, _ = OracleEngine(8).run(p, env, spd=spd0)
        np.testing.assert_array_equal(env2["A"],
                                      [6, 4, 3, 8, 0, 0, 0, 0])

    def test_irmw_sequential_and_oob_drop(self):
        p = _prog(isa.IRMW("i32", "A", "ADD", "idx", "val"))
        env = {"A": np.zeros(4, np.int32)}
        spd0 = {"idx": np.asarray([0, 0, 3, 99, -1, 2, 2, 2], np.int32),
                "val": np.ones(8, np.int32)}
        env2, _ = OracleEngine(8).run(p, env, spd=spd0)
        np.testing.assert_array_equal(env2["A"], [2, 0, 3, 1])

    def test_irmw_integer_wraparound(self):
        p = _prog(isa.IRMW("i32", "A", "MUL", "idx", "val"))
        env = {"A": np.full(2, 2 ** 30, np.int32)}
        spd0 = {"idx": np.zeros(8, np.int32),
                "val": np.full(8, 3, np.int32)}
        env2, _ = OracleEngine(8).run(p, env, spd=spd0)
        # must wrap modulo 2^32 silently, like XLA
        assert env2["A"][0] == np.int32(2 ** 30 * 3 ** 8 & 0xFFFFFFFF)

    def test_rng_truncates_at_capacity(self):
        p = _prog(isa.RNG("o", "j", "lo", "hi", rs1=4))
        spd0 = {"lo": np.zeros(8, np.int32),
                "hi": np.full(8, 3, np.int32)}
        _, spd = OracleEngine(8).run(p, {}, spd=spd0)
        assert int(spd["_rng_total"]) == 24     # the whole stream
        np.testing.assert_array_equal(spd["o"], [0, 0, 0, 1])
        np.testing.assert_array_equal(spd["j"], [0, 1, 2, 0])
        np.testing.assert_array_equal(spd["o__mask"], [1, 1, 1, 1])

    def test_alu_matches_engine_bitwise(self):
        p = _prog(isa.ALUV("i32", "XOR", "c", "a", "b"),
                  isa.ALUS("i32", "SHR", "d", "c", rs=2))
        spd0 = {"a": np.arange(8, dtype=np.int32) * 7,
                "b": np.asarray([3] * 8, np.int32)}
        _, ospd = OracleEngine(8).run(p, {}, spd=spd0)
        _, espd = Engine(tile_size=8).run(
            p, {}, spd={k: jnp.asarray(v) for k, v in spd0.items()})
        np.testing.assert_array_equal(ospd["d"], np.asarray(espd["d"]))


class TestSourceEvaluator:
    def test_plain_gather_store(self):
        env = {"B": np.asarray([2, 0, 1], np.int32),
               "A": np.asarray([10., 20., 30.], np.float32),
               "out": np.zeros(3, np.float32)}
        pat = Pattern([Access("ST", "out", Var("i"),
                              value=Load("A", Load("B", Var("i"))),
                              dtype="f32")], name="t")
        env2, _ = oracle.run_pattern(pat, env, n=3)
        np.testing.assert_array_equal(env2["out"], [30., 10., 20.])

    def test_range_loop_rowsum(self):
        env = {"H": np.asarray([0, 2, 2, 5], np.int32),
               "V": np.arange(5, dtype=np.float32) + 1,
               "y": np.zeros(3, np.float32)}
        from repro.core.compiler import BinOp
        pat = Pattern([Access("RMW", "y", Var("i"),
                              value=Load("V", Var("j")), op="ADD",
                              dtype="f32")],
                      range_loop=RangeLoop(
                          "j", Load("H", Var("i")),
                          Load("H", BinOp("ADD", Var("i"), 1))),
                      name="rowsum")
        env2, _ = oracle.run_pattern(pat, env, n=3)
        np.testing.assert_array_equal(env2["y"], [3., 0., 12.])

    def test_loads_stream_masked_by_cond(self):
        from repro.core.compiler import Compare
        env = {"A": np.arange(4, dtype=np.float32),
               "D": np.asarray([1., -1., 1., -1.], np.float32),
               "s": np.zeros(4, np.float32)}
        pat = Pattern([Access("LD", "A", Var("i"), dtype="f32",
                              cond=Compare("GT", Load("D", Var("i")), 0.0)),
                       Access("ST", "s", Var("i"),
                              value=Load("D", Var("i")), dtype="f32")],
                      name="condld")
        _, loads = oracle.run_pattern(pat, env, n=4)
        np.testing.assert_array_equal(loads["A"], [0., 0., 2., 0.])


class TestHarnessDetectsBugs:
    """Meta-tests: the differential harness must flag real divergence."""

    def test_mismatch_raises(self):
        got = np.asarray([1, 2, 3], np.int32)
        want = np.asarray([1, 9, 3], np.int32)
        with pytest.raises(harness.ParityError):
            harness._assert_match("t", got, want, rtol=0, atol=0)

    def test_broken_engine_is_caught(self, monkeypatch):
        """Sabotage bulk_scatter's duplicate policy; parity must fail."""
        from repro.core import bulk_ops
        from repro.testing import conformance
        real = bulk_ops.bulk_scatter

        def first_write_wins(table, idx, values, cond=None, optimize=True):
            return real(table, idx[::-1], values[::-1], cond=None if
                        cond is None else cond[::-1], optimize=optimize)
        monkeypatch.setattr(
            "repro.core.engine.bulk_ops.bulk_scatter", first_write_wins)
        case = conformance.build("hashjoin_build")
        with pytest.raises(harness.ParityError):
            harness.check_pattern_parity(
                case.pattern, case.env, n=case.n,
                configs=[harness.EngineConfig(True, False, False, 64)])

    def test_oracle_engine_agreement_on_seed_program(self):
        """Direct spot check: engine vs oracle on a hand-built program."""
        rng = np.random.default_rng(3)
        prog = _prog(
            isa.SLD("i32", "B", "idx", rs1=0),
            isa.ILD("f32", "A", "v", "idx"),
            isa.ALUS("f32", "MUL", "v2", "v", rs=2.0),
            isa.IST("f32", "out", "idx", "v2"),
            tile_size=16)
        env = {"A": rng.normal(size=32).astype(np.float32),
               "B": rng.integers(0, 32, size=16).astype(np.int32),
               "out": np.zeros(32, np.float32)}
        oenv, ospd = OracleEngine(16).run(prog, env)
        eenv, espd = Engine(tile_size=16).run(
            prog, {k: jnp.asarray(v) for k, v in env.items()})
        np.testing.assert_allclose(np.asarray(eenv["out"]), oenv["out"],
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(espd["v2"]), ospd["v2"],
                                   rtol=1e-6)
