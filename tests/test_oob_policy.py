"""Unified out-of-range index policy: loads clamp, stores drop.

One policy (DESIGN.md §"OOB policy"), asserted at every layer that touches
an index: the functional bulk ops (every optimize/kernel path), the Pallas
kernel refs, the engine's ISA paths — including conditional (tc-masked)
IST/IRMW across the optimize × kernel × jit matrix with all-masked and
OOB streams — and the ISA oracle, which is the ground truth the policy is
defined against.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import bulk_gather, bulk_rmw, bulk_scatter, isa
from repro.core.engine import Engine
from repro.testing import OracleEngine
from repro.testing.harness import _assert_match

N_ROWS = 64


@pytest.fixture
def rng():
    return np.random.default_rng(7)


def oob_stream(rng, n=96, n_rows=N_ROWS):
    """Mixed in-range / negative / overshooting indices."""
    idx = rng.integers(0, n_rows, size=n).astype(np.int32)
    pos = rng.choice(n, size=n // 3, replace=False)
    neg = -rng.integers(1, n_rows + 2, size=pos.shape[0])
    big = n_rows + rng.integers(0, n_rows + 2, size=pos.shape[0])
    idx[pos] = np.where(rng.random(pos.shape[0]) < 0.5, neg, big)
    return idx


UFUNC_AT = {"ADD": np.add, "MAX": np.maximum, "MIN": np.minimum,
            "MUL": np.multiply, "AND": np.bitwise_and, "OR": np.bitwise_or,
            "XOR": np.bitwise_xor}


def rmw_1d_case(rng, case, dtype, op):
    """(table, idx, values, cond) of one RMW into a 1-D table. Values are
    integers (products of +-1 and +-2 under MUL), so float results are
    exact in any order, but for ``gt_rows`` in float32, whose values are
    fractional."""
    lanes = {"lt_rows": N_ROWS // 4, "gt_rows": 4 * N_ROWS,
             "empty": 0}.get(case, 48)
    idx = rng.integers(0, N_ROWS, size=lanes).astype(np.int32)
    cond = None
    if case == "one_row":
        idx[:] = 5
    elif case == "distinct":
        idx = rng.permutation(N_ROWS)[:lanes].astype(np.int32)
    elif case == "oob":
        idx = oob_stream(rng, n=lanes)
    elif case == "cond":
        cond = rng.random(lanes) < 0.5
    if op == "MUL":
        vals = rng.choice([-2, -1, 1, 2], size=lanes)
    else:
        vals = rng.integers(-64, 65, size=lanes)
    if case == "gt_rows" and np.issubdtype(dtype, np.floating):
        vals = (rng.uniform(0.5, 2.0, size=lanes) if op == "MUL"
                else rng.normal(size=lanes))
    table = rng.integers(-1024, 1024, size=N_ROWS)
    return table.astype(dtype), idx, vals.astype(dtype), cond


def lane_scatters_and_gathers(table_shape, lanes: int):
    """The gathers and scatters of ``bulk_rmw``'s StableHLO (ADD, float32)
    whose indices or updates are ``lanes`` long, as (name, operand shapes,
    whether the indices are declared unique)."""
    import jax
    from jaxlib.mlir import ir
    sds = jax.ShapeDtypeStruct
    lowered = bulk_rmw.lower(
        sds(table_shape, jnp.float32), sds((lanes,), jnp.int32),
        sds((lanes,) + table_shape[1:], jnp.float32), op="ADD")
    found = []

    def visit(op):
        if op.name in ("stablehlo.gather", "stablehlo.scatter"):
            shapes = [list(ir.RankedTensorType(o.type).shape)
                      for o in op.operands]
            # gather: (operand, indices); scatter: (operand, indices, updates)
            if any(s[0] == lanes for s in shapes[1:]):
                unique = "unique_indices" in op.attributes and \
                    ir.BoolAttr(op.attributes["unique_indices"]).value
                found.append((op.name, shapes, unique))
        return ir.WalkResult.ADVANCE

    lowered.compiler_ir("stablehlo").operation.walk(visit)
    return found


def test_rmw_1d_lowers_to_one_unique_lane_scatter():
    """A 1-D table's RMW moves its lanes through no gather and one scatter,
    of unique run ends (the segment path had two gathers and three
    scatters); a 2-D table keeps the unique scatter of whole rows."""
    lanes, rows = 2 ** 16, 2 ** 12
    assert lane_scatters_and_gathers((rows,), lanes) == [
        ("stablehlo.scatter", [[rows], [lanes, 1], [lanes]], True)]
    assert ("stablehlo.scatter", [[rows, 8], [lanes, 1], [lanes, 8]], True) \
        in lane_scatters_and_gathers((rows, 8), lanes)


# ---------------------------------------------------------------------------
# bulk-op level: every optimize/kernel path agrees with the policy
# ---------------------------------------------------------------------------

class TestBulkOps:
    def test_gather_clamps_all_paths(self, rng):
        table = rng.normal(size=(N_ROWS,)).astype(np.float32)
        idx = oob_stream(rng)
        want = table[np.clip(idx, 0, N_ROWS - 1)]
        for sort in (False, True):
            for dedup in (False, True):
                got = bulk_gather(jnp.asarray(table), jnp.asarray(idx),
                                  sort=sort, dedup=dedup)
                np.testing.assert_array_equal(np.asarray(got), want,
                                              err_msg=f"{sort=} {dedup=}")

    def test_gather_clamps_kernel_path_2d(self, rng):
        table = rng.normal(size=(N_ROWS, 4)).astype(np.float32)
        idx = oob_stream(rng)
        want = table[np.clip(idx, 0, N_ROWS - 1)]
        got = bulk_gather(jnp.asarray(table), jnp.asarray(idx),
                          use_kernel=True, block_rows=16, lanes=8)
        np.testing.assert_array_equal(np.asarray(got), want)

    def test_scatter_drops_oob_and_negative(self, rng):
        table = rng.normal(size=(N_ROWS,)).astype(np.float32)
        idx = oob_stream(rng)
        vals = rng.normal(size=idx.shape[0]).astype(np.float32)
        want = table.copy()
        for k in range(idx.shape[0]):          # sequential: last write wins
            if 0 <= idx[k] < N_ROWS:
                want[idx[k]] = vals[k]
        for optimize in (False, True):
            got = bulk_scatter(jnp.asarray(table), jnp.asarray(idx),
                               jnp.asarray(vals), optimize=optimize)
            np.testing.assert_array_equal(np.asarray(got), want,
                                          err_msg=f"{optimize=}")

    @pytest.mark.parametrize("op", ["ADD", "MIN", "MAX", "AND", "OR",
                                    "XOR", "MUL"])
    def test_rmw_drops_oob_and_negative(self, rng, op):
        table = rng.integers(0, 2 ** 12, size=N_ROWS).astype(np.int32)
        idx = oob_stream(rng)
        vals = rng.integers(0, 2 ** 8, size=idx.shape[0]).astype(np.int32)
        from repro.testing.harness import _np_rmw
        want = _np_rmw(table, idx, vals, op)
        for optimize in (False, True):
            got = bulk_rmw(jnp.asarray(table), jnp.asarray(idx),
                           jnp.asarray(vals), op=op, optimize=optimize)
            np.testing.assert_array_equal(np.asarray(got), want,
                                          err_msg=f"{op=} {optimize=}")

    @pytest.mark.parametrize("case", ["one_row", "distinct", "oob", "cond",
                                      "empty", "lt_rows", "gt_rows"])
    @pytest.mark.parametrize("dtype", [np.float32, np.int32])
    @pytest.mark.parametrize("op", list(UFUNC_AT))
    def test_rmw_1d_scan_matches_ufunc_at(self, rng, op, dtype, case):
        """The 1-D combine (sort, segmented scan, per-row read) against
        NumPy's unbuffered ``ufunc.at``."""
        table, idx, vals, cond = rmw_1d_case(rng, case, dtype, op)
        call = lambda: bulk_rmw(  # noqa: E731
            jnp.asarray(table), jnp.asarray(idx), jnp.asarray(vals), op=op,
            cond=None if cond is None else jnp.asarray(cond))
        if op in ("AND", "OR", "XOR") and dtype is np.float32 \
                and case != "empty":
            with pytest.raises(ValueError, match="integer table"):
                call()
            return
        keep = (idx >= 0) & (idx < N_ROWS)
        if cond is not None:
            keep &= cond
        want = table.copy()
        if keep.any():      # NumPy has no bitwise ops on float32 at all
            UFUNC_AT[op].at(want, idx[keep], vals[keep])
        got = np.asarray(call())
        assert got.dtype == table.dtype
        if np.issubdtype(dtype, np.floating) and case == "gt_rows":
            # fractional float sums and products: another rounding order
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
        else:
            np.testing.assert_array_equal(got, want)

    def test_rmw_drops_oob_kernel_path_2d(self, rng):
        table = rng.normal(size=(N_ROWS, 4)).astype(np.float32)
        idx = oob_stream(rng)
        vals = rng.normal(size=(idx.shape[0], 4)).astype(np.float32)
        from repro.testing.harness import _np_rmw
        want = _np_rmw(table, idx, vals, "ADD")
        got = bulk_rmw(jnp.asarray(table), jnp.asarray(idx),
                       jnp.asarray(vals), op="ADD", use_kernel=True,
                       block_rows=16, lanes=8)
        # float ADD reductions are legally reordered (§3.1): allclose
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# kernel refs: the Pallas oracles implement the same policy
# ---------------------------------------------------------------------------

class TestKernelRefs:
    def test_gather_ref_clamps(self):
        from repro.kernels.gather.ref import row_table_gather_ref
        table = jnp.arange(8.0)
        # block 3 * 4 rows + offset 2 = row 14: past the table -> clamps
        out = row_table_gather_ref(
            table, jnp.asarray([0, 3], jnp.int32),
            jnp.asarray([[0, 1], [2, 3]], jnp.int32),
            block_rows=4, lanes=2)
        np.testing.assert_array_equal(np.asarray(out), [0, 1, 7, 7])

    def test_rmw_ref_drops(self):
        from repro.kernels.scatter_rmw.ref import row_table_rmw_ref
        table = jnp.zeros(8)
        out = row_table_rmw_ref(
            table, jnp.asarray([0, 3], jnp.int32),
            jnp.asarray([1, 1], jnp.int32),
            jnp.asarray([[0, 1], [2, 3]], jnp.int32),
            jnp.ones((4,)), block_rows=4, lanes=2)
        # rows 14, 15 drop; rows 0, 1 land
        np.testing.assert_array_equal(np.asarray(out),
                                      [1, 1, 0, 0, 0, 0, 0, 0])

    def test_row_table_rmw_wrapper_drops_negative_dest(self):
        from repro.kernels.scatter_rmw.ops import row_table_rmw
        table = jnp.zeros((16, 2))
        dest = jnp.asarray([-5, -1, 2, 7, 16, 99], jnp.int32)  # sorted
        vals = jnp.ones((6, 2))
        for use_ref in (True, False):
            out = row_table_rmw(table, dest, vals, op="ADD", block_rows=8,
                                lanes=4, use_ref=use_ref)
            want = np.zeros((16, 2))
            want[2] = want[7] = 1.0
            np.testing.assert_array_equal(np.asarray(out), want,
                                          err_msg=f"{use_ref=}")


# ---------------------------------------------------------------------------
# engine ISA level: conditional IST/IRMW across optimize x kernel x jit,
# all-masked and OOB streams, vs the ISA oracle
# ---------------------------------------------------------------------------

ENGINE_CONFIGS = [(o, k, j) for o in (True, False) for k in (False, True)
                  for j in (False, True)]


def _cond_store_program(kind: str, op: str = "ADD") -> isa.AccessProgram:
    instrs = [
        isa.SLD("i32", "IDX", "t_i"),
        isa.SLD("f32", "VALS", "t_v"),
        isa.SLD("i32", "COND", "t_c"),
    ]
    if kind == "IST":
        instrs.append(isa.IST("f32", "T", "t_i", "t_v", tc="t_c"))
    else:
        instrs.append(isa.IRMW("f32", "T", op, "t_i", "t_v", tc="t_c"))
    return isa.AccessProgram(instrs, tile_size=96, name=f"cond_{kind}")


def _run_both(prog, env):
    """(engine env, oracle env) for every engine config; yields tuples."""
    oeng = OracleEngine(tile_size=prog.tile_size)
    oenv, _ = oeng.run(prog, {k: np.array(v) for k, v in env.items()})
    for o, k, j in ENGINE_CONFIGS:
        eng = Engine(tile_size=prog.tile_size, optimize=o, use_kernel=k)
        step = eng.jit_run(prog) if j else \
            (lambda e, r, s: eng.run(prog, e, r, s))
        genv, _ = step({k: jnp.asarray(v) for k, v in env.items()}, {}, {})
        yield (f"opt={int(o)} kern={int(k)} jit={int(j)}", genv, oenv)


@pytest.mark.parametrize("kind", ["IST", "IRMW"])
@pytest.mark.parametrize("mask", ["mixed", "all_true", "all_false"])
def test_conditional_store_matrix(rng, kind, mask):
    """tc-masked IST/IRMW parity on an OOB-poisoned stream."""
    n = 96
    idx = oob_stream(rng, n=n)
    cond = {"mixed": rng.integers(0, 2, size=n),
            "all_true": np.ones(n),
            "all_false": np.zeros(n)}[mask].astype(np.int32)
    env = {"IDX": idx,
           "VALS": rng.normal(size=n).astype(np.float32),
           "COND": cond,
           "T": rng.normal(size=N_ROWS).astype(np.float32)}
    prog = _cond_store_program(kind)
    for label, genv, oenv in _run_both(prog, env):
        _assert_match(f"[{label} {kind} {mask}] env[T]", genv["T"],
                      oenv["T"], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("op", ["MIN", "MAX"])
def test_conditional_irmw_ops_matrix(rng, op):
    n = 96
    env = {"IDX": oob_stream(rng, n=n),
           "VALS": rng.normal(size=n).astype(np.float32),
           "COND": rng.integers(0, 2, size=n).astype(np.int32),
           "T": rng.normal(size=N_ROWS).astype(np.float32)}
    prog = _cond_store_program("IRMW", op=op)
    for label, genv, oenv in _run_both(prog, env):
        # MIN/MAX are order-independent even in floats: bit-exact
        np.testing.assert_array_equal(
            np.asarray(genv["T"]), oenv["T"], err_msg=f"{label} {op}")


def test_conditional_ild_oob_matrix(rng):
    """tc-masked ILD on an OOB stream: clamped load, masked lanes read 0."""
    n = 96
    env = {"IDX": oob_stream(rng, n=n),
           "COND": rng.integers(0, 2, size=n).astype(np.int32),
           "SRC": rng.normal(size=N_ROWS).astype(np.float32),
           "OUT": np.zeros(n, np.float32)}
    prog = isa.AccessProgram([
        isa.SLD("i32", "IDX", "t_i"),
        isa.SLD("i32", "COND", "t_c"),
        isa.ILD("f32", "SRC", "t_x", "t_i", tc="t_c"),
        isa.SLD("i32", "IDX", "t_i2"),       # keep OUT observable via SST
        isa.SST("f32", "OUT", "t_x"),
    ], tile_size=96, name="cond_ild")
    for label, genv, oenv in _run_both(prog, env):
        np.testing.assert_array_equal(np.asarray(genv["OUT"]), oenv["OUT"],
                                      err_msg=label)


def test_sst_negative_start_drops():
    """Strided store with a negative start: lanes before row 0 drop (the
    engine previously wrapped them)."""
    prog = isa.AccessProgram([
        isa.SLD("f32", "SRC", "t_x"),
        isa.SST("f32", "T", "t_x", rs1="start"),
    ], tile_size=8, name="sst_neg")
    env = {"SRC": np.arange(8, dtype=np.float32),
           "T": np.zeros(16, np.float32)}
    regs = {"start": -3}
    oeng = OracleEngine(tile_size=8)
    oenv, _ = oeng.run(prog, {k: np.array(v) for k, v in env.items()}, regs)
    for o in (True, False):
        eng = Engine(tile_size=8, optimize=o)
        genv, _ = eng.run(prog, {k: jnp.asarray(v) for k, v in env.items()},
                          regs)
        np.testing.assert_array_equal(np.asarray(genv["T"]), oenv["T"])
    # the first 3 lanes dropped, lanes 3.. landed at rows 0..4
    np.testing.assert_array_equal(
        oenv["T"][:6], np.asarray([3, 4, 5, 6, 7, 0], np.float32))
