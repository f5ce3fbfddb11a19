"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import bulk_gather, bulk_rmw, coalesce, make_row_table_plan
from repro.kernels.gather import ops as gops
from repro.kernels.scatter_rmw import ops as sops

SHAPES = [
    # (n_rows, d, n_idx, block_rows, lanes)
    (256, 128, 100, 64, 32),
    (1024, 128, 4096, 128, 128),
    (1024, 256, 513, 256, 64),
    (4096, 512, 2048, 512, 128),
    (777, 128, 300, 128, 32),       # non-multiple table rows
]
DTYPES = [np.float32, jnp.bfloat16, np.int32]


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(7)


def _mk_table(rng, n, d, dtype):
    x = rng.normal(size=(n, d)).astype(np.float32)
    if dtype == np.int32:
        return jnp.asarray((x * 100).astype(np.int32))
    return jnp.asarray(x).astype(dtype)


class TestGatherKernel:
    @pytest.mark.parametrize("shape", SHAPES, ids=str)
    @pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
    def test_vs_ref(self, rng, shape, dtype):
        n, d, t, br, lanes = shape
        table = _mk_table(rng, n, d, dtype)
        idx = jnp.asarray(rng.integers(0, n, size=(t,)).astype(np.int32))
        uniq, _, _ = coalesce(idx)
        n_pad = -(-n // br) * br
        plan = make_row_table_plan(uniq, n_rows=n_pad, block_rows=br,
                                   lanes=lanes)
        out_k = gops.row_table_gather(table, plan)
        out_r = gops.row_table_gather(table, plan, use_ref=True)
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))

    @pytest.mark.parametrize("locality", ["uniform", "zipf", "sequential"])
    def test_end_to_end_distributions(self, rng, locality):
        n, d, t = 2048, 128, 1000
        table = _mk_table(rng, n, d, np.float32)
        if locality == "uniform":
            idx = rng.integers(0, n, size=(t,))
        elif locality == "zipf":
            idx = rng.zipf(1.3, size=(t,)) % n
        else:
            idx = (np.arange(t) * 2) % n
        idx = jnp.asarray(idx.astype(np.int32))
        out = bulk_gather(table, idx, use_kernel=True, block_rows=256,
                          lanes=64)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(table)[np.asarray(idx)])

    def test_single_index(self, rng):
        table = _mk_table(rng, 256, 128, np.float32)
        out = bulk_gather(table, jnp.asarray([7], jnp.int32),
                          use_kernel=True, block_rows=64, lanes=8)
        np.testing.assert_array_equal(np.asarray(out)[0],
                                      np.asarray(table)[7])


class TestScatterRmwKernel:
    @pytest.mark.parametrize("shape", SHAPES, ids=str)
    @pytest.mark.parametrize("op", ["ADD", "MAX", "MIN"])
    def test_vs_naive(self, rng, shape, op):
        n, d, t, br, lanes = shape
        table = _mk_table(rng, n, d, np.float32)
        idx = jnp.asarray(rng.integers(0, n, size=(t,)).astype(np.int32))
        vals = jnp.asarray(rng.normal(size=(t, d)).astype(np.float32))
        out_k = bulk_rmw(table, idx, vals, op=op, use_kernel=True,
                         block_rows=br, lanes=lanes)
        out_n = bulk_rmw(table, idx, vals, op=op, optimize=False)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_n),
                                   rtol=3e-5, atol=3e-5)

    def test_untouched_blocks_pass_through(self, rng):
        n, d = 1024, 128
        table = _mk_table(rng, n, d, np.float32)
        # touch only rows in the 3rd block
        idx = jnp.asarray([300, 301, 310], jnp.int32)
        vals = jnp.ones((3, d), jnp.float32)
        out = bulk_rmw(table, idx, vals, op="ADD", use_kernel=True,
                       block_rows=128, lanes=8)
        ref = np.asarray(table).copy()
        ref[[300, 301, 310]] += 1
        np.testing.assert_allclose(np.asarray(out), ref)

    def test_wrapper_vs_kernel_ref(self, rng):
        """ops.row_table_rmw ref path == kernel path."""
        n, d, t = 512, 128, 600
        table = _mk_table(rng, n, d, np.float32)
        dest = jnp.sort(jnp.asarray(
            rng.choice(n, size=t, replace=False) if t <= n else
            rng.integers(0, n, size=t), dtype=jnp.int32))
        # unique sorted dests
        dest = jnp.unique(dest, size=min(t, n), fill_value=n)
        vals = jnp.asarray(rng.normal(size=(dest.shape[0], d)
                                      ).astype(np.float32))
        out_k = sops.row_table_rmw(table, dest, vals, op="ADD",
                                   block_rows=128, lanes=64)
        out_r = sops.row_table_rmw(table, dest, vals, op="ADD",
                                   block_rows=128, lanes=64, use_ref=True)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=1e-6)


class TestDerivedSettings:
    """Mode, tile and dtype come from the platform, the width and the
    dtype (``kernels.common``), never from the caller."""

    def test_interpreted_on_cpu_refused_elsewhere(self, monkeypatch):
        import jax
        from repro.kernels import common
        assert common.interpret() is True
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="neither"):
            common.interpret()

    @pytest.mark.parametrize("dtype", [jnp.float16, jnp.int8, jnp.int16])
    def test_unsupported_dtype_refused_before_the_kernel(self, rng, dtype):
        table = jnp.zeros((64, 128), dtype)
        idx = jnp.asarray([1, 5, 9], jnp.int32)
        with pytest.raises(ValueError, match="row-table kernels take"):
            bulk_gather(table, idx, use_kernel=True)
        with pytest.raises(ValueError, match="row-table kernels take"):
            sops.row_table_rmw(table, idx, jnp.ones((3, 128), dtype))

    @pytest.mark.parametrize("d", [128, 256, 576, 6144, 16384])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_tile_fits_vmem_budget(self, d, dtype):
        from repro.kernels import common
        block_rows, lanes = common.tile_shape(d, dtype)
        row = d * jnp.dtype(dtype).itemsize
        assert block_rows & (block_rows - 1) == 0 and lanes & (lanes - 1) == 0
        assert block_rows % common.row_group(dtype) == 0 and lanes % 8 == 0
        # RMW: table block in + out, update block, each double-buffered
        assert 2 * (2 * block_rows * row + lanes * d * 4) <= 16 << 20

    def test_too_wide_rows_refused(self):
        from repro.kernels import common
        with pytest.raises(ValueError, match="too wide"):
            common.tile_shape(1 << 17, jnp.float32)
