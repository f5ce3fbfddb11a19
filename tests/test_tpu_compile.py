"""Compile-only checks of the row-table kernels for a TPU v5e, no chip needed.

The TPU compiler is asked, for a described (not attached) v5e, to compile
both Pallas kernels at the widths the deployments use — a 1 KB YCSB record
(D=256), smollm-135m's d_model (576) and dbrx-132b's (6144) — for float32,
int32 and bfloat16 tables of 2 GiB, with the tile ``tile_shape`` derives.
A compile that passes proves the tiling and VMEM budget hold; it says
nothing about results or speed.

The topology is described inside a module fixture (only the worker that
runs this file loads the TPU library), and the persistent compile cache is
off around these compiles: their entries cannot be read back without a
chip.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import common
from repro.kernels.gather import gather as gather_kernel
from repro.kernels.scatter_rmw import scatter_rmw as rmw_kernel

TABLE_BYTES = 2 << 30
KEYS = 1 << 16
DTYPES = [jnp.float32, jnp.int32, jnp.bfloat16]
WIDTHS = [256, 576, 6144]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # the kernels derive their mode from the process's backend (the
        # CPU here); these compiles are for the chip
        mp.setattr(common, "interpret", lambda: False)
        cache_was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was)
            compilation_cache.reset_cache()
            jax.clear_caches()   # no chip-traced kernel may serve a CPU call


def _shapes(d, dtype, one_chip):
    block_rows, lanes = common.tile_shape(d, dtype)
    itemsize = jnp.dtype(dtype).itemsize
    rows = TABLE_BYTES // (d * itemsize) // block_rows * block_rows
    tiles = -(-KEYS // lanes) + min(rows // block_rows, KEYS)

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    return block_rows, lanes, {
        "table": s((rows, d), dtype),
        "tile_block": s((tiles,), jnp.int32),
        "tile_first": s((tiles,), jnp.int32),
        "offsets": s((tiles, lanes), jnp.int32),
        "vals": s((tiles * lanes, d), dtype),
    }


@pytest.mark.parametrize("d", WIDTHS)
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda t: jnp.dtype(t).name)
@pytest.mark.parametrize("kernel", ["gather", "rmw"])
def test_kernel_compiles_for_v5e(one_chip, kernel, dtype, d):
    block_rows, lanes, a = _shapes(d, dtype, one_chip)
    if kernel == "gather":
        lowered = gather_kernel.row_table_gather.lower(
            a["table"], a["tile_block"], a["offsets"],
            block_rows=block_rows, lanes=lanes)
    else:
        lowered = rmw_kernel.row_table_rmw.lower(
            a["table"], a["tile_block"], a["tile_first"], a["offsets"],
            a["vals"], block_rows=block_rows, lanes=lanes, op="ADD")
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_misaligned_tile_refused_before_the_kernel(one_chip):
    with pytest.raises(ValueError, match="multiple of 16"):
        common.check_tile(24, 8, jnp.bfloat16)
    with pytest.raises(ValueError, match="multiple of 8"):
        common.check_tile(64, 4, jnp.float32)
