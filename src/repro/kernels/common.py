"""What the row-table kernels derive from the platform, the dtype and the
row width (DESIGN.md §2).

* ``interpret()`` — Pallas mode: interpreted on the CPU (the tests),
  compiled on a TPU, refused on any other backend.
* ``row_group(dtype)`` — rows one dynamic row access touches. 32-bit rows
  are addressed one at a time; a 16-bit table packs two rows per 32-bit
  sublane, so Mosaic only accepts row slices aligned to its 16-row tile,
  and the kernels read (and write back) the aligned 16-row group around
  the wanted row.
* ``tile_shape(d, dtype)`` — ``(block_rows, lanes)`` sized so that the
  double-buffered blocks of either kernel fit the default scoped VMEM of
  a TPU v5e (16 MiB) at any row width.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# One table block (a "DRAM row") and one lane block, in bytes. The RMW
# kernel double-buffers a table block in, a table block out and a lane
# block of updates: 2 * (2 + 2 + 1) MiB = 10 MiB of VMEM at most.
BLOCK_BYTES = 2 << 20
LANE_BYTES = 1 << 20
MAX_BLOCK_ROWS = 1024
MAX_LANES = 256
MIN_LANES = 8

_ROW_GROUP = {jnp.dtype(jnp.float32): 1, jnp.dtype(jnp.int32): 1,
              jnp.dtype(jnp.uint32): 1, jnp.dtype(jnp.bfloat16): 16}


def interpret() -> bool:
    """True on the CPU, False on a TPU; any other backend raises."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"the row-table kernels run compiled on a TPU or interpreted on the "
        f"CPU; backend {backend!r} is neither")


def row_group(dtype) -> int:
    """Rows per dynamic row access (1 for 32-bit tables, 16 for bf16)."""
    dt = jnp.dtype(dtype)
    try:
        return _ROW_GROUP[dt]
    except KeyError:
        raise ValueError(
            f"the row-table kernels take float32, int32, uint32 or bfloat16 "
            f"tables, not {dt}") from None


def _pow2_floor(n: int) -> int:
    return 1 << (max(int(n), 1).bit_length() - 1)


def tile_shape(d: int, dtype) -> tuple:
    """``(block_rows, lanes)`` for a table of ``d``-wide rows of ``dtype``.

    Both are powers of two, so they meet the (8, 128) / (16, 128) tiling
    rule. Lanes are sized on 4-byte words: bf16 rows travel through the
    kernels as float32."""
    group = row_group(dtype)
    row_bytes = d * jnp.dtype(dtype).itemsize
    min_rows = max(group, 8)
    if min_rows * row_bytes > BLOCK_BYTES:
        raise ValueError(
            f"rows of {d} x {jnp.dtype(dtype)} are too wide for the "
            f"row-table kernels: {min_rows} rows exceed the "
            f"{BLOCK_BYTES >> 20} MiB block budget")
    block_rows = min(_pow2_floor(BLOCK_BYTES // row_bytes), MAX_BLOCK_ROWS)
    lanes = min(max(_pow2_floor(LANE_BYTES // (4 * d)), MIN_LANES),
                MAX_LANES)
    return block_rows, lanes


def check_tile(block_rows: int, lanes: int, dtype) -> None:
    """Refuse, before the kernel, a caller-set tile the chip cannot take.
    The interpreter takes any tile (the tests use tiny ones)."""
    group = row_group(dtype)
    if interpret():
        return
    if block_rows % max(group, 8) or lanes % 8:
        raise ValueError(
            f"block_rows={block_rows} must be a multiple of {max(group, 8)} "
            f"and lanes={lanes} a multiple of 8 for {jnp.dtype(dtype)} "
            f"tables on a TPU")
