"""Bring-up smoke: the access service and its Pallas kernels on a TPU.

    python chip_smoke.py             # one chip: service phase, kernel phase
    python chip_smoke.py --chips 4   # four chips: the sharded service only

The data is a YCSB usertable: 1 KB records (10 fields of 100 B, held as
256 float32 words per row), read and updated with scrambled-Zipfian keys
(constant 0.99), generated from ``--seed``; nothing is read from disk.
Every result is compared with a plain NumPy reference written here
(``table[idx]``, ``np.add.at``). Table values and updates are small
integers held as float32, so every sum is exact and the comparison is
equality, whatever order the device adds in.

Phases (one process; each prints one JSON line):

* ``service`` — a few flush windows through ``AccessService``, several
  tenants connected in each. Per window each tenant submits a fused
  gather, an ADD ``submit_rmw`` share and one Table-1 ISA program (an
  indirect load, ``A[B[i]]``) over a 1-D word table.
* ``kernel`` — ``bulk_gather``/``bulk_rmw`` with ``use_kernel=True``, the
  Pallas row-table kernels compiled for the chip, on the same table; and
  a bfloat16 table through the same kernels.
* ``mesh`` (``--chips 4`` only) — the service over ``AccessService(mesh=)``:
  sharded gathers and an ADD RMW over a table row-partitioned across the
  chips and built sharded, never whole on one device.

The script exits non-zero on a platform other than TPU, on any wrong
result, exception or ``FailedResult``, and when the scheduler reports a
vmap fallback or a failed exchange prefetch. Its last line is
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ZIPF_CONSTANT = 0.99
VALUE_RANGE = 1024        # table values: integers in [-1024, 1024)
UPDATE_RANGE = 8          # RMW updates: integers in [-8, 8]


@dataclasses.dataclass(frozen=True)
class Sizes:
    rows: int = 1 << 21          # 2 GiB of 1 KB records
    width: int = 256             # float32 words per record
    keys: int = 1 << 16          # gather keys per tenant per window
    updates: int = 1 << 16       # RMW updates per window, all tenants
    tenants: int = 4
    windows: int = 3
    words: int = 1 << 26         # 1-D word table of the ISA program
    tile: int = 16384            # ISA program tile (AccessService default)
    bf16_rows: int = 1 << 18     # bfloat16 table of the kernel phase


ONE_CHIP = Sizes()
FOUR_CHIPS = dataclasses.replace(ONE_CHIP, rows=1 << 23)   # 2 GiB per chip


class SmokeError(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)


# ---------------------------------------------------------------------------
# data and reference
# ---------------------------------------------------------------------------

def fnv1a64(x: np.ndarray) -> np.ndarray:
    """YCSB's FNV-1a hash of each 64-bit value (ScrambledZipfianGenerator)."""
    x = x.astype(np.uint64)
    h = np.full(x.shape, 0xCBF29CE484222325, np.uint64)
    for _ in range(8):
        h ^= x & np.uint64(0xFF)
        h *= np.uint64(0x100000001B3)
        x >>= np.uint64(8)
    return h


class ZipfKeys:
    """Scrambled-Zipfian keys over ``n`` items: rank r is drawn with
    probability proportional to 1/r^0.99, then hashed onto the key space
    so that the hot keys are spread over the table, as YCSB does."""

    def __init__(self, n: int, rng: np.random.Generator):
        w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** ZIPF_CONSTANT
        self.cdf = np.cumsum(w) / w.sum()
        self.n = n
        self.rng = rng

    def __call__(self, k: int) -> np.ndarray:
        rank = np.searchsorted(self.cdf, self.rng.random(k))
        return (fnv1a64(rank) % np.uint64(self.n)).astype(np.int32)


def device_table(shape, dtype, seed: int, *, sharding=None,
                 high: int = VALUE_RANGE):
    """Table of integers in [-high, high) made on the device (sharded when
    asked)."""
    import jax
    import jax.numpy as jnp

    def make():
        v = jax.random.randint(jax.random.key(seed), shape, -high, high,
                               dtype=jnp.int32)
        return v.astype(dtype)
    return jax.jit(make, out_shardings=sharding)()


def updates(rng: np.random.Generator, k: int, width: int) -> np.ndarray:
    return rng.integers(-UPDATE_RANGE, UPDATE_RANGE + 1,
                        size=(k, width)).astype(np.float32)


def same(got, want: np.ndarray, what: str) -> None:
    got = np.asarray(got)
    check(got.shape == want.shape, f"{what}: shape {got.shape} != "
                                   f"{want.shape}")
    check(np.array_equal(got, want), f"{what}: values differ from the "
                                     f"NumPy reference")


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


@contextlib.contextmanager
def compile_clock():
    """Seconds the XLA/Mosaic backend spent compiling inside the block
    (tracing and lowering are not counted: their events nest)."""
    import jax.monitoring as mon
    spent = [0.0]

    def listen(event, duration, **_):
        if event == _BACKEND_COMPILE:
            spent[0] += duration
    mon.register_event_duration_secs_listener(listen)
    try:
        yield spent
    finally:
        mon.unregister_event_duration_listener(listen)


def peak_bytes(device):
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def report(phase: str, **facts) -> dict:
    line = {"phase": phase, **facts}
    print(json.dumps(line), flush=True)
    return line


def check_counters(stats: dict) -> dict:
    counters = {k: stats.get(k, 0) for k in
                ("plan_cache_hits", "vmap_fallbacks", "prefetch_errors",
                 "group_errors")}
    counters["trace_misses"] = stats["engine"]["trace_misses"]
    counters["exchange_measure_errors"] = stats["engine"].get(
        "exchange_measure_errors", 0)
    for name in ("vmap_fallbacks", "prefetch_errors", "group_errors",
                 "exchange_measure_errors"):
        check(counters[name] == 0, f"{name}: {counters}")
    return counters


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def serve_windows(svc, table, ref: np.ndarray, sizes: Sizes,
                  rng: np.random.Generator, *, program=None):
    """Run ``sizes.windows`` flush windows of gathers, one fused ADD RMW
    and (where ``program`` is given) one ISA program per tenant; check each
    ticket against ``ref``, which is updated in place. Returns the table
    after the last window and the seconds spent serving."""
    import jax

    keys = ZipfKeys(sizes.rows, rng)
    cores = [svc.connect(f"tenant{t}") for t in range(sizes.tenants)]
    share = sizes.updates // sizes.tenants
    served = 0.0
    for w in range(sizes.windows):
        gidx = [keys(sizes.keys) for _ in cores]
        uidx = [keys(share) for _ in cores]
        uval = [updates(rng, share, sizes.width) for _ in cores]
        pin = [program.inputs(rng) for _ in cores] if program else []
        t0 = time.perf_counter()
        gathers = [c.submit_gather(table, i) for c, i in zip(cores, gidx)]
        rmws = [c.submit_rmw(table, i, v, op="ADD")
                for c, i, v in zip(cores, uidx, uval)]
        progs = [c.submit(program.prog, *p) for c, p in zip(cores, pin)]
        svc.flush()
        # wait() re-raises the error of a ticket that holds a FailedResult
        got_g = [svc.wait(t) for t in gathers]
        got_r = [svc.wait(t) for t in rmws]
        got_p = [svc.wait(t) for t in progs]
        jax.block_until_ready((got_g, got_r, got_p))
        served += time.perf_counter() - t0

        for t, (i, got) in enumerate(zip(gidx, got_g)):
            same(got, ref[i], f"window {w} tenant {t} gather")
        for t, (p, got) in enumerate(zip(pin, got_p)):
            program.check(got, p, f"window {w} tenant {t} program")
        for i, v in zip(uidx, uval):
            np.add.at(ref, i, v)
        check(all(r is got_r[0] for r in got_r),
              f"window {w}: RMW tickets resolved to different tables")
        same(got_r[0], ref, f"window {w} table after the fused RMW")
        table = got_r[0]
    return table, served


class WordRead:
    """Table-1 indirect load ``A[B[i]]`` over a 1-D word table, one tile."""

    def __init__(self, words, ref: np.ndarray, tile: int):
        import jax.numpy as jnp
        from repro.core import Access, Load, Pattern, Var, compile_pattern
        pattern = Pattern([Access("LD", "A", Load("B", Var("i")),
                                  dtype="f32")], name="word_read")
        self.prog, info = compile_pattern(pattern, tile_size=tile)
        self.out = info["loads"]["A"]
        self.words, self.ref, self.tile = words, ref, tile
        self.iota = jnp.arange(tile, dtype=jnp.int32)
        self.keys = None

    def inputs(self, rng: np.random.Generator):
        if self.keys is None:
            self.keys = ZipfKeys(self.ref.shape[0], rng)
        env = {"A": self.words, "B": self.keys(self.tile),
               "__iota__": self.iota}
        regs = {"tile_base": 0, "N": self.tile, "tile_end": self.tile}
        return env, regs

    def check(self, got, inputs, what: str) -> None:
        env, _ = inputs
        _, spd = got
        same(spd[self.out], self.ref[env["B"]], what)


def service_phase(sizes: Sizes, seed: int):
    """(a) Flush windows through ``AccessService`` on one device. Returns
    the table after the last window, its host reference and the facts."""
    import jax
    import jax.numpy as jnp
    from repro.serve import AccessService

    dev = jax.devices()[0]
    rng = np.random.default_rng(seed)
    table = device_table((sizes.rows, sizes.width), jnp.float32, seed)
    words = device_table((sizes.words,), jnp.float32, seed + 1)
    ref, ref_words = np.array(table), np.asarray(words)
    program = WordRead(words, ref_words, sizes.tile)
    svc = AccessService(tile_size=sizes.tile, auto_flush=0)
    with compile_clock() as compiled:
        table, served = serve_windows(svc, table, ref, sizes, rng,
                                      program=program)
    counters = check_counters(svc.stats())
    facts = report("service", wall_s=served, compile_s=compiled[0],
                   peak_bytes_in_use=peak_bytes(dev), windows=sizes.windows,
                   tenants=sizes.tenants, table_bytes=table.nbytes,
                   **counters)
    return table, ref, facts


def kernel_phase(table, ref: np.ndarray, sizes: Sizes, seed: int) -> dict:
    """(b) The Pallas row-table kernels through ``bulk_gather`` and
    ``bulk_rmw`` on the service's table, then on a bfloat16 table."""
    import jax
    import jax.numpy as jnp
    from repro.core import bulk_gather, bulk_rmw
    from repro.kernels import common

    dev = jax.devices()[0]
    rng = np.random.default_rng(seed + 2)
    keys = ZipfKeys(sizes.rows, rng)
    idx = keys(sizes.keys)
    uidx = keys(sizes.updates)
    uval = updates(rng, sizes.updates, sizes.width)
    # bf16 holds integers exactly up to 256: one update per row, so each
    # row sees a single rounding-free add
    bf16 = device_table((sizes.bf16_rows, sizes.width), jnp.bfloat16,
                        seed + 3, high=128)
    bf_ref = np.asarray(bf16).astype(np.float32)
    bf_idx = rng.permutation(sizes.bf16_rows)[:sizes.keys].astype(np.int32)
    bf_val = updates(rng, sizes.keys, sizes.width)

    t0 = time.perf_counter()
    with compile_clock() as compiled:
        got = jax.block_until_ready(bulk_gather(table, idx, use_kernel=True))
        new = jax.block_until_ready(
            bulk_rmw(table, uidx, uval, op="ADD", use_kernel=True))
        bf_got = bulk_gather(bf16, idx % sizes.bf16_rows, use_kernel=True)
        bf_new = jax.block_until_ready(bulk_rmw(
            bf16, bf_idx, jnp.asarray(bf_val, jnp.bfloat16), op="ADD",
            use_kernel=True))
    wall = time.perf_counter() - t0

    same(got, ref[idx], "kernel gather")
    np.add.at(ref, uidx, uval)
    same(new, ref, "kernel RMW")
    same(np.asarray(bf_got).astype(np.float32),
         bf_ref[idx % sizes.bf16_rows], "bf16 kernel gather")
    np.add.at(bf_ref, bf_idx, bf_val)
    same(np.asarray(bf_new).astype(np.float32), bf_ref, "bf16 kernel RMW")
    compiled_kernels = not common.interpret()
    if compiled_kernels:      # a Mosaic kernel, not the Pallas interpreter
        for fn, args in ((bulk_gather, (table, idx)),
                         (bulk_rmw, (table, uidx, uval))):
            check("tpu_custom_call" in fn.lower(*args,
                                                use_kernel=True).as_text(),
                  f"{fn.__name__}: no compiled kernel in its program")
    return report("kernel", wall_s=wall, compile_s=compiled[0],
                  peak_bytes_in_use=peak_bytes(dev), keys=sizes.keys,
                  updates=sizes.updates, compiled_kernels=compiled_kernels)


def mesh_phase(sizes: Sizes, seed: int, chips: int) -> dict:
    """``--chips N``: the service over a row-partitioned table on N chips."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.distributed.mesh import as_mesh
    from repro.serve import AccessService

    mesh = as_mesh(chips)
    rows = NamedSharding(mesh, P(mesh.axis_names[0]))
    rng = np.random.default_rng(seed)
    table = device_table((sizes.rows, sizes.width), jnp.float32, seed,
                         sharding=rows)
    shards = {s.device.id: s.data.shape for s in table.addressable_shards}
    check(len(shards) == chips and all(
        s[0] == sizes.rows // chips for s in shards.values()),
        f"table not row-partitioned over {chips} chips: {shards}")
    placed = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
              for d in mesh.devices.flat}
    ref = np.array(table)
    svc = AccessService(mesh=mesh, auto_flush=0)
    with compile_clock() as compiled:
        table, served = serve_windows(svc, table, ref, sizes, rng)
    counters = check_counters(svc.stats())
    check(len(svc.last_report.shard_stats) > 0,
          "no fused node ran on the mesh")
    return report("mesh", wall_s=served, compile_s=compiled[0],
                  chips=chips, windows=sizes.windows,
                  bytes_in_use_after_build=placed,
                  bytes_in_use={d.id: (d.memory_stats() or {}).get(
                      "bytes_in_use") for d in mesh.devices.flat},
                  peak_bytes_in_use={d.id: peak_bytes(d)
                                     for d in mesh.devices.flat},
                  **counters)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded service phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {platform!r}; no result",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    if args.chips == 1:
        table, ref, _ = service_phase(ONE_CHIP, args.seed)
        kernel_phase(table, ref, ONE_CHIP, args.seed)
    else:
        mesh_phase(FOUR_CHIPS, args.seed, args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
