"""The flush path's ``dx.*`` profiler spans (``repro.plan.spans``).

One window through ``AccessService``, traced by ``jax.profiler`` on the
CPU, yields the documented span tree: the submit, the lowering with one
span per pass, the emit with one span per root node, the report, and a
``dx.sync.*``/``dx.h2d.*`` span around each host read and upload carrying
the bytes it moved. The sharded names come from four virtual devices."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.plan import PIPELINE
from repro.plan.spans import to_device, to_host

HERE = Path(__file__).resolve().parent


def traced_spans(fn, trace_dir) -> list:
    """Run ``fn`` under the profiler (host tracer as the benchmark sets
    it); return its ``dx.*`` host events as (name, start, end, stats)."""
    from jax.profiler import ProfileData, ProfileOptions
    opts = ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = next(Path(trace_dir).rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("dx."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda e: e[1])


def inside(spans, name, parent) -> bool:
    """Every ``name`` span lies inside some ``parent`` span."""
    outer = [(s, e) for n, s, e, _ in spans if n == parent]
    return all(any(ps <= s and e <= pe for ps, pe in outer)
               for n, s, e, _ in spans if n == name)


def one_window(svc, gather_idx, rmw_idx):
    """A gather and an ADD RMW on two tables, flushed as one window."""
    rows = 64
    a = jnp.arange(rows * 4, dtype=jnp.float32).reshape(rows, 4)
    b = jnp.zeros((rows, 4), jnp.float32)
    vals = jnp.ones((len(rmw_idx), 4), jnp.float32)
    jax.block_until_ready((a, b, vals, gather_idx))
    g = svc.submit_gather(a, gather_idx)
    r = svc.submit_rmw(b, rmw_idx, vals, op="ADD")
    svc.flush_async().result()
    return svc.wait(g), svc.wait(r)


@pytest.fixture(scope="module")
def local_window(tmp_path_factory):
    """A 32-lane RMW (a power of two: no padding) from a host index
    array, and a gather whose resident device stream repeats rows. A
    fresh service lowers the traced window (its plan cache is empty, so
    the cost model measures); the shapes compiled before the trace."""
    from repro.serve import AccessService
    gidx = jnp.asarray(np.arange(48) % 12, jnp.int32)
    ridx = (np.arange(32) * 5 % 64).astype(np.int32)
    one_window(AccessService(tile_size=64, auto_flush=0), gidx, ridx)
    svc = AccessService(tile_size=64, auto_flush=0)
    return traced_spans(lambda: one_window(svc, gidx, ridx),
                        tmp_path_factory.mktemp("trace"))


def test_span_tree_of_one_window(local_window):
    names = {n for n, *_ in local_window}
    want = ({"dx.submit", "dx.h2d.submit", "dx.flush", "dx.flush.lower",
             "dx.flush.hazard_scan", "dx.cost.measure", "dx.flush.emit",
             "dx.emit.gather.bulk", "dx.emit.rmw.bulk", "dx.flush.report",
             "dx.sync.gather_unique", "dx.h2d.gather_unique",
             "dx.sync.rmw_idx", "dx.h2d.rmw"}
            | {f"dx.pass.{p}" for p in PIPELINE})
    assert want <= names, want - names
    assert sum(n == "dx.flush" for n, *_ in local_window) == 1
    assert sum(n == "dx.submit" for n, *_ in local_window) == 2


@pytest.mark.parametrize("name,parent", [
    ("dx.h2d.submit", "dx.submit"),
    ("dx.flush.lower", "dx.flush"),
    *[(f"dx.pass.{p}", "dx.flush.lower") for p in PIPELINE],
    ("dx.flush.hazard_scan", "dx.flush.lower"),
    ("dx.cost.measure", "dx.pass.coalesce"),
    ("dx.flush.emit", "dx.flush"),
    ("dx.emit.gather.bulk", "dx.flush.emit"),
    ("dx.emit.rmw.bulk", "dx.flush.emit"),
    ("dx.sync.gather_unique", "dx.emit.gather.bulk"),
    ("dx.h2d.gather_unique", "dx.emit.gather.bulk"),
    ("dx.sync.rmw_idx", "dx.emit.rmw.bulk"),
    ("dx.h2d.rmw", "dx.emit.rmw.bulk"),
    ("dx.flush.report", "dx.flush"),
])
def test_span_nesting(local_window, name, parent):
    assert any(n == name for n, *_ in local_window)
    assert inside(local_window, name, parent)


def test_transfer_bytes(local_window):
    by = {n: st for n, _, _, st in local_window}
    assert by["dx.sync.rmw_idx"]["bytes"] == 32 * 4     # int32 lanes
    assert by["dx.h2d.rmw"]["bytes"] == 32 * 4         # values stay put
    assert by["dx.h2d.submit"]["bytes"] == 32 * 4       # the host idx
    assert by["dx.cost.measure"]["outcome"] == "measured"
    assert by["dx.sync.gather_unique"]["bytes"] > 0
    # the 32-lane RMW needs no padding, so its values stay on the device
    assert "dx.sync.rmw_values" not in by
    assert by["dx.emit.rmw.bulk"]["combine"] == "segment"   # 2-D table


def test_rmw_span_names_scan_combine(tmp_path):
    """An RMW into a 1-D table marks its emit span ``combine=scan``."""
    from repro.serve import AccessService
    svc = AccessService(tile_size=64, auto_flush=0)
    table = jnp.zeros(64, jnp.float32)
    idx = np.arange(32, dtype=np.int32) % 8

    def window():
        t = svc.submit_rmw(table, idx, jnp.ones(32, jnp.float32), op="ADD")
        svc.flush_async().result()
        return svc.wait(t)

    window()
    spans = traced_spans(window, tmp_path)
    by = {n: st for n, _, _, st in spans}
    assert by["dx.emit.rmw.bulk"]["combine"] == "scan"


def test_padded_rmw_reads_its_values(tmp_path):
    """An RMW of 20 lanes pads to 32: its values come to the host and go
    back with the padded keys."""
    from repro.serve import AccessService
    svc = AccessService(tile_size=64, auto_flush=0)
    gidx = jnp.asarray(np.arange(24) % 6, jnp.int32)
    ridx = np.arange(20, dtype=np.int32)
    one_window(svc, gidx, ridx)
    spans = traced_spans(lambda: one_window(svc, gidx, ridx), tmp_path)
    by = {n: st for n, _, _, st in spans}
    assert by["dx.sync.rmw_idx"]["bytes"] == 20 * 4
    assert by["dx.sync.rmw_values"]["bytes"] == 20 * 4 * 4
    assert by["dx.h2d.rmw"]["bytes"] == 32 * 4 + 32 * 4 * 4  # padded


def test_results_unchanged_under_the_profiler(tmp_path):
    from repro.serve import AccessService
    svc = AccessService(tile_size=64, auto_flush=0)
    gidx = jnp.asarray(np.arange(48) % 12, jnp.int32)
    ridx = (np.arange(32) * 5 % 64).astype(np.int32)
    plain = one_window(svc, gidx, ridx)
    traced = []
    traced_spans(lambda: traced.append(one_window(svc, gidx, ridx)),
                 tmp_path)
    for x, y in zip(plain, traced[0]):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_to_host_counts_device_bytes_only(tmp_path):
    dev = jnp.arange(10, dtype=jnp.int32)
    host = np.arange(10, dtype=np.int32)
    out = []
    spans = traced_spans(lambda: out.extend(
        [to_host(dev, "one"), to_host([dev, host], "many"),
         to_host(host, "none")]), tmp_path)
    np.testing.assert_array_equal(out[0], host)
    assert [type(x) for x in out[1]] == [np.ndarray, np.ndarray]
    assert {n: st["bytes"] for n, _, _, st in spans} == {
        "dx.sync.one": 40, "dx.sync.many": 40, "dx.sync.none": 0}


def test_to_device_passes_device_arrays_through():
    dev = jnp.arange(4)
    assert to_device(dev, "t") is dev
    up = to_device(np.arange(4, dtype=np.int64), "t")
    assert isinstance(up, jax.Array) and up.dtype == jnp.int32


SHARDED = """
import json, sys
sys.path.insert(0, {here!r})
import jax, jax.numpy as jnp, numpy as np
from test_spans import one_window, traced_spans
from repro.serve import AccessService
svc = AccessService(tile_size=64, auto_flush=0, mesh=4)
gidx = jnp.asarray(np.arange(48) % 12, jnp.int32)
ridx = jnp.asarray(np.arange(32) * 5 % 64, jnp.int32)
one_window(svc, gidx, ridx)
spans = traced_spans(lambda: one_window(svc, gidx, ridx), {trace!r})
print(json.dumps([[n, s, e] for n, s, e, _ in spans]))
"""


def test_sharded_span_names(tmp_path):
    """Four virtual devices need a process of their own."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(HERE.parent / "src"))
    out = subprocess.run(
        [sys.executable, "-c",
         SHARDED.format(here=str(HERE), trace=str(tmp_path))],
        capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    spans = [(n, s, e, {}) for n, s, e in
             json.loads(out.stdout.strip().splitlines()[-1])]
    names = {n for n, *_ in spans}
    want = {"dx.emit.gather.sharded", "dx.emit.rmw.sharded",
            "dx.prefetch.gather", "dx.prefetch.rmw",
            "dx.sync.exchange_plan"}
    assert want <= names, want - names
    assert inside(spans, "dx.sync.exchange_plan", "dx.pass.shard")
    assert inside(spans, "dx.prefetch.rmw", "dx.flush.emit")
