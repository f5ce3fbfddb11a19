"""One benchmark cell, run once, through the access service's public entry.

A cell names a configuration (``bench/configs/<name>.json``: the deployment,
its table, dtype and flush policy) and a traffic mix
(``bench/traffic/<name>.json``: clients, request size, operation mix, key
distribution, loop). Each metric is a reader of its own,
``bench/metrics/<name>.py``, that reduces the run's record (``Run``) to one
number or to nothing. The harness knows no cell, mix or metric by name.

The loop is closed: each client has at most one request outstanding and
sends its next one as soon as the last one's results are ready on the
device. A request is one client's bulk submission of ``request_ops`` keys:
a ``submit_gather`` of the read share and a ``submit_rmw`` ADD of the rest.
Windows close by the service's own flush policy (``auto_flush`` in the
configuration); the harness closes one itself only after the measured
window has ended, to drain what is left.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

import numpy as np

import data
import reference
import roofline
import trace_reduce

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_SECONDS = 5.0        # the traced stretch of a --trace 1 run
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class Refused(RuntimeError):
    """The run cannot give a result here (platform, chips, device kind)."""


# ---------------------------------------------------------------------------
# discovery: everything about a cell comes from files found by name
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    mix: dict
    end_to_end: list
    per_layer: list
    root: Path


def _json(path) -> dict:
    return json.loads(Path(path).read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: Path = ROOT) -> Cell:
    spec = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    cfg = _json(root / conf["file"])
    mix = _json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    check_sizes(cfg, mix)
    return Cell(
        name=name, chips=int(w["chips"]), cfg=cfg, mix=mix,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        root=root)


def check_sizes(cfg: dict, mix: dict) -> None:
    """The sizes a configuration states in its source's terms are the sizes
    the run uses, and the mix's keys have a generator: else ValueError.

    ``recordcount`` (YCSB) and ``max_key`` (NAS IS) are the table's rows;
    ``fieldcount`` fields of ``fieldlength`` bytes fit in a row; keys
    resident on the device number ``total_keys``, ``request_ops`` for each
    client."""
    rows = cfg["rows"]
    for key in ("recordcount", "max_key"):
        if key in cfg and cfg[key] != rows:
            raise ValueError(f"{key} {cfg[key]} is not the table's {rows} "
                             "rows")
    if "fieldcount" in cfg:
        record = cfg["fieldcount"] * cfg["fieldlength"]
        room = max(cfg["row_words"], 1) * np.dtype(cfg["dtype"]).itemsize
        if record > room:
            raise ValueError(f"a {record}-byte record does not fit a "
                             f"{room}-byte row")
    data.check_keys(mix)
    if mix["keys"] == "resident":
        keys = mix["clients"] * mix["request_ops"]
        for key in ("total_keys", "max_key"):
            if key not in cfg:
                raise ValueError(f"resident keys need the configuration's "
                                 f"{key}")
        if keys != cfg["total_keys"]:
            raise ValueError(f"the mix's {mix['clients']} clients of "
                             f"{mix['request_ops']} keys hold {keys} keys, "
                             f"not the configuration's {cfg['total_keys']}")


def metric_reader(root: Path, name: str):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# the run's record, which the metric readers reduce
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    window_s: float              # window start to its last completion
    ops: int                     # operations of the window's requests
    latencies_s: list            # every request of the window, in seconds
    setup_s: float
    peak_bytes: int              # on the fullest chip
    data_bytes: int              # table and resident keys, all chips
    chips: int
    spans: list                  # (name, start, end) inside the window
    compiles: int                # backend compiles inside the window
    trace: dict | None           # trace_reduce's summary (--trace 1)
    peaks: dict
    needed_bytes: object         # () -> least HBM bytes of the window


class Spans:
    """The benchmark's spans around its calls into the service: kept in
    memory, and written into the profiler's trace while one runs."""

    def __init__(self):
        self.records = []
        self.annotation = None

    @contextlib.contextmanager
    def span(self, name: str):
        ann = self.annotation(name) if self.annotation else \
            contextlib.nullcontext()
        t0 = time.perf_counter()
        with ann:
            yield
        self.records.append((name, t0, time.perf_counter()))


class CompileCount:
    def __init__(self):
        self.count = 0

    def __call__(self, event, duration, **_):
        if event == _BACKEND_COMPILE:
            self.count += 1


# ---------------------------------------------------------------------------
# the deployment on the device
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Deployment:
    table: object
    pool: list
    values: list                 # device update values, one per value set
    col: np.ndarray
    words: tuple
    data_bytes: int
    mesh: object


def build(cell: Cell, seed: int, devices) -> Deployment:
    """Table, keys and update values, made on the device from the seed."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec, \
        SingleDeviceSharding

    cfg, mix = cell.cfg, cell.mix
    words = data.seed_words(seed)
    rows, width = cfg["rows"], cfg["row_words"]
    shape = (rows, width) if width else (rows,)
    mesh = None
    if cell.chips > 1:
        from repro.distributed.mesh import as_mesh
        mesh = as_mesh(cell.chips)
        rowwise = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
    else:
        rowwise = SingleDeviceSharding(devices[0])
    one = SingleDeviceSharding(devices[0])

    def make_table():
        u = jnp.uint32
        flat = jax.lax.broadcasted_iota(u, shape, 0) * u(max(width, 1))
        if width:
            flat = flat + jax.lax.broadcasted_iota(u, shape, 1)
        return data.cell_values(flat, words, cfg["value_range"],
                                jnp).astype(cfg["dtype"])
    table = jax.jit(make_table, out_shardings=rowwise)()
    col = data.column_pattern(width, seed)
    data_bytes = table.nbytes

    if mix["keys"] == "resident":
        lanes, clients = mix["request_ops"], mix["clients"]
        kwords = data.seed_words(int(seed) + 1)
        generate = data.RESIDENT_KEYS[mix["key_distribution"]]

        def make_keys():
            lane = jax.lax.iota(jnp.uint32, cfg["total_keys"])
            k = generate(lane, kwords, cfg["max_key"], jnp)
            return tuple(k[c * lanes:(c + 1) * lanes]
                         for c in range(clients))
        keys = jax.jit(make_keys, out_shardings=one)()
        ones = jax.jit(lambda: jnp.ones((lanes,), cfg["dtype"]),
                       out_shardings=one)()
        coef = np.ones(lanes, np.int8)
        pool = [data.Request(c, c, np.zeros(0, np.int32), keys[c], 0, coef)
                for c in range(clients)]
        values = [ones]
        data_bytes += sum(k.nbytes for k in keys) + ones.nbytes
    else:
        pool, sets = data.host_pool(cfg, mix, seed)
        if sets:
            stacked = np.stack(sets)

            def make_values(c):
                v = c.astype(jnp.float32)
                if width:
                    v = v[:, :, None] * jnp.asarray(col, jnp.float32)
                return tuple(v.astype(cfg["dtype"]))
            # left uncommitted, so that a mesh may place them as it needs
            values = list(jax.jit(make_values)(stacked))
        else:
            values = []
    return Deployment(table, pool, values, col, words, int(data_bytes), mesh)


# ---------------------------------------------------------------------------
# the closed loop
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Flight:
    req: data.Request
    t_submit: float
    parts: int
    version: int = -1            # table version its gather read
    outputs: list = dataclasses.field(default_factory=list)
    dispatched: int = 0
    failed: bool = False


class ClosedLoop:
    def __init__(self, svc, dep: Deployment, mix: dict, spans: Spans,
                 seed: int):
        self.svc, self.dep, self.mix, self.spans = svc, dep, mix, spans
        self.table = dep.table
        self.clients = [svc.connect(f"client{c}")
                        for c in range(mix["clients"])]
        self.by_client = [[r for r in dep.pool if r.client == c]
                          for c in range(mix["clients"])]
        self.turn = [0] * mix["clients"]
        self.pending, self.inflight = [], collections.deque()
        self.version = 0
        self.ledger = reference.Ledger(dep.pool)
        self.windows = []            # (read pool ids, update pool ids)
        self.flushes = 0
        self.sample = np.random.default_rng([int(seed), 3])
        self.completed = []          # (flight, done time)
        self.copying = []            # sampled answers on their way out
        self.error = None
        orig = svc.flush_async

        def flush_async(*args, **kwargs):
            with spans.span("flush_async"):
                handle = orig(*args, **kwargs)
            self.flushes += 1
            return handle
        svc.flush_async = flush_async

    # -- submission ----------------------------------------------------------

    def submit(self, c: int) -> None:
        pool = self.by_client[c]
        req = pool[self.turn[c] % len(pool)]
        self.turn[c] += 1
        parts = [k for k, idx in (("read", req.read_idx),
                                  ("update", req.update_idx))
                 if int(np.size(idx))]
        f = Flight(req, time.perf_counter(), len(parts))
        for kind in parts:
            before = self.flushes
            with self.spans.span("submit"):
                if kind == "read":
                    f.version = self.version
                    t = self.clients[c].submit_gather(self.table,
                                                      req.read_idx)
                else:
                    t = self.clients[c].submit_rmw(
                        self.table, req.update_idx,
                        self.dep.values[req.value_set], op="ADD")
            self.pending.append((kind, t, f))
            if self.flushes != before:
                self._close()

    def _close(self) -> None:
        """The service flushed: collect the window's results through
        ``wait`` and move its finished requests in flight."""
        window, self.pending = self.pending, []
        reads, updates, new = [], [], None
        for kind, t, f in window:
            try:
                out = self.svc.wait(t)
                f.outputs.append(out)
                if kind == "update":
                    new = out
            except Exception as e:       # a failed plan node's error
                f.failed = True
                self.error = self.error or repr(e)
            (reads if kind == "read" else updates).append(f.req.pool_id)
            f.dispatched += 1
            if f.dispatched == f.parts:
                self.inflight.append(f)
        self.windows.append((reads, updates))
        if updates:
            self.ledger.versions.append(updates)
            self.version += 1
            self.table = new if new is not None else self.table

    # -- the loop ------------------------------------------------------------

    def run(self, stop) -> None:
        """Serve until ``stop(now)``; then drain every outstanding request.
        Clients keep one request outstanding each."""
        import jax
        for c in range(len(self.clients)):
            self.submit(c)
        while self.inflight or self.pending:
            if not self.inflight:
                if not stop(time.perf_counter()):
                    raise RuntimeError(
                        "the flush policy left a window open with every "
                        "client waiting on it")
                with self.spans.span("drain"):
                    self.svc.flush_async(inflight_ok=True)
                self._close()
                continue
            with self.spans.span("wait"):
                jax.block_until_ready(self.inflight[0].outputs)
            now = time.perf_counter()
            done = [self.inflight.popleft()]
            while self.inflight and all(
                    o.is_ready() for o in self.inflight[0].outputs):
                done.append(self.inflight.popleft())
            for f in done:
                self._retire(f, now)
            for f in done:
                if not stop(now):
                    self.submit(f.req.client)
        self._collect()

    def _collect(self) -> None:
        for pid, version, out in self.copying:
            self.ledger.reads.append((pid, version, np.asarray(out)))
        self.copying = []

    def _retire(self, f: Flight, now: float) -> None:
        # a sampled answer is copied to the host in the background and
        # collected at the next retirement, so that neither the copy's wait
        # nor a pile of held device buffers lands in the window
        self._collect()
        if (not f.failed and np.size(f.req.read_idx)
                and self.sample.random() < self.mix["check_share"]):
            f.outputs[0].copy_to_host_async()
            self.copying.append((f.req.pool_id, f.version, f.outputs[0]))
        f.outputs = []               # release the device buffers
        self.completed.append((f, now))


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def _peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def final_rows(dep: Deployment, cfg: dict, seed: int) -> np.ndarray:
    """Rows of the final table to compare: all of a small table; else the
    hottest updated rows and a uniform sample, drawn from the seed."""
    rows = cfg["rows"]
    if rows <= cfg["check_rows"]:
        return np.arange(rows, dtype=np.int32)
    rng = np.random.default_rng([int(seed), 4])
    hot = np.zeros(0, np.int32)
    upd = [np.asarray(r.update_idx) for r in dep.pool
           if np.size(r.update_idx)]
    if upd:
        cat = np.concatenate(upd)
        u, n = np.unique(cat, return_counts=True)
        hot = u[np.argsort(-n)[:cfg["check_rows"] // 4]]
    rest = rng.choice(rows, cfg["check_rows"] - hot.size, replace=False)
    return np.unique(np.concatenate([hot, rest.astype(np.int32)]))


def _use_compile_cache(root: Path) -> None:
    """JAX's persistent cache at the checkout's fixed path (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), keeping every compile."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_devices(chips: int, *, require_tpu: bool = True):
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise Refused(f"needs a TPU, found {platform!r}")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, found {len(devices)}")
    return devices


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, out_dir: Path, control: bool = False,
             chip: bool = True, peaks: dict | None = None) -> dict:
    """Set up, serve for ``seconds``, check against the reference and
    return the result line (a dict). With ``control`` the bfloat16
    reference stands in the program's place: ``correct`` and ``checks`` are
    its verdict and readings, and ``program`` holds the program's own, by
    the same comparison. ``chip=False`` (tests
    only) skips the look for a TPU and leaves JAX's compile cache alone."""
    import jax.monitoring as monitoring

    if chip:    # the TPU runtime's logs stay in the checkout, not in /tmp
        os.environ.setdefault("TPU_LOG_DIR",
                              str(cell.root / ".bench_out" / "tpu_logs"))
    devices = check_devices(cell.chips, require_tpu=chip)
    peaks = peaks if peaks is not None else \
        roofline.peaks_for(devices[0].device_kind)
    if chip:
        _use_compile_cache(cell.root)
    compiles = CompileCount()
    monitoring.register_event_duration_secs_listener(compiles)
    try:
        return _run(cell, seed, seconds, trace, t_start, out_dir, control,
                    devices, peaks, compiles)
    finally:
        monitoring.unregister_event_duration_listener(compiles)


def _run(cell, seed, seconds, trace, t_start, out_dir, control, devices,
         peaks, compiles) -> dict:
    import jax
    sys.path.insert(0, str(cell.root / "src"))
    from repro.serve import AccessService

    cfg, mix = cell.cfg, cell.mix
    dep = build(cell, seed, devices)
    svc_cfg = cfg["service"]
    svc = AccessService(tile_size=svc_cfg["tile_size"],
                        auto_flush=svc_cfg["auto_flush"],
                        max_batch=svc_cfg["max_batch"], mesh=dep.mesh)
    spans = Spans()
    loop = ClosedLoop(svc, dep, mix, spans, seed)
    rows_to_check = final_rows(dep, cfg, seed)

    # warm-up: the cell's own traffic, so exactly its shapes compile
    warm = mix["warm_requests_per_client"] * mix["clients"]
    loop.run(lambda now: len(loop.completed) >= warm)
    jax.block_until_ready(loop.table)
    warm_done = len(loop.completed)
    # set-up's objects live for the whole run: keep them out of the
    # collector's scans, so that a full collection in the window is short
    gc.collect()
    gc.freeze()

    length = min(seconds, TRACE_SECONDS) if trace else seconds
    trace_dir = out_dir / "trace"
    window_span = contextlib.ExitStack()
    if trace:
        from jax.profiler import ProfileOptions, TraceAnnotation
        opts = ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        spans.annotation = TraceAnnotation
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        window_span.enter_context(TraceAnnotation("window"))
    spans.records.clear()
    compiles.count = 0
    first_window = len(loop.windows)
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    end = t0 + length
    loop.run(lambda now: now >= end)
    jax.block_until_ready(loop.table)
    t_drained = time.perf_counter()
    gc.unfreeze()
    window_span.close()
    if trace:
        jax.profiler.stop_trace()
    in_window = compiles.count
    peak = _peak_bytes(devices[:cell.chips])

    # the window's requests are those submitted in its first ``length``
    # seconds; the rate takes all their work over all the time until the
    # last of them completed, so no request is cut in two
    served = loop.completed[warm_done:]
    latencies = [t - f.t_submit for f, t in served]
    ops = sum(mix["request_ops"] for f, t in served if not f.failed)
    span_s = max((t for _, t in served), default=t0) - t0
    failed = sum(1 for f, _ in served if f.failed)
    windows = loop.windows[first_window:]
    width = cfg["row_words"]
    row_bytes = max(width, 1) * np.dtype(cfg["dtype"]).itemsize

    def needed():
        return roofline.needed_bytes(windows, dep.pool, row_bytes,
                                     row_bytes)

    summary = None
    if trace:
        summary = trace_reduce.reduce_file(_xplane(trace_dir))
    run = Run(window_s=span_s, ops=ops, latencies_s=latencies,
              setup_s=setup_s, peak_bytes=peak, data_bytes=dep.data_bytes,
              chips=cell.chips, spans=spans.records, compiles=in_window,
              trace=summary, peaks=peaks, needed_bytes=needed)

    # the check: the program's state is read, then freed, before the
    # reference runs
    final_served = np.asarray(loop.table[rows_to_check])
    ledger, error = loop.ledger, loop.error
    del loop, svc, dep.table, dep.values
    ref = reference.Reference(cfg, dep.words, dep.col)

    def judge(stand_in: bool):
        checks = reference.compare(ledger, ref, rows_to_check, final_served,
                                   control=stand_in)
        checks["failed"] = {"value": failed, "limit": 0}
        return all(v["value"] <= v["limit"] for v in checks.values()), checks
    correct, checks = judge(control)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = metric_reader(cell.root, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    line = {"correct": bool(correct), "attempted": len(served),
            "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        line["breakdown"] = {"device_ops": summary["device_ops"],
                             "idle_gaps": summary["idle_gaps"]}
    line["facts"] = {"windows": len(windows), "drain_s": t_drained - end,
                     "reads_checked": len(ledger.reads),
                     "rows_checked": int(rows_to_check.size),
                     "compiles_in_window": in_window,
                     "error": error}
    if control:     # what the program served, judged the same way
        own_correct, own = judge(False)
        line["program"] = {"correct": own_correct, "checks": own}
    line["checks"] = checks
    return line


def _xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    return found[-1]
