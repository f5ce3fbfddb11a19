"""One benchmark cell, run once, through the access service's public entry.

A cell names a configuration (``bench/configs/<name>.json``: the deployment,
its sizes, dtype and flush policy) and a traffic mix
(``bench/traffic/<name>.json``: clients, request sizes, operation mix,
loop). Each metric is a reader of its own, ``bench/metrics/<name>.py``,
that reduces the run's record (``Run``) to one number or to nothing. The
configuration's ``kind`` names its deployment kind,
``bench/kinds/<kind>.py``: what the deployment holds, what a request sends
and how the result is checked. The harness knows no cell, mix, metric or
kind by name.

The loop is closed: each client has at most one request outstanding and
sends its next one as soon as the last one's results are ready on the
device. Windows close by the service's own flush policy (``auto_flush`` in
the configuration); the harness closes one itself only after the measured
window has ended, to drain what is left.

A kind module gives ``check(cfg, mix)``, which raises ValueError where the
sizes its source states are not what runs, and ``build(cfg, mix, seed,
chips, devices)``, which makes the deployment on the device from the seed
and returns an object with:

    data_bytes           bytes of the data it keeps on the device, all chips
    mesh                 None, or the mesh the service spans
    request(c)           client ``c``'s next request: an object whose
                         ``parts`` names its submissions, in order; it may
                         be formed on the device from the outputs of the
                         client's last request, with no host read
    submit(req, part, client)
                         one submission through the client's handle; the
                         service's ticket
    closed(window)       a window closed: ``window`` lists (request, part,
                         output, or None where its ticket raised) in
                         submission order; the kind takes its new state
                         handles and returns its record of the window
    retired(req, outputs)
                         the request's outputs are ready (None where a
                         part failed); the kind keeps what it needs
    drained()            every request sent has completed
    ops(req)             operations the request does
    needed_bytes(windows)
                         least HBM bytes of these windows' accesses, from
                         the records ``closed`` returned
    read_back()          after the window: reads what it compares to the
                         host, drops its device state, and returns counts
                         of what it will compare
    judge(control)       {name: {"value", "limit"}} against its own plain
                         reference; with ``control`` the reference's
                         lower-precision stand-in is judged in the
                         program's place
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
    kind: object                 # the configuration's kind module


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
    kind = find_kind(root, cfg)
    kind.check(cfg, mix)
    return Cell(
        name=name, chips=int(w["chips"]), cfg=cfg, mix=mix,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        root=root, kind=kind)


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod          # for the dataclasses a module defines
    spec.loader.exec_module(mod)
    return mod


def find_kind(root: Path, cfg: dict):
    """The module of the configuration's ``kind``; ValueError naming the
    known kinds where it names none of them."""
    kinds = root / "bench" / "kinds"
    known = sorted(p.stem for p in kinds.glob("*.py"))
    name = cfg.get("kind")
    if name not in known:
        raise ValueError(f"configuration {cfg.get('name', '')!r} names kind "
                         f"{name!r}; known kinds: {known}")
    return _module(kinds / f"{name}.py", f"bench_kind_{name}")


def metric_reader(root: Path, name: str):
    return _module(root / "bench" / "metrics" / f"{name}.py",
                   f"bench_metric_{name}").read


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
    data_bytes: int              # kept on the device, all chips
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
# the closed loop
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Flight:
    req: object                  # the kind's request
    client: int
    t_submit: float
    outputs: list = dataclasses.field(default_factory=list)
    dispatched: int = 0
    failed: bool = False


class ClosedLoop:
    def __init__(self, svc, dep, clients: int, spans: Spans):
        self.svc, self.dep, self.spans = svc, dep, spans
        self.clients = [svc.connect(f"client{c}") for c in range(clients)]
        self.pending, self.inflight = [], collections.deque()
        self.windows = []            # the kind's record of each window
        self.flushes = 0
        self.completed = []          # (flight, done time)
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
        req = self.dep.request(c)
        f = Flight(req, c, time.perf_counter())
        for part in req.parts:
            before = self.flushes
            with self.spans.span("submit"):
                t = self.dep.submit(req, part, self.clients[c])
            self.pending.append((part, t, f))
            if self.flushes != before:
                self._close()

    def _close(self) -> None:
        """The service flushed: collect the window's results through
        ``wait`` and move its finished requests in flight."""
        window, self.pending = self.pending, []
        done = []
        for part, t, f in window:
            out = None
            try:
                out = self.svc.wait(t)
                f.outputs.append(out)
            except Exception as e:       # a failed plan node's error
                f.failed = True
                self.error = self.error or repr(e)
            done.append((f.req, part, out))
            f.dispatched += 1
            if f.dispatched == len(f.req.parts):
                self.inflight.append(f)
        self.windows.append(self.dep.closed(done))

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
                    self.submit(f.client)
        self.dep.drained()

    def _retire(self, f: Flight, now: float) -> None:
        self.dep.retired(f.req, None if f.failed else f.outputs)
        f.outputs = []               # release the device buffers
        self.completed.append((f, now))


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def _peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


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
    return the result line (a dict). With ``control`` the kind's
    lower-precision reference stands in the program's place: ``correct``
    and ``checks`` are its verdict and readings, and ``program`` holds the
    program's own, by the same comparison. ``chip=False`` (tests only)
    skips the look for a TPU and leaves JAX's compile cache alone."""
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
    dep = cell.kind.build(cfg, mix, seed, cell.chips, devices)
    svc_cfg = cfg["service"]
    svc = AccessService(tile_size=svc_cfg["tile_size"],
                        auto_flush=svc_cfg["auto_flush"],
                        max_batch=svc_cfg["max_batch"], mesh=dep.mesh)
    spans = Spans()
    loop = ClosedLoop(svc, dep, mix["clients"], spans)

    # warm-up: the cell's own traffic, so exactly its shapes compile
    warm = mix["warm_requests_per_client"] * mix["clients"]
    loop.run(lambda now: len(loop.completed) >= warm)
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
    ops = sum(dep.ops(f.req) for f, t in served if not f.failed)
    span_s = max((t for _, t in served), default=t0) - t0
    failed = sum(1 for f, _ in served if f.failed)
    windows = loop.windows[first_window:]

    summary = None
    if trace:
        summary = trace_reduce.reduce_file(_xplane(trace_dir))
    run = Run(window_s=span_s, ops=ops, latencies_s=latencies,
              setup_s=setup_s, peak_bytes=peak, data_bytes=dep.data_bytes,
              chips=cell.chips, spans=spans.records, compiles=in_window,
              trace=summary, peaks=peaks,
              needed_bytes=lambda: dep.needed_bytes(windows))

    # the check: the program's state is read, then freed, before the
    # reference runs
    checked = dep.read_back()
    error = loop.error
    del loop, svc

    def judge(stand_in: bool):
        checks = dep.judge(stand_in)
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
                     **checked,
                     "compiles_in_window": in_window,
                     "error": error}
    if control:     # what the program served, judged the same way
        own_correct, own = judge(False)
        line["program"] = {"correct": own_correct, "checks": own}
    line["checks"] = checks
    return line


def _xplane(trace_dir: Path) -> Path:
    found = sorted((p.stat().st_mtime, p)
                   for p in Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    return found[-1][1]
