"""The reduction of the program's own ``dx.*`` spans (``program_trace``) and
the four readers built on it, on synthetic planes and on traces recorded
on a TPU v5e."""
import dataclasses
import gzip
import shutil
import types

import pytest
from conftest import BENCH
from test_bench_trace import _E, _L, _P

import harness
import program_trace
import trace_reduce

READERS = ("lower_host_ms", "sync_host_ms", "host_transfer_mb",
           "idle_in_flush_share")
OLD_TRACE = BENCH / "testdata" / "ycsb_a_zipf_tiny.xplane.pb.gz"
SPANS_TRACE = BENCH / "testdata" / "ycsb_a_zipf_spans.xplane.pb.gz"


@dataclasses.dataclass
class _S(_E):
    """An event with stats, as the profiler keeps a span's keywords."""
    stats: tuple = ()


def _chip(*ops):
    return _P("/device:TPU:0", [
        _L("XLA Modules", [_E("jit_f(1)", 0, 100)]),
        _L("XLA Ops", [_E(f"%fusion.{i} = f32[2] fusion()", s, e - s)
                       for i, (s, e) in enumerate(ops)])])


def _planes(flush_lower_start=12):
    """Window [0, 100); the chip runs [0,15) [25,36) [44,62) [80,100), so
    it idles in [15,25) [36,44) [62,80): 36 ns."""
    host = _P("/host:CPU", [_L("python", [
        _E("window", 0, 100),
        _E("dx.flush", -10, 8),                   # warm-up: outside
        _E("dx.flush", 10, 40),
        _E("dx.flush.lower", flush_lower_start, 30 - flush_lower_start),
        _S("dx.sync.a", 20, 8, (("bytes", 400),)),
        _E("dx.flush.emit", 30, 18),
        _S("dx.sync.b", 35, 10, (("bytes", 1000),)),
        _S("dx.h2d.c", 40, 2, (("bytes", 100),)),
        _E("flush_async", 9, 42),                 # a benchmark span
        _E("dx.submit", 60, 10),
        _S("dx.h2d.submit", 62, 2, (("bytes", 50),)),
        _E("dx.flush", 110, 10)])])               # after the window
    return [host, _chip((0, 15), (25, 36), (44, 62), (80, 100))]


def test_idle_split_exactly_by_innermost_span():
    r = program_trace.reduce_program(_planes())
    assert r["window_s"] == pytest.approx(100e-9)
    want = {"dx.flush.lower": 5, "dx.sync.a": 5, "dx.sync.b": 6,
            "dx.h2d.c": 2, "dx.h2d.submit": 2, "dx.submit": 6,
            "outside": 10}
    assert r["idle_by_span"] == {k: pytest.approx(v * 1e-9)
                                 for k, v in want.items()}
    idle = trace_reduce.reduce_planes(_planes())
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        idle["window_s"] - idle["busy_s"])


def test_a_tie_goes_to_the_span_that_ends_first():
    """``dx.flush`` and ``dx.flush.lower`` open at the same instant: the
    lowering, which ends first, is the innermost."""
    r = program_trace.reduce_program(_planes(flush_lower_start=10))
    assert r["idle_by_span"]["dx.flush.lower"] == pytest.approx(5e-9)
    assert "dx.flush" not in r["idle_by_span"]


def test_span_totals_bytes_and_idle():
    s = program_trace.reduce_program(_planes())["spans"]
    assert s["dx.flush"] == {"count": 1, "total_s": pytest.approx(40e-9),
                             "bytes": 0, "idle_s": pytest.approx(18e-9)}
    assert s["dx.sync.b"]["bytes"] == 1000
    assert s["dx.submit"]["idle_s"] == pytest.approx(8e-9)
    assert "flush_async" not in s


def _read(monkeypatch, name, prog):
    """The reader's value where the run's trace reduces to ``prog``."""
    monkeypatch.setattr(program_trace, "of", lambda run: prog)
    return harness.metric_reader(BENCH.parent, name)(None)


@pytest.mark.parametrize("name,value", [
    ("lower_host_ms", 18e-6), ("sync_host_ms", 18e-6),
    ("host_transfer_mb", 1550e-6), ("idle_in_flush_share", 18.0)])
def test_readers_on_synthetic_planes(monkeypatch, name, value):
    prog = program_trace.reduce_program(_planes())
    assert _read(monkeypatch, name, prog) == pytest.approx(value)


@pytest.mark.parametrize("name", READERS)
def test_readers_give_nothing_without_program_spans(monkeypatch, name):
    """The parent of these spans has none: the readers say nothing and do
    not raise."""
    host = _P("/host:CPU", [_L("python", [_E("window", 0, 100),
                                          _E("flush_async", 10, 20)])])
    prog = program_trace.reduce_program([host, _chip((0, 50))])
    assert prog["spans"] == {}
    assert prog["idle_by_span"] == {"outside": pytest.approx(50e-9)}
    assert _read(monkeypatch, name, prog) is None
    assert _read(monkeypatch, name, None) is None


def _checkout_with(tmp_path, trace):
    """A checkout whose last traced run wrote ``trace``."""
    out = tmp_path / ".bench_out" / "cell" / "trace" / "plugins" / \
        "profile" / "1"
    out.mkdir(parents=True)
    with gzip.open(trace, "rb") as src, \
            open(out / "host.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tmp_path


@pytest.mark.parametrize("trace", [OLD_TRACE, SPANS_TRACE],
                         ids=["before_spans", "with_spans"])
def test_the_run_finds_its_own_trace(tmp_path, trace, capsys):
    root = _checkout_with(tmp_path, trace)
    run = types.SimpleNamespace(trace=trace_reduce.reduce_file(trace))
    prog = program_trace.of(run, root)
    assert prog == program_trace.reduce_file(trace)
    assert bool(prog["spans"]) is (trace == SPANS_TRACE)
    assert capsys.readouterr().err == ""
    other = types.SimpleNamespace(trace=dict(run.trace, window_s=1.0))
    assert program_trace.of(other, root) is None      # not this run's
    assert "another run's trace" in capsys.readouterr().err
    assert program_trace.of(run, tmp_path / "empty") is None
    assert "no trace file" in capsys.readouterr().err
    assert program_trace.of(types.SimpleNamespace(trace=None), root) is None
    assert capsys.readouterr().err == ""              # an untraced run


@pytest.fixture(scope="module")
def recorded():
    return (program_trace.reduce_file(SPANS_TRACE),
            trace_reduce.reduce_file(SPANS_TRACE))


def test_recorded_spans_trace(recorded):
    prog, base = recorded
    assert base["devices"] == 1 and 0 < base["busy_s"] < base["window_s"]
    assert prog["window_s"] == base["window_s"]
    spans = prog["spans"]
    assert spans["dx.flush"]["count"] >= 2
    assert spans["dx.flush"]["count"] == spans["dx.flush.lower"]["count"]
    assert spans["dx.flush.lower"]["total_s"] < spans["dx.flush"]["total_s"]
    assert spans["dx.sync.rmw_idx"]["bytes"] > 0
    assert prog["idle_by_span"] and "outside" in prog["idle_by_span"]
    idle = base["window_s"] - base["busy_s"]
    assert sum(prog["idle_by_span"].values()) == pytest.approx(idle,
                                                               rel=1e-3)
    assert spans["dx.flush"]["idle_s"] <= idle


def test_recorded_spans_fall_inside_the_window():
    from jax.profiler import ProfileData
    with gzip.open(SPANS_TRACE, "rb") as f:
        planes = list(ProfileData.from_serialized_xspace(f.read()).planes)
    (lo, hi), = [(s, e) for s, e, n in trace_reduce._host_spans(planes)
                 if n == trace_reduce.WINDOW]
    flushes = [s for s, e, n, _ in program_trace._program_spans(
        planes, float("-inf"), float("inf")) if n == "dx.flush"]
    assert flushes and all(lo <= s < hi for s in flushes)
    ops = trace_reduce._op_events(trace_reduce._device_planes(planes)[0])
    assert any(lo <= s < hi for s, *_ in ops)


@pytest.mark.parametrize("name", READERS)
def test_readers_on_the_recorded_trace(monkeypatch, recorded, name):
    prog, base = recorded
    value = _read(monkeypatch, name, prog)
    assert value is not None and value >= 0
    if name == "idle_in_flush_share":
        assert value <= 100.0 * (1 - base["busy_s"] / base["window_s"])
