"""The reduction from a profiler trace to busy, idle and per-op time, on a
short trace of ``ycsb_a_zipf`` recorded on a TPU v5e (0.33 s window)."""
import dataclasses

import pytest
from conftest import BENCH

import trace_reduce

TRACE = BENCH / "testdata" / "ycsb_a_zipf_tiny.xplane.pb.gz"


def test_recorded_chip_trace():
    r = trace_reduce.reduce_file(TRACE)
    assert r["devices"] == 1                  # the runtime's custom plane
    assert r["window_s"] == pytest.approx(0.330129896)      # is no chip
    assert r["busy_s"] == pytest.approx(0.099651006)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["collective_s"] == 0
    assert r["device_ops"][0][0] == "jit_bulk_rmw/copy"
    assert 1 <= len(r["device_ops"]) <= 10
    assert sum(v for _, v in r["device_ops"]) <= r["busy_s"] * 1.000001
    labels = {k for k, _ in r["idle_gaps"]}
    assert labels <= set(trace_reduce.HOST_SPANS) | {"host"}
    gaps = [v for _, v in r["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and len(gaps) <= 10
    assert sum(gaps) <= r["window_s"] - r["busy_s"] + 1e-9


def test_union_merges_and_clips():
    iv = [(0, 5, "a"), (3, 8, "b"), (10, 12, "c"), (-4, -1, "d")]
    assert trace_reduce.union(iv, 1, 11) == [[1, 8], [10, 11]]


def test_op_name_drops_ids_and_operands():
    assert trace_reduce.op_name(
        "%fusion.4 = f32[8]{0} fusion(f32[8]{0} %all-to-all.1)") == "fusion"
    assert trace_reduce.op_name("%all-to-all.3 = s32[4] all-to-all(%x)") \
        == "all-to-all"


@dataclasses.dataclass
class _E:
    name: str
    start_ns: float
    duration_ns: float


@dataclasses.dataclass
class _L:
    name: str
    events: list


@dataclasses.dataclass
class _P:
    name: str
    lines: list


def test_two_chips_and_a_collective():
    host = _P("/host:CPU", [_L("python", [
        _E("window", 0, 100), _E("flush_async", 10, 20),
        _E("wait", 60, 40)])])

    def chip(i, shift):
        return _P(f"/device:TPU:{i}", [
            _L("XLA Modules", [_E("jit_f(123)", 30 + shift, 30)]),
            _L("XLA Ops", [_E("%fusion.2 = f32[2] fusion()", 30 + shift, 5),
                           _E("%all_to_all.1 = f32[2] all-to-all()",
                              35 + shift, 5),
                           _E("%all-gather.1 = f32[2] all-gather()",
                              40 + shift, 20)])])
    planes = [host, chip(0, 0), chip(1, 10),
              _P("/device:CUSTOM:Megascale Trace", [])]
    r = trace_reduce.reduce_planes(planes)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(30e-9)
    assert r["collective_s"] == pytest.approx(25e-9)
    assert dict(r["device_ops"]) == {"jit_f/all-gather": pytest.approx(20e-9),
                                     "jit_f/all_to_all": pytest.approx(5e-9),
                                     "jit_f/fusion": pytest.approx(5e-9)}
    # chip 0 idles in [0, 30) under flush_async's midpoint and in [60, 100)
    assert r["idle_gaps"] == [["wait", pytest.approx(40e-9)],
                              ["flush_async", pytest.approx(30e-9)]]
