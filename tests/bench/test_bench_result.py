"""The result line's keys, and the refusal to run anywhere but a TPU."""
import json
import subprocess
import sys

from conftest import BENCH, run_tiny

import harness


def test_last_line_keys(tiny_root):
    line = run_tiny(tiny_root, "a")
    assert {"correct", "attempted", "failed", "metrics", "device"} <= \
        set(line)
    assert list(line)[-1] == "checks"
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"ops_per_s", "p95_request_ms",
                                    "setup_s"}      # no peak on the CPU
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(line)


def test_traced_run_reports_per_layer_metrics_only(tiny_root, monkeypatch):
    monkeypatch.setattr(harness.trace_reduce, "reduce_file", lambda p: {
        "busy_s": 0.1, "window_s": 0.5, "devices": 1, "collective_s": 0.0,
        "device_ops": [["jit_x/fusion", 0.1]],
        "idle_gaps": [["flush_async", 0.2]]})
    monkeypatch.setattr(harness, "_xplane", lambda d: d)
    import time
    line = harness.run_cell(harness.find_cell("a", tiny_root), 3, 0.3,
                            True, t_start=time.perf_counter(),
                            out_dir=tiny_root / ".bench_out", chip=False,
                            peaks={"hbm_bytes_per_s": 1e9})
    assert set(line["metrics"]) == {"flush_host_ms", "compiles_in_window",
                                    "submit_host_us"}
    assert line["device"]["busy_s"] == 0.1
    assert line["device"]["window_s"] == 0.5
    assert line["breakdown"]["idle_gaps"] == [["flush_async", 0.2]]
    assert line["correct"] is True


def test_refuses_off_tpu_with_no_result():
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "ycsb_a_zipf",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr
