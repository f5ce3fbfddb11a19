"""Tiny cells for the benchmark's CPU tests: a checkout root in a temporary
directory with BENCHMARK.json, small configurations and mixes, and the
benchmark's own metric readers."""
import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

PEAKS = {"hbm_bytes_per_s": 819e9}

TINY_SERVICE = {"tile_size": 256, "auto_flush": 4, "max_batch": 32}
TINY_TABLE = {"kind": "keyed", "rows": 2048, "row_words": 16,
              "dtype": "float32", "value_range": 1024, "check_rows": 512,
              "service": TINY_SERVICE}
TINY_HIST = {"kind": "keyed", "rows": 1024, "row_words": 0,
             "dtype": "float32", "value_range": 1024, "check_rows": 1024,
             "total_keys": 16384, "max_key": 1024, "service": TINY_SERVICE}
TINY_A = {"loop": "closed", "clients": 4, "request_ops": 256,
          "read_fraction": 0.5, "key_distribution": "scrambled_zipfian",
          "zipf_constant": 0.99, "keys": "host", "pool_per_client": 4,
          "value_sets": 2, "update_range": 4,
          "warm_requests_per_client": 2, "check_share": 1.0}
TINY_C = dict(TINY_A, read_fraction=1.0, key_distribution="uniform",
              value_sets=0, update_range=0)
TINY_IS = {"loop": "closed", "clients": 4, "request_ops": 4096,
           "read_fraction": 0.0, "key_distribution": "nas_is",
           "keys": "resident", "pool_per_client": 1, "value_sets": 1,
           "update_range": 0, "warm_requests_per_client": 1,
           "check_share": 0.0}


def _metric(name, **kw):
    return {"name": name, "unit": "x", "better": "lower",
            "source": "host_clock", **kw}


def make_root(tmp: Path) -> Path:
    """A checkout root holding three tiny cells and the real metric
    readers and deployment kinds."""
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir()
    shutil.copytree(BENCH / "metrics", tmp / "bench" / "metrics")
    shutil.copytree(BENCH / "kinds", tmp / "bench" / "kinds",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name, body in (("tiny_table", TINY_TABLE), ("tiny_hist", TINY_HIST)):
        (tmp / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(body))
    for name, body in (("tiny_a", TINY_A), ("tiny_c", TINY_C),
                       ("tiny_is", TINY_IS)):
        (tmp / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(body))
    spec = {
        "configs": [{"name": n, "file": f"bench/configs/{n}.json"}
                    for n in ("tiny_table", "tiny_hist")],
        "workloads": [
            {"name": "a", "config": "tiny_table", "traffic": "tiny_a",
             "chips": 1},
            {"name": "c", "config": "tiny_table", "traffic": "tiny_c",
             "chips": 1},
            {"name": "is", "config": "tiny_hist", "traffic": "tiny_is",
             "chips": 1}],
        "end_to_end": [_metric(n) for n in (
            "ops_per_s", "p95_request_ms", "peak_hbm_per_data_byte",
            "setup_s")],
        "per_layer": [_metric(n) for n in (
            "flush_host_ms", "compiles_in_window", "submit_host_us")]}
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def run_tiny(root: Path, cell: str, seed: int = 7, **kw) -> dict:
    import time

    import harness
    return harness.run_cell(harness.find_cell(cell, root), seed, 0.5, False,
                            t_start=time.perf_counter(),
                            out_dir=root / ".bench_out", chip=False,
                            peaks=PEAKS, **kw)


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)
