"""The needed-bytes arithmetic of ``access_roofline`` and the peaks table."""
import json

import numpy as np
import pytest

import data
import roofline


def test_window_bytes_counts_distinct_rows_once():
    reads = [np.array([1, 2, 2, 3]), np.array([3, 4])]     # 4 distinct
    updates = [np.array([7, 7, 8])]                       # 2 distinct
    got = roofline.window_bytes(reads, updates, row_bytes=1024,
                                value_row_bytes=1024)
    assert got == (4 * 1024            # distinct rows read once
                   + 6 * (1024 + 4)    # result rows written, indices read
                   + 2 * 2 * 1024      # updated rows read and written
                   + 3 * (4 + 1024))   # update indices and values read


def test_needed_bytes_sums_windows_and_reuses_equal_ones():
    pool = [data.Request(i, 0, np.array([i, i + 1]), np.array([i]), 0,
                         np.ones(1, np.int8)) for i in range(3)]
    windows = [([0, 1], [2]), ([1, 0], [2]), ([2], [])]
    one = roofline.window_bytes([pool[0].read_idx, pool[1].read_idx],
                                [pool[2].update_idx], 16, 16)
    last = roofline.window_bytes([pool[2].read_idx], [], 16, 16)
    assert roofline.needed_bytes(windows, pool, 16, 16) == 2 * one + last


def test_long_streams_count_distinct_rows_alike():
    rng = np.random.default_rng(0)
    s = [rng.integers(0, 1 << 16, size=1 << 20) for _ in range(2)]
    assert roofline._distinct(s) == np.unique(np.concatenate(s)).size


def test_peaks_of_the_v5e_and_an_unknown_kind(tmp_path):
    p = roofline.peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9
    assert p["bf16_flops_per_s"] == 197e12 and p["ici_bits_per_s"] == 1.6e12
    assert "cloud.google.com" in p["source"]
    with pytest.raises(KeyError, match="TPU v9"):
        roofline.peaks_for("TPU v9")
    other = tmp_path / "peaks.json"
    other.write_text(json.dumps({"cpu": {"hbm_bytes_per_s": 1.0}}))
    assert roofline.peaks_for("cpu", other)["hbm_bytes_per_s"] == 1.0
