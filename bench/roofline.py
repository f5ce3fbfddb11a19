"""The least HBM traffic a run's accesses need, and the peaks to divide by.

A flush window's gathers need each distinct row read once, each result row
written once and each index read once. Its updates need each distinct
updated row read and written once, and each index and update value read
once. The count depends only on the benchmark's own indices and shapes,
never on how the service implements the access (XLA or a Pallas kernel).
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"
INDEX_BYTES = 4


def peaks_for(device_kind: str, path: Path = PEAKS) -> dict:
    """The peaks of one device kind; an unknown kind is an error."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table)}")
    return table[device_kind]


def _distinct(streams: list) -> int:
    if not streams:
        return 0
    cat = np.concatenate([np.asarray(s).reshape(-1) for s in streams])
    if cat.size > 1 << 20:        # counting beats sorting on long streams
        return int(np.count_nonzero(np.bincount(cat)))
    return int(np.unique(cat).size)


def window_bytes(reads: list, updates: list, row_bytes: int,
                 value_row_bytes: int) -> int:
    """Needed bytes of one flush window: ``reads`` and ``updates`` are its
    index streams."""
    read_lanes = sum(int(np.size(s)) for s in reads)
    upd_lanes = sum(int(np.size(s)) for s in updates)
    return (_distinct(reads) * row_bytes
            + read_lanes * (row_bytes + INDEX_BYTES)
            + 2 * _distinct(updates) * row_bytes
            + upd_lanes * (INDEX_BYTES + value_row_bytes))


def needed_bytes(windows: list, pool: list, row_bytes: int,
                 value_row_bytes: int) -> int:
    """Sum over flush windows, each given as ``(read pool ids, update pool
    ids)``; windows of the same make-up are counted once and reused."""
    memo, total = {}, 0
    for reads, updates in windows:
        key = (tuple(sorted(reads)), tuple(sorted(updates)))
        if key not in memo:
            memo[key] = window_bytes(
                [pool[p].read_idx for p in key[0]],
                [pool[p].update_idx for p in key[1]],
                row_bytes, value_row_bytes)
        total += memo[key]
    return total
