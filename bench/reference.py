"""The plain reference and the comparison that decides ``correct``.

The reference is ``table[idx]`` and ``table[idx] += values`` in NumPy,
replayed over every update the service applied in the run, warm-up
included. It imports nothing of the program and reads nothing the program
made: initial cells are recomputed from the seed (``data.cell_values``) and
updates from the benchmark's own streams. An update of lane ``j`` in
column ``w`` is ``coef[j] * col[w]``, so a row's state after any prefix of
the run is ``init[row, w] + s[row] * col[w]``, where ``s[row]`` sums the
coefficients that reached it; ``s`` is kept in float64 and is exact.

Table cells and updates are integers held as float32 and every sum stays
below 2**24, so the served values are exact whatever order the device adds
in: each compared number is a widest absolute gap, and its limit is 0.

The control puts this same reference, computed in bfloat16 (the precision
below the configuration's float32), in the program's place.
"""
from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np

from data import cell_values

LIMIT = 0.0        # exact comparison: integer data held in float32


@dataclasses.dataclass
class Ledger:
    """What the run applied and what it read back, in the order it ran.

    ``versions[k]`` lists the pool ids of the updates fused into the window
    that made table version ``k + 1``; ``reads`` holds sampled gathers as
    ``(pool_id, version read, served rows)``."""
    pool: list
    versions: list = dataclasses.field(default_factory=list)
    reads: list = dataclasses.field(default_factory=list)


class Reference:
    def __init__(self, cfg: dict, words: tuple, col: np.ndarray):
        self.width = cfg["row_words"]
        self.high = cfg["value_range"]
        self.words = words
        self.col = col.astype(np.float64)

    def init_rows(self, rows: np.ndarray) -> np.ndarray:
        """Initial cells of ``rows``: (len(rows), width) float64; width 1
        for a 1-D table."""
        w = max(self.width, 1)
        flat = (rows.astype(np.uint32)[:, None] * np.uint32(w)
                + np.arange(w, dtype=np.uint32)[None, :])
        return cell_values(flat, self.words, self.high).astype(np.float64)

    def rows(self, rows: np.ndarray, s: np.ndarray) -> np.ndarray:
        """Reference state of ``rows`` whose coefficient sums are ``s``."""
        return self.init_rows(rows) + s[:, None] * self.col[None, :]

    def rows_bf16(self, rows: np.ndarray, s: np.ndarray) -> np.ndarray:
        """The control: the same state held and added in bfloat16."""
        bf = ml_dtypes.bfloat16
        out = (self.init_rows(rows).astype(bf)
               + (s[:, None] * self.col[None, :]).astype(bf))
        return out.astype(bf).astype(np.float64)


def _positions(sorted_rows: np.ndarray, idx: np.ndarray):
    """Lanes of ``idx`` that fall in ``sorted_rows``, and where."""
    if sorted_rows.size == 0:
        return np.zeros(0, np.int64), np.zeros(idx.shape, bool)
    p = np.searchsorted(sorted_rows, idx)
    p = np.minimum(p, sorted_rows.size - 1)
    hit = sorted_rows[p] == idx
    return p[hit], hit


def coefficient_sums(ledger: Ledger, rows: np.ndarray,
                     k: int) -> np.ndarray:
    """``s`` over ``rows`` after the first ``k`` versions (float64)."""
    srows, back = np.unique(np.asarray(rows), return_inverse=True)
    counts = np.zeros(len(ledger.pool), np.int64)
    for v in ledger.versions[:k]:
        np.add.at(counts, np.asarray(v, np.int64), 1)
    s = np.zeros(srows.size, np.float64)
    for pid in np.nonzero(counts)[0]:
        req = ledger.pool[pid]
        p, hit = _positions(srows, np.asarray(req.update_idx))
        s += counts[pid] * np.bincount(
            p, weights=np.asarray(req.coef)[hit].astype(np.float64),
            minlength=srows.size)
    return s[back.reshape(-1)]


def compare(ledger: Ledger, ref: Reference, final_rows: np.ndarray,
            final_served: np.ndarray, *, control: bool = False) -> dict:
    """The compared numbers: the widest gap of any sampled gather and of
    the sampled rows of the final table. With ``control`` the bfloat16
    reference stands in for what the service served."""
    state = ref.rows_bf16 if control else None
    gaps = {}
    if ledger.reads:
        gap = 0.0
        for pid, version, served in ledger.reads:
            rows = np.asarray(ledger.pool[pid].read_idx)
            s = coefficient_sums(ledger, rows, version)
            want = ref.rows(rows, s)
            got = state(rows, s) if control else \
                np.asarray(served, np.float64).reshape(want.shape)
            gap = max(gap, float(np.max(np.abs(got - want))))
        gaps["read_gap"] = gap
    s = coefficient_sums(ledger, final_rows, len(ledger.versions))
    want = ref.rows(final_rows, s)
    got = state(final_rows, s) if control else \
        np.asarray(final_served, np.float64).reshape(want.shape)
    gaps["state_gap"] = float(np.max(np.abs(got - want)))
    return {name: {"value": v, "limit": LIMIT} for name, v in gaps.items()}
