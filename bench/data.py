"""Data and traffic made from ``--seed``: tables, index streams, updates.

Every value the benchmark feeds the service is a pure function of the seed.
Table cells and IS keys come from one integer hash (``mix32``) written twice,
once for NumPy and once for ``jax.numpy``, so the device can build a table in
one jitted call while the reference recomputes any row it needs on the host,
without reading anything back from the program.
"""
from __future__ import annotations

import dataclasses

import numpy as np

_M1, _M2 = 0x85EBCA6B, 0xC2B2AE35     # murmur3 finalizer constants
RANK_SEED = 12          # Zipfian ranks: the same for every seed


def seed_words(seed: int) -> tuple:
    """Two 32-bit words from any non-negative seed (wider than 32 bits too)."""
    w = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return int(w[0]), int(w[1])


def mix32(x, xp=np):
    """murmur3's 32-bit finalizer; identical on NumPy and ``jax.numpy``
    uint32 arrays (both wrap on overflow)."""
    u = xp.uint32
    x = x ^ (x >> u(16))
    x = x * u(_M1)
    x = x ^ (x >> u(13))
    x = x * u(_M2)
    return x ^ (x >> u(16))


def cell_values(flat, words: tuple, high: int, xp=np):
    """Integer cell values in ``[-high, high)`` for flat cell positions
    (``row * width + column``), as int32."""
    u = xp.uint32
    h = mix32(mix32(flat.astype(u) ^ u(words[0])) + u(words[1]), xp)
    return (h % u(2 * high)).astype(xp.int32) - high


def nas_is_keys(lane, words: tuple, max_key: int, xp=np):
    """NAS IS keys: the sum of four uniforms of ``max_key / 4`` each (the
    mean of four uniforms scaled to the key range), one per lane."""
    u = xp.uint32
    bits = int(max_key).bit_length() - 3          # log2(max_key / 4)
    lane = lane.astype(u) * u(4)
    key = xp.zeros(lane.shape, u)
    for i in range(4):
        h = mix32((lane + u(i)) ^ u(words[0]), xp) + u(words[1])
        key = key + (mix32(h, xp) >> u(32 - bits))
    return key.astype(xp.int32)


# the mix's ``key_distribution``s that have a generator: keys drawn on the
# host (``keys: host``, ``host_pool``), and keys made on the device
# (``keys: resident``) by (lane, seed words, max_key, xp) -> int32 keys
HOST_KEYS = ("scrambled_zipfian", "uniform")
RESIDENT_KEYS = {"nas_is": nas_is_keys}


def fnv1a64(x: np.ndarray) -> np.ndarray:
    """YCSB's FNV-1a hash of each 64-bit value (ScrambledZipfianGenerator)."""
    x = x.astype(np.uint64)
    h = np.full(x.shape, 0xCBF29CE484222325, np.uint64)
    for _ in range(8):
        h ^= x & np.uint64(0xFF)
        h *= np.uint64(0x100000001B3)
        x >>= np.uint64(8)
    return h


class ZipfKeys:
    """Scrambled-Zipfian keys over ``n`` items: rank r is drawn with
    probability proportional to 1/r^theta, then hashed onto the key space
    so that the hot keys are spread over the table, as YCSB does.

    The ranks come from ``rank_rng``, which every seed shares, and the seed
    enters through the scramble (``salt``): every seed then asks for the
    same multiset of ranks, so the same amount of duplication and work,
    on other rows."""

    def __init__(self, n: int, theta: float, rank_rng: np.random.Generator,
                 salt: int):
        w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** theta
        self.cdf = np.cumsum(w) / w.sum()
        self.n = n
        self.rng = rank_rng
        self.salt = np.uint64(salt) << np.uint64(32)

    def __call__(self, k: int) -> np.ndarray:
        rank = np.searchsorted(self.cdf, self.rng.random(k)).astype(np.uint64)
        return (fnv1a64(rank ^ self.salt) % np.uint64(self.n)).astype(
            np.int32)


@dataclasses.dataclass
class Request:
    """One pool entry: the keys a client reads and the keys it updates.
    The update of lane j in column w is ``coef[j] * col[w]``, where
    ``coef`` is value set ``value_set`` of the mix."""
    pool_id: int
    client: int
    read_idx: object           # host (or device) int32 keys
    update_idx: object
    value_set: int
    coef: np.ndarray


def host_pool(cfg: dict, mix: dict, seed: int) -> list:
    """Per-client pools of host index streams (``keys: host``). Every seed
    gets the same sizes; only the keys and coefficients differ."""
    rng = np.random.default_rng([int(seed), 1])
    rows = cfg["rows"]
    if mix["key_distribution"] == "scrambled_zipfian":
        keys = ZipfKeys(rows, mix["zipf_constant"],
                        np.random.default_rng(RANK_SEED),
                        seed_words(seed)[0])
    else:
        def keys(k):
            return rng.integers(0, rows, size=k, dtype=np.int32)
    n_read = read_lanes(mix)
    n_upd = mix["request_ops"] - n_read
    span = mix["update_range"]
    sets = [rng.integers(-span, span + 1, size=n_upd).astype(np.int8)
            for _ in range(mix["value_sets"] if n_upd else 0)]
    pool = []
    for c in range(mix["clients"]):
        for _ in range(mix["pool_per_client"]):
            v = len(pool) % len(sets) if sets else 0
            pool.append(Request(len(pool), c, keys(n_read), keys(n_upd), v,
                                sets[v] if sets else np.zeros(0, np.int8)))
    return pool, sets


def read_lanes(mix: dict) -> int:
    return int(round(mix["request_ops"] * mix["read_fraction"]))


def column_pattern(width: int, seed: int) -> np.ndarray:
    """Per-column multiplier of an update, in {-2, -1, 1, 2} (1 for a 1-D
    table): a column that lands in the wrong place shows."""
    if width == 0:
        return np.ones(1, np.int8)
    rng = np.random.default_rng([int(seed), 2])
    return (rng.integers(1, 3, size=width) *
            rng.choice([-1, 1], size=width)).astype(np.int8)
