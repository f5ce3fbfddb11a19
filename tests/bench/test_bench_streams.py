"""Inputs are a pure function of the seed, and the NumPy and device twins
of the benchmark's hash agree bit for bit."""
import jax.numpy as jnp
import numpy as np
from conftest import TINY_A, TINY_C, TINY_TABLE

import data


def _streams(seed, mix):
    pool, sets = data.host_pool(TINY_TABLE, mix, seed)
    return [(r.read_idx, r.update_idx, r.coef) for r in pool], sets


def _same(a, b):
    return all(np.array_equal(x, y) for p, q in zip(a, b)
               for x, y in zip(p, q))


def test_index_streams_repeat_for_a_seed_and_differ_across_seeds():
    big = 3_000_000_017                      # wider than 32 bits
    for mix in (TINY_A, TINY_C):
        a, sa = _streams(big, mix)
        b, sb = _streams(big, mix)
        c, _ = _streams(big + 1, mix)
        assert _same(a, b) and all(np.array_equal(x, y)
                                   for x, y in zip(sa, sb))
        assert not _same(a, c)
        # every seed gets the same sizes
        assert [tuple(map(np.shape, r)) for r in a] == \
            [tuple(map(np.shape, r)) for r in c]
        for r, _, _ in a:
            assert r.dtype == np.int32 and r.min() >= 0 \
                and r.max() < TINY_TABLE["rows"]


def test_zipf_keys_are_skewed_and_uniform_keys_are_not():
    zipf, _ = _streams(5, dict(TINY_A, request_ops=4096, read_fraction=1.0))
    flat = np.concatenate([r for r, _, _ in zipf])
    top = np.sort(np.bincount(flat))[::-1]
    assert top[0] > 20 * flat.size / TINY_TABLE["rows"]
    uni, _ = _streams(5, dict(TINY_C, request_ops=4096))
    flat = np.concatenate([r for r, _, _ in uni])
    assert np.bincount(flat).max() < 5 * flat.size / TINY_TABLE["rows"]


def test_device_and_host_hash_agree():
    words = data.seed_words(2**40 + 3)
    flat = np.arange(1 << 12, dtype=np.uint32) * np.uint32(977)
    host = data.cell_values(flat, words, 1024)
    dev = data.cell_values(jnp.asarray(flat), words, 1024, jnp)
    assert np.array_equal(host, np.asarray(dev))
    assert host.min() >= -1024 and host.max() < 1024
    lane = np.arange(1 << 12, dtype=np.uint32)
    k_host = data.nas_is_keys(lane, words, 1 << 21)
    k_dev = data.nas_is_keys(jnp.asarray(lane), words, 1 << 21, jnp)
    assert np.array_equal(k_host, np.asarray(k_dev))
    assert k_host.min() >= 0 and k_host.max() < 1 << 21
    # the mean of four uniforms: centred, thinner at the ends
    assert abs(k_host.mean() / (1 << 20) - 1) < 0.05
