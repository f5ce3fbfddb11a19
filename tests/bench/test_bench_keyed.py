"""The ``keyed`` kind's traffic is pinned. For one seed, the tiny cells'
table, pools and keys, update values, sampled-read choices, compared rows
and the needed bytes of a fixed list of windows hash to fixed digests; a
change that moves one of them changes the work that every keyed cell does,
and so its numbers."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness

SEED = 3_000_000_019
WINDOWS = [([0, 1], [2, 3]), ([1, 0], [3, 2]), ([2], []), ([], [0])]
SHARE = 0.37                  # a check share that draws both ways
PINNED = {
    "a": {"pool": "e9676ee833b7c802", "values": "3f3d5c0cd1ca1fea",
          "table": "58f94919cce84e82", "sampled": "63de1550c9aa4a8d",
          "n_sampled": 13, "rows": "98f850a0c7929b50",
          "needed_bytes": 161024, "data_bytes": 131072},
    "c": {"pool": "7b081638d578abdf", "values": "e3b0c44298fc1c14",
          "table": "58f94919cce84e82", "sampled": "63de1550c9aa4a8d",
          "n_sampled": 13, "rows": "8b60e1ae68af8b44",
          "needed_bytes": 160640, "data_bytes": 131072},
    "is": {"pool": "2601fb6d83b28488", "values": "3e4b790cef2bd913",
           "table": "b8ce9126399a940c", "sampled": "e3b0c44298fc1c14",
           "n_sampled": 0, "rows": "0c0063a202613a59",
           "needed_bytes": 181880, "data_bytes": 86016},
}


def digest(items) -> str:
    h = hashlib.sha256()
    for a in items:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_keyed_traffic_is_pinned(tiny_root, cell):
    found = harness.find_cell(cell, tiny_root)
    mix = dict(found.mix, check_share=SHARE)
    dep = found.kind.build(found.cfg, mix, SEED, 1, jax.devices())
    got = {"pool": digest([x for r in dep.pool for x in (
               r.pool_id, r.client, r.read_idx, r.update_idx, r.value_set,
               r.coef)]),
           "values": digest(dep.values),
           "table": digest([dep.table])}
    # which answers are kept for the check: every pool entry retired three
    # times, in pool order, the k-th time reading table version k
    for k in range(3):
        for r in dep.pool:
            dep.retired(found.kind.Sent(r, (), k),
                        [jnp.asarray(np.array([r.pool_id, k], np.int32))])
    dep.drained()
    got["sampled"] = digest([np.array([pid, v], np.int64)
                             for pid, v, _ in dep.ledger.reads])
    got["n_sampled"] = len(dep.ledger.reads)
    got["rows"] = digest([dep.rows])
    got["needed_bytes"] = dep.needed_bytes(WINDOWS)
    got["data_bytes"] = dep.data_bytes
    assert got == PINNED[cell]


def test_requests_go_round_robin_through_each_clients_pool(tiny_root):
    found = harness.find_cell("a", tiny_root)
    dep = found.kind.build(found.cfg, found.mix, SEED, 1, jax.devices())
    per = found.mix["pool_per_client"]
    for c in range(found.mix["clients"]):
        sent = [dep.request(c) for _ in range(2 * per)]
        ids = [s.entry.pool_id for s in sent]
        assert ids == 2 * [r.pool_id for r in dep.pool if r.client == c]
        assert all(s.parts == ("read", "update") for s in sent)
