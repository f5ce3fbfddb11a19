"""The ``bfs`` kind (Graph500 BFS through access programs) at a tiny size
on the CPU: correct against its own reference, not correct for its
control or for a Range Fuser that ignores its start, and refused where
the sizes it states are not those that run."""
import json

import jax
import numpy as np
import pytest
from conftest import run_tiny

import harness
from repro.core import range_fuser
from repro.serve import AccessService

TINY_GRAPH = {"kind": "bfs", "scale": 10, "edgefactor": 16,
              "vertices": 1024, "undirected_edges": 16384,
              "csr_entries": 32768,
              "initiator": {"A": 0.57, "B": 0.19, "C": 0.19, "D": 0.05},
              "search_keys": 64,
              "service": {"tile_size": 256, "auto_flush": 4,
                          "max_batch": 4}}
TINY_SEARCHES = {"loop": "closed", "clients": 4, "chunk_lanes": 256,
                 "warm_requests_per_client": 2}


def _add_bfs(root, cfg=TINY_GRAPH, mix=TINY_SEARCHES):
    bench = root / "bench"
    (bench / "configs" / "tiny_graph.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "tiny_bfs.json").write_text(json.dumps(mix))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny_graph",
                            "file": "bench/configs/tiny_graph.json"})
    spec["workloads"].append({"name": "bfs", "config": "tiny_graph",
                              "traffic": "tiny_bfs", "chips": 1})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def test_bfs_is_correct_and_its_control_is_not(tiny_root):
    _add_bfs(tiny_root)
    line = run_tiny(tiny_root, "bfs", seed=2 ** 40 + 17)
    assert line["correct"] is True and line["failed"] == 0
    assert line["checks"]["parent_gap"]["value"] == 0
    assert line["attempted"] > 0 and line["metrics"]["ops_per_s"]["value"] > 0
    assert line["facts"]["compiles_in_window"] == 0
    assert line["facts"]["vertices_checked"] == 4 * 1024
    line = run_tiny(tiny_root, "bfs", seed=23, control=True)
    assert line["correct"] is False
    assert line["checks"]["parent_gap"]["value"] > 0
    assert line["program"]["correct"] is True


def test_a_fuser_that_caps_its_total_is_not_correct(tiny_root,
                                                     monkeypatch):
    """A Range Fuser that reports ``min(total, capacity)``: each tile takes
    one request and its entries past the capacity are never relaxed."""
    _add_bfs(tiny_root)
    orig = range_fuser.fuse_ranges

    def capped(lo, hi, *, capacity, start=0, cond=None):
        outer, inner, total = orig(lo, hi, capacity=capacity, start=start,
                                   cond=cond)
        return outer, inner, jax.numpy.minimum(total, capacity)
    monkeypatch.setattr(range_fuser, "fuse_ranges", capped)
    line = run_tiny(tiny_root, "bfs", seed=37)
    assert line["correct"] is False and line["failed"] == 0
    assert line["checks"]["parent_gap"]["value"] > 0


def test_a_fuser_that_ignores_its_start_is_not_correct(tiny_root,
                                                       monkeypatch):
    """Every tile re-reads its frontier tile's first entries."""
    _add_bfs(tiny_root)
    orig = range_fuser.fuse_ranges

    def from_zero(lo, hi, *, capacity, start=0, cond=None):
        return orig(lo, hi, capacity=capacity, cond=cond)
    monkeypatch.setattr(range_fuser, "fuse_ranges", from_zero)
    line = run_tiny(tiny_root, "bfs", seed=29)
    assert line["correct"] is False and line["failed"] == 0


def test_needed_bytes_count_every_lane(tiny_root):
    _add_bfs(tiny_root)
    cell = harness.find_cell("bfs", tiny_root)
    dep = cell.kind.build(cell.cfg, cell.mix, 31, 1, jax.devices())
    svc = AccessService(tile_size=256, auto_flush=0, max_batch=4)
    window = []
    for c in range(4):
        tile = dep.request(c)
        window.append((tile, dep.submit(tile, "expand", svc.connect(
            f"c{c}"))))
    svc.flush()
    outs = [(tile, svc.wait(t)) for tile, t in window]
    record = dep.closed([(tile, "expand", out) for tile, out in outs])
    for tile, out in outs:
        dep.retired(tile, [out])
    dep.read_back()
    assert dep.judge(False)["parent_gap"]["value"] == 0
    lanes = sum(t.lanes for t in record)
    assert lanes == sum(dep.ops(t) for t in record) > 0
    need = dep.needed_bytes([record])
    # adjacency, RMW index and value: 12 bytes a lane; 8 a frontier
    # vertex; each distinct parent row read and written (at least one)
    assert need > 12 * lanes + 8 * sum(t.vertices for t in record)
    assert need <= 20 * lanes + 8 * sum(t.vertices for t in record)


BAD = {
    "scale": ({"scale": 11}, {}),
    "edgefactor": ({"edgefactor": 8}, {}),
    "csr_entries": ({"csr_entries": 16384}, {}),
    "chunk_lanes": ({}, {"chunk_lanes": 512}),
    "past_int32": ({"scale": 26, "vertices": 2 ** 26,
                    "undirected_edges": 2 ** 30, "csr_entries": 2 ** 31},
                   {}),
    "more_clients_than_keys": ({"search_keys": 2}, {}),
}


@pytest.mark.parametrize("case", sorted(BAD))
def test_stated_sizes_must_be_what_runs(tiny_root, case):
    cfg_edit, mix_edit = BAD[case]
    _add_bfs(tiny_root, dict(TINY_GRAPH, **cfg_edit),
             dict(TINY_SEARCHES, **mix_edit))
    with pytest.raises(ValueError):
        harness.find_cell("bfs", tiny_root)


def test_the_cell_as_configured_passes_its_check():
    cell = harness.find_cell("graph500_bfs")
    assert cell.cfg["kind"] == "bfs" and cell.chips == 1
    assert cell.cfg["csr_entries"] == 2 ** 30
    names = [m["name"] for m in cell.per_layer]
    assert {"program_dispatch_ms", "program_stack_mb",
            "access_roofline"} <= set(names)
    for other in ("ycsb_a_zipf", "nas_is_c_rank", "ycsb_c_uniform"):
        names = [m["name"] for m in harness.find_cell(other).per_layer]
        assert "program_dispatch_ms" not in names
        assert "program_stack_mb" not in names


@pytest.mark.parametrize("spans,want", [
    ({"dx.flush": {"count": 4, "total_s": 1.0, "bytes": 0},
      "dx.program.run": {"count": 4, "total_s": 0.2, "bytes": 8e6}},
     (50.0, 2.0)),
    ({"dx.flush": {"count": 4, "total_s": 1.0, "bytes": 0}},
     (None, None))], ids=["programs", "no_programs"])
def test_program_metrics_read_the_program_run_span(monkeypatch, spans,
                                                   want):
    import program_trace
    monkeypatch.setattr(program_trace, "of",
                        lambda run: {"spans": spans, "window_s": 5.0})
    got = tuple(harness.metric_reader(harness.ROOT, name)(None)
                for name in ("program_dispatch_ms", "program_stack_mb"))
    assert got == want


def test_replay_is_the_plain_reference():
    """The kind's replay of a whole search equals a level-synchronous BFS
    written independently here."""
    kind = harness.find_kind(harness.ROOT, {"kind": "bfs"})
    rng = np.random.default_rng(0)
    n = 64
    src = rng.integers(0, n, 300)
    dst = rng.integers(0, n, 300)
    s = np.concatenate([src, dst])
    d = np.concatenate([dst, src])
    order = np.lexsort((d, s))
    indptr = np.concatenate([[0], np.cumsum(np.bincount(s, minlength=n))])
    adj = d[order].astype(np.int32)
    indptr = indptr.astype(np.int32)
    root = int(s[0])
    want = np.full(n, -1)
    want[root] = root
    level = {root: 0}
    frontier = [root]
    while frontier:
        nxt = {}
        for v in frontier:
            for w in adj[indptr[v]:indptr[v + 1]]:
                if int(w) not in level:
                    nxt[int(w)] = min(nxt.get(int(w), n), v)
        for w, p in nxt.items():
            level[w] = level[frontier[0]] + 1
            want[w] = p
        frontier = sorted(nxt)
    # each level's requests: tiles of 4 frontier vertices, at most 4
    # entries a request and at least one request a tile
    tiles = []
    for lv in range(max(level.values()) + 1):
        front = sorted(v for v, x in level.items() if x == lv)
        for first in range(0, len(front), 4):
            total = sum(indptr[v + 1] - indptr[v]
                        for v in front[first:first + 4])
            tiles += [(lv, first, s0) for s0 in range(0, max(total, 1), 4)]
    replay = kind.Replay(indptr, adj, 6, 4, False)
    table, left = replay.search(0, root, len(tiles))
    assert left == 0
    reached = table != kind.UNREACHED
    assert set(np.flatnonzero(reached)) == set(level)
    np.testing.assert_array_equal(table[reached] & 63, want[reached])
    np.testing.assert_array_equal(table[reached] >> 6,
                                  [level[v] for v in np.flatnonzero(reached)])


def test_the_build_in_blocks_makes_one_graph(monkeypatch):
    """The CSR does not depend on how many edges the build makes at once."""
    kind = harness.find_kind(harness.ROOT, {"kind": "bfs"})
    whole = [np.asarray(a) for a in kind.build_csr(TINY_GRAPH, 41,
                                                   jax.devices()[0])]
    monkeypatch.setattr(kind, "BUILD_BLOCK", 1 << 11)
    blocks = [np.asarray(a) for a in kind.build_csr(TINY_GRAPH, 41,
                                                    jax.devices()[0])]
    np.testing.assert_array_equal(whole[0], blocks[0])
    assert whole[0][-1] == TINY_GRAPH["csr_entries"]
    # each vertex has the same neighbours (their order follows the blocks)
    src = np.repeat(np.arange(TINY_GRAPH["vertices"]), np.diff(whole[0]))
    a, b = (adj[np.lexsort((adj, src))] for _, adj in (whole, blocks))
    np.testing.assert_array_equal(a, b)
