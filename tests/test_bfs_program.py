"""Graph500 BFS as access programs through ``AccessService.submit``
(``apps.bfs.search_parents``) against a plain NumPy Graph500 reference,
and what the scheduler does with such program groups: one vmapped
executable call under ``dx.program.run``, no read of device-resident
regions to the host for its report."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import bfs
from repro.core.scheduler import ProgramResult, Scheduler
from repro.serve import AccessService


def _kronecker(scale, edgefactor, seed):
    return bfs.make_graph(seed, n=1 << scale, avg_deg=edgefactor,
                          kronecker=True)


def _roots(g, count, seed):
    """``count`` distinct roots among the vertices of degree at least 1."""
    return np.random.default_rng(seed).permutation(
        np.flatnonzero(np.diff(g.indptr) > 0))[:count]


@pytest.mark.parametrize("scale,capacity,clients", [
    (10, 64, 4), (11, 256, 3), (12, 512, 4)])
def test_program_mode_matches_the_reference(scale, capacity, clients):
    g = _kronecker(scale, 16, scale)
    roots = _roots(g, clients + 2, seed=7)
    svc = AccessService(tile_size=capacity, auto_flush=0, max_batch=clients)
    got = bfs.search_parents(g, roots, capacity=capacity, clients=clients,
                             service=svc)
    shift = bfs.level_shift(g.n)
    for root, table in zip(roots, got):
        want = bfs.reference_parents(g, int(root))
        np.testing.assert_array_equal(table, want)
        levels = np.bincount((want >> shift)[want != bfs.UNREACHED])
        # the capacity is smaller than a level: levels take several tiles
        assert levels.max() > capacity
    assert svc.scheduler.stats["vmap_groups"] > 0
    assert svc.scheduler.stats["vmap_fallbacks"] == 0


def test_reference_parents_are_a_bfs_tree():
    g = _kronecker(10, 16, 1)
    root = int(_roots(g, 1, seed=2)[0])
    table = bfs.reference_parents(g, root)
    shift = bfs.level_shift(g.n)
    dist = bfs.reference(g, root, levels=g.n)
    reached = table != bfs.UNREACHED
    np.testing.assert_array_equal(reached, dist < bfs.INF)
    np.testing.assert_array_equal((table >> shift)[reached], dist[reached])
    parent = table & ((1 << shift) - 1)
    for v in np.flatnonzero(reached & (np.arange(g.n) != root)):
        p = parent[v]
        nbrs = g.adj[g.indptr[v]:g.indptr[v + 1]]
        # the smallest neighbour one level up
        assert p == nbrs[dist[nbrs] == dist[v] - 1].min()


def test_kronecker_graph_shape():
    g = _kronecker(10, 16, 0)
    assert g.n == 1024 and g.n_edges == 2 * 16 * 1024
    # each edge in both directions: the adjacency is symmetric
    src = np.repeat(np.arange(g.n), np.diff(g.indptr))
    fwd = np.sort(src.astype(np.int64) * g.n + g.adj)
    bwd = np.sort(g.adj.astype(np.int64) * g.n + src)
    np.testing.assert_array_equal(fwd, bwd)


def test_a_tile_reports_its_uncapped_total():
    g = _kronecker(10, 16, 4)
    indptr, adj = jnp.asarray(g.indptr), jnp.asarray(g.adj)
    root = int(np.argmax(np.diff(g.indptr)))      # a hub
    s = bfs.Search(0, [root], indptr, adj, capacity=16)
    svc = AccessService(tile_size=16, auto_flush=0)
    tile = s.next()
    out = svc.wait(svc.submit(s.program, tile.env, tile.regs))
    assert isinstance(out, ProgramResult)
    jax.block_until_ready(out)
    assert out.is_ready()
    s.retire(tile, out)
    degree = int(g.indptr[root + 1] - g.indptr[root])
    assert degree > 16
    assert tile.total == degree and tile.lanes == 16
    assert (s.level, s.first, s.start) == (0, 0, 16)   # same tile, next


def test_levels_past_the_packing_fail():
    # a path of 6 vertices: 3 vertex bits leave room for level 2 at most
    n = 6
    indptr = np.zeros(n + 1, np.int32)
    edges = [[1], [0, 2], [1, 3], [2, 4], [3, 5], [4]]
    indptr[1:] = np.cumsum([len(e) for e in edges])
    adj = np.concatenate(edges).astype(np.int32)
    assert bfs.max_level(bfs.level_shift(n)) == (2 ** 31 - 1 >> 3) - 1
    g = bfs.Graph(indptr, adj)
    np.testing.assert_array_equal(
        bfs.reference_parents(g, 0) >> 3, [0, 1, 2, 3, 4, 5])
    got = bfs.search_parents(g, [0], capacity=8, clients=1)
    np.testing.assert_array_equal(got[0], bfs.reference_parents(g, 0))


def _program_window(sched, env_of):
    prog = bfs.frontier_program(16)
    g = _kronecker(8, 4, 3)
    indptr, adj = env_of(g.indptr), env_of(g.adj)
    tickets = []
    for c in range(2):
        queue = np.full(16, g.n, np.int32)
        queue[0] = c
        tickets.append(sched.submit(
            prog, {"queue": env_of(queue), "indptr": indptr, "adj": adj,
                   "parent": env_of(np.full(g.n, bfs.UNREACHED, np.int32))},
            {"start": 0, "levelbits": 1 << 8}, tenant=f"t{c}"))
    return tickets


def test_program_group_over_device_arrays_reads_nothing_back():
    """The report's index-stream extraction skips regions already on the
    device: a window of programs over device arrays moves nothing from
    the device to the host (a guarded transfer would raise)."""
    sched = Scheduler()
    tickets = _program_window(sched, jnp.asarray)
    with jax.transfer_guard_device_to_host("disallow"):
        report = sched.flush()
        assert report.groups[0].vmapped
        assert report.groups[0].cross_coalescing == {}
    for t in tickets:
        assert isinstance(sched.result(t), ProgramResult)


def test_program_group_over_host_arrays_keeps_its_report():
    """Host regions still feed the coalescing report."""
    sched = Scheduler()
    _program_window(sched, np.asarray)
    report = sched.flush()
    assert report.groups[0].vmapped
    assert "indptr" in report.groups[0].cross_coalescing


def test_program_run_span_counts_the_stacked_bytes(tmp_path):
    from test_spans import traced_spans
    sched = Scheduler()
    _program_window(sched, jnp.asarray)
    sched.flush()                       # compile outside the trace
    spans = traced_spans(
        lambda: (_program_window(sched, jnp.asarray), sched.flush()),
        tmp_path)
    runs = [s for s in spans if s[0] == "dx.program.run"]
    assert len(runs) == 1
    # per member: the queue tile stacked in, the parent table in and out
    n = 256
    assert runs[0][3]["bytes"] == 2 * (16 * 4 + 2 * n * 4)
    emit = [s for s in spans if s[0] == "dx.emit.program_group.vmap"]
    assert emit and emit[0][1] <= runs[0][1] and runs[0][2] <= emit[0][2]
