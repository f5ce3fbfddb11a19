"""Level-synchronous BFS push — the paper's graph domain (GAP BFS).

Each level expands the frontier's CSR adjacency ranges through the Range
Fuser (``fuse_ranges`` — the paper's Fig. 5 unit, exactly the
frontier-expansion shape it exists for), gathers the neighbor ids with one
bulk fetch, and relaxes distances with one fused conditional ``MIN`` RMW:

    access  k : (outer, inner, total) = fuse_ranges(H[:-1], H[1:],
                                                    cond=frontier_k)
                nbrs = adj[inner]                   (submit_gather)
    compute k : dist = MIN-RMW(dist, nbrs, k+1, cond=valid)
                frontier_{k+1} = (dist == k+1)      (newly discovered)

The frontier is a dense boolean mask and the fused edge stream has static
capacity (the edge count), so every shape is static and nothing ever syncs
to the host: pipelined, level k+1's expansion dispatches while level k's
relaxation is still in flight — on a mesh, with the gather and the RMW
each running owner-locally per shard. Everything is int32, so eager,
pipelined and sharded runs are all bit-exact against the sequential
NumPy oracle (MIN is order-independent).

Graph500 BFS (Kernel 2) runs as DX100 access programs instead
(``search_parents``; the on-chip benchmark's ``bfs`` kind drives the same
``Search``): one program relaxes at most ``capacity`` adjacency entries
of one level, fused from a tile of the level's frontier queue (``SLD``
queue -> ``ILD`` indptr lo/hi -> ``RNG`` from a start register -> ``ILD``
adj and parent -> ``ALUS`` pack -> ``IRMW MIN`` parent), submitted
through ``AccessService.submit``. A parent word packs
``(level << shift) | parent``, so ``MIN`` keeps the earliest level and,
within it, the smallest parent: a deterministic Graph500 parent tree.
A level longer than one tile takes several programs (RNG start 0,
capacity, ...); the next level's queue is made on the device from the
parent table once the last of them has retired.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bulk_ops, isa, range_fuser
from repro.pipeline import DecoupledLoop, run_sequential

INF = np.int32(2 ** 30)


@dataclasses.dataclass
class Graph:
    indptr: np.ndarray   # (n+1,) int32 CSR offsets
    adj: np.ndarray      # (E,)   int32 neighbor ids

    @property
    def n(self) -> int:
        return self.indptr.shape[0] - 1

    @property
    def n_edges(self) -> int:
        return self.adj.shape[0]


def make_graph(seed: int = 0, *, n: int = 512, avg_deg: int = 4,
               kronecker: bool = False) -> Graph:
    """Random directed graph in CSR (degree-capped, self-loops allowed —
    they relax to a no-op). ``kronecker=True`` makes Graph500's graph
    instead: ``n`` a power of two, ``avg_deg`` its edgefactor
    (``avg_deg * n`` undirected edges, each level drawing one bit of the
    row and then one of the column conditioned on it, initiator
    ``KRONECKER_ABC``), vertex labels randomly permuted, every edge stored
    in both directions (self-loops and duplicates kept)."""
    rng = np.random.default_rng(seed)
    if kronecker:
        return _kronecker(rng, n, avg_deg)
    deg = rng.integers(0, 2 * avg_deg + 1, size=n)
    indptr = np.zeros(n + 1, np.int32)
    indptr[1:] = np.cumsum(deg)
    adj = rng.integers(0, n, size=int(indptr[-1])).astype(np.int32)
    return Graph(indptr, adj)


def reference(g: Graph, src: int, *, levels: int) -> np.ndarray:
    """Sequential frontier-queue BFS capped at ``levels`` hops."""
    dist = np.full(g.n, INF, np.int32)
    dist[src] = 0
    frontier = [src]
    for level in range(levels):
        nxt = []
        for v in frontier:
            for e in range(g.indptr[v], g.indptr[v + 1]):
                w = g.adj[e]
                if dist[w] == INF:
                    dist[w] = level + 1
                    nxt.append(w)
        frontier = nxt
    return dist


def run(g: Graph, src: int, *, levels: int, mode: str = "pipelined",
        service=None, mesh=None) -> np.ndarray:
    """BFS distances after ``levels`` push iterations (NumPy int32).

    Modes as in ``apps.spmv.run``. The access phase exercises
    ``range_fuser`` (frontier expansion) + the scheduler's gather fast
    path; the compute phase submits the fused conditional MIN RMW.
    """
    lo = jnp.asarray(g.indptr[:-1])
    hi = jnp.asarray(g.indptr[1:])
    # an edgeless graph still runs: one sentinel row (every fused lane is
    # invalid, so it is never observed — gathers just need a non-empty table)
    adj = jnp.asarray(g.adj if g.n_edges else np.zeros(1, np.int32))
    cap = max(g.n_edges, 1)
    dist0 = jnp.full((g.n,), INF, jnp.int32).at[src].set(0)
    frontier0 = jnp.zeros((g.n,), bool).at[src].set(True)

    def expand(frontier):
        outer, inner, total = range_fuser.fuse_ranges(
            lo, hi, capacity=cap, cond=frontier)
        valid = range_fuser.fused_valid_mask(total, cap)
        return inner, valid

    if mode == "eager":
        dist, frontier = dist0, frontier0
        for level in range(levels):
            inner, valid = expand(frontier)
            nbrs = bulk_ops.bulk_gather(adj, inner)
            dist = bulk_ops.bulk_rmw(
                dist, nbrs, jnp.full((cap,), level + 1, jnp.int32),
                op="MIN", cond=valid)
            frontier = dist == (level + 1)
        return np.asarray(dist)

    if service is None:
        from repro.serve import AccessService
        service = AccessService(mesh=mesh, auto_flush=0)
    sched = service.scheduler
    aux = {}   # k -> validity mask of that level's fused edge stream

    def access(loop, k, state):
        _, frontier = state
        inner, valid = expand(frontier)
        aux[k] = valid
        return loop.submit_gather(adj, inner)

    def compute(k, state, nbrs):
        dist, _ = state
        valid = aux.pop(k)
        t = sched.submit_rmw(dist, nbrs,
                             jnp.full((cap,), k + 1, jnp.int32),
                             op="MIN", cond=valid)
        # second window of the level: the RMW. inflight_ok — this window
        # deliberately overlaps the loop's already-dispatched access
        # window (the in-flight guard exists for accidental overlap)
        sched.flush_async(inflight_ok=True)
        dist = sched.result(t)    # future — never synced on host
        return dist, dist == (k + 1)

    state = (dist0, frontier0)
    if mode == "sequential":
        state = run_sequential(service, state, levels, access, compute)
    elif mode == "pipelined":
        state = DecoupledLoop(service).run(state, levels, access, compute)
    else:
        raise ValueError(f"unknown mode {mode!r}")
    return np.asarray(state[0])


def demo(seed: int = 0, *, mode: str = "pipelined", mesh=None,
         levels: int = 8) -> np.ndarray:
    return run(make_graph(seed), 0, levels=levels, mode=mode, mesh=mesh)


def demo_reference(seed: int = 0, *, levels: int = 8) -> np.ndarray:
    return reference(make_graph(seed), 0, levels=levels)


# ---------------------------------------------------------------------------
# Graph500 BFS (Kernel 2) as access programs
# ---------------------------------------------------------------------------

UNREACHED = np.int32(2 ** 31 - 1)
KRONECKER_ABC = (0.57, 0.19, 0.19)      # Graph500's initiator; D = 0.05


def level_shift(n: int) -> int:
    """Bits of a vertex id: a parent word is ``(level << shift) | parent``."""
    return max(1, int(n - 1).bit_length())


def max_level(shift: int) -> int:
    """Deepest level a parent word can hold; the next value up is
    ``UNREACHED``'s."""
    return (int(UNREACHED) >> shift) - 1


def frontier_program(capacity: int) -> isa.AccessProgram:
    """Relax up to ``capacity`` adjacency entries of one frontier tile.

    Regions: ``queue`` (the tile of the frontier queue, padded with the
    vertex count ``n``, whose range is empty), ``indptr`` (n+1 CSR
    offsets), ``adj`` and ``parent`` (packed words, written). Registers:
    ``start`` (first fused position of this program's lanes) and
    ``levelbits`` (``(level + 1) << shift``). ``_rng_total`` comes back as
    the tile's whole fused count, so the client knows how many programs
    the tile takes."""
    return isa.AccessProgram((
        isa.SLD("i32", "queue", "v"),
        isa.ALUS("i32", "ADD", "v1", "v", 1),
        isa.ILD("i32", "indptr", "lo", "v"),
        isa.ILD("i32", "indptr", "hi", "v1"),
        isa.RNG("outer", "inner", "lo", "hi", rs1=capacity, rs2="start"),
        isa.ILD("i32", "adj", "nbr", "inner", tc="outer__mask"),
        isa.ILD("i32", "queue", "par", "outer", tc="outer__mask"),
        isa.ALUS("i32", "OR", "packed", "par", rs="levelbits"),
        isa.IRMW("i32", "parent", "MIN", "nbr", "packed",
                 tc="outer__mask"),
    ), tile_size=capacity, name="bfs_frontier_tile")


@partial(jax.jit, static_argnames=("n",))
def start_search(root, *, n: int):
    """A parent table with only ``root`` reached, at level 0, as its own
    parent."""
    return jnp.full((n,), UNREACHED, jnp.int32).at[root].set(
        jnp.asarray(root, jnp.int32))


@partial(jax.jit, static_argnames=("shift", "length"))
def level_queue(table, level, *, shift: int, length: int):
    """The vertices at ``level``, ascending, padded to ``length`` with the
    vertex count; and how many there are."""
    n = table.shape[0]
    at = (table >> shift) == level
    queue = jnp.sort(jnp.where(at, jnp.arange(n, dtype=jnp.int32), n))
    if length > n:
        queue = jnp.concatenate([queue, jnp.full((length - n,), n,
                                                 jnp.int32)])
    return queue, jnp.sum(at, dtype=jnp.int32)


@partial(jax.jit, static_argnames=("size",))
def queue_tile(queue, first, *, size: int):
    return jax.lax.dynamic_slice(queue, (first,), (size,))


@dataclasses.dataclass
class Tile:
    """One request: one program over a frontier tile's adjacency."""
    client: int
    search: int          # the client's search it belongs to (0, 1, ...)
    root: int
    level: int           # level of the frontier being expanded
    first: int           # first queue position of the frontier tile
    start: int           # first fused position within the tile's entries
    vertices: int        # frontier vertices in the tile
    env: dict = dataclasses.field(default=None, repr=False)
    regs: dict = dataclasses.field(default=None)
    total: int = -1      # the tile's fused entries, from the program
    lanes: int = 0       # entries this request relaxed

    parts = ("expand",)


class Search:
    """One client's Graph500 searches, a root after another (cycling).

    ``next()`` gives the client's next request; ``retire(tile, out)``
    takes the program's outputs once they are ready: the new parent table
    and the tile's fused total, which says whether the tile needs another
    program. When a level's last program retires, the next level's queue
    is made from the table on the device and only its length is read
    back; an empty one ends the search (``on_done(search, table)``, the
    search's number and its final table) and starts the next."""

    def __init__(self, client: int, roots, indptr, adj, *, capacity: int,
                 on_done=None):
        self.client, self.roots = client, [int(r) for r in roots]
        self.indptr, self.adj = indptr, adj
        self.capacity = int(capacity)
        self.n = int(indptr.shape[0]) - 1
        self.shift = level_shift(self.n)
        self.length = -(-self.n // self.capacity) * self.capacity
        self.on_done = on_done
        self.program = frontier_program(self.capacity)
        self.done = 0                # searches finished
        self._begin(0)

    def _begin(self, search: int) -> None:
        self.search = search
        self.root = self.roots[search % len(self.roots)]
        # placed where the graph is, as the program's outputs will be, so
        # that every search's launches share one executable
        self.table = jax.device_put(start_search(self.root, n=self.n),
                                    self.indptr.sharding)
        self.level = 0
        self._open_level()

    def _open_level(self) -> None:
        queue, count = level_queue(self.table, self.level, shift=self.shift,
                                   length=self.length)
        self.count = int(count)
        if not self.count:
            self.done += 1
            if self.on_done is not None:
                self.on_done(self.search, self.table)
            self._begin(self.search + 1)
            return
        if self.level + 1 > max_level(self.shift):
            raise RuntimeError(
                f"BFS level {self.level + 1} does not fit a parent word of "
                f"{self.shift} vertex bits")
        self.queue, self.first, self.start = queue, 0, 0

    def next(self) -> Tile:
        tile = Tile(self.client, self.search, self.root, self.level,
                    self.first, self.start,
                    min(self.capacity, self.count - self.first))
        tile.env = {"queue": queue_tile(self.queue, self.first,
                                        size=self.capacity),
                    "indptr": self.indptr, "adj": self.adj,
                    "parent": self.table}
        tile.regs = {"start": self.start,
                     "levelbits": (self.level + 1) << self.shift}
        return tile

    def retire(self, tile: Tile, out) -> None:
        """``out``: the program's ``(env, spd)``, ready; None where it
        failed, and then the same tile goes again."""
        tile.env = None
        if out is None:
            return
        env, spd = out
        self.table = env["parent"]
        tile.total = int(spd["_rng_total"])
        tile.lanes = min(self.capacity, max(tile.total - tile.start, 0))
        if tile.start + self.capacity < tile.total:
            self.start += self.capacity
            return
        self.first, self.start = self.first + self.capacity, 0
        if self.first < self.count:
            return
        self.level += 1
        self._open_level()


def search_parents(g: Graph, roots, *, capacity: int, clients: int = 4,
                   service=None) -> list:
    """Graph500 Kernel 2 through ``AccessService.submit``: ``clients``
    concurrent searches (client c takes roots c, c + clients, ...), each
    request one ``frontier_program``; the window holds one program of
    each active client. Returns each root's packed parent table (NumPy),
    in root order."""
    from repro.serve import AccessService
    svc = service or AccessService(tile_size=capacity, auto_flush=0,
                                   max_batch=clients)
    indptr = jnp.asarray(g.indptr)
    adj = jnp.asarray(g.adj if g.n_edges else np.zeros(1, np.int32))
    tables = [None] * len(roots)
    searches = []
    for c in range(min(clients, len(roots))):
        mine = range(c, len(roots), clients)      # positions in ``roots``

        def done(search, table, mine=mine):
            if search < len(mine):
                tables[mine[search]] = np.asarray(table)
        searches.append(Search(c, [roots[i] for i in mine], indptr, adj,
                               capacity=capacity, on_done=done))
    handles = [svc.connect(f"bfs{s.client}") for s in searches]
    while True:
        live = [s for s in searches if s.done < len(s.roots)]
        if not live:
            return tables
        sent = []
        for s in live:
            tile = s.next()
            sent.append((s, tile, handles[s.client].submit(
                s.program, tile.env, tile.regs)))
        svc.flush()
        for s, tile, ticket in sent:
            s.retire(tile, svc.wait(ticket))


def _kronecker(rng, n: int, edgefactor: int) -> Graph:
    scale = int(n).bit_length() - 1
    if n != 1 << scale:
        raise ValueError(f"a Kronecker graph has 2**scale vertices, not {n}")
    a, b, c = KRONECKER_ABC
    m = edgefactor * n
    row = np.zeros(m, np.int64)
    col = np.zeros(m, np.int64)
    for bit in range(scale):
        ii = rng.random(m) > a + b
        jj = rng.random(m) > np.where(ii, c / (1 - a - b), a / (a + b))
        row += ii.astype(np.int64) << bit
        col += jj.astype(np.int64) << bit
    perm = rng.permutation(n)
    src = np.concatenate([perm[row], perm[col]])
    dst = np.concatenate([perm[col], perm[row]])
    order = np.lexsort((dst, src))
    indptr = np.zeros(n + 1, np.int64)
    indptr[1:] = np.cumsum(np.bincount(src, minlength=n))
    return Graph(indptr.astype(np.int32), dst[order].astype(np.int32))


def reference_parents(g: Graph, root: int) -> np.ndarray:
    """Plain level-synchronous Graph500 BFS: each vertex's packed word
    ``(level << shift) | parent`` with the smallest parent among those one
    level up; ``UNREACHED`` elsewhere."""
    shift = level_shift(g.n)
    table = np.full(g.n, UNREACHED, np.int32)
    table[root] = root
    frontier = np.asarray([root])
    level = 0
    while frontier.size:
        lo, hi = g.indptr[frontier], g.indptr[frontier + 1]
        src = np.repeat(frontier, hi - lo)
        pos = np.repeat(lo - np.cumsum(hi - lo) + (hi - lo), hi - lo) + \
            np.arange(int((hi - lo).sum()))
        dst = g.adj[pos]
        cand = np.full(g.n, UNREACHED, np.int32)
        np.minimum.at(cand, dst, ((level + 1) << shift) | src)
        fresh = (table == UNREACHED) & (cand != UNREACHED)
        table[fresh] = cand[fresh]
        frontier = np.flatnonzero(fresh)
        level += 1
    return table
