"""Graph500 BFS deployments: a Kronecker graph in CSR, and concurrent
searches that expand their frontiers through DX100 access programs.

The graph is made on the device from the seed, in blocks: Graph500's
Kronecker generator (per level one bit of the row, then one bit of the
column conditioned on it; initiator A/B/C from the configuration), vertex
labels permuted by a seeded bijection, each edge stored in both
directions (self-loops and duplicates kept). ``indptr`` holds n+1 int32
offsets and ``adj`` the 2 * edges neighbour ids.

A request is one ``repro.apps.bfs.frontier_program`` through
``AccessService.submit``: at most ``chunk_lanes`` adjacency entries of one
client's current level, fused from a tile of its frontier queue. Each
client runs its own searches, one after another, from the configuration's
search keys (client c takes keys c, c + clients, ...), into a parent
table of its own: ``(level << scale) | parent`` words, ``MIN``-updated.

The check reads every client's parent table back and compares it whole,
vertex by vertex, with a plain NumPy replay on the CSR the build made.
The replay runs the client's searches from its own roots and its own
frontiers, relaxing every lane of each completed level with
``np.minimum.at``, and of the level still in flight the lanes of as many
requests as the client completed there, each request's extent found
from its own frontier (tiles of ``chunk_lanes`` vertices, at most
``chunk_lanes`` entries a request). Only the count of completed requests
comes from the run, so lanes a program drops or a request it skips show
up as a gap. It imports nothing of the program. The control applies
each lane as an overwrite in CSR order instead (the last writer of a
level wins): a valid BFS tree whose parents are not the smallest.
"""
from __future__ import annotations

import math
import time

import numpy as np

import data

GOLDEN = 0x9E3779B1
CANDIDATES = 4096            # drawn to find the search keys
REPLAY_LANES = 1 << 24       # lanes the reference expands at once
BUILD_BLOCK = 1 << 24        # edges the build makes at once, at most


def check(cfg: dict, mix: dict) -> None:
    """The sizes the configuration states in Graph500's terms are those the
    run uses: else ValueError."""
    scale, ef = cfg["scale"], cfg["edgefactor"]
    want = {"vertices": 1 << scale, "undirected_edges": ef << scale,
            "csr_entries": 2 * (ef << scale)}
    for key, value in want.items():
        if cfg[key] != value:
            raise ValueError(f"{key} {cfg[key]} is not the {value} of scale "
                             f"{scale}, edgefactor {ef}")
    if cfg["csr_entries"] > 2 ** 31 - 1:
        raise ValueError(f"{cfg['csr_entries']} CSR entries pass the int32 "
                         "index streams' end")
    if mix["chunk_lanes"] != cfg["service"]["tile_size"]:
        raise ValueError(f"chunk_lanes {mix['chunk_lanes']} is not the "
                         f"service's tile of {cfg['service']['tile_size']}")
    if mix["clients"] > cfg["search_keys"]:
        raise ValueError(f"{mix['clients']} clients for "
                         f"{cfg['search_keys']} search keys")
    ini = cfg["initiator"]
    if abs(ini["A"] + ini["B"] + ini["C"] + ini["D"] - 1) > 1e-9:
        raise ValueError(f"initiator {ini} does not sum to 1")


# ---------------------------------------------------------------------------
# the graph, made on the device
# ---------------------------------------------------------------------------

def _threshold(p: float) -> int:
    return min(int(round(p * 2 ** 32)), 2 ** 32 - 1)


def _words(seed: int, count: int) -> np.ndarray:
    return np.random.SeedSequence([int(seed), 17]).generate_state(
        count, np.uint32)


def _permute(x, keys, scale: int):
    """A seeded bijection of [0, 2**scale): odd multiplies, adds and
    xor-shifts, each invertible modulo 2**scale (``keys``: uint32 pairs)."""
    import jax.numpy as jnp
    u = jnp.uint32
    mask = u((1 << scale) - 1)
    for k in range(keys.shape[0]):
        x = (x * (keys[k, 0] | u(1)) + keys[k, 1]) & mask
        x = x ^ (x >> u(scale // 2 + 1))
    return x


def kronecker_block(first, count: int, cfg: dict, words):
    """Endpoints (row, col) of edges ``first`` .. ``first + count - 1``,
    vertex labels permuted (int32, on the device). ``words``: the seed's
    ``2 * scale + 6`` uint32 words, traced, so that one compile serves
    every seed."""
    import jax
    import jax.numpy as jnp
    u = jnp.uint32
    scale, ini = cfg["scale"], cfg["initiator"]
    ab = ini["A"] + ini["B"]
    t_row = u(_threshold(ab))
    t_col_hi = u(_threshold(ini["C"] / (1 - ab)))     # row bit 1
    t_col_lo = u(_threshold(ini["A"] / ab))           # row bit 0
    keys = words[:2 * scale].reshape(scale, 2)
    e = (jnp.asarray(first).astype(u) + jnp.arange(count, dtype=u)) * \
        u(GOLDEN)

    def level(bit, rc):
        row, col = rc
        ii = data.mix32(e ^ keys[bit, 0], jnp) >= t_row
        jj = data.mix32(e ^ keys[bit, 1], jnp) >= jnp.where(
            ii, t_col_hi, t_col_lo)
        return (row | (ii.astype(u) << bit.astype(u)),
                col | (jj.astype(u) << bit.astype(u)))
    row, col = jax.lax.fori_loop(0, scale, level, (jnp.zeros(count, u),
                                                   jnp.zeros(count, u)))
    perm = words[2 * scale:].reshape(-1, 2)
    return (_permute(row, perm, scale).astype(jnp.int32),
            _permute(col, perm, scale).astype(jnp.int32))


def build_csr(cfg: dict, seed: int, device):
    """(indptr, adj) on ``device``: a degree pass, then a fill pass, over
    blocks of at most ``BUILD_BLOCK`` edges made anew in each. The seed
    enters as an argument, so the two passes compile once for every
    seed."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    n, m = cfg["vertices"], cfg["undirected_edges"]
    block = math.gcd(m, BUILD_BLOCK)      # divides the edges
    blocks = m // block
    one = SingleDeviceSharding(device)
    words = jax.device_put(_words(seed, 2 * cfg["scale"] + 6), one)

    def degrees(words):
        def body(b, deg):
            row, col = kronecker_block(b * block, block, cfg, words)
            return deg.at[row].add(1).at[col].add(1)
        deg = jax.lax.fori_loop(0, blocks, body, jnp.zeros(n, jnp.int32))
        return jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(deg)])

    def fill(words, indptr):
        lane = jnp.arange(2 * block, dtype=jnp.int32)

        def body(b, carry):
            adj, cursor = carry
            row, col = kronecker_block(b * block, block, cfg, words)
            src, dst = jax.lax.sort((jnp.concatenate([row, col]),
                                     jnp.concatenate([col, row])),
                                    num_keys=1, is_stable=True)
            head = jnp.concatenate([jnp.ones(1, bool), src[1:] != src[:-1]])
            rank = lane - jax.lax.cummax(jnp.where(head, lane, 0))
            adj = adj.at[cursor[src] + rank].set(dst, unique_indices=True)
            return adj, cursor.at[src].add(1)
        adj, _ = jax.lax.fori_loop(
            0, blocks, body, (jnp.zeros(2 * m, jnp.int32), indptr[:-1]))
        return adj

    indptr = jax.jit(degrees, out_shardings=one)(words)
    adj = jax.jit(fill, out_shardings=one)(words, indptr)
    return indptr, adj


def search_keys(indptr, count: int, seed: int) -> list:
    """``count`` distinct vertices of degree at least 1, drawn from the
    seed (Graph500's search keys)."""
    import jax
    import jax.numpy as jnp
    n = indptr.shape[0] - 1

    def draw(indptr, w):
        lane = jnp.arange(CANDIDATES, dtype=jnp.uint32)
        v = (data.mix32(lane * jnp.uint32(GOLDEN) ^ w, jnp)
             % jnp.uint32(n)).astype(jnp.int32)
        return v, indptr[v + 1] - indptr[v]
    v, deg = (np.asarray(a) for a in jax.jit(draw)(
        indptr, _words(int(seed) + 1, 1)[0]))
    keys = []
    for vertex, d in zip(v.tolist(), deg.tolist()):
        if d > 0 and vertex not in keys:
            keys.append(vertex)
    if len(keys) < count:
        raise RuntimeError(f"{len(keys)} of {CANDIDATES} candidates have "
                           f"degree >= 1; {count} search keys are needed")
    return keys[:count]


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

UNREACHED = np.int32(2 ** 31 - 1)


class Replay:
    """One client's searches replayed on the host CSR, from its own roots
    and its own frontiers: ``run(roots, requests)`` does what that many
    requests of the client do, in the order they are made (a level's
    frontier queue in tiles of ``capacity`` vertices; each tile's entries,
    in CSR order, in requests of at most ``capacity`` lanes, at least one
    a tile), and returns the table of the search it ends in. Only the
    count of requests comes from the run."""

    def __init__(self, indptr: np.ndarray, adj: np.ndarray, scale: int,
                 capacity: int, overwrite: bool):
        self.indptr, self.adj = indptr, adj
        self.n = indptr.shape[0] - 1
        self.shift, self.capacity = scale, capacity
        self.overwrite = overwrite
        self.levels = {}     # (search, level) -> (frontier, its offsets)

    def run(self, roots: list, requests: int) -> np.ndarray:
        search = 0
        while True:
            table, left = self.search(search, roots[search % len(roots)],
                                      requests)
            if left is None:
                return table
            search, requests = search + 1, left

    def search(self, search: int, root: int, budget: int):
        """(table, requests left over once the search ended), or (table,
        None) where the budget ran out inside it."""
        table = np.full(self.n, UNREACHED, np.int32)
        table[root] = root
        level = 0
        while True:
            front = np.flatnonzero((table >> self.shift) == level)
            if not front.size:
                return table, budget
            if level + 1 > (int(UNREACHED) >> self.shift) - 1:
                raise RuntimeError(f"BFS level {level + 1} does not fit a "
                                   f"parent word of {self.shift} vertex bits")
            deg = self.indptr[front + 1] - self.indptr[front]
            cum = np.concatenate([[0], np.cumsum(deg, dtype=np.int64)])
            self.levels[(search, level)] = front, cum
            ends = self.request_ends(cum)
            if budget < ends.size:      # the level still in flight
                b = int(ends[budget - 1]) if budget else 0
                self._apply(table, search, level, b)
                return table, None
            self._apply(table, search, level, int(cum[-1]))
            budget -= ends.size
            level += 1

    def request_ends(self, cum: np.ndarray) -> np.ndarray:
        """Where each of a level's requests ends in its CSR-order stream."""
        cap = self.capacity
        bounds = cum[np.minimum(np.arange(0, cum.size - 1 + cap, cap),
                                cum.size - 1)]
        ends = []
        for a, b in zip(bounds[:-1].tolist(), bounds[1:].tolist()):
            ends += list(range(a + cap, b, cap)) + [b]
        return np.asarray(ends, np.int64)

    def _apply(self, table, search: int, level: int, b: int) -> None:
        """Relaxes positions [0, b) of the level's stream, in CSR order."""
        for lo in range(0, b, REPLAY_LANES):
            dst, src = self.lanes(search, level, lo,
                                  min(b, lo + REPLAY_LANES))
            self._relax(table, dst, ((level + 1) << self.shift) | src,
                        level)

    def lanes(self, search: int, level: int, a: int, b: int):
        """(neighbour, parent) of positions [a, b) of the level's stream."""
        front, cum = self.levels[(search, level)]
        v0 = int(np.searchsorted(cum, a, side="right")) - 1
        v1 = int(np.searchsorted(cum, b - 1, side="right")) - 1
        vs = front[v0:v1 + 1]
        begin = self.indptr[vs].astype(np.int64)
        end = self.indptr[vs + 1].astype(np.int64)
        begin[0] += a - cum[v0]
        end[-1] -= cum[v1 + 1] - b
        count = end - begin
        pos = np.repeat(begin - np.cumsum(count) + count, count) + \
            np.arange(b - a)
        return self.adj[pos], np.repeat(vs, count).astype(np.int32)

    def _relax(self, table, dst, vals, level: int) -> None:
        if not self.overwrite:
            np.minimum.at(table, dst, vals)
            return
        # the control: unreached vertices and those of this level take the
        # last writer, in CSR order
        fresh = table[dst] >= np.int32((level + 1) << self.shift)
        table[dst[fresh]] = vals[fresh]


# ---------------------------------------------------------------------------
# the deployment through one run
# ---------------------------------------------------------------------------

class Bfs:
    mesh = None

    def __init__(self, cfg: dict, mix: dict, seed: int, chips: int,
                 devices):
        # first: a program without Graph500 BFS fails here, before the
        # graph is made
        from repro.apps.bfs import Search
        import jax

        self.cfg, self.mix = cfg, mix
        self.clients, self.capacity = mix["clients"], mix["chunk_lanes"]
        # the set-up's stages, timed to their completion on the device
        t = [time.perf_counter()]
        self.indptr, self.adj = build_csr(cfg, seed, devices[0])
        jax.block_until_ready(self.adj)
        t.append(time.perf_counter())
        self.keys = search_keys(self.indptr, cfg["search_keys"], seed)
        t.append(time.perf_counter())
        self.searches = [
            Search(c, self.keys[c::self.clients], self.indptr, self.adj,
                   capacity=self.capacity)
            for c in range(self.clients)]
        t.append(time.perf_counter())
        self.stages_s = dict(zip(("build", "search_keys", "first_queues"),
                                 np.diff(t).tolist()))
        self.program = self.searches[0].program
        n = cfg["vertices"]
        self.data_bytes = int(self.adj.nbytes + self.indptr.nbytes
                              + self.clients * n * 4)
        # each client's completed requests, as (level, lanes)
        self.served = [[] for _ in range(self.clients)]

    # -- traffic -------------------------------------------------------------

    def request(self, c: int):
        return self.searches[c].next()

    def submit(self, tile, part: str, client):
        return client.submit(self.program, tile.env, tile.regs)

    def closed(self, window: list) -> list:
        return [tile for tile, _, out in window if out is not None]

    def retired(self, tile, outputs) -> None:
        self.searches[tile.client].retire(
            tile, None if outputs is None else outputs[0])
        if outputs is not None:
            self.served[tile.client].append((tile.level, tile.lanes))

    def drained(self) -> None:
        pass

    def ops(self, tile) -> int:
        return tile.lanes

    def needed_bytes(self, windows: list) -> int:
        """Per request: each valid adjacency entry read once, each frontier
        vertex's two ``indptr`` words, each index and value lane of the
        ``MIN``, and each distinct parent row it updates read and written
        once (counted on the host CSR, from the reference's frontiers)."""
        total = 0
        for tiles in windows:
            for t in tiles:
                total += 12 * t.lanes + 8 * t.vertices
                total += 8 * self._distinct(t)
        return total

    def _distinct(self, t) -> int:
        replay = self.replays.get(t.client)
        key = (t.search, t.level)
        if replay is None or key not in replay.levels or not t.lanes:
            return 0
        front, cum = replay.levels[key]
        a = int(cum[min(t.first, front.size)]) + t.start
        dst, _ = replay.lanes(t.search, t.level, a, a + t.lanes)
        seen = np.zeros(replay.n, bool)
        seen[dst] = True
        return int(np.count_nonzero(seen))

    # -- the check -----------------------------------------------------------

    def read_back(self) -> dict:
        self.host_indptr = np.asarray(self.indptr)
        self.host_adj = np.asarray(self.adj)
        self.final = [np.asarray(s.table) for s in self.searches]
        self.searches = self.indptr = self.adj = None
        self.replays = {}
        return {"vertices_checked": int(sum(f.size for f in self.final)),
                "requests_checked": sum(len(s) for s in self.served),
                "search_keys": self.keys[:8],
                "setup_stages_s": self.stages_s,
                "requests_level_lanes": self.served}

    def judge(self, control: bool) -> dict:
        """Each client's searches are replayed from its roots for as many
        requests as it completed, and the table it ends in is compared
        whole with the client's (the replays' frontiers also give
        ``needed_bytes``)."""
        gap = 0
        for c, table in enumerate(self.final):
            replay = Replay(self.host_indptr, self.host_adj,
                            self.cfg["scale"], self.capacity, control)
            want = replay.run(self.keys[c::self.clients],
                              len(self.served[c]))
            gap += int(np.count_nonzero(want != table))
            if not control:
                self.replays[c] = replay
        return {"parent_gap": {"value": gap, "limit": 0}}


build = Bfs         # build(cfg, mix, seed, chips, devices)
