"""Keyed deployments: one table of rows, and requests of keys against it.

A request is one client's bulk submission of ``request_ops`` keys: a
``submit_gather`` of the read share (``read_fraction``) and a
``submit_rmw`` ADD of the rest. Host keys (``keys: host``) come from
per-client pools drawn in set-up and sent round robin; resident keys
(``keys: resident``, NAS IS) are made on the device, one slice a client,
each counted by an ADD of ones. A gather reads the table as it stood when
its window opened: reads of a window are ordered before its writes.

The check (``reference.py``) compares a seeded share of the gathers and
sampled rows of the final table with a plain NumPy replay of every
applied update.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import data
import reference
import roofline


def check(cfg: dict, mix: dict) -> None:
    """The sizes a configuration states in its source's terms are the sizes
    the run uses, and the mix's keys have a generator: else ValueError.

    ``recordcount`` (YCSB) and ``max_key`` (NAS IS) are the table's rows;
    ``fieldcount`` fields of ``fieldlength`` bytes fit in a row; keys
    resident on the device number ``total_keys``, ``request_ops`` for each
    client."""
    rows = cfg["rows"]
    for key in ("recordcount", "max_key"):
        if key in cfg and cfg[key] != rows:
            raise ValueError(f"{key} {cfg[key]} is not the table's {rows} "
                             "rows")
    if "fieldcount" in cfg:
        record = cfg["fieldcount"] * cfg["fieldlength"]
        room = max(cfg["row_words"], 1) * np.dtype(cfg["dtype"]).itemsize
        if record > room:
            raise ValueError(f"a {record}-byte record does not fit a "
                             f"{room}-byte row")
    known = data.RESIDENT_KEYS if mix["keys"] == "resident" else \
        data.HOST_KEYS
    if mix["key_distribution"] not in known:
        raise ValueError(f"{mix['keys']} keys cannot follow "
                         f"{mix['key_distribution']!r}; known: "
                         f"{sorted(known)}")
    if mix["keys"] == "resident":
        keys = mix["clients"] * mix["request_ops"]
        for key in ("total_keys", "max_key"):
            if key not in cfg:
                raise ValueError(f"resident keys need the configuration's "
                                 f"{key}")
        if keys != cfg["total_keys"]:
            raise ValueError(f"the mix's {mix['clients']} clients of "
                             f"{mix['request_ops']} keys hold {keys} keys, "
                             f"not the configuration's {cfg['total_keys']}")


@dataclasses.dataclass
class Sent:
    """One submission of a pool entry."""
    entry: data.Request
    parts: tuple                 # "read", "update", or both, in order
    version: int = -1            # table version its gather read


def final_rows(pool: list, cfg: dict, seed: int) -> np.ndarray:
    """Rows of the final table to compare: all of a small table; else the
    hottest updated rows and a uniform sample, drawn from the seed."""
    rows = cfg["rows"]
    if rows <= cfg["check_rows"]:
        return np.arange(rows, dtype=np.int32)
    rng = np.random.default_rng([int(seed), 4])
    hot = np.zeros(0, np.int32)
    upd = [np.asarray(r.update_idx) for r in pool if np.size(r.update_idx)]
    if upd:
        cat = np.concatenate(upd)
        u, n = np.unique(cat, return_counts=True)
        hot = u[np.argsort(-n)[:cfg["check_rows"] // 4]]
    rest = rng.choice(rows, cfg["check_rows"] - hot.size, replace=False)
    return np.unique(np.concatenate([hot, rest.astype(np.int32)]))


class Keyed:
    """The table and its traffic through one run, and the check."""

    def __init__(self, cfg: dict, mix: dict, seed: int, chips: int,
                 devices):
        """Table, keys and update values, made on the device from the
        seed."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec, \
            SingleDeviceSharding

        words = data.seed_words(seed)
        rows, width = cfg["rows"], cfg["row_words"]
        shape = (rows, width) if width else (rows,)
        self.mesh = None
        if chips > 1:
            from repro.distributed.mesh import as_mesh
            self.mesh = as_mesh(chips)
            rowwise = NamedSharding(self.mesh,
                                    PartitionSpec(self.mesh.axis_names[0]))
        else:
            rowwise = SingleDeviceSharding(devices[0])
        one = SingleDeviceSharding(devices[0])

        def make_table():
            u = jnp.uint32
            flat = jax.lax.broadcasted_iota(u, shape, 0) * u(max(width, 1))
            if width:
                flat = flat + jax.lax.broadcasted_iota(u, shape, 1)
            return data.cell_values(flat, words, cfg["value_range"],
                                    jnp).astype(cfg["dtype"])
        table = jax.jit(make_table, out_shardings=rowwise)()
        col = data.column_pattern(width, seed)
        data_bytes = table.nbytes

        if mix["keys"] == "resident":
            lanes, clients = mix["request_ops"], mix["clients"]
            kwords = data.seed_words(int(seed) + 1)
            generate = data.RESIDENT_KEYS[mix["key_distribution"]]

            def make_keys():
                lane = jax.lax.iota(jnp.uint32, cfg["total_keys"])
                k = generate(lane, kwords, cfg["max_key"], jnp)
                return tuple(k[c * lanes:(c + 1) * lanes]
                             for c in range(clients))
            keys = jax.jit(make_keys, out_shardings=one)()
            ones = jax.jit(lambda: jnp.ones((lanes,), cfg["dtype"]),
                           out_shardings=one)()
            coef = np.ones(lanes, np.int8)
            pool = [data.Request(c, c, np.zeros(0, np.int32), keys[c], 0,
                                 coef)
                    for c in range(clients)]
            values = [ones]
            data_bytes += sum(k.nbytes for k in keys) + ones.nbytes
        else:
            pool, sets = data.host_pool(cfg, mix, seed)
            if sets:
                stacked = np.stack(sets)

                def make_values(c):
                    v = c.astype(jnp.float32)
                    if width:
                        v = v[:, :, None] * jnp.asarray(col, jnp.float32)
                    return tuple(v.astype(cfg["dtype"]))
                # left uncommitted, so that a mesh may place them as it
                # needs
                values = list(jax.jit(make_values)(stacked))
            else:
                values = []

        self.cfg, self.mix, self.words, self.col = cfg, mix, words, col
        self.data_bytes = int(data_bytes)
        # the table as made stays live until the check, and so counts in
        # the run's peak memory
        self.made = self.table = table
        self.pool, self.values = pool, values
        self.by_client = [[r for r in pool if r.client == c]
                          for c in range(mix["clients"])]
        self.turn = [0] * mix["clients"]
        self.version = 0
        self.ledger = reference.Ledger(pool)
        self.sample = np.random.default_rng([int(seed), 3])
        self.copying = []            # sampled answers on their way out
        self.rows = final_rows(pool, cfg, seed)
        self.row_bytes = max(width, 1) * np.dtype(cfg["dtype"]).itemsize

    # -- traffic -------------------------------------------------------------

    def request(self, c: int) -> Sent:
        pool = self.by_client[c]
        entry = pool[self.turn[c] % len(pool)]
        self.turn[c] += 1
        return Sent(entry, tuple(k for k, idx in (("read", entry.read_idx),
                                                  ("update",
                                                   entry.update_idx))
                                 if int(np.size(idx))))

    def submit(self, sent: Sent, part: str, client):
        if part == "read":
            sent.version = self.version
            return client.submit_gather(self.table, sent.entry.read_idx)
        return client.submit_rmw(self.table, sent.entry.update_idx,
                                 self.values[sent.entry.value_set], op="ADD")

    def closed(self, window: list) -> tuple:
        """The window's (read pool ids, update pool ids); its updates make
        the next table version."""
        reads, updates, new = [], [], None
        for sent, part, out in window:
            (reads if part == "read" else updates).append(
                sent.entry.pool_id)
            if part == "update" and out is not None:
                new = out
        if updates:
            self.ledger.versions.append(updates)
            self.version += 1
            self.table = new if new is not None else self.table
        return reads, updates

    def retired(self, sent: Sent, outputs) -> None:
        # a sampled answer is copied to the host in the background and
        # collected at the next retirement, so that neither the copy's wait
        # nor a pile of held device buffers lands in the window
        self.drained()
        if (outputs is not None and np.size(sent.entry.read_idx)
                and self.sample.random() < self.mix["check_share"]):
            outputs[0].copy_to_host_async()
            self.copying.append((sent.entry.pool_id, sent.version,
                                 outputs[0]))

    def drained(self) -> None:
        for pid, version, out in self.copying:
            self.ledger.reads.append((pid, version, np.asarray(out)))
        self.copying = []

    def ops(self, sent: Sent) -> int:
        return self.mix["request_ops"]

    def needed_bytes(self, windows: list) -> int:
        return roofline.needed_bytes(windows, self.pool, self.row_bytes,
                                     self.row_bytes)

    # -- the check -----------------------------------------------------------

    def read_back(self) -> dict:
        self.final = np.asarray(self.table[self.rows])
        self.made = self.table = self.values = None
        self.ref = reference.Reference(self.cfg, self.words, self.col)
        return {"reads_checked": len(self.ledger.reads),
                "rows_checked": int(self.rows.size)}

    def judge(self, control: bool) -> dict:
        return reference.compare(self.ledger, self.ref, self.rows,
                                 self.final, control=control)


build = Keyed       # build(cfg, mix, seed, chips, devices)
