"""The harness finds a configuration, a traffic mix, a metric and a
deployment kind by name, from files of their own, with no edit to any file
already there."""
import json

import pytest
from conftest import TINY_A, TINY_SERVICE, TINY_TABLE, run_tiny

import harness
from repro.core import bulk_ops


def test_new_config_mix_and_metric_are_picked_up(tiny_root):
    bench = tiny_root / "bench"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "configs" / "wide.json").write_text(
        json.dumps(dict(TINY_TABLE, rows=4096)))
    (bench / "traffic" / "two_clients.json").write_text(
        json.dumps(dict(TINY_A, clients=2)))
    (bench / "metrics" / "requests_seen.py").write_text(
        "def read(run):\n    return float(len(run.latencies_s))\n")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "wide",
                            "file": "bench/configs/wide.json"})
    spec["workloads"].append({"name": "new", "config": "wide",
                              "traffic": "two_clients", "chips": 1})
    spec["end_to_end"].append({"name": "requests_seen", "unit": "count",
                               "workloads": ["new"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.find_cell("new", tiny_root)
    assert cell.cfg["rows"] == 4096 and cell.mix["clients"] == 2
    assert "requests_seen" in [m["name"] for m in cell.end_to_end]
    assert "requests_seen" not in [
        m["name"] for m in harness.find_cell("a", tiny_root).end_to_end]
    line = run_tiny(tiny_root, "new")
    assert line["correct"]
    assert line["metrics"]["requests_seen"]["value"] == line["attempted"]
    assert all(p.read_bytes() == b for p, b in before.items())


# A kind that no cell of the benchmark has: each client follows a chain of
# keys through a fixed successor table, the next request's keys being the
# gather of the last one's, made on the device, and lowers a 1-D table to
# the minimum of its values at each key it visits.
TOY_KIND = '''
import dataclasses

import numpy as np


def check(cfg, mix):
    if cfg["rows"] & (cfg["rows"] - 1):
        raise ValueError("rows must be a power of two")


def first_keys(rows, lanes, clients, seed, xp):
    lane = xp.arange(clients * lanes, dtype=xp.uint32)
    k = lane * xp.uint32(2654435761) + xp.uint32(seed % 2**32)
    return (k % xp.uint32(rows)).astype(xp.int32).reshape(clients, lanes)


def successor(keys, rows):              # a permutation: 5 is odd
    return (keys * 5 + 1) % rows


def lowered_to(keys, c, xp):
    return ((keys * 37 + c * 11) % 1009).astype(xp.int32)


def initial(rows, xp):
    return (1009 + xp.arange(rows, dtype=xp.int32) % 7).astype(xp.int32)


@dataclasses.dataclass
class Step:
    client: int
    keys: object
    parts: tuple = ("follow", "lower")


def build(cfg, mix, seed, chips, devices):
    import jax
    import jax.numpy as jnp
    rows, lanes = cfg["rows"], mix["lanes"]

    def make():
        return (initial(rows, jnp),
                successor(jnp.arange(rows, dtype=jnp.int32), rows),
                first_keys(rows, lanes, mix["clients"], seed, jnp))
    table, nxt, keys = jax.jit(make)()
    return Toy(cfg, mix, seed, table, nxt, list(keys))


class Toy:
    mesh = None

    def __init__(self, cfg, mix, seed, table, nxt, keys):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.table, self.nxt, self.next_keys = table, nxt, keys
        self.data_bytes = table.nbytes + nxt.nbytes
        self.sent = [0] * mix["clients"]

    def request(self, c):
        self.sent[c] += 1
        return Step(c, self.next_keys[c])

    def submit(self, step, part, client):
        import jax.numpy as jnp
        if part == "follow":
            return client.submit_gather(self.nxt, step.keys)
        values = lowered_to(step.keys, step.client, jnp)
        return client.submit_rmw(self.table, step.keys, values, op="MIN")

    def closed(self, window):
        for step, part, out in window:
            if part == "lower" and out is not None:
                self.table = out
        return sum(int(step.keys.shape[0]) for step, _, _ in window)

    def retired(self, step, outputs):
        if outputs is not None:
            self.next_keys[step.client] = outputs[0]

    def drained(self):
        pass

    def ops(self, step):
        return 2 * int(step.keys.shape[0])

    def needed_bytes(self, windows):
        return 12 * sum(windows)        # index, row and value per lane

    def read_back(self):
        self.final = np.asarray(self.table)
        self.table = self.nxt = self.next_keys = None
        return {"rows_checked": int(self.final.size)}

    def judge(self, control):
        rows, lanes = self.cfg["rows"], self.mix["lanes"]
        want = initial(rows, np)
        keys = first_keys(rows, lanes, self.mix["clients"], self.seed, np)
        for c, n in enumerate(self.sent):
            k = keys[c]
            for _ in range(n):
                np.minimum.at(want, k, lowered_to(k, c, np))
                k = successor(k, rows)
        got = want.astype(np.int8) if control else self.final
        gap = int(np.max(np.abs(got.astype(np.int64) - want)))
        return {"state_gap": {"value": gap, "limit": 0}}
'''


def _add_toy(root):
    bench = root / "bench"
    (bench / "kinds" / "toy.py").write_text(TOY_KIND)
    (bench / "configs" / "toy_chain.json").write_text(json.dumps(
        {"kind": "toy", "rows": 1024, "service": TINY_SERVICE}))
    (bench / "traffic" / "toy_walk.json").write_text(json.dumps(
        {"loop": "closed", "clients": 4, "lanes": 64,
         "warm_requests_per_client": 2}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy_chain",
                            "file": "bench/configs/toy_chain.json"})
    spec["workloads"].append({"name": "toy", "config": "toy_chain",
                              "traffic": "toy_walk", "chips": 1})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def test_new_kind_is_new_files_only(tiny_root):
    bench = tiny_root / "bench"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    _add_toy(tiny_root)
    line = run_tiny(tiny_root, "toy", seed=2**40 + 5)
    assert line["correct"] is True and line["failed"] == 0
    assert line["checks"]["state_gap"]["value"] == 0
    assert line["attempted"] > 0
    assert line["metrics"]["ops_per_s"]["value"] > 0
    assert all(p.read_bytes() == b for p, b in before.items())
    # the control, the state held in int8, is judged in its place
    line = run_tiny(tiny_root, "toy", seed=9, control=True)
    assert line["correct"] is False and line["program"]["correct"] is True


def test_new_kind_with_half_its_lanes_dropped_is_not_correct(tiny_root,
                                                             monkeypatch):
    _add_toy(tiny_root)
    orig = bulk_ops.bulk_rmw

    def half(table, idx, values, **kw):
        n = idx.shape[0] // 2
        cond = kw.pop("cond", None)
        return orig(table, idx[:n], values[:n],
                    cond=None if cond is None else cond[:n], **kw)
    monkeypatch.setattr(bulk_ops, "bulk_rmw", half)
    line = run_tiny(tiny_root, "toy", seed=21)
    assert line["correct"] is False and line["failed"] == 0


@pytest.mark.parametrize("kind", [None, "sorted_set"],
                         ids=["missing", "unknown"])
def test_a_configuration_must_name_a_known_kind(tiny_root, kind):
    path = tiny_root / "bench" / "configs" / "tiny_table.json"
    cfg = {k: v for k, v in json.loads(path.read_text()).items()
           if k != "kind"}
    if kind:
        cfg["kind"] = kind
    path.write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match=r"known kinds: \['keyed'\]"):
        harness.find_cell("a", tiny_root)


BAD_SIZES = {
    "recordcount_is_not_rows": ("tiny_table", "tiny_a",
                                {"recordcount": 4096}, {}),
    "record_too_wide": ("tiny_table", "tiny_a",
                        {"fieldcount": 10, "fieldlength": 100}, {}),
    "max_key_is_not_rows": ("tiny_hist", "tiny_is", {"max_key": 2048}, {}),
    "total_keys_is_not_the_clients_keys": ("tiny_hist", "tiny_is",
                                           {"total_keys": 32768}, {}),
    "resident_keys_of_no_generator": ("tiny_hist", "tiny_is", {},
                                      {"key_distribution": "uniform"}),
    "host_keys_of_no_generator": ("tiny_table", "tiny_a", {},
                                  {"key_distribution": "nas_is"}),
}


@pytest.mark.parametrize("case", sorted(BAD_SIZES))
def test_stated_sizes_and_keys_must_be_what_runs(tiny_root, case):
    """A configuration's sizes in its source's terms and a mix's key
    distribution are checked when the cell is found, before any run."""
    config, mix, cfg_edit, mix_edit = BAD_SIZES[case]
    for path, edit in ((tiny_root / "bench" / "configs" / f"{config}.json",
                        cfg_edit),
                       (tiny_root / "bench" / "traffic" / f"{mix}.json",
                        mix_edit)):
        path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                        **edit)))
    cell = {"tiny_a": "a", "tiny_is": "is"}[mix]
    with pytest.raises(ValueError):
        harness.find_cell(cell, tiny_root)


def test_unknown_workload_names_the_known_ones(tiny_root):
    with pytest.raises(KeyError, match="known"):
        harness.find_cell("nope", tiny_root)


def test_every_metric_of_the_benchmark_has_a_reader():
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.metric_reader(harness.ROOT, m["name"]))
    for w in spec["workloads"]:
        cell = harness.find_cell(w["name"])
        assert cell.chips == w["chips"]
