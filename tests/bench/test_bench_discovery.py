"""The harness finds a configuration, a traffic mix and a metric by name,
from files of their own, with no edit to any file already there."""
import json

import pytest
from conftest import TINY_A, TINY_TABLE, run_tiny

import harness


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
