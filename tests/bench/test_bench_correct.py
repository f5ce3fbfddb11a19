"""``correct`` is decided by a comparison that fails: the bfloat16 control
reads above every limit, and so does a run with the timed path broken
underneath, once for each fault a cell can have."""
import json
import os
import subprocess
import sys
from pathlib import Path

import control
import jax.numpy as jnp
import pytest
from conftest import run_tiny

from repro.core import bulk_ops
from repro.core.scheduler import Scheduler


@pytest.mark.parametrize("cell", ["a", "c", "is"])
def test_sound_run_is_correct_and_the_control_is_not(tiny_root, cell):
    line = run_tiny(tiny_root, cell, seed=11, control=True)
    own = line["program"]
    assert own["correct"] is True
    assert all(v["value"] <= v["limit"] for v in own["checks"].values())
    # the control stands in the program's place, judged by the same rule
    assert line["correct"] is False
    assert line["checks"].keys() == own["checks"].keys()
    assert any(v["value"] > v["limit"] for v in line["checks"].values())
    assert control.verdict([line]) == 0


def test_control_script_fails_a_control_that_reads_correct():
    sound = {"correct": False, "program": {"correct": True}}
    assert control.verdict([sound, sound]) == 0
    assert control.verdict([sound, dict(sound, correct=True)]) == 1
    assert control.verdict([dict(sound, program={"correct": False})]) == 1


def _unchanged(table, idx, values, **kw):
    return table


def _half_batch(orig):
    def rmw(table, idx, values, **kw):
        n = idx.shape[0] // 2
        cond = kw.pop("cond", None)
        return orig(table, idx[:n], values[:n],
                    cond=None if cond is None else cond[:n], **kw)
    return rmw


def _altered_answer(orig):
    def emit(self, node, ctx):
        orig(self, node, ctx)
        tid = node.members[0].ticket.tid
        res = self._results[tid]
        self._results[tid] = jnp.asarray(res).at[0].add(1)
    return emit


FAULTS = {
    "state_unchanged": ("a", lambda mp: mp.setattr(
        bulk_ops, "bulk_rmw", _unchanged)),
    "half_batch_left_out": ("is", lambda mp: mp.setattr(
        bulk_ops, "bulk_rmw", _half_batch(bulk_ops.bulk_rmw))),
    "answer_altered": ("c", lambda mp: mp.setattr(
        Scheduler, "_execute_gathers",
        _altered_answer(Scheduler._execute_gathers))),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, fault):
    cell, plant = FAULTS[fault]
    plant(monkeypatch)
    line = run_tiny(tiny_root, cell, seed=13)
    assert line["correct"] is False
    assert line["failed"] == 0            # wrong answers, not errors


EXCHANGE_FAULT = """
import json, sys, tempfile
from pathlib import Path
sys.path.insert(0, {here!r})
import jax
from conftest import make_root, run_tiny
root = make_root(Path(tempfile.mkdtemp()))
spec = json.loads((root / "BENCHMARK.json").read_text())
spec["workloads"].append({{"name": "a4", "config": "tiny_table",
                          "traffic": "tiny_a", "chips": 4}})
(root / "BENCHMARK.json").write_text(json.dumps(spec))
if {drop}:     # every lane stays on the chip that sent it
    jax.lax.all_to_all = lambda x, axis_name, split, concat, **kw: x
print(json.dumps(run_tiny(root, "a4", seed=17)["correct"]))
"""


@pytest.mark.parametrize("drop", [False, True],
                         ids=["exchange_sound", "exchange_left_out"])
def test_exchange_between_chips(drop):
    """Four virtual devices need a process of their own."""
    here = str(Path(__file__).resolve().parent)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(Path(here).parents[1] / "src"))
    out = subprocess.run(
        [sys.executable, "-c", EXCHANGE_FAULT.format(here=here, drop=drop)],
        capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) is (not drop)
