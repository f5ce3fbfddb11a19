"""chip_smoke.py's phases at tiny sizes on the CPU, against its own NumPy
reference, and its refusal to run anywhere but on a TPU."""
import importlib.util
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]

TINY = dict(rows=1 << 10, width=128, keys=1 << 8, updates=1 << 8, tenants=3,
            windows=2, words=1 << 12, tile=256, bf16_rows=1 << 9)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod      # dataclasses resolve through it
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny(smoke):
    return smoke.Sizes(**TINY)


def test_service_and_kernel_phases_match_reference(smoke, tiny):
    table, ref, facts = smoke.service_phase(tiny, seed=3)
    assert facts["windows"] == tiny.windows
    assert facts["vmap_fallbacks"] == facts["prefetch_errors"] == 0
    assert facts["plan_cache_hits"] == tiny.windows - 1
    np.testing.assert_array_equal(np.asarray(table), ref)
    facts = smoke.kernel_phase(table, ref, tiny, seed=3)
    assert facts["keys"] == tiny.keys
    assert facts["compiled_kernels"] is False     # interpreted on the CPU


def test_mesh_phase_on_one_device(smoke, tiny):
    facts = smoke.mesh_phase(tiny, seed=5, chips=1)
    assert facts["chips"] == 1 and facts["prefetch_errors"] == 0


def test_wrong_reference_fails_the_smoke(smoke, tiny):
    import jax.numpy as jnp
    from repro.serve import AccessService
    table = smoke.device_table((tiny.rows, tiny.width), jnp.float32, 0)
    ref = np.array(table) + 1          # a reference the device cannot match
    with pytest.raises(smoke.SmokeError, match="gather"):
        smoke.serve_windows(AccessService(auto_flush=0), table, ref, tiny,
                            np.random.default_rng(0))


def test_failed_ticket_fails_the_smoke(smoke, tiny, monkeypatch):
    import jax.numpy as jnp
    from repro.core import scheduler
    from repro.serve import AccessService

    def broken(*a, **k):
        raise RuntimeError("injected RMW failure")
    monkeypatch.setattr(scheduler.bulk_ops, "bulk_rmw", broken)
    table = smoke.device_table((tiny.rows, tiny.width), jnp.float32, 0)
    with pytest.raises(RuntimeError, match="injected RMW failure"):
        smoke.serve_windows(AccessService(auto_flush=0), table,
                            np.array(table), tiny, np.random.default_rng(0))


def test_zipf_keys_in_range_and_skewed(smoke):
    n = 1 << 12
    keys = smoke.ZipfKeys(n, np.random.default_rng(0))(1 << 14)
    assert keys.dtype == np.int32 and keys.min() >= 0 and keys.max() < n
    # the hottest key of a 0.99 Zipfian takes ~1/H(n) of the draws, far
    # above a uniform key's 1/n
    assert np.bincount(keys).max() > 50 * keys.shape[0] / n


def test_entry_point_refuses_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs a TPU" in out.err
