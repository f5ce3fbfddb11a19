"""The Range Fuser's start operand: a fused stream longer than the tile is
issued as successive tiles, each from a traced start position, and the
uncapped total says how many remain."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import Engine, fuse_ranges, isa, range_fuser
from repro.testing.oracle import OracleEngine


def _ranges(seed):
    """Ranges of lengths 0..6 with one hub longer than three tiles."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(0, 7, size=40).astype(np.int32)
    lens[17] = 53                                  # the hub
    lo = rng.integers(0, 1000, size=40).astype(np.int32)
    return lo, lo + lens


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_tiles_concatenated_are_the_whole_stream(seed):
    lo, hi = _ranges(seed)
    cap = 16
    whole_o, whole_i, whole_t = fuse_ranges(jnp.asarray(lo), jnp.asarray(hi),
                                            capacity=4096)
    total = int(whole_t)
    assert total == int((hi - lo).sum())
    outer, inner = [], []
    for start in range(0, total, cap):
        o, i, t = fuse_ranges(jnp.asarray(lo), jnp.asarray(hi),
                              capacity=cap, start=start)
        assert int(t) == total                     # uncapped, every tile
        valid = np.asarray(range_fuser.fused_valid_mask(t, cap, start))
        assert valid.sum() == min(cap, total - start)
        outer.append(np.asarray(o)[valid])
        inner.append(np.asarray(i)[valid])
    np.testing.assert_array_equal(np.concatenate(outer),
                                  np.asarray(whole_o)[:total])
    np.testing.assert_array_equal(np.concatenate(inner),
                                  np.asarray(whole_i)[:total])
    # the hub alone spans several tiles and none of its lanes is lost
    assert (np.concatenate(outer) == 17).sum() == 53


def test_a_tile_past_the_end_is_empty():
    lo, hi = _ranges(0)
    total = int((hi - lo).sum())
    _, _, t = fuse_ranges(jnp.asarray(lo), jnp.asarray(hi), capacity=16,
                          start=total + 16)
    assert int(t) == total
    assert not np.asarray(range_fuser.fused_valid_mask(
        t, 16, total + 16)).any()


def _tile_program(cap):
    return isa.AccessProgram((
        isa.RNG("o", "j", "lo", "hi", rs1=cap, rs2="start"),
        isa.ILD("i32", "A", "x", "j", tc="o__mask"),
    ), tile_size=cap, name="rng_tile")


def test_start_register_does_not_recompile():
    lo, hi = _ranges(3)
    total = int((hi - lo).sum())
    eng = Engine(tile_size=16)
    exe = eng.executable(_tile_program(16))
    a = jnp.arange(1100, dtype=jnp.int32) * 3
    spd = {"lo": jnp.asarray(np.resize(lo, 16)),
           "hi": jnp.asarray(np.resize(hi, 16))}
    totals = []
    for start in range(0, 64, 16):
        _, out = exe({"A": a}, {"start": start}, spd)
        totals.append(int(out["_rng_total"]))
    assert exe.traces == 1 and exe.calls == 4
    assert len(set(totals)) == 1


@pytest.mark.parametrize("start", [0, 5, 16, 40])
def test_engine_matches_the_oracle_from_a_start(start):
    lo, hi = _ranges(4)
    spd = {"lo": np.resize(lo, 16).astype(np.int32),
           "hi": np.resize(hi, 16).astype(np.int32)}
    env = {"A": np.arange(1100, dtype=np.int32) * 7}
    prog = _tile_program(16)
    _, want = OracleEngine(16).run(prog, env, {"start": start}, spd)
    _, got = Engine(tile_size=16).run(
        prog, {k: jnp.asarray(v) for k, v in env.items()}, {"start": start},
        {k: jnp.asarray(v) for k, v in spd.items()})
    for name in ("o", "j", "x", "o__mask", "_rng_total"):
        np.testing.assert_array_equal(np.asarray(got[name]), want[name])


def test_docstrings_describe_the_start_operand():
    assert "start" in isa.RNG.__doc__ and "rs2" in isa.RNG.__doc__
    assert "uncapped" in isa.RNG.__doc__
    assert "start" in range_fuser.fuse_ranges.__doc__
    assert "uncapped" in range_fuser.fuse_ranges.__doc__
    assert "start" in range_fuser.__doc__
