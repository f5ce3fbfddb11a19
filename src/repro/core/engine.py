"""DX100 engine: executes an AccessProgram against memory regions.

The paper's Controller dispatches instructions to four functional units with
scoreboard hazard tracking; here the program is *traced once* into a single
jitted XLA computation — dataflow replaces the scoreboard, async DMA replaces
the fill/request/response pipeline, and the scratchpad is a dict of named
tile arrays threaded through the trace.

Usage:
    eng = Engine(tile_size=16384)
    out_env, spd = eng.run(program, env={"A": a, "B": b}, regs={"N": n})
`env` holds the memory regions (the paper's main-memory arrays); regions
written by IST/IRMW come back updated in `out_env`. `spd` is the final
scratchpad (packed tiles the "cores" read back).

Compile cache: ``Engine.executable(program)`` returns a ``TracedExecutable``
— a reusable jitted handle cached per *structural signature* (instruction
stream modulo the display name), so repeat submissions of structurally
identical programs never re-trace. ``executable(program, batch=k)`` returns
the ``jax.vmap``-batched variant the scheduler uses to run ``k`` compatible
programs as one XLA computation. ``Engine.stats`` counts cache traffic.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Mapping, Optional

import jax
import jax.numpy as jnp

from repro.core import bulk_ops, isa, range_fuser


@functools.lru_cache(maxsize=1024)
def structural_signature(program: isa.AccessProgram) -> tuple:
    """Hashable structural identity of a program.

    Covers everything that shapes the traced computation — instruction
    opcodes, operand/tile/region names, immediates and tile size — while
    excluding the display ``name``. Programs with equal signatures trace to
    identical XLA graphs (given equal env/reg structure), so they share one
    compile-cache entry and can be batched lane-wise by the scheduler.
    """
    return (program.tile_size,) + tuple(
        (type(ins).__name__,)
        + tuple((f.name, getattr(ins, f.name))
                for f in dataclasses.fields(ins))
        for ins in program.instrs)


class TracedExecutable:
    """A compile-cached jitted handle for one program structure.

    ``traces`` counts actual (re)traces via a Python side effect inside the
    traced function — it stays at 1 across any number of same-structure
    calls, which is the counter the compile-cache tests assert on.

    ``batch=None`` executes one program via ``__call__``; ``batch=k``
    executes ``k`` programs at once via ``run_batch``: per-program envs and
    regs go in as pytrees, stacking, the ``jax.vmap`` over lanes AND the
    per-lane unstacking all happen *inside the single jitted computation* —
    one XLA dispatch per flush instead of hundreds of eager primitive
    dispatches (stack/convert/slice), which is where a CPU hot path
    actually spends its time.

    Regions named in ``shared`` are not stacked: the one resident copy is
    closed over by the vmapped lane function, so it is broadcast to every
    lane without replication — the multi-tenant case of N programs reading
    one table. Shared regions must be read-only in the program (the
    scheduler guarantees this by excluding IST/IRMW/SST targets).
    """

    def __init__(self, engine: "Engine", program: isa.AccessProgram,
                 key: tuple, *, batch: Optional[int] = None,
                 shared: frozenset = frozenset()):
        self.engine = engine
        self.program = program
        self.key = key
        self.batch = batch
        self.shared = frozenset(shared)
        self.calls = 0
        self.traces = 0

        def _run(env, regs, spd):
            self.traces += 1        # fires only while tracing
            return engine.run(program, env, regs, spd)

        if batch is None:
            self._fn = jax.jit(_run)
            return

        def _run_batch(menvs, senv, regs_list, spd):
            self.traces += 1
            stacked = {k: jnp.stack([e[k] for e in menvs])
                       for k in menvs[0]}
            stacked = engine._constrain_batch(stacked)
            regs = {k: jnp.asarray([r[k] for r in regs_list])
                    for k in regs_list[0]}

            def lane(menv, lregs):
                out_env, out_spd = engine.run(
                    program, {**menv, **senv}, lregs, spd)
                for k in senv:          # read-only: drop the pass-through
                    out_env.pop(k)
                return out_env, out_spd

            out_env, out_spd = jax.vmap(lane, axis_size=batch)(stacked, regs)
            # unstack per lane inside the trace: slices compile into the
            # same computation, so results come back as per-program arrays
            return tuple(
                ({k: v[i] for k, v in out_env.items()},
                 {k: v[i] for k, v in out_spd.items()})
                for i in range(batch))

        self._batch_fn = jax.jit(_run_batch)

    def __call__(self, env, regs=None, spd=None):
        if self.batch is not None:
            raise TypeError("batched executable: use run_batch(envs, regs)")
        self.calls += 1
        return self._fn(dict(env), dict(regs or {}), dict(spd or {}))

    def run_batch(self, envs, regs_list, spd=None):
        """Execute ``batch`` programs: ``envs[i]``/``regs_list[i]`` belong
        to lane i (shared regions may appear in every env — the first copy
        is used). Returns a list of per-lane ``(env, spd)`` results, with
        shared regions merged back in untouched."""
        if self.batch is None or len(envs) != self.batch:
            raise TypeError(
                f"executable compiled for batch={self.batch}, "
                f"got {len(envs)} envs")
        self.calls += 1
        senv = {k: envs[0][k] for k in self.shared}
        menvs = tuple({k: v for k, v in e.items() if k not in self.shared}
                      for e in envs)
        outs = self._batch_fn(menvs, senv, tuple(dict(r) for r in regs_list),
                              dict(spd or {}))
        if not self.shared:
            return list(outs)
        return [({**oe, **senv}, os) for oe, os in outs]


class Engine:
    # Name of the registered plan backend (``repro.plan.emit``) the
    # scheduler lowers through for this engine. Mesh engines override it
    # ("sharded") and register their pass/emitter table at import — the
    # registry, not duck-typing, routes every flush window.
    plan_backend = "local"

    def __init__(self, tile_size: int = 16384, *, optimize: bool = True,
                 use_kernel: bool = False):
        self.tile_size = int(tile_size)
        self.optimize = optimize
        self.use_kernel = use_kernel
        self._cache: Dict[tuple, TracedExecutable] = {}
        self.stats = {"trace_requests": 0, "trace_misses": 0}

    # -- compile cache -------------------------------------------------------
    @property
    def cache_hits(self) -> int:
        return self.stats["trace_requests"] - self.stats["trace_misses"]

    def executable(self, program: isa.AccessProgram, *,
                   batch: Optional[int] = None,
                   shared: frozenset = frozenset()) -> TracedExecutable:
        """Fetch (or build) the cached jitted executable for ``program``.

        The cache key is the structural signature plus every engine knob
        that changes lowering (tile size, optimize, kernel routing), the
        vmap batch width and the shared-region set. Two programs differing
        only in ``name`` share an entry; jax.jit's own shape cache guards
        differing env shapes.
        """
        key = self._cache_key(program, batch, shared)
        self.stats["trace_requests"] += 1
        exe = self._cache.get(key)
        if exe is None:
            self.stats["trace_misses"] += 1
            exe = TracedExecutable(self, program, key, batch=batch,
                                   shared=shared)
            self._cache[key] = exe
        return exe

    def _cache_key(self, program: isa.AccessProgram,
                   batch: Optional[int], shared) -> tuple:
        # single source of truth: executable() and peek_cached() must
        # never drift apart on what identifies a cached trace
        return (structural_signature(program), self.tile_size,
                self.optimize, self.use_kernel, batch, frozenset(shared))

    def peek_cached(self, program: isa.AccessProgram, *,
                    batch: Optional[int] = None,
                    shared: frozenset = frozenset()) -> bool:
        """True if the compile cache already holds this executable —
        read-only (never instantiates): the cost model / ``explain()``
        consult it for trace-state without perturbing the counters."""
        return self._cache_key(program, batch, shared) in self._cache

    # -- batch placement hook ------------------------------------------------
    def _constrain_batch(self, stacked: Dict) -> Dict:
        """Hook applied to the stacked lane arrays of a batched executable.
        The base engine leaves placement to XLA; mesh-backed engines
        (``distributed.ShardedEngine``) override this to spread the lane
        axis across their devices."""
        return stacked

    # -- scalar operand resolution (register file) -------------------------
    @staticmethod
    def _reg(regs: Mapping, r):
        if isinstance(r, str):
            return regs[r]
        return r

    def _cond(self, spd, tc):
        if tc is None:
            return None
        return spd[tc].astype(bool)

    # -- instruction semantics ---------------------------------------------
    def _exec(self, ins: isa.Instr, env: Dict, spd: Dict, regs: Mapping):
        ts = self.tile_size
        if isinstance(ins, isa.SLD):
            # Note: lanes beyond the trip count (rs2) continue the stride
            # progression (clipped reads) rather than being zeroed — their
            # architectural content is undefined, and downstream guards
            # (compiler-emitted `i < tile_end` masks) rely on the address
            # progression staying monotone. Lanes failing TC read 0.
            start = self._reg(regs, ins.rs1)
            stride = self._reg(regs, ins.rs3)
            base = env[ins.base]
            i = jnp.arange(ts, dtype=jnp.int32)
            addr = jnp.asarray(start, jnp.int32) + i * jnp.asarray(
                stride, jnp.int32)
            vals = base[jnp.clip(addr, 0, base.shape[0] - 1)]
            vals = vals.astype(isa.DTYPES[ins.dtype])
            cond = self._cond(spd, ins.tc)
            if cond is not None:
                vals = jnp.where(cond, vals, jnp.zeros_like(vals))
            spd[ins.td] = vals
        elif isinstance(ins, isa.SST):
            start = jnp.asarray(self._reg(regs, ins.rs1), jnp.int32)
            count = self._reg(regs, ins.rs2)
            stride = jnp.asarray(self._reg(regs, ins.rs3), jnp.int32)
            base = env[ins.base]
            i = jnp.arange(ts, dtype=jnp.int32)
            count = jnp.where(jnp.asarray(count) < 0, ts, count)
            addr = start + i * stride
            # stores drop (policy): negative addresses route out with the
            # invalid lanes instead of wrapping; >= n drops via mode="drop"
            valid = (i < count) & (addr >= 0)
            cond = self._cond(spd, ins.tc)
            if cond is not None:
                valid = valid & cond
            addr = jnp.where(valid, addr, base.shape[0])
            env[ins.base] = base.at[addr].set(
                spd[ins.ts].astype(base.dtype), mode="drop")
        elif isinstance(ins, isa.ILD):
            cond = self._cond(spd, ins.tc)
            idx = spd[ins.ts1].astype(jnp.int32)
            if cond is not None:
                idx = jnp.where(cond, idx, 0)
            out = bulk_ops.bulk_gather(
                env[ins.base], idx,
                sort=self.optimize, dedup=self.optimize,
                use_kernel=self.use_kernel and env[ins.base].ndim == 2)
            if cond is not None:
                zshape = (-1,) + (1,) * (out.ndim - 1)
                out = jnp.where(cond.reshape(zshape), out, 0)
            spd[ins.td] = out.astype(isa.DTYPES[ins.dtype])
        elif isinstance(ins, isa.IST):
            env[ins.base] = bulk_ops.bulk_scatter(
                env[ins.base], spd[ins.ts1].astype(jnp.int32),
                spd[ins.ts2].astype(env[ins.base].dtype),
                cond=self._cond(spd, ins.tc), optimize=self.optimize)
        elif isinstance(ins, isa.IRMW):
            env[ins.base] = bulk_ops.bulk_rmw(
                env[ins.base], spd[ins.ts1].astype(jnp.int32),
                spd[ins.ts2].astype(env[ins.base].dtype), op=ins.op,
                cond=self._cond(spd, ins.tc), optimize=self.optimize,
                use_kernel=self.use_kernel and env[ins.base].ndim == 2)
        elif isinstance(ins, isa.ALUV):
            a, b = spd[ins.ts1], spd[ins.ts2]
            out = isa.alu_apply(ins.op, a, b)
            cond = self._cond(spd, ins.tc)
            if cond is not None:
                out = jnp.where(cond, out, jnp.zeros_like(out))
            spd[ins.td] = out.astype(isa.DTYPES[ins.dtype])
        elif isinstance(ins, isa.ALUS):
            a = spd[ins.ts]
            b = jnp.asarray(self._reg(regs, ins.rs), a.dtype)
            out = isa.alu_apply(ins.op, a, b)
            cond = self._cond(spd, ins.tc)
            if cond is not None:
                out = jnp.where(cond, out, jnp.zeros_like(out))
            spd[ins.td] = out.astype(isa.DTYPES[ins.dtype])
        elif isinstance(ins, isa.RNG):
            cap = self._reg(regs, ins.rs1)
            cap = self.tile_size if (isinstance(cap, int) and cap < 0) \
                else int(cap)
            start = self._reg(regs, ins.rs2)
            outer, inner, total = range_fuser.fuse_ranges(
                spd[ins.ts1], spd[ins.ts2], capacity=cap, start=start,
                cond=self._cond(spd, ins.tc))
            spd[ins.td1] = outer
            spd[ins.td2] = inner
            spd["_rng_total"] = total
            # validity mask of this tile of the fused stream (the hardware's
            # finish bits): downstream stores/RMWs must be guarded by it.
            spd[ins.td1 + "__mask"] = range_fuser.fused_valid_mask(
                total, cap, start).astype(jnp.int32)
        else:
            raise TypeError(f"unknown instruction {ins!r}")

    # -- program execution ---------------------------------------------------
    def run(self, program: isa.AccessProgram, env: Mapping,
            regs: Mapping | None = None, spd: Mapping | None = None):
        """Trace/execute the program; returns (env, spd) after retirement."""
        env = dict(env)
        spd = dict(spd or {})
        regs = dict(regs or {})
        # fail fast with a named culprit instead of a KeyError deep in
        # the instruction loop (dict-key checks only: jit-trace-safe)
        program.check_inputs(env, regs, spd)
        for ins in program.instrs:
            self._exec(ins, env, spd, regs)
        return env, spd

    def jit_run(self, program: isa.AccessProgram):
        """Compile (or fetch from the compile cache) a reusable jitted
        callable — repeat calls with a structurally identical program return
        the same ``TracedExecutable`` and never re-trace."""
        return self.executable(program)
