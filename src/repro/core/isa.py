"""DX100 instruction set (Table 2 of the paper), as a JAX-traceable IR.

The paper encodes each instruction in 192 bits delivered by three 64-bit
memory-mapped stores. Here an ``AccessProgram`` is a list of instruction
dataclasses operating on named scratchpad *tiles* and a scalar *register
file*; ``repro.core.engine`` compiles a program into one fused jitted
function. Tiles are 1-D arrays of ``tile_size`` elements (the paper's 16K
default), with a validity count per tile standing in for the hardware
size/ready bits.

Supported, mirroring the paper:
  * access types  : ILD (indirect load), IST (indirect store), IRMW
  * stream types  : SLD, SST  (strided loads/stores)
  * compute       : ALUV (tile op tile), ALUS (tile op scalar)
  * loop fusion   : RNG (range fuser)
  * DTYPE         : u32,i32,f32,u64,i64,f64 (+bf16 as a TPU-native extension)
  * OP            : ADD SUB MUL MIN MAX AND OR XOR SHR SHL LT LE GT GE EQ
  * conditions    : every instruction takes an optional condition tile TC
  * IRMW restriction: only associative+commutative ops (ADD MIN MAX AND OR
    XOR MUL) — the engine reorders accesses, exactly as in §3.1.
"""
from __future__ import annotations

import dataclasses
from typing import Mapping, Optional, Sequence, Union

import jax.numpy as jnp

# ---------------------------------------------------------------------------
# dtypes and ops
# ---------------------------------------------------------------------------

DTYPES = {
    "u32": jnp.uint32,
    "i32": jnp.int32,
    "f32": jnp.float32,
    "u64": jnp.uint64,
    "i64": jnp.int64,
    "f64": jnp.float64,
    "bf16": jnp.bfloat16,  # TPU-native extension
}

ALU_OPS = (
    "ADD", "SUB", "MUL", "MIN", "MAX",
    "AND", "OR", "XOR", "SHR", "SHL",
    "LT", "LE", "GT", "GE", "EQ",
)

# §3.1: IRMW supports only a reorder-safe (associative & commutative) subset.
RMW_OPS = ("ADD", "MIN", "MAX", "AND", "OR", "XOR", "MUL")


def alu_apply(op: str, a, b):
    """Semantics of the OP field, shared by ALU unit and Word Modifier."""
    if op == "ADD":
        return a + b
    if op == "SUB":
        return a - b
    if op == "MUL":
        return a * b
    if op == "MIN":
        return jnp.minimum(a, b)
    if op == "MAX":
        return jnp.maximum(a, b)
    if op == "AND":
        return a & b
    if op == "OR":
        return a | b
    if op == "XOR":
        return a ^ b
    if op == "SHR":
        return a >> b
    if op == "SHL":
        return a << b
    if op == "LT":
        return (a < b)
    if op == "LE":
        return (a <= b)
    if op == "GT":
        return (a > b)
    if op == "GE":
        return (a >= b)
    if op == "EQ":
        return (a == b)
    raise ValueError(f"unknown ALU op {op!r}")


def rmw_identity(op: str, dtype):
    """Identity element used to mask inactive lanes of a reordered RMW."""
    dt = jnp.dtype(dtype)
    if op == "ADD":
        return jnp.zeros((), dt)
    if op == "MUL":
        return jnp.ones((), dt)
    if op == "MIN":
        if jnp.issubdtype(dt, jnp.floating):
            return jnp.array(jnp.inf, dt)
        return jnp.array(jnp.iinfo(dt).max, dt)
    if op == "MAX":
        if jnp.issubdtype(dt, jnp.floating):
            return jnp.array(-jnp.inf, dt)
        return jnp.array(jnp.iinfo(dt).min, dt)
    if op == "AND":
        return (jnp.array(-1, dt) if jnp.issubdtype(dt, jnp.signedinteger)
                else ~jnp.zeros((), dt))
    if op in ("OR", "XOR"):
        return jnp.zeros((), dt)
    raise ValueError(
        f"op {op!r} is not a legal IRMW op (must be one of {RMW_OPS})")


# ---------------------------------------------------------------------------
# instructions
# ---------------------------------------------------------------------------

Reg = Union[str, int, float]  # register name, or an immediate


@dataclasses.dataclass(frozen=True)
class Instr:
    """Base class; ``defs``/``uses`` drive the scoreboard hazard check."""

    def defs(self) -> Sequence[str]:  # tiles written
        return ()

    def uses(self) -> Sequence[str]:  # tiles read
        return ()


@dataclasses.dataclass(frozen=True)
class ILD(Instr):
    """SPD[td][i] = BASE[SPD[ts1][i]]  (if SPD[tc][i])."""
    dtype: str
    base: str          # name of the memory region (array) in the environment
    td: str
    ts1: str
    tc: Optional[str] = None

    def defs(self):
        return (self.td,)

    def uses(self):
        return (self.ts1,) + ((self.tc,) if self.tc else ())


@dataclasses.dataclass(frozen=True)
class IST(Instr):
    """BASE[SPD[ts1][i]] = SPD[ts2][i]  (if SPD[tc][i])."""
    dtype: str
    base: str
    ts1: str
    ts2: str
    tc: Optional[str] = None

    def defs(self):
        return ()

    def uses(self):
        return (self.ts1, self.ts2) + ((self.tc,) if self.tc else ())


@dataclasses.dataclass(frozen=True)
class IRMW(Instr):
    """BASE[SPD[ts1][i]] = OP(BASE[SPD[ts1][i]], SPD[ts2][i])."""
    dtype: str
    base: str
    op: str
    ts1: str
    ts2: str
    tc: Optional[str] = None

    def __post_init__(self):
        if self.op not in RMW_OPS:
            raise ValueError(
                f"IRMW op {self.op!r} not associative+commutative; "
                f"legal: {RMW_OPS}")

    def defs(self):
        return ()

    def uses(self):
        return (self.ts1, self.ts2) + ((self.tc,) if self.tc else ())


@dataclasses.dataclass(frozen=True)
class SLD(Instr):
    """SPD[td][i] = BASE[rs1 + i*rs3] for i < rs2  (if SPD[tc][i])."""
    dtype: str
    base: str
    td: str
    rs1: Reg = 0      # start
    rs2: Reg = -1     # count (-1 = full tile)
    rs3: Reg = 1      # stride
    tc: Optional[str] = None

    def defs(self):
        return (self.td,)

    def uses(self):
        return (self.tc,) if self.tc else ()


@dataclasses.dataclass(frozen=True)
class SST(Instr):
    """BASE[rs1 + i*rs3] = SPD[ts][i] for i < rs2  (if SPD[tc][i])."""
    dtype: str
    base: str
    ts: str
    rs1: Reg = 0
    rs2: Reg = -1
    rs3: Reg = 1
    tc: Optional[str] = None

    def defs(self):
        return ()

    def uses(self):
        return (self.ts,) + ((self.tc,) if self.tc else ())


@dataclasses.dataclass(frozen=True)
class ALUV(Instr):
    """SPD[td][i] = OP(SPD[ts1][i], SPD[ts2][i])."""
    dtype: str
    op: str
    td: str
    ts1: str
    ts2: str
    tc: Optional[str] = None

    def __post_init__(self):
        if self.op not in ALU_OPS:
            raise ValueError(f"unknown ALU op {self.op!r}")

    def defs(self):
        return (self.td,)

    def uses(self):
        return (self.ts1, self.ts2) + ((self.tc,) if self.tc else ())


@dataclasses.dataclass(frozen=True)
class ALUS(Instr):
    """SPD[td][i] = OP(SPD[ts][i], RF[rs])."""
    dtype: str
    op: str
    td: str
    ts: str
    rs: Reg = 0
    tc: Optional[str] = None

    def __post_init__(self):
        if self.op not in ALU_OPS:
            raise ValueError(f"unknown ALU op {self.op!r}")

    def defs(self):
        return (self.td,)

    def uses(self):
        return (self.ts,) + ((self.tc,) if self.tc else ())


@dataclasses.dataclass(frozen=True)
class RNG(Instr):
    """Range fuser (Fig. 5): flatten `for i: for j in [TS1[i], TS2[i])`.

    Writes outer iteration numbers to td1 and inner induction values to td2,
    compacted; rs1 holds the output-capacity register (defaults to tile).
    rs2 holds the start operand: the first fused position of this tile, so
    a stream longer than the capacity is issued as successive tiles (start
    0, capacity, 2*capacity, ...) without a recompile when it is a named
    register. ``_rng_total`` receives the whole stream's uncapped count
    and ``td1 + "__mask"`` this tile's valid lanes (start + lane < total).
    """
    td1: str
    td2: str
    ts1: str
    ts2: str
    rs1: Reg = -1
    tc: Optional[str] = None
    rs2: Reg = 0

    def defs(self):
        return (self.td1, self.td2)

    def uses(self):
        return (self.ts1, self.ts2) + ((self.tc,) if self.tc else ())


@dataclasses.dataclass(frozen=True)
class AccessProgram:
    """A sequence of DX100 instructions plus static metadata.

    ``tile_size`` is the paper's TILE (16K default). ``inputs`` names the
    memory regions (arrays) the program reads; ``outputs`` names regions it
    writes (IST/IRMW targets) and scratchpad tiles the host will read back.
    """
    instrs: tuple
    tile_size: int = 16384
    name: str = "dx100_program"

    def __post_init__(self):
        object.__setattr__(self, "instrs", tuple(self.instrs))
        self.validate()

    def validate(self):
        """Scoreboard-style static hazard & legality checks (§3.5, §4.2).

        - WAW/RAW tracked by def/use order is inherently respected since the
          engine executes sequentially in dataflow; we instead check the
          paper's *legality* rules: a region written by IST/IRMW must not be
          read by ILD/SLD later in the same program (the single-writer
          exclusivity rule), and RMW ops must be reorder-safe (checked in
          IRMW.__post_init__).
        """
        written_regions = set()
        for ins in self.instrs:
            if isinstance(ins, (ILD, SLD)):
                if ins.base in written_regions:
                    raise ValueError(
                        f"illegal program: region {ins.base!r} read after "
                        "indirect write within one program (aliasing hazard, "
                        "paper §4.2 Legality)")
            if isinstance(ins, (IST, IRMW, SST)):
                written_regions.add(ins.base)
            if isinstance(ins, RNG) and ins.td1 == ins.td2:
                raise ValueError(
                    f"illegal program: RNG writes both outer and inner "
                    f"streams to one tile {ins.td1!r} (duplicate "
                    "destination — the second write clobbers the first)")

    def scratch_tiles(self):
        tiles = []
        for ins in self.instrs:
            for t in tuple(ins.defs()) + tuple(ins.uses()):
                if t is not None and t not in tiles:
                    tiles.append(t)
        return tiles

    def external_tiles(self):
        """Tiles read before any instruction defines them — the warm
        scratchpad state a launch must supply via ``spd``. Accounts for
        RNG's implicit definitions (``td1 + "__mask"``, ``_rng_total``)."""
        defined, external = set(), []
        for ins in self.instrs:
            for t in ins.uses():
                if t is not None and t not in defined \
                        and t not in external:
                    external.append(t)
            for t in ins.defs():
                defined.add(t)
            if isinstance(ins, RNG):
                defined.add(ins.td1 + "__mask")
                defined.add("_rng_total")
        return tuple(external)

    def regions(self):
        """Memory region names the program touches, in first-use order."""
        out = []
        for ins in self.instrs:
            base = getattr(ins, "base", None)
            if base is not None and base not in out:
                out.append(base)
        return tuple(out)

    def register_names(self):
        """Scalar register names (string-valued Reg fields) the program
        reads, in first-use order."""
        out = []
        for ins in self.instrs:
            for field in ("rs", "rs1", "rs2", "rs3"):
                r = getattr(ins, field, None)
                if isinstance(r, str) and r not in out:
                    out.append(r)
        return tuple(out)

    def check_inputs(self, env: Mapping, regs: Mapping,
                     spd: Mapping) -> None:
        """Validate a launch's inputs upfront with a clear diagnostic.

        Without this, a missing region/register/tile dies deep inside
        the engine's instruction loop (or the compiler's jit trace) as
        an opaque ``KeyError``. Shares the DX001 contract with
        ``repro.analysis.program`` — pure dict-key checks, safe under a
        jit trace.
        """
        missing = [r for r in self.regions() if r not in env]
        if missing:
            raise ValueError(
                f"program {self.name!r}: memory region(s) {missing} not "
                f"in env (known: {sorted(env)}) [DX001]")
        missing = [r for r in self.register_names() if r not in regs]
        if missing:
            raise ValueError(
                f"program {self.name!r}: scalar register(s) {missing} "
                f"not in regs (known: {sorted(regs)}) [DX001]")
        missing = [t for t in self.external_tiles() if t not in spd]
        if missing:
            raise ValueError(
                f"program {self.name!r}: tile(s) {missing} read before "
                f"any definition and not supplied via spd (known: "
                f"{sorted(spd)}) [DX001]")
