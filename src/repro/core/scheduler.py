"""Shared multi-tenant access engine: cross-program batching + coalescing.

The paper's defining system property is that one DX100 serves *many* cores
(Fig. 2): each core posts bulk access programs through MMIO queues and the
accelerator reorders, interleaves and coalesces accesses *across* the
outstanding requests. This module is that shared frontend:

  * ``Scheduler.submit`` / ``submit_gather`` / ``submit_rmw`` enqueue work
    from a logical core (``tenant``) as **AccessPlan IR leaves**
    (``repro.plan.nodes``) and return ``Ticket``s; ``poll``/``result``
    read the retired results back — the async MMIO submit/poll protocol.
  * ``flush_async`` drains the queues in round-robin tenant order and
    **lowers the window through the plan pass pipeline**
    (``normalize -> group -> fuse -> coalesce -> shard -> batch``,
    ``repro.plan.passes``): structural-signature grouping, cross-request
    gather/RMW fusion, coalescing and backend selection (eager vs bulk vs
    sharded, ``repro.plan.cost``) are all pass decisions on the plan
    tree — this module's ``_execute_*`` methods are only the registered
    *emitters* that execute the already-annotated nodes.
  * ``explain()`` returns the lowered plan for the pending window with
    per-pass deltas; the same plan object is then executed by the next
    flush and travels on ``FlushReport.plan`` (node ids round-trip).
  * Lowering *decisions* are cached per structural window signature (the
    plan cache): repeat windows — the decoupled pipeline's steady state —
    replay the recorded skeleton instead of re-deciding.

Everything degrades safely: a group whose program vmap cannot trace falls
back to per-program cached executables, and any plan node whose emission
raises resolves its tickets to ``FailedResult`` without poisoning the
rest of the window.

When the backing engine spans a device mesh (``distributed.ShardedEngine``),
the engine's ``plan_backend`` names the registered "sharded" backend: its
shard pass wraps mesh-eligible fused nodes in ``ShardedNode`` and its
emitters run them owner-locally per shard (§6.6 address-range
partitioning) — core never imports (or duck-type-probes) the distributed
package; ``FlushReport.shard_stats`` carries the per-shard record.
"""
from __future__ import annotations

import dataclasses
import os
import weakref
from collections import OrderedDict
from functools import partial
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation, annotate_function

from repro.analysis import hazards as analysis_hazards
from repro.analysis.diagnostics import HazardError
from repro.core import bulk_ops, isa, reorder
from repro.core.engine import Engine, structural_signature
from repro.plan import cost as plan_cost
from repro.plan import emit as plan_emit
from repro.plan import nodes as plan_nodes
from repro.plan import passes as plan_passes
from repro.plan.explain import Explanation
from repro.plan.spans import to_device, to_host, uploading

# lowering-decision cache entries kept per scheduler (LRU)
PLAN_CACHE_SIZE = 256


# ---------------------------------------------------------------------------
# tickets and results
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Ticket:
    """Handle returned by submit; redeem via ``poll``/``result``."""
    tid: int
    tenant: str


@dataclasses.dataclass
class FailedResult:
    """Stored in place of a result when the owning plan node's execution
    raised; ``Scheduler.result`` re-raises ``error``."""
    error: Exception


class QueueFullError(RuntimeError):
    """Raised by ``Scheduler.result`` for a submission that admission
    control rejected (the tenant's bounded queue was full at submit)."""


@dataclasses.dataclass
class QueueFull(FailedResult):
    """Terminal ticket state for a rejected submission.

    Stored at *submit* time — the leaf is never enqueued, so a rejected
    submission can never reach a flush window or mutate a table. ``poll``
    returns it (callers branch on ``isinstance``); ``result`` re-raises
    the carried ``QueueFullError``.
    """
    tenant: str = ""


@dataclasses.dataclass
class GroupReport:
    """Per-group execution record of one flush.

    ``cross_coalescing`` maps region -> (cross-request gain, sum of
    per-request unique counts, fused unique count). It is computed lazily
    on first access — measurement is pure reporting and must not tax the
    flush hot path. The thunk reference is dropped on first
    materialization: a long-lived report (``AccessService.last_report``)
    must not pin the index streams the thunk closed over.
    """
    n_programs: int
    program_name: str
    vmapped: bool               # executed as one vmapped XLA call
    fell_back: bool             # vmap trace failed -> per-program loop
    error: Optional[str] = None  # repr of the exception, if the group died
    _coalescing_thunk: Optional[object] = dataclasses.field(
        default=None, repr=False)
    _coalescing: Optional[Dict[str, Tuple[float, int, int]]] = \
        dataclasses.field(default=None, repr=False)

    @property
    def cross_coalescing(self) -> Dict[str, Tuple[float, int, int]]:
        if self._coalescing is None:
            thunk, self._coalescing_thunk = self._coalescing_thunk, None
            self._coalescing = thunk() if thunk else {}
        return self._coalescing


@dataclasses.dataclass
class FlushReport:
    """Execution record of one flush window.

    ``gather_coalescing`` maps table id -> (cross-request gain, sum of
    per-request unique counts, fused unique count); ``rmw_coalescing``
    maps (table id, op) likewise. Both are computed lazily on first access
    — the streams they measure may still be in flight when the window
    dispatches (the decoupled pipeline submits access chains built from
    un-materialized arrays), and forcing them on the flush hot path would
    sync the device. As with ``GroupReport``, the thunk reference is
    dropped after first materialization so a long-lived report releases
    the closed-over streams.

    ``plan`` is the executed (and stripped — array payloads released)
    AccessPlan: render it via ``repro.plan.explain(report)``.
    """
    order: Tuple[Tuple[str, int], ...]    # (tenant, tid) execution order
    groups: Tuple[GroupReport, ...]
    n_programs: int
    n_gathers: int
    # table id ("gather") / ("rmw", table id, op) -> per-shard exchange/
    # coalescing record (ShardStats), filled only when the engine spans a
    # device mesh
    shard_stats: Dict[object, object] = dataclasses.field(
        default_factory=dict)
    n_rmws: int = 0
    plan: Optional[plan_nodes.Plan] = dataclasses.field(
        default=None, repr=False)
    # window hazard diagnostics (analysis.hazards; array-free tuples)
    diagnostics: Tuple = ()
    _gather_thunk: Optional[object] = dataclasses.field(
        default=None, repr=False)
    _gather_coalescing: Optional[Dict] = dataclasses.field(
        default=None, repr=False)
    _rmw_thunk: Optional[object] = dataclasses.field(
        default=None, repr=False)
    _rmw_coalescing: Optional[Dict] = dataclasses.field(
        default=None, repr=False)

    @property
    def gather_coalescing(self) -> Dict[int, Tuple[float, int, int]]:
        if self._gather_coalescing is None:
            thunk, self._gather_thunk = self._gather_thunk, None
            self._gather_coalescing = thunk() if thunk else {}
        return self._gather_coalescing

    @property
    def rmw_coalescing(self) -> Dict[tuple, Tuple[float, int, int]]:
        if self._rmw_coalescing is None:
            thunk, self._rmw_thunk = self._rmw_thunk, None
            self._rmw_coalescing = thunk() if thunk else {}
        return self._rmw_coalescing

    def exchange_summary(self) -> Optional[Dict[str, object]]:
        """Fold the window's per-stream ``ShardStats`` into one
        wire-level record: post-dedup lane count, fraction served
        without fabric traffic, bytes shipped (chosen codec vs raw),
        and the mean route/exec overlap over split-dispatched nodes
        (None when every node ran fused). Returns None for
        single-device windows. Reading the stats materializes them
        (device sync) — call off the flush hot path, as
        ``serve.telemetry`` does."""
        if not self.shard_stats:
            return None
        lanes = local = idx_b = idx_raw = wire = 0
        ov_sum, ov_n = 0.0, 0
        for st in self.shard_stats.values():
            s = st.sent
            lanes += int(s.sum())
            local += int(np.trace(s))
            idx_b += st.idx_bytes
            idx_raw += st.idx_bytes_raw
            wire += st.bytes_on_wire
            if st.overlap_fraction is not None:
                ov_sum += st.overlap_fraction
                ov_n += 1
        return {
            "nodes": len(self.shard_stats),
            "lanes": lanes,
            "local_fraction": local / max(lanes, 1),
            "bytes_on_wire": wire,
            "idx_bytes": idx_b,
            "compression_ratio": (idx_raw / idx_b) if idx_b else 1.0,
            "overlap_fraction": (ov_sum / ov_n) if ov_n else None,
        }


class FlushHandle:
    """Non-blocking handle for one dispatched flush window.

    ``flush_async`` drains the queues and *dispatches* every plan node —
    JAX's async dispatch means the XLA computations are in flight, not
    finished, when it returns. ``poll()`` reports (without blocking)
    whether every result retired by the window is resident; ``result()``
    blocks until they all are and returns the window's ``FlushReport``.
    ``result()`` is idempotent: once the window has retired, repeat calls
    hand back the materialized report without ever re-syncing. Tickets
    stay redeemable through ``Scheduler.poll``/``result`` exactly as for
    a blocking flush — redeeming a ticket whose arrays are still in
    flight simply hands back futures.
    """

    def __init__(self, report: FlushReport, leaves: tuple):
        self.report = report
        self._leaves = leaves
        self._done = not leaves

    def poll(self) -> bool:
        """True once every array retired by this window is resident."""
        if self._done:
            return True
        if all(leaf.is_ready() for leaf in self._leaves
               if hasattr(leaf, "is_ready")):
            self._leaves = ()
            self._done = True
            return True
        return False

    @property
    def done(self) -> bool:
        """Retired (or explicitly resolved) — the in-flight guard's test."""
        return self._done or self.poll()

    def result(self) -> FlushReport:
        """Block until the window has fully retired; returns its report.
        Idempotent — a second call never blocks or re-syncs."""
        if not self._done:
            jax.block_until_ready(list(self._leaves))
            self._leaves = ()
            self._done = True
        return self.report


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

def _leaf_struct(x) -> tuple:
    # memoized dtype_str: submit pays it per env leaf, and un-memoized
    # str(np.dtype) was ~40% of the submit+lower path (plan_overhead)
    x = jnp.asarray(x) if not hasattr(x, "shape") else x
    return tuple(x.shape), plan_passes.dtype_str(x.dtype)


def _env_struct(env: Mapping) -> tuple:
    return tuple(sorted((k,) + _leaf_struct(v) for k, v in env.items()))


class Scheduler:
    """Shared access-engine frontend over one (long-lived) ``Engine``.

    Parameters:
      engine     : the backing engine; defaults to a fresh one. Long-lived —
                   its compile cache is what kills per-call re-tracing.
      max_batch  : cap on programs fused into one vmap group per flush.
      cost_model : ``repro.plan.CostModel`` override (forced backends,
                   measurement budget); defaults to the standard model.
      verify     : run the plan-IR structural verifier after every
                   lowering pass (``repro.analysis.verify``); default
                   from env ``DX100_PLAN_VERIFY`` (conftest turns it on
                   suite-wide).
      strict     : refuse to flush a window carrying ERROR-severity
                   hazard diagnostics (``HazardError``; queues are left
                   intact); default from env ``DX100_STRICT_HAZARDS``.
    """

    def __init__(self, engine: Optional[Engine] = None, *,
                 tile_size: int = 16384, optimize: bool = True,
                 use_kernel: bool = False, max_batch: int = 32,
                 cost_model: Optional[plan_cost.CostModel] = None,
                 verify: Optional[bool] = None,
                 strict: Optional[bool] = None):
        self.engine = engine if engine is not None else Engine(
            tile_size=tile_size, optimize=optimize, use_kernel=use_kernel)
        self.max_batch = int(max_batch)
        if verify is None:
            verify = os.environ.get(
                "DX100_PLAN_VERIFY", "") not in ("", "0")
        if strict is None:
            strict = os.environ.get(
                "DX100_STRICT_HAZARDS", "") not in ("", "0")
        self.verify = bool(verify)
        self.strict = bool(strict)
        self.cost = cost_model if cost_model is not None \
            else plan_cost.CostModel()
        self._queue: List[plan_nodes.ProgramNode] = []
        self._gather_queue: List[plan_nodes.GatherNode] = []
        self._rmw_queue: List[plan_nodes.RmwNode] = []
        self._results: Dict[int, tuple] = {}
        self._next_tid = 0
        self._rr_cursor = 0          # rotates the round-robin start tenant
        # weakref: the guard must observe the last window's done-ness, but
        # must not pin an abandoned handle's report/leaves for the
        # scheduler's lifetime (the report-lifetime rule — a dropped
        # handle releases its window; a gc'd handle lifts the guard)
        self._inflight: Optional[weakref.ref] = None
        # queue-fingerprint -> lowered Plan (explain()/flush share one
        # lowering); plan cache: window signature -> decision Skeleton
        self._lowered: Optional[tuple] = None
        self._plan_cache: "OrderedDict[tuple, plan_passes.Skeleton]" = \
            OrderedDict()
        # per-tenant serving policy (configure_tenant): SLO weight drives
        # WFQ drain order, max_pending bounds the tenant's queue share
        self._tenant_weight: Dict[str, float] = {}
        self._tenant_cap: Dict[str, int] = {}
        self._tenant_pending: Dict[str, int] = {}
        # WFQ virtual time, advanced only across drain-limited windows
        # (a full drain resets it — nobody is waiting, history is moot)
        self._vtime: Dict[str, float] = {}
        self.stats = {"flushes": 0, "programs": 0, "gathers": 0,
                      "rmws": 0, "vmap_groups": 0, "vmap_fallbacks": 0,
                      "singleton_groups": 0, "group_errors": 0,
                      "plan_cache_hits": 0, "plan_cache_misses": 0,
                      "rejects": 0, "deferrals": 0,
                      "hazard_errors": 0, "hazard_warnings": 0,
                      "hazards_by_tenant": {}, "rmw_scan_combines": 0}

    # -- submission ----------------------------------------------------------

    @property
    def pending(self) -> int:
        return (len(self._queue) + len(self._gather_queue)
                + len(self._rmw_queue))

    def _ticket(self, tenant: str) -> Ticket:
        t = Ticket(self._next_tid, tenant)
        self._next_tid += 1
        return t

    def configure_tenant(self, tenant: str, *,
                         weight: Optional[float] = None,
                         max_pending: Optional[int] = None) -> None:
        """Set a tenant's serving policy.

        ``weight``: SLO weight for weighted-fair drain order (default 1.0;
        higher = served earlier inside a window and a larger share of
        drain-limited windows). ``max_pending``: bound on the tenant's
        queued-but-unflushed submissions — submits past it are rejected
        with a ``QueueFull`` ticket (admission control; None = unbounded).
        """
        if weight is not None:
            if weight <= 0:
                raise ValueError(f"weight must be > 0, got {weight}")
            self._tenant_weight[tenant] = float(weight)
        if max_pending is not None:
            if max_pending < 0:
                raise ValueError(
                    f"max_pending must be >= 0, got {max_pending}")
            self._tenant_cap[tenant] = int(max_pending)

    def _admit(self, tenant: str) -> Optional[Ticket]:
        """Admission control: None if the tenant may enqueue, else a
        ticket already resolved to ``QueueFull`` (nothing was enqueued —
        a rejected submission can never mutate a table)."""
        cap = self._tenant_cap.get(tenant)
        if cap is not None and self._tenant_pending.get(tenant, 0) >= cap:
            t = self._ticket(tenant)
            self.stats["rejects"] += 1
            self._results[t.tid] = QueueFull(
                QueueFullError(
                    f"tenant {tenant!r} queue full ({cap} pending): "
                    "submission rejected by admission control"),
                tenant=tenant)
            return t
        self._tenant_pending[tenant] = \
            self._tenant_pending.get(tenant, 0) + 1
        return None

    @partial(annotate_function, name="dx.submit")
    def submit(self, program: isa.AccessProgram, env: Mapping,
               regs: Mapping | None = None, *,
               tenant: str = "core0") -> Ticket:
        """Enqueue one program launch from ``tenant``; returns a Ticket.

        ``env`` maps region names to arrays; ``regs`` holds scalar
        registers (``tile_base``/``N``/... — python numbers). Execution is
        deferred to ``flush``.
        """
        rejected = self._admit(tenant)
        if rejected is not None:
            return rejected
        src_refs = tuple(env.values())   # pin caller objects (id stability)
        src_ids = {k: id(v) for k, v in env.items()}
        # keep caller arrays as-is: device transfer happens once, inside the
        # batched jit dispatch, not as one eager device_put per leaf here
        env = {k: v if hasattr(v, "shape") else np.asarray(v)
               for k, v in env.items()}
        regs = dict(regs or {})
        key = (structural_signature(program), _env_struct(env),
               tuple(sorted(regs)))
        leaf = plan_nodes.ProgramNode(
            nid=-1, ticket=self._ticket(tenant), program=program, env=env,
            regs=regs, group_key=key, src_ids=src_ids, src_refs=src_refs)
        self._queue.append(leaf)
        return leaf.ticket

    @partial(annotate_function, name="dx.submit")
    def submit_gather(self, table, idx, *, tenant: str = "core0") -> Ticket:
        """Bulk fast-path: C = table[idx] with *cross-request* coalescing.

        All pending gathers against the same table object are fused into a
        single plan node at flush time (whose backend — direct, coalesced
        or mesh-sharded — the cost model picks); the result for this
        ticket is the (N,)- or (N, D)-shaped gathered array.
        """
        rejected = self._admit(tenant)
        if rejected is not None:
            return rejected
        jtable = to_device(table, "submit")
        # flatten up front: the coalesced fetch always worked on the flat
        # stream (coalesce_streams reshapes), so the eager backend must
        # see the same shape — one canonical form for every path
        jidx = to_device(idx, "submit").astype(jnp.int32).reshape(-1)
        leaf = plan_nodes.GatherNode(
            nid=-1, ticket=self._ticket(tenant), table=jtable, idx=jidx,
            table_id=id(table), table_ref=table,
            n_lanes=int(jidx.shape[0]), table_rows=int(jtable.shape[0]))
        self._gather_queue.append(leaf)
        return leaf.ticket

    @partial(annotate_function, name="dx.submit")
    def submit_rmw(self, table, idx, values, *, op: str = "ADD",
                   cond=None, tenant: str = "core0") -> Ticket:
        """Bulk RMW fast-path: ``table[idx] op= values`` with cross-request
        fusion.

        All pending RMWs with the same ``op`` against the same table object
        are concatenated into ONE ``bulk_rmw`` (sort -> segment-combine ->
        unique scatter) at flush time, so duplicate destinations across
        tenants merge before touching memory. ``op`` must be in
        ``isa.RMW_OPS`` (associative + commutative, §3.1). ``cond``: an
        optional bool mask — False lanes are no-ops. The ticket resolves to
        the table's state at the *end of the flush window* (after every
        fused RMW group that touches it); gathers in the same window read
        the window's initial state — don't mix reads and writes of one
        table inside a window.
        """
        if op not in isa.RMW_OPS:
            raise ValueError(f"op {op!r} not in RMW_OPS {isa.RMW_OPS}")
        rejected = self._admit(tenant)
        if rejected is not None:
            return rejected
        jtable = to_device(table, "submit")
        jidx = to_device(idx, "submit").astype(jnp.int32).reshape(-1)
        leaf = plan_nodes.RmwNode(
            nid=-1, ticket=self._ticket(tenant), table=jtable, idx=jidx,
            values=to_device(values, "submit"), op=op,
            cond=None if cond is None
            else to_device(cond, "submit").reshape(-1),
            table_id=id(table), table_ref=table,
            n_lanes=int(jidx.shape[0]), table_rows=int(jtable.shape[0]))
        self._rmw_queue.append(leaf)
        return leaf.ticket

    # -- retrieval -----------------------------------------------------------

    def poll(self, ticket: Ticket):
        """Non-blocking: the retired result, a ``FailedResult`` if the
        owning plan node's execution raised, or None while still queued."""
        return self._results.get(ticket.tid)

    def result(self, ticket: Ticket):
        """Retrieve (and forget) a result, flushing first if needed.
        Re-raises the execution error if this ticket's node failed."""
        if ticket.tid not in self._results:
            if any(leaf.ticket.tid == ticket.tid
                   for q in (self._queue, self._gather_queue,
                             self._rmw_queue) for leaf in q):
                self.flush(inflight_ok=True)
            if ticket.tid not in self._results:
                raise KeyError(f"unknown ticket {ticket}")
        out = self._results.pop(ticket.tid)
        if isinstance(out, FailedResult):
            raise out.error
        return out

    # -- fairness ------------------------------------------------------------

    def _wfq_keyed(self, queue: Sequence, cursor: int,
                   queue_rank: int) -> List[tuple]:
        """Weighted-fair drain keys for one queue: ``(key, leaf)`` pairs.

        Virtual-finish-time WFQ: tenant ``t``'s ``j``-th queued leaf
        (FIFO within a tenant) finishes at ``vtime[t] + (j+1)/weight[t]``
        — a weight-2 tenant lands two leaves per unit of virtual time
        where a weight-1 tenant lands one. Ties break by the
        cursor-rotated tenant rank, so with equal weights and idle vtime
        (the default: all keys ``j+1``) the order is *exactly* the
        round-robin this replaced: every tenant's j-th leaf, start tenant
        rotating per flush. ``queue_rank`` orders programs before gathers
        before RMWs on cross-queue key ties (joint drain-limited
        selection).
        """
        by_tenant: "OrderedDict[str, list]" = OrderedDict()
        for leaf in queue:
            by_tenant.setdefault(leaf.ticket.tenant, []).append(leaf)
        tenants = list(by_tenant)
        if not tenants:
            return []
        start = cursor % len(tenants)
        rank = {t: i for i, t in
                enumerate(tenants[start:] + tenants[:start])}
        keyed = []
        for t, leaves in by_tenant.items():
            w = self._tenant_weight.get(t, 1.0)
            base = self._vtime.get(t, 0.0)
            for j, leaf in enumerate(leaves):
                keyed.append(((base + (j + 1) / w, rank[t], j, queue_rank),
                              leaf))
        return keyed

    def _fair_order(self, queue: Sequence, cursor: int) -> List:
        """Weighted-fair order across tenants, FIFO within a tenant
        (plain rotated round-robin when every weight is the default 1.0).
        ``cursor`` picks the start tenant; ``flush`` advances it once per
        flush (not per queue) so a tenant that happens to sort first gets
        no standing head-of-line advantage.
        """
        keyed = self._wfq_keyed(queue, cursor, 0)
        keyed.sort(key=lambda e: e[0])
        return [leaf for _, leaf in keyed]

    # -- lowering (submission leaves -> AccessPlan) --------------------------

    def _lower_pending(self, drain_limit: Optional[int] = None) \
            -> plan_nodes.Plan:
        """Lower the pending queues through the plan pass pipeline.

        The lowering is cached against the exact queue contents (and
        round-robin cursor), so ``explain()`` followed by ``flush()``
        lowers once and executes the very plan it reported. Lowering
        *decisions* additionally hit the structural plan cache
        (``window_signature`` -> ``Skeleton``) across windows.

        ``drain_limit`` caps the window: the limit leaves with the
        smallest WFQ keys — selected jointly across all three queues —
        form the window; the rest stay queued (FIFO preserved) for the
        next flush. The deferred remainder rides with the cached lowering
        so ``flush_async`` drains exactly what was lowered.
        """
        fingerprint = (tuple(id(leaf) for leaf in self._queue),
                       tuple(id(leaf) for leaf in self._gather_queue),
                       tuple(id(leaf) for leaf in self._rmw_queue),
                       self._rr_cursor, drain_limit)
        if self._lowered is not None and self._lowered[0] == fingerprint:
            return self._lowered[1]
        cursor = self._rr_cursor
        queues = (self._queue, self._gather_queue, self._rmw_queue)
        deferred = None
        if drain_limit is not None and 0 <= drain_limit < self.pending:
            keyed = []
            for qi, q in enumerate(queues):
                keyed.extend(self._wfq_keyed(q, cursor, qi))
            keyed.sort(key=lambda e: e[0])
            take = {id(leaf) for _, leaf in keyed[:drain_limit]}
            # window keeps kind blocks (programs, gathers, RMWs) with the
            # selected leaves in WFQ order inside each block
            leaves = tuple(
                leaf for qi in range(3)
                for _, leaf in sorted(
                    (e for e in keyed if id(e[1]) in take
                     and e[0][3] == qi), key=lambda e: e[0]))
            deferred = tuple([leaf for leaf in q if id(leaf) not in take]
                             for q in queues)
        else:
            leaves = (tuple(self._fair_order(self._queue, cursor))
                      + tuple(self._fair_order(self._gather_queue, cursor))
                      + tuple(self._fair_order(self._rmw_queue, cursor)))
        order = tuple((leaf.ticket.tenant, leaf.ticket.tid)
                      for leaf in leaves)
        backend = plan_emit.backend_for(self.engine)
        signature = plan_passes.window_signature(
            leaves, self.max_batch, backend.name)
        skeleton = None
        if leaves:
            skeleton = self._plan_cache.get(signature)
            if skeleton is not None:
                self._plan_cache.move_to_end(signature)
                self.stats["plan_cache_hits"] += 1
            else:
                self.stats["plan_cache_misses"] += 1
        ctx = plan_passes.LowerContext(
            max_batch=self.max_batch, cost=self.cost, engine=self.engine,
            num_shards=int(getattr(self.engine, "num_shards", 1)),
            sharded_capable=backend.sharded, replay=skeleton,
            verify=self.verify)
        plan = plan_passes.lower(leaves, order, ctx, backend)
        plan.signature = signature
        plan.cache_hit = skeleton is not None
        # hazard scan rides the cached lowering: explain() and the flush
        # see one scan, and it is O(leaves) by design (analysis.hazards)
        with TraceAnnotation("dx.flush.hazard_scan"):
            plan.diagnostics = analysis_hazards.scan_window(plan.leaves)
        if leaves and skeleton is None:
            self._plan_cache[signature] = plan_passes.skeleton_of(plan)
            while len(self._plan_cache) > PLAN_CACHE_SIZE:
                self._plan_cache.popitem(last=False)
        self._lowered = (fingerprint, plan, deferred)
        return plan

    def explain(self) -> Explanation:
        """Lower the *pending* window (without executing or consuming it)
        and return the renderable plan — per-pass deltas, fusion and
        coalescing decisions, chosen backends. The next ``flush`` executes
        exactly this plan (same object, same node ids), which then rides
        on ``FlushReport.plan``.
        """
        return Explanation(self._lower_pending())

    # -- execution -----------------------------------------------------------

    def flush(self, *, inflight_ok: bool = False,
              drain_limit: Optional[int] = None) -> FlushReport:
        """Blocking flush: dispatch the window and wait for retirement.

        A thin wrapper over ``flush_async`` — the decoupled access/execute
        pipeline (``repro.pipeline``) uses the async form directly so
        iteration k+1's access window can dispatch while iteration k's
        compute is still in flight.
        """
        return self.flush_async(inflight_ok=inflight_ok,
                                drain_limit=drain_limit).result()

    @partial(annotate_function, name="dx.flush")
    def flush_async(self, *, inflight_ok: bool = False,
                    drain_limit: Optional[int] = None) -> FlushHandle:
        """Drain the queues: lower to a plan, emit every node, retire.

        Non-blocking: every node is *dispatched* (JAX async dispatch — the
        XLA computations run behind the returned handle); ``poll``/
        ``result`` on the ``FlushHandle`` observe/await retirement. A node
        whose execution raises does not poison the flush: its members'
        tickets resolve to ``FailedResult`` (re-raised by ``result``) and
        every other node still executes.

        While a previous async window is still in flight (its handle
        neither resolved via ``result()`` nor observed retired via
        ``poll()``), another flush raises ``RuntimeError`` unless
        ``inflight_ok=True`` — multi-window overlap is exactly what the
        decoupled pipeline does deliberately, and what an unmanaged caller
        gets by accident.

        ``drain_limit`` bounds the window to the limit leaves with the
        smallest WFQ keys (per-tenant SLO weights, ``configure_tenant``);
        deferred leaves stay queued and their tenants' virtual times
        advance so the next window carries the fairness debt forward.
        """
        prev = self._inflight() if self._inflight is not None else None
        if prev is not None and not prev.done and not inflight_ok:
            raise RuntimeError(
                "flush while a previous async flush window is still in "
                "flight: resolve its FlushHandle (result()) or poll() it "
                "to retirement first, or pass inflight_ok=True to overlap "
                "windows deliberately (what repro.pipeline.DecoupledLoop "
                "does)")
        try:
            with TraceAnnotation("dx.flush.lower"):
                plan = self._lower_pending(drain_limit)
        except Exception as e:
            # last resort: per-leaf/per-node isolation lives in the
            # passes, but an unforeseen lowering failure must still fail
            # the WINDOW, never poison the scheduler — drain the queues,
            # resolve every pending ticket to FailedResult, and leave
            # future flushes healthy
            pending = (self._queue + self._gather_queue + self._rmw_queue)
            self._queue, self._gather_queue, self._rmw_queue = [], [], []
            self._lowered = None
            self._tenant_pending.clear()
            self._vtime.clear()
            self._rr_cursor += 1
            self.stats["flushes"] += 1
            self.stats["group_errors"] += 1
            failed = FailedResult(e)
            for leaf in pending:
                self._results.setdefault(leaf.ticket.tid, failed)
            report = FlushReport(
                order=tuple((lf.ticket.tenant, lf.ticket.tid)
                            for lf in pending),
                groups=(), n_programs=0, n_gathers=0, n_rmws=0)
            handle = FlushHandle(report, ())
            self._inflight = weakref.ref(handle)
            return handle
        if self.strict:
            errs = [d for d in plan.diagnostics if d.severity == "ERROR"]
            if errs:
                # refuse BEFORE any queue mutation: the window stays
                # pending, so the caller can explain() the offending
                # plan, drop submissions, or re-flush non-strict
                raise HazardError(errs)
        deferred = self._lowered[2] if self._lowered is not None else None
        if deferred is None:
            self._queue, self._gather_queue, self._rmw_queue = [], [], []
            self._vtime.clear()              # full drain: no fairness debt
            self._tenant_pending.clear()
        else:
            # drain-limited window: deferred leaves stay queued (FIFO);
            # drained tenants' virtual time advances by served/weight so
            # the next window's WFQ keys carry the debt forward
            self._queue, self._gather_queue, self._rmw_queue = \
                (list(q) for q in deferred)
            self.stats["deferrals"] += sum(len(q) for q in deferred)
            for tenant, _ in plan.order:
                w = self._tenant_weight.get(tenant, 1.0)
                self._vtime[tenant] = self._vtime.get(tenant, 0.0) + 1.0 / w
            self._tenant_pending.clear()
            for q in (self._queue, self._gather_queue, self._rmw_queue):
                for leaf in q:
                    t = leaf.ticket.tenant
                    self._tenant_pending[t] = \
                        self._tenant_pending.get(t, 0) + 1
        self._lowered = None
        self._rr_cursor += 1                 # once per flush, not per queue

        ctx = plan_emit.EmitContext(
            scheduler=self, engine=self.engine, results=self._results,
            stats=self.stats, make_failed=FailedResult,
            make_group_error=lambda node, e: GroupReport(
                len(node.members), node.members[0].program.name,
                vmapped=False, fell_back=False, error=repr(e)))
        with TraceAnnotation("dx.flush.emit"):
            plan_emit.execute(plan, ctx, plan_emit.backend_for(self.engine))
        with TraceAnnotation("dx.flush.report"):
            counts = plan.counts()
            self.stats["flushes"] += 1
            self.stats["programs"] += counts["programs"]
            self.stats["gathers"] += counts["gathers"]
            self.stats["rmws"] += counts["rmws"]
            for d in plan.diagnostics:
                bucket = ("hazard_errors" if d.severity == "ERROR"
                          else "hazard_warnings")
                self.stats[bucket] += 1
                for tenant in d.tenants:
                    per = self.stats["hazards_by_tenant"].setdefault(
                        tenant, {"errors": 0, "warnings": 0})
                    per["errors" if d.severity == "ERROR"
                        else "warnings"] += 1

            gather_streams = {g.table_id: tuple(g.streams)
                              for g in plan.fused("gather")}
            rmw_streams = {(r.table_id, r.op): tuple(m.idx for m in r.members)
                           for r in plan.fused("rmw")}
            report = FlushReport(
                order=plan.order,
                groups=tuple(ctx.group_reports),
                n_programs=counts["programs"],
                n_gathers=counts["gathers"],
                shard_stats=ctx.shard_stats,
                n_rmws=counts["rmws"],
                plan=plan,
                diagnostics=plan.diagnostics,
                _gather_thunk=(lambda s=gather_streams: {
                    k: reorder.cross_stream_gain(v) for k, v in s.items()}),
                _rmw_thunk=(lambda s=rmw_streams: {
                    k: reorder.cross_stream_gain(v) for k, v in s.items()}))
            leaves = jax.tree_util.tree_leaves(
                [v for v in (self._results.get(tid) for _, tid in plan.order)
                 if v is not None and not isinstance(v, FailedResult)])
            plan.strip()   # release array payloads; structure stays readable
            handle = FlushHandle(report, tuple(leaves))
            self._inflight = weakref.ref(handle)
            return handle

    # -- emitters (registered on the "local" backend) ------------------------
    # Thin by contract: every fusion/grouping/backend decision was made by
    # the passes; these only execute the annotated node.

    def _execute_group(self, node: plan_nodes.BatchedGroup,
                       ctx: plan_emit.EmitContext) -> None:
        members = node.members
        prog = members[0].program
        # streams are extracted eagerly (cheap NumPy, and it must not pin
        # the members' envs in a long-lived report); the gain computation
        # itself stays lazy — it runs only if the report is actually read
        entries = _coalescing_entries(members)
        thunk = (lambda e=entries: _coalescing_gains(e))
        if node.backend != "vmap":
            if len(members) == 1:
                self.stats["singleton_groups"] += 1
            for sub in members:
                exe = self.engine.executable(sub.program)
                self._results[sub.ticket.tid] = exe(sub.env, sub.regs, {})
            ctx.group_reports.append(GroupReport(
                len(members), prog.name, vmapped=False, fell_back=False,
                _coalescing_thunk=thunk))
            return

        exe = self.engine.executable(prog, batch=len(members),
                                     shared=node.shared)
        try:
            outs = exe.run_batch([s.env for s in members],
                                 [s.regs for s in members])
            for sub, out in zip(members, outs):
                self._results[sub.ticket.tid] = out
            self.stats["vmap_groups"] += 1
            ctx.group_reports.append(GroupReport(
                len(members), prog.name, vmapped=True, fell_back=False,
                _coalescing_thunk=thunk))
        except Exception:
            # vmap could not trace this program shape: run each member
            # through the (still cached) single-program executable.
            self.stats["vmap_fallbacks"] += 1
            for sub in members:
                exe1 = self.engine.executable(sub.program)
                self._results[sub.ticket.tid] = exe1(sub.env, sub.regs, {})
            ctx.group_reports.append(GroupReport(
                len(members), prog.name, vmapped=False, fell_back=True,
                _coalescing_thunk=thunk))

    def _execute_gathers(self, node: plan_nodes.FusedGather,
                         ctx: plan_emit.EmitContext) -> None:
        if node.backend == "eager":
            # direct clamped read — the coalesce pass decided dedup
            # cannot pay for itself on this stream
            for m, stream in zip(node.members, node.streams):
                self._results[m.ticket.tid] = node.table[stream]
            return
        uniq = to_host(node.unique_idx, "gather_unique")
        cap = _bucket_pow2(uniq.shape[0])
        if cap > uniq.shape[0]:
            # pad the fetch to the bucket with row 0 (in-range, so loads
            # clamp semantics are untouched); inverses never point at pads
            uniq = np.concatenate(
                [uniq, np.zeros(cap - uniq.shape[0], uniq.dtype)])
        with uploading("gather_unique", uniq):
            packed = node.table[uniq]          # single fused fetch
        for m, inv in zip(node.members, node.inverses):
            self._results[m.ticket.tid] = packed[inv]

    def _execute_rmws(self, node: plan_nodes.FusedRmw,
                      ctx: plan_emit.EmitContext) -> None:
        table = ctx.tables.get(node.table_id, node.table)
        idx = to_host(node.idx, "rmw_idx").reshape(-1)
        vals, cond = node.values, node.cond
        cap = _bucket_pow2(idx.shape[0]) if idx.shape[0] else 0
        if cap > idx.shape[0]:
            # pad to the bucket with past-the-end destinations: the OOB
            # store policy (stores drop) discards them on every path, so
            # padded lanes are no-ops regardless of value
            pad = cap - idx.shape[0]
            vals = to_host(vals, "rmw_values").reshape(
                (idx.shape[0],) + np.shape(table)[1:])
            idx = np.concatenate(
                [idx, np.full(pad, np.shape(table)[0], idx.dtype)])
            vals = np.concatenate(
                [vals, np.zeros((pad,) + vals.shape[1:], vals.dtype)])
            if cond is not None:
                cond = np.concatenate(
                    [to_host(cond, "rmw_values").reshape(-1).astype(bool),
                     np.zeros(pad, bool)])
        with uploading("rmw", idx, vals, cond):
            new = bulk_ops.bulk_rmw(table, idx, vals, op=node.op,
                                    cond=cond,
                                    optimize=self.engine.optimize)
        combine = bulk_ops.rmw_combine(np.ndim(table), node.op,
                                       self.engine.optimize)
        ctx.span.set_metadata(combine=combine)
        if combine == "scan":
            self.stats["rmw_scan_combines"] += 1
        ctx.tables[node.table_id] = new
        ctx.rmw_members.setdefault(node.table_id, []).extend(node.members)


def _bucket_pow2(n: int) -> int:
    """Smallest power of two >= n, floored at 16.

    Fused stream lengths vary with window composition, and every distinct
    length is a fresh XLA compile of the fetch/RMW executable — under
    open-loop traffic with adaptive windows that is an unbounded compile
    stream (and enough accumulated CPU executables eventually crash the
    XLA compiler). Bucketing caps shape diversity at O(log max_len)
    executables per table shape; padded lanes are provable no-ops (row-0
    fetches nothing new, past-the-end stores drop)."""
    return max(16, 1 << int(n - 1).bit_length())


# ---------------------------------------------------------------------------
# cross-program coalescing measurement (module-level so the lazy report
# thunk closes over extracted index streams only — never over plan leaves
# or their envs)
# ---------------------------------------------------------------------------

def _coalescing_entries(members: Sequence) -> Dict[str, list]:
    """Per target region: [(caller-array id, static index stream), ...]
    across the group's members. Small NumPy arrays only."""
    per_region: Dict[str, list] = {}
    for sub in members:
        for region, stream in _static_index_streams(sub).items():
            per_region.setdefault(region, []).append(
                (sub.src_ids.get(region), stream))
    return per_region


def _coalescing_gains(per_region: Dict[str, list]) -> Dict:
    """Score the coalescing the shared engine could apply across the
    group's indirect streams, per target region (reported in the flush
    report; execution stays on the bit-faithful engine path).

    Only regions backed by the *same caller array* across members count —
    two tenants indexing private tables that happen to share a region name
    have no rows to reuse.
    """
    out = {}
    for region, entries in per_region.items():
        ids = {i for i, _ in entries}
        if len(entries) < 2 or len(ids) != 1 or None in ids:
            continue
        out[region] = reorder.cross_stream_gain([s for _, s in entries])
    return out


def _static_index_streams(sub: plan_nodes.ProgramNode) \
        -> Dict[str, np.ndarray]:
    """Best-effort static evaluation of each ILD's index stream.

    Walks the program propagating tiles computable from python-int regs and
    env contents (SLD with int start/stride, ILD through a known tile, ALUS
    with int operands). Unresolvable tiles (RNG outputs, traced regs,
    condition-masked chains) simply drop out — this feeds *reporting* only.
    """
    known: Dict[str, np.ndarray] = {}
    streams: Dict[str, list] = {}
    ts = sub.program.tile_size

    def _reg(r):
        if isinstance(r, str):
            v = sub.regs.get(r)
            return v if isinstance(v, (int, float, np.integer)) else None
        return r

    for ins in sub.program.instrs:
        if isinstance(ins, isa.SLD) and ins.tc is None:
            start, stride = _reg(ins.rs1), _reg(ins.rs3)
            if start is None or stride is None or ins.base not in sub.env:
                continue
            base = np.asarray(sub.env[ins.base])
            addr = int(start) + np.arange(ts, dtype=np.int64) * int(stride)
            known[ins.td] = base[np.clip(addr, 0, base.shape[0] - 1)]
        elif isinstance(ins, isa.ILD):
            idx = known.get(ins.ts1)
            if idx is None or ins.base not in sub.env:
                continue
            count = ts
            n = _reg("N")
            if n is not None:
                count = min(ts, int(n))
            streams.setdefault(ins.base, []).append(
                idx[:count].astype(np.int64))
            base = np.asarray(sub.env[ins.base])
            if base.ndim == 1:
                # propagate ignoring the condition mask: lanes past the trip
                # count are cut by [:count] above; this feeds reporting only.
                known[ins.td] = base[
                    np.clip(idx.astype(np.int64), 0, base.shape[0] - 1)]
        elif isinstance(ins, isa.ALUS):
            a, b = known.get(ins.ts), _reg(ins.rs)
            if a is None or b is None:
                continue
            try:
                known[ins.td] = np.asarray(isa.alu_apply(ins.op, a, b))
            except Exception:
                continue
    return {r: np.concatenate(s) for r, s in streams.items() if s}


# ---------------------------------------------------------------------------
# "local" backend registration: the default pass table plus this module's
# thin emitters. The sharded variant is registered by
# ``repro.distributed.engine`` — never probed from here.
# ---------------------------------------------------------------------------

def _emit_program_group(node, ctx):
    ctx.scheduler._execute_group(plan_nodes.unwrap(node), ctx)


def _emit_fused_gather(node, ctx):
    ctx.scheduler._execute_gathers(plan_nodes.unwrap(node), ctx)


def _emit_fused_rmw(node, ctx):
    ctx.scheduler._execute_rmws(plan_nodes.unwrap(node), ctx)


plan_emit.register_backend("local", emitters={
    ("program_group", "vmap"): _emit_program_group,
    ("program_group", "eager"): _emit_program_group,
    ("gather", "bulk"): _emit_fused_gather,
    ("gather", "eager"): _emit_fused_gather,
    ("rmw", "bulk"): _emit_fused_rmw,
})
