"""ShardedEngine: the bulk-access engine spanning a JAX device mesh.

Paper §6.6, option 1: multiple DX100 units partition the address range, and
each bulk request stream is split by owner unit so that the reorder /
coalesce / interleave pipeline runs *next to the memory that holds the
rows*. Here a 1-D device mesh plays the unit array and ``shard_map`` the
fabric. Per shard, per call (DESIGN.md §5):

  1. **dedup before the fabric** — each shard runs the unique-set pass
     (``exchange.dedup_stream`` / ``combine_duplicates``) over its own
     slice *before* any lane is considered for routing, so duplicate rows
     never ship;
  2. **owner-local lanes never enter the fabric** — the deduped slice is
     split into the part this shard already owns (served straight from the
     local table slice) and the remote spill; only the spill is packed
     into static per-owner buckets (``exchange.partition_by_owner``) whose
     capacity is the *measured* worst per-(source, owner) spill, not the
     worst-case slice length;
  3. **compressed wire** — because the spill is sorted and unique, its
     buckets are strictly ascending row runs; the cost model
     (``CostModel.exchange_plan``) picks "raw" int32 lanes, an occupancy
     "bitmap", or packed 16-bit "delta" words per node, and one
     ``all_to_all`` ships the chosen encoding;
  4. the owner serves received rows with a direct table take (they arrive
     pre-sorted and pre-deduped per source — no second sort) and gather
     values return via the inverse ``all_to_all``; RMWs are **one-way**:
     pre-combined updates land and merge owner-locally, nothing returns.

Lane *placement* is also a plan decision: the host-side exchange planner
(``_measure_exchange``) compares the natural "block" slicing against an
owner-major permutation of the padded stream and, when the measured
local-fraction gain clears the cost model's cutoff, applies the
permutation inside the jitted call ("owner" placement) so most lanes
start life on the shard that owns them.

The route (exchange dispatch) and exec (owner-local compute) stages are
built both fused (one jit — the direct-call hot path) and split
(``gather_start``/``gather_finish``, ``rmw_start``/``rmw_finish``) so the
emit stage can dispatch every sharded node's exchange before any node's
exec and overlap fabric with compute across nodes.

``ShardedEngine`` extends ``Engine``: programs, the compile cache and the
``Scheduler`` frontend all keep working, batched program groups additionally
fan out lane-wise across the mesh (``_constrain_batch``). Importing this
module registers the **"sharded" plan backend** (``repro.plan.emit``): a
shard pass that wraps mesh-eligible fused gather/RMW nodes in
``ShardedNode`` (cost-model placement + exchange plan) plus the owner-local
emitters and their route-stage prefetchers — core lowers through the
registry and never imports (or duck-type-probes) this package.
"""
from __future__ import annotations

import dataclasses
import types
from collections import OrderedDict
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import plan
from repro.core import bulk_ops, isa, reorder
from repro.core.engine import Engine
from repro.distributed import exchange
from repro.distributed.mesh import as_mesh
from repro.plan.cost import CostModel, ExchangePlan
from repro.plan.spans import to_device, to_host


class ShardStats:
    """Per-stream record of one sharded bulk access.

    Counts are **post-dedup**: ``sent[i, j]`` is the number of *distinct*
    rows in shard ``i``'s slice owned by shard ``j`` (the diagonal never
    enters the fabric); ``received[j]`` / ``unique[j]`` are each owner's
    landed lane count (self-local + received spill) and distinct-row
    count. ``sent.sum() == received.sum()`` holds by construction — the
    measured bucket capacity is exact, so the exchange can never drop a
    lane — and ``unique[j]`` is placement-invariant (every requested row
    owned by ``j`` lands on ``j`` at least once).

    Wire accounting is static per call geometry: ``idx_bytes`` is what the
    chosen codec shipped for the off-diagonal index spill,
    ``idx_bytes_raw`` what raw int32 lanes would have cost, and
    ``bytes_on_wire`` adds the value payload (gather return / RMW
    forward). ``overlap_fraction`` is 1.0 when the fabric exchange had
    already completed before the exec stage dispatched (split emit path),
    0.0 when it had not, and None for fused single-dispatch calls.

    Recording holds device arrays so it never blocks the flush hot path
    (same discipline as the lazy ``GroupReport`` coalescing thunk); the
    first read of any count field materializes all of them to NumPy *and
    releases the device references*, so a long-lived report
    (``AccessService.last_report``) cannot pin exchange buffers.
    """

    def __init__(self, sent: jax.Array, received: jax.Array,
                 unique: jax.Array, *, placement: str = "block",
                 codec: str = "raw", capacity: int = 0,
                 idx_bytes: int = 0, idx_bytes_raw: int = 0,
                 bytes_on_wire: int = 0,
                 overlap: Optional[float] = None):
        self._device: Optional[tuple] = (sent, received, unique)
        self._host: Optional[tuple] = None
        self.placement = placement
        self.codec = codec
        self.capacity = int(capacity)
        self.idx_bytes = int(idx_bytes)
        self.idx_bytes_raw = int(idx_bytes_raw)
        self.bytes_on_wire = int(bytes_on_wire)
        self._overlap = overlap

    def _materialize(self) -> tuple:
        if self._host is None:
            dev, self._device = self._device, None
            self._host = tuple(np.asarray(x) for x in dev)
        return self._host

    @property
    def sent(self) -> np.ndarray:
        return self._materialize()[0]

    @property
    def received(self) -> np.ndarray:
        return self._materialize()[1]

    @property
    def unique(self) -> np.ndarray:
        return self._materialize()[2]

    @property
    def num_shards(self) -> int:
        return int(self.received.shape[0])

    @property
    def coalescing_gain(self) -> np.ndarray:
        """Owner-local dedup factor per shard (#landed / #distinct)."""
        r, u = self.received, self.unique
        return r / np.maximum(u, 1)

    @property
    def local_fraction(self) -> float:
        """Fraction of post-dedup requests already resident on their
        source shard (the diagonal of the exchange matrix — no fabric
        traffic)."""
        s = self.sent
        return float(np.trace(s) / max(s.sum(), 1))

    @property
    def compression_ratio(self) -> float:
        """Raw-vs-shipped index wire ratio (1.0 = uncompressed)."""
        if not self.idx_bytes:
            return 1.0
        return self.idx_bytes_raw / self.idx_bytes

    @property
    def overlap_fraction(self) -> Optional[float]:
        return self._overlap

    def set_overlap(self, f: float) -> None:
        self._overlap = float(f)

    def __repr__(self) -> str:
        # deliberately does not materialize (repr of a live report must not
        # force a device sync)
        state = "host" if self._host is not None else "device"
        return (f"ShardStats(<{state}> place={self.placement} "
                f"codec={self.codec})")


@dataclasses.dataclass
class ExchangeInflight:
    """Handle for a dispatched route stage awaiting its exec stage
    (``gather_start``/``rmw_start`` -> ``*_finish``)."""
    kind: str
    fns: object = None
    route: tuple = ()
    perm: object = None
    n: int = 0
    xplan: ExchangePlan = None
    cap: int = 0
    codec: str = "raw"
    rows_per: int = 0
    value_nbytes: int = 0


class ShardedEngine(Engine):
    """Drop-in ``Engine`` whose bulk streams span a device mesh.

    ``mesh``: None (all visible devices), an int shard count, or a 1-D
    ``jax.sharding.Mesh``. Everything else matches ``Engine``; a 1-shard
    mesh degenerates to single-device behaviour (and is how the parity
    harness anchors the collective path to the oracle).
    """

    plan_backend = "sharded"     # registered below at import time
    #: streams longer than this never get a host-side exchange measurement
    #: (the fallback plan — block placement, raw wire, worst-case capacity
    #: — is always correct, just not minimal)
    measure_limit = 1 << 16

    def __init__(self, mesh=None, *, tile_size: int = 16384,
                 optimize: bool = True, use_kernel: bool = False,
                 cost_model: Optional[CostModel] = None):
        super().__init__(tile_size=tile_size, optimize=optimize,
                         use_kernel=use_kernel)
        self.mesh = as_mesh(mesh)
        self.axis = self.mesh.axis_names[0]
        self.num_shards = int(self.mesh.shape[self.axis])
        self._shard_fns: Dict[tuple, object] = {}
        # (id(idx), id(valid), n_rows, kind, ns) -> (idx, valid, meas,
        # perm): strong refs keep the ids stable; jax arrays only (an
        # in-place-mutable numpy stream must be re-measured every call —
        # a stale capacity could drop lanes)
        self._xplan_cache: "OrderedDict[tuple, tuple]" = OrderedDict()
        self.exchange_cost = cost_model or CostModel()
        self.last_shard_stats: Optional[ShardStats] = None
        # exchange measurements that raised and fell back to the
        # unmeasured plan (still correct; counted so a smoke can see it)
        self.stats["exchange_measure_errors"] = 0

    # -- static padding to the mesh-divisible shapes shard_map needs --------
    # (table padding/unpadding lives *inside* the jitted graphs so a
    # non-divisible table never pays a separate eager O(table) concatenate
    # per call; only the small index/valid streams are padded here)

    def _pad_stream(self, idx: jax.Array, valid=None):
        n = int(idx.shape[0])
        per = -(-n // self.num_shards)
        pad = per * self.num_shards - n
        if pad:
            idx = jnp.concatenate([idx, jnp.zeros((pad,), idx.dtype)])
        mask = jnp.arange(per * self.num_shards, dtype=jnp.int32) < n
        if valid is not None:
            if pad:
                valid = jnp.concatenate([valid, jnp.zeros((pad,), bool)])
            mask = mask & valid
        return idx, mask, per

    # -- host-side exchange planning ----------------------------------------

    def _measure_exchange(self, idx, valid, *, n_rows: int, kind: str):
        """Measure the post-dedup exchange of one stream on the host,
        without ever blocking on an in-flight device array (the
        ``measure_factor`` discipline): replicates the jitted pipeline's
        clip/drop, pad, slice and per-slice-unique semantics in NumPy
        exactly — the measured capacity sizes a lossy (``mode="drop"``)
        buffer, so "close" is not good enough. Returns ``(meas, perm)``
        for ``CostModel.exchange_plan`` or ``(None, None)`` when the
        stream is not host-resident or over budget."""
        try:
            n = int(idx.shape[0])
        except (AttributeError, TypeError):
            idx = np.asarray(idx)
            n = int(idx.shape[0])
        if n == 0 or n > self.measure_limit:
            return None, None
        for a in (idx, valid):
            if a is not None and hasattr(a, "is_ready") and \
                    not a.is_ready():
                return None, None
        h = to_host(idx, "exchange_plan").reshape(-1).astype(np.int64)
        hv = np.ones(n, bool) if valid is None else \
            to_host(valid, "exchange_plan").reshape(-1).astype(bool)
        if kind == "gather":
            h = np.clip(h, 0, n_rows - 1)          # loads clamp
        else:
            hv = hv & (h >= 0) & (h < n_rows)      # stores drop
        return self._measure_padded(h, hv, n_rows=n_rows)

    def _measure_padded(self, h: np.ndarray, hv: np.ndarray, *,
                        n_rows: int):
        """Core of the planner: given the canonicalized host stream,
        evaluate both placements (block slices vs the owner-major
        permutation) — measured diagonal fraction, exact worst
        per-(source, owner) spill (power-of-two bucketed), and per-codec
        wire words for the cost model to compare."""
        ns = self.num_shards
        rows_per = -(-n_rows // ns)
        n = int(h.shape[0])
        per = -(-n // ns)
        L = per * ns
        hp = np.zeros(L, np.int64)
        hp[:n] = h
        vp = np.zeros(L, bool)
        vp[:n] = hv
        owner = np.clip(hp // rows_per, 0, ns - 1)
        # owner-major permutation: stable sort by owner key, invalid lanes
        # last — the exact trace the device applies (perm is an argument,
        # so both placements share one compiled graph)
        key = np.where(vp, owner, ns)
        perm = np.argsort(key, kind="stable").astype(np.int32)
        meas = {}
        for placement, p in (("block", None), ("owner", perm)):
            sp = hp if p is None else hp[p]
            vv = vp if p is None else vp[p]
            diag = total = spill = 0
            for s in range(ns):
                sl = sp[s * per:(s + 1) * per]
                u = np.unique(sl[vv[s * per:(s + 1) * per]])
                cnt = np.bincount(np.clip(u // rows_per, 0, ns - 1),
                                  minlength=ns)
                total += int(cnt.sum())
                diag += int(cnt[s])
                cnt[s] = 0
                spill = max(spill, int(cnt.max()))
            cap = min(exchange.bucket_capacity(spill), per)
            meas[f"local_{placement}"] = diag / max(total, 1)
            meas[f"cap_{placement}"] = cap
            if spill == 0:
                # nothing crosses the fabric: encoding would be pure
                # overhead, so only raw is legal
                wire = {"raw": cap, "bitmap": None, "delta": None}
            else:
                wire = {"raw": cap,
                        "bitmap": exchange.bitmap_words(rows_per),
                        "delta": (exchange.delta_words(cap)
                                  if rows_per <= (1 << 16) else None)}
            meas[f"wire_{placement}"] = wire
        return meas, perm

    def _seed_cache(self, key, idx, valid, meas, perm) -> None:
        self._xplan_cache[key] = (idx, valid, meas, perm)
        self._xplan_cache.move_to_end(key)
        while len(self._xplan_cache) > 64:
            self._xplan_cache.popitem(last=False)

    def _plan_exchange(self, idx, valid, *, n_rows: int, kind: str,
                       placement: Optional[str] = None,
                       codec: Optional[str] = None):
        """Measure (or replay a cached measurement for the same stream
        *object*) and let the cost model decide. ``placement``/``codec``
        pin the policy — the plan-IR annotation path, where the shard
        pass already decided and ``explain()`` reported it — while the
        capacity is always taken from the fresh measurement."""
        key = (id(idx), id(valid), n_rows, kind, self.num_shards)
        hit = self._xplan_cache.get(key)
        if hit is not None and hit[0] is idx and hit[1] is valid:
            meas, perm = hit[2], hit[3]
            self._xplan_cache.move_to_end(key)
        else:
            meas, perm = self._measure_exchange(idx, valid, n_rows=n_rows,
                                                kind=kind)
            if meas is not None and isinstance(idx, jax.Array):
                self._seed_cache(key, idx, valid, meas, perm)
        cm = self.exchange_cost
        if placement is not None or codec is not None:
            cm = dataclasses.replace(
                cm, force_placement=placement or cm.force_placement,
                force_codec=codec or cm.force_codec)
        xplan = cm.exchange_plan(meas)
        if xplan.placement == "owner" and perm is None:
            # a pinned "owner" placement without a measurable stream has
            # no permutation to apply — fall back to block, never guess
            xplan = dataclasses.replace(xplan, placement="block")
        return xplan, (perm if xplan.placement == "owner" else None)

    def plan_node_exchange(self, node, cost) -> ExchangePlan:
        """Shard-pass hook: measure a mesh-placed fused node's exchange
        and let ``cost`` pick (placement, codec, capacity). Measures from
        the *member* streams (caller-resident arrays, is_ready-guarded —
        the post-coalesce ``unique_idx`` is usually still in flight at
        lowering time) and replicates the device dedup/pad layout on the
        host, then seeds the per-call plan cache so emission reuses the
        measurement without re-probing readiness."""
        ns = self.num_shards
        try:
            if node.kind == "gather":
                if node.unique_idx is None or node.n_lanes == 0 or \
                        node.n_lanes > self.measure_limit:
                    return cost.exchange_plan(None)
                for s in node.streams:
                    if hasattr(s, "is_ready") and not s.is_ready():
                        return cost.exchange_plan(None)
                cat = np.concatenate(
                    [s.reshape(-1) for s in
                     to_host(list(node.streams), "exchange_plan")])
                u = np.unique(np.clip(cat.astype(np.int64), 0,
                                      node.table_rows - 1))
                # replicate the coalesce pass's padded layout: sorted
                # unique values first, pad (pad_valid False) after
                L_pad = int(node.unique_idx.shape[0])
                h = np.zeros(L_pad, np.int64)
                h[:u.shape[0]] = u
                hv = np.zeros(L_pad, bool)
                hv[:u.shape[0]] = True
                meas, perm = self._measure_padded(
                    h, hv, n_rows=node.table_rows)
                key = (id(node.unique_idx), id(node.pad_valid),
                       node.table_rows, "gather", ns)
                self._seed_cache(key, node.unique_idx, node.pad_valid,
                                 meas, perm)
            else:
                if node.idx is None or node.n_lanes == 0 or \
                        node.n_lanes > self.measure_limit:
                    return cost.exchange_plan(None)
                arrs = [m.idx for m in node.members]
                conds = [m.cond for m in node.members]
                for a in arrs + [c for c in conds if c is not None]:
                    if hasattr(a, "is_ready") and not a.is_ready():
                        return cost.exchange_plan(None)
                h = np.concatenate(
                    [a.reshape(-1) for a in to_host(arrs, "exchange_plan")]
                ).astype(np.int64)
                hv = np.concatenate(
                    [np.ones(m.n_lanes, bool) if c is None
                     else to_host(c, "exchange_plan").reshape(-1)
                     .astype(bool)
                     for m, c in zip(node.members, conds)])
                hv = hv & (h >= 0) & (h < node.table_rows)
                meas, perm = self._measure_padded(
                    h, hv, n_rows=node.table_rows)
                key = (id(node.idx), id(node.cond), node.table_rows,
                       "rmw", ns)
                self._seed_cache(key, node.idx, node.cond, meas, perm)
        except Exception:
            self.stats["exchange_measure_errors"] += 1
            return cost.exchange_plan(None)
        xplan = cost.exchange_plan(meas)
        if xplan.placement == "owner" and perm is None:
            xplan = dataclasses.replace(xplan, placement="block")
        return xplan

    def _concretize(self, xplan: ExchangePlan, perm, per: int):
        """Turn a plan into the static call geometry: effective capacity
        (worst case = slice length when unmeasured), effective codec
        (compression needs a measured capacity bound), and the placement
        permutation (identity for block — same trace either way)."""
        cap = int(xplan.capacity) if xplan.capacity else per
        codec = xplan.codec if xplan.capacity else "raw"
        L = per * self.num_shards
        if perm is not None and xplan.placement == "owner":
            perm_arr = to_device(perm, "exchange_perm")
        else:
            perm_arr = jnp.arange(L, dtype=jnp.int32)
        return cap, codec, perm_arr

    # -- sharded bulk ops ----------------------------------------------------

    def sharded_gather(self, table, idx, *, valid=None,
                       placement: Optional[str] = None,
                       codec: Optional[str] = None) -> jax.Array:
        """``C = table[idx]`` with dedup and the reorder→coalesce pipeline
        running owner-locally on every shard; sets ``last_shard_stats``.

        ``valid``: optional (len(idx),) bool mask — lanes marked False
        never enter the exchange (no fabric traffic, excluded from stats)
        and read 0. Lets callers with statically padded streams (the
        scheduler's coalesce padding) keep shapes — and hence the cached
        shard_map trace — stable instead of slicing to a data-dependent
        length. ``placement``/``codec`` pin the exchange plan (the
        annotated plan-IR path)."""
        table = jnp.asarray(table)
        n_rows = int(table.shape[0])
        idx_arr = jnp.asarray(idx).astype(jnp.int32)
        n = int(idx_arr.shape[0])
        if n == 0:
            self.last_shard_stats = None
            return table[idx_arr]
        xplan, perm = self._plan_exchange(idx, valid, n_rows=n_rows,
                                          kind="gather",
                                          placement=placement, codec=codec)
        # loads clamp (policy): same as bulk_gather, so a mesh of any size
        # agrees with the single-device engine on OOB streams
        idx_p, mask, per = self._pad_stream(
            jnp.clip(idx_arr, 0, n_rows - 1), valid)
        cap, codec_eff, perm_arr = self._concretize(xplan, perm, per)
        rows_per = -(-n_rows // self.num_shards)
        fns = self._shard_fn("gather", rows_per, per, cap, codec_eff)
        out, sent, recv, uniq = fns.fused(table, idx_p, mask, perm_arr)
        self._record_stats(sent, recv, uniq, xplan=xplan, cap=cap,
                           codec=codec_eff, rows_per=rows_per,
                           value_nbytes=self._row_nbytes(table))
        return out[:n]

    def sharded_rmw(self, table, idx, values, *, op: str = "ADD",
                    valid=None, placement: Optional[str] = None,
                    codec: Optional[str] = None):
        """``table[idx] op= values`` across the mesh, **one-way**:
        duplicate destinations merge with ``op`` on the source shard
        (``combine_duplicates``), one combined update per distinct row
        crosses the fabric, and nothing returns — owner-local
        segment-combine then applies local + received updates in a single
        unique-scatter. ``op`` must be in ``isa.RMW_OPS`` (associative +
        commutative — §3.1). ``valid`` masks lanes out of the update
        entirely (the emitters pass the fused node's ``cond`` here, so
        masked lanes no longer ship identity payloads)."""
        if op not in isa.RMW_OPS:
            raise ValueError(f"op {op!r} not in RMW_OPS {isa.RMW_OPS} "
                             "(sharded RMW needs reorder-safe combines)")
        table = jnp.asarray(table)
        idx_arr = jnp.asarray(idx).astype(jnp.int32)
        n = int(idx_arr.shape[0])
        if n == 0:
            self.last_shard_stats = None
            return table
        n_rows = int(table.shape[0])
        values = jnp.asarray(values).reshape(
            (n,) + table.shape[1:]).astype(table.dtype)
        xplan, perm = self._plan_exchange(idx, valid, n_rows=n_rows,
                                          kind="rmw",
                                          placement=placement, codec=codec)
        # stores drop (policy): negative/OOB destinations never enter the
        # exchange (no fabric traffic, excluded from stats), matching the
        # single-device bulk_rmw route-out
        in_range = (idx_arr >= 0) & (idx_arr < n_rows)
        if valid is not None:
            in_range = in_range & jnp.asarray(valid).reshape(-1)
        idx_p, mask, per = self._pad_stream(idx_arr, in_range)
        pad = per * self.num_shards - n
        if pad:
            values = jnp.concatenate(
                [values, jnp.zeros((pad,) + values.shape[1:],
                                   values.dtype)])
        cap, codec_eff, perm_arr = self._concretize(xplan, perm, per)
        rows_per = -(-n_rows // self.num_shards)
        fns = self._shard_fn("rmw", rows_per, per, cap, codec_eff, op)
        new_table, sent, recv, uniq = fns.fused(table, idx_p, mask,
                                                values, perm_arr)
        self._record_stats(sent, recv, uniq, xplan=xplan, cap=cap,
                           codec=codec_eff, rows_per=rows_per,
                           value_nbytes=self._row_nbytes(table))
        return new_table

    # -- split route/exec API (the emit stage's overlap machinery) ----------

    def gather_start(self, table, idx, *, valid=None,
                     placement: Optional[str] = None,
                     codec: Optional[str] = None) -> ExchangeInflight:
        """Dispatch the route stage (dedup → split → pack → index
        ``all_to_all``) of a sharded gather without touching the table;
        finish with ``gather_finish``. Lets the emit stage put every
        node's fabric exchange in flight before any node's owner-local
        compute dispatches."""
        table = jnp.asarray(table)     # shape/dtype only — no compute
        n_rows = int(table.shape[0])
        idx_arr = jnp.asarray(idx).astype(jnp.int32)
        n = int(idx_arr.shape[0])
        if n == 0:
            return ExchangeInflight(kind="gather:empty")
        xplan, perm = self._plan_exchange(idx, valid, n_rows=n_rows,
                                          kind="gather",
                                          placement=placement, codec=codec)
        idx_p, mask, per = self._pad_stream(
            jnp.clip(idx_arr, 0, n_rows - 1), valid)
        cap, codec_eff, perm_arr = self._concretize(xplan, perm, per)
        rows_per = -(-n_rows // self.num_shards)
        fns = self._shard_fn("gather", rows_per, per, cap, codec_eff)
        return ExchangeInflight(
            kind="gather", fns=fns, route=fns.route(idx_p, mask, perm_arr),
            perm=perm_arr, n=n, xplan=xplan, cap=cap, codec=codec_eff,
            rows_per=rows_per, value_nbytes=self._row_nbytes(table))

    def gather_finish(self, table, fl: ExchangeInflight) -> jax.Array:
        """Exec stage of ``gather_start``: owner-local takes, the inverse
        value exchange, and lane unpacking. Probes (non-blocking) whether
        the routed exchange already completed — the measured overlap
        fraction on ``last_shard_stats``."""
        table = jnp.asarray(table)
        if fl.kind == "gather:empty":
            self.last_shard_stats = None
            return table[jnp.zeros((0,), jnp.int32)]
        (inv, is_local, local_row, order, slot, r_local, recv_valid,
         sent, n_recv, n_uniq, mask2) = fl.route
        overlap = 1.0 if self._probe_ready(r_local, recv_valid) else 0.0
        out = fl.fns.exec(table, fl.perm, inv, is_local, local_row,
                          order, slot, r_local, recv_valid, mask2)
        self._record_stats(sent, n_recv, n_uniq, xplan=fl.xplan,
                           cap=fl.cap, codec=fl.codec,
                           rows_per=fl.rows_per,
                           value_nbytes=fl.value_nbytes, overlap=overlap)
        return out[:fl.n]

    def rmw_start(self, table, idx, values, *, op: str = "ADD",
                  valid=None, placement: Optional[str] = None,
                  codec: Optional[str] = None) -> ExchangeInflight:
        """Route stage of a sharded RMW: pre-combine, split, and ship both
        the encoded index spill and the combined payload — the complete
        fabric traffic of the one-way contract. Only the table update
        itself remains for ``rmw_finish``, which is what lets RMW
        exchanges overlap the window's other owner-local work (and why
        the route stage only needs the table's shape/dtype, never its
        current contents)."""
        if op not in isa.RMW_OPS:
            raise ValueError(f"op {op!r} not in RMW_OPS {isa.RMW_OPS} "
                             "(sharded RMW needs reorder-safe combines)")
        table = jnp.asarray(table)     # shape/dtype only — no compute
        n_rows = int(table.shape[0])
        idx_arr = jnp.asarray(idx).astype(jnp.int32)
        n = int(idx_arr.shape[0])
        if n == 0:
            return ExchangeInflight(kind="rmw:empty")
        values = jnp.asarray(values).reshape(
            (n,) + table.shape[1:]).astype(table.dtype)
        xplan, perm = self._plan_exchange(idx, valid, n_rows=n_rows,
                                          kind="rmw",
                                          placement=placement, codec=codec)
        in_range = (idx_arr >= 0) & (idx_arr < n_rows)
        if valid is not None:
            in_range = in_range & jnp.asarray(valid).reshape(-1)
        idx_p, mask, per = self._pad_stream(idx_arr, in_range)
        pad = per * self.num_shards - n
        if pad:
            values = jnp.concatenate(
                [values, jnp.zeros((pad,) + values.shape[1:],
                                   values.dtype)])
        cap, codec_eff, perm_arr = self._concretize(xplan, perm, per)
        rows_per = -(-n_rows // self.num_shards)
        fns = self._shard_fn("rmw", rows_per, per, cap, codec_eff, op)
        return ExchangeInflight(
            kind="rmw", fns=fns,
            route=fns.route(idx_p, mask, values, perm_arr),
            perm=perm_arr, n=n, xplan=xplan, cap=cap, codec=codec_eff,
            rows_per=rows_per, value_nbytes=self._row_nbytes(table))

    def rmw_finish(self, table, fl: ExchangeInflight):
        """Exec stage of ``rmw_start``: one owner-local
        segment-combine + unique-scatter over the landed (local +
        received) update stream."""
        table = jnp.asarray(table)
        if fl.kind == "rmw:empty":
            self.last_shard_stats = None
            return table
        cat_idx, cat_vals, cat_valid, sent, n_recv, n_uniq = fl.route
        overlap = 1.0 if self._probe_ready(cat_idx, cat_vals) else 0.0
        new_table = fl.fns.exec(table, cat_idx, cat_vals, cat_valid)
        self._record_stats(sent, n_recv, n_uniq, xplan=fl.xplan,
                           cap=fl.cap, codec=fl.codec,
                           rows_per=fl.rows_per,
                           value_nbytes=fl.value_nbytes, overlap=overlap)
        return new_table

    @staticmethod
    def _probe_ready(*arrays) -> bool:
        """Non-blocking: did the routed exchange finish before exec
        dispatch? (The measured overlap signal — never a sync.)"""
        try:
            return all(a.is_ready() for a in arrays)
        except AttributeError:
            return True

    @staticmethod
    def _row_nbytes(table) -> int:
        nb = int(jnp.dtype(table.dtype).itemsize)
        for d in table.shape[1:]:
            nb *= int(d)
        return nb

    # -- scheduler batch fan-out --------------------------------------------

    def _constrain_batch(self, stacked: Dict) -> Dict:
        """Place the stacked lane axis of a batched program group across
        the mesh: N grouped programs execute as num_shards device-local
        sub-batches of one SPMD computation."""
        if self.num_shards == 1:
            return stacked
        spec = NamedSharding(self.mesh, P(self.axis))
        return {k: (jax.lax.with_sharding_constraint(v, spec)
                    if v.shape[0] % self.num_shards == 0 else v)
                for k, v in stacked.items()}

    # -- shard_map builders (cached per static geometry) ---------------------

    def _shard_fn(self, kind: str, rows_per: int, per: int, cap: int,
                  codec: str, op: str | None = None):
        key = (kind, rows_per, per, cap, codec, op)
        fns = self._shard_fns.get(key)
        if fns is None:
            fns = self._build(kind, rows_per, per, cap, codec, op)
            self._shard_fns[key] = fns
        return fns

    def _build(self, kind: str, rows_per: int, per: int, cap: int,
               codec: str, op: str | None):
        ns, axis = self.num_shards, self.axis
        C = int(cap)
        sharded = P(axis)
        pad_rows = rows_per * ns

        def _pad_table(table):
            # inside the jit: the pad fuses with the resharding transfer
            # instead of materializing an eager full copy per call
            pr = pad_rows - table.shape[0]
            if pr:
                table = jnp.concatenate(
                    [table,
                     jnp.zeros((pr,) + table.shape[1:], table.dtype)])
            return table

        def _wire_indices(send_idx, send_valid):
            """One collective ships the remote index spill (raw lanes
            with a -1 invalid sentinel, or the codec's words); returns
            the owner-side (local_rows, valid) bucket buffer."""
            if codec == "raw":
                enc = jnp.where(send_valid, send_idx, -1)
                recv = jax.lax.all_to_all(enc, axis, 0, 0, tiled=True)
                recv_valid = recv >= 0
                _, r_local = reorder.shard_bulk_indices(
                    jnp.maximum(recv, 0), num_shards=ns, n_rows=pad_rows)
                return jnp.where(recv_valid, r_local, 0), recv_valid
            enc_fn, dec_fn, _ = exchange.CODECS[codec]
            words = enc_fn(send_idx, send_valid, rows_per=rows_per,
                           num_shards=ns)
            rwords = jax.lax.all_to_all(words, axis, 0, 0, tiled=True)
            return dec_fn(rwords, rows_per=rows_per, num_shards=ns,
                          capacity=C)

        def _split_by_owner(u_idx, u_valid):
            """Local/remote split of a deduped slice + the full (diagonal
            included) post-dedup routing counts."""
            me = jax.lax.axis_index(axis)
            owner, local_row = reorder.shard_bulk_indices(
                u_idx, num_shards=ns, n_rows=pad_rows)
            owner = jnp.clip(owner, 0, ns - 1)
            is_local = u_valid & (owner == me)
            is_remote = u_valid & (owner != me)
            okey = jnp.where(u_valid, owner, ns)
            sent = jax.ops.segment_sum(
                jnp.ones_like(okey), okey, num_segments=ns + 1)[:ns]
            return local_row, is_local, is_remote, sent

        def gather_route(idx_l, valid_l):
            u_idx, u_valid, inv, _ = exchange.dedup_stream(idx_l, valid_l)
            local_row, is_local, is_remote, sent = \
                _split_by_owner(u_idx, u_valid)
            send_idx, send_valid, order, slot, _ = \
                exchange.partition_by_owner(
                    u_idx, is_remote, rows_per=rows_per, num_shards=ns,
                    capacity=C)
            r_local, recv_valid = _wire_indices(send_idx, send_valid)
            n_recv = jnp.sum(is_local.astype(jnp.int32)) + \
                jnp.sum(recv_valid.astype(jnp.int32))
            cat_idx = jnp.concatenate(
                [jnp.where(is_local, local_row, 0), r_local])
            cat_valid = jnp.concatenate([is_local, recv_valid])
            n_uniq = exchange.masked_unique_count(cat_idx, cat_valid)
            return (inv, is_local, local_row, order, slot, r_local,
                    recv_valid, sent, n_recv[None], n_uniq[None])

        def gather_exec(table_l, inv, is_local, local_row, order, slot,
                        r_local, recv_valid, mask_l):
            # direct take: received buckets are pre-sorted and pre-deduped
            # per source, so the owner never pays a second sort
            vals = table_l[jnp.clip(r_local, 0, rows_per - 1)]
            vshape = (-1,) + (1,) * (vals.ndim - 1)
            vals = jnp.where(recv_valid.reshape(vshape), vals, 0)
            back = jax.lax.all_to_all(vals, axis, 0, 0, tiled=True)
            remote = exchange.unpack_result(back, order, slot, ~is_local)
            local_vals = table_l[jnp.clip(local_row, 0, rows_per - 1)]
            u_vals = jnp.where(is_local.reshape(vshape), local_vals,
                               remote)
            out = u_vals[inv]
            return jnp.where(mask_l.reshape(vshape), out, 0)

        def rmw_route(idx_l, valid_l, vals_l):
            u_idx, u_vals, u_valid, _ = exchange.combine_duplicates(
                idx_l, vals_l, valid_l, op=op)
            local_row, is_local, is_remote, sent = \
                _split_by_owner(u_idx, u_valid)
            send_idx, send_valid, order, slot, _ = \
                exchange.partition_by_owner(
                    u_idx, is_remote, rows_per=rows_per, num_shards=ns,
                    capacity=C)
            r_local, recv_valid = _wire_indices(send_idx, send_valid)
            send_vals = exchange.pack_payload(u_vals, order, slot,
                                              num_shards=ns, capacity=C)
            recv_vals = jax.lax.all_to_all(send_vals, axis, 0, 0,
                                           tiled=True)
            cat_idx = jnp.concatenate(
                [jnp.where(is_local, local_row, 0), r_local])
            cat_valid = jnp.concatenate([is_local, recv_valid])
            cat_vals = jnp.concatenate([u_vals, recv_vals])
            n_recv = jnp.sum(cat_valid.astype(jnp.int32))
            n_uniq = exchange.masked_unique_count(cat_idx, cat_valid)
            return (cat_idx, cat_vals, cat_valid, sent, n_recv[None],
                    n_uniq[None])

        def rmw_exec(table_l, cat_idx, cat_vals, cat_valid):
            # owner-local combine-then-scatter over local + landed
            # updates; masked lanes write the op identity to row 0 (a
            # no-op by definition of the identity)
            return bulk_ops.bulk_rmw(table_l, cat_idx, cat_vals, op=op,
                                     cond=cat_valid, optimize=True)

        if kind == "gather":
            route_sm = jax.shard_map(gather_route, mesh=self.mesh,
                                     in_specs=(sharded, sharded),
                                     out_specs=(sharded,) * 10)
            exec_sm = jax.shard_map(gather_exec, mesh=self.mesh,
                                    in_specs=(sharded,) * 9,
                                    out_specs=sharded)

            def route_fn(idx, mask, perm):
                return route_sm(idx[perm], mask[perm]) + (mask[perm],)

            def exec_fn(table, perm, inv, is_local, local_row, order,
                        slot, r_local, recv_valid, mask2):
                out = exec_sm(_pad_table(table), inv, is_local, local_row,
                              order, slot, r_local, recv_valid, mask2)
                # undo the placement permutation (exact inverse: perm is
                # a full permutation, every lane written once)
                return jnp.zeros_like(out).at[perm].set(
                    out, unique_indices=True)

            def fused_fn(table, idx, mask, perm):
                (inv, is_local, local_row, order, slot, r_local,
                 recv_valid, sent, n_recv, n_uniq, mask2) = \
                    route_fn(idx, mask, perm)
                out = exec_fn(table, perm, inv, is_local, local_row,
                              order, slot, r_local, recv_valid, mask2)
                return out, sent, n_recv, n_uniq
        elif kind == "rmw":
            route_sm = jax.shard_map(rmw_route, mesh=self.mesh,
                                     in_specs=(sharded,) * 3,
                                     out_specs=(sharded,) * 6)
            exec_sm = jax.shard_map(rmw_exec, mesh=self.mesh,
                                    in_specs=(sharded,) * 4,
                                    out_specs=sharded)

            def route_fn(idx, mask, vals, perm):
                return route_sm(idx[perm], mask[perm], vals[perm])

            def exec_fn(table, cat_idx, cat_vals, cat_valid):
                new = exec_sm(_pad_table(table), cat_idx, cat_vals,
                              cat_valid)
                return new[:table.shape[0]]

            def fused_fn(table, idx, mask, vals, perm):
                cat_idx, cat_vals, cat_valid, sent, n_recv, n_uniq = \
                    route_fn(idx, mask, vals, perm)
                new = exec_fn(table, cat_idx, cat_vals, cat_valid)
                return new, sent, n_recv, n_uniq
        else:
            raise ValueError(kind)
        return types.SimpleNamespace(fused=jax.jit(fused_fn),
                                     route=jax.jit(route_fn),
                                     exec=jax.jit(exec_fn))

    def _record_stats(self, sent, recv, uniq, *, xplan: ExchangePlan,
                      cap: int, codec: str, rows_per: int,
                      value_nbytes: int,
                      overlap: Optional[float] = None) -> ShardStats:
        # reshape only — no host transfer here, so back-to-back sharded
        # calls (a flush over many tables) keep dispatching asynchronously
        ns = self.num_shards
        offd = ns * (ns - 1)
        idx_bytes = 4 * offd * exchange.codec_wire_words(
            codec, rows_per=rows_per, capacity=cap)
        st = ShardStats(
            sent.reshape(ns, ns), recv, uniq, placement=xplan.placement,
            codec=codec, capacity=cap, idx_bytes=idx_bytes,
            idx_bytes_raw=4 * offd * cap,
            bytes_on_wire=idx_bytes + offd * cap * value_nbytes,
            overlap=overlap)
        self.last_shard_stats = st
        return st


# ---------------------------------------------------------------------------
# "sharded" plan backend: shard-placement pass + owner-local emitters.
# Registered at import (base: the scheduler's "local" backend) — the
# scheduler routes through the registry keyed on ``Engine.plan_backend``.
# ---------------------------------------------------------------------------

def _shard_place(p: "plan.Plan", ctx: "plan.LowerContext") -> "plan.Plan":
    """The mesh variant of the pipeline's ``shard`` slot: per fused node
    the cost model (or the replayed plan-cache skeleton) picks "bulk" vs
    "sharded"; mesh-placed nodes are wrapped in ``ShardedNode`` carrying
    the exchange plan (placement/codec from the cost model or the
    replayed skeleton — capacity is always re-measured, a replayed
    data-dependent bound could drop lanes on different data)."""
    roots, notes, gi, ri, xi = [], [], 0, 0, 0
    replay = ctx.replay
    for node in p.roots:
        if getattr(node, "error", None) is not None:
            roots.append(node)         # error nodes never place
            continue
        if isinstance(node, plan.FusedGather):
            if node.backend == "eager":
                backend = "eager"
            elif replay is not None and gi < len(replay.gather_backends):
                backend = replay.gather_backends[gi]
            else:
                backend = ctx.cost.gather_backend(node, ctx)
            gi += 1
        elif isinstance(node, plan.FusedRmw):
            if replay is not None and ri < len(replay.rmw_backends):
                backend = replay.rmw_backends[ri]
            else:
                backend = ctx.cost.rmw_backend(node, ctx)
            ri += 1
        else:
            roots.append(node)
            continue
        if backend != node.backend:
            node = dataclasses.replace(node, backend=backend)
        if backend == "sharded":
            cost = ctx.cost
            if replay is not None and xi < len(replay.exchange_plans):
                # replay pins the *policy*; the measurement still runs so
                # the capacity (and the owner permutation) match the data
                pl_, cd_ = replay.exchange_plans[xi]
                cost = dataclasses.replace(ctx.cost, force_placement=pl_,
                                           force_codec=cd_)
            xi += 1
            if hasattr(ctx.engine, "plan_node_exchange"):
                xp = ctx.engine.plan_node_exchange(node, cost)
            else:
                xp = cost.exchange_plan(None)
            node = plan.ShardedNode(
                nid=ctx.nid(), inner=node, num_shards=ctx.num_shards,
                placement=xp.placement, codec=xp.codec,
                capacity=xp.capacity,
                est_local_fraction=xp.est_local_fraction)
            notes.append(f"{node.inner.kind}#{node.inner.nid} -> sharded "
                         f"(mesh={ctx.num_shards}, "
                         f"rows={node.inner.table_rows}) {xp.describe()}")
        else:
            notes.append(f"{node.kind}#{node.nid} -> {backend} "
                         f"(rows={node.table_rows} < mesh or forced)")
        roots.append(node)
    p = dataclasses.replace(p, roots=tuple(roots))
    d = plan.PassDelta("shard", len(p.leaves) + len(roots),
                       len(p.leaves) + len(roots), tuple(notes))
    return dataclasses.replace(p, trace=p.trace + (d,))


def _prefetch_gather_sharded(node, ctx: "plan.EmitContext"):
    """Route-stage prefetch: put this gather's exchange on the fabric
    before any node's exec dispatches (the emit stage's double buffer)."""
    g = plan.unwrap(node)
    if g.unique_idx is None or int(g.unique_idx.shape[0]) == 0:
        return
    ctx.exchange_inflight[node.nid] = ctx.engine.gather_start(
        g.table, g.unique_idx, valid=g.pad_valid,
        placement=node.placement, codec=node.codec)


def _emit_gather_sharded(node, ctx: "plan.EmitContext"):
    """Owner-local fused fetch across the mesh. Coalesce padding
    (replicas of the max index) is masked out via ``pad_valid`` rather
    than sliced off: pad lanes would skew the exchange toward the max
    row's owner and pollute the per-shard stats, but a data-dependent
    slice length would force a fresh shard_map trace per distinct
    n_unique and a host sync — the mask keeps shapes static and dispatch
    async."""
    g = plan.unwrap(node)
    fl = ctx.exchange_inflight.pop(node.nid, None)
    if fl is not None:
        packed = ctx.engine.gather_finish(g.table, fl)
    else:
        packed = ctx.engine.sharded_gather(
            g.table, g.unique_idx, valid=g.pad_valid,
            placement=node.placement, codec=node.codec)
    if ctx.engine.last_shard_stats is not None:
        ctx.shard_stats[g.table_id] = ctx.engine.last_shard_stats
    for m, inv in zip(g.members, g.inverses):
        ctx.results[m.ticket.tid] = packed[inv]


def _prefetch_rmw_sharded(node, ctx: "plan.EmitContext"):
    """Route-stage prefetch for a sharded RMW: the one-way exchange
    (indices + combined payload) needs only the table's shape/dtype, so
    it can fly before earlier nodes' updates to the same table land."""
    r = plan.unwrap(node)
    if r.idx is None or r.n_lanes == 0:
        return
    ctx.exchange_inflight[node.nid] = ctx.engine.rmw_start(
        r.table, r.idx, r.values, op=r.op, valid=r.cond,
        placement=node.placement, codec=node.codec)


def _emit_rmw_sharded(node, ctx: "plan.EmitContext"):
    """Owner-local fused RMW across the mesh; ``cond`` lanes are masked
    out of the exchange entirely (they used to ship identity payloads)."""
    r = plan.unwrap(node)
    table = ctx.tables.get(r.table_id, r.table)
    fl = ctx.exchange_inflight.pop(node.nid, None)
    if fl is not None:
        new = ctx.engine.rmw_finish(table, fl)
    else:
        new = ctx.engine.sharded_rmw(table, r.idx, r.values, op=r.op,
                                     valid=r.cond,
                                     placement=node.placement,
                                     codec=node.codec)
    if ctx.engine.last_shard_stats is not None:
        ctx.shard_stats[("rmw", r.table_id, r.op)] = \
            ctx.engine.last_shard_stats
    ctx.tables[r.table_id] = new
    ctx.rmw_members.setdefault(r.table_id, []).extend(r.members)


plan.register_backend(
    "sharded", base="local", sharded=True,
    passes_override={"shard": _shard_place},
    emitters={
        ("gather", "sharded"): _emit_gather_sharded,
        ("rmw", "sharded"): _emit_rmw_sharded,
    },
    prefetchers={
        ("gather", "sharded"): _prefetch_gather_sharded,
        ("rmw", "sharded"): _prefetch_rmw_sharded,
    })
