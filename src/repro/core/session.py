"""Unified ``Database`` session API: one query surface, cost-routed plans
(paper §II architecture + §III–§V techniques behind a single SQL door).

The paper's Mercury system exposes *one* SQL entry point behind which a
cost-based planner picks among the polymorphic vectorization engine's
formats, the distributed scan routes, and the differential-refresh
materialized views; PolarDB-IMCI and L-Store stress the same point — HTAP
value comes from transparent routing, not from callers hand-picking an
engine.  This module is that routing layer for the repro:

* ``Database`` — the session façade.  ``db = Database(store)`` (or
  ``db.create_table(name, schema)``), then ``db.query(Query) -> ResultSet``,
  ``db.explain(Query) -> Plan``, ``db.create_mav / create_mjv``.  Every
  query goes through a two-stage compiler:

* ``plan_logical(Query, schema)`` — normalizes the query into a small
  ``LogicalPlan`` IR: predicates are validated against the schema,
  de-duplicated, paired ``GE+LE`` bounds collapse into one ``BETWEEN``
  (so the device planner's single-range shape matches more queries), and
  aggregates are alias-checked.

* ``plan_physical(LogicalPlan, cost.ScanEstimate, TableCalibration)`` —
  chooses the physical route from the sketch-driven selectivity estimate
  (the same closed-loop estimate the executors feed back into):

    - **mav** — a registered ``MaterializedAggView`` whose definition the
      query subsumes answers it from the container ⊕ pending-mlog merge.
      Delta freshness is checked through the ``MLog`` first: a purged tail
      (``MLogPurged``) or a pending tail beyond the staleness horizon
      falls back to a base-table scan route.
    - **sharded** — the mesh fan-out (``ShardedScanExecutor``) when the
      estimated surviving rows justify a multi-shard width
      (``cost.choose_shards``); the executor then applies its own
      coalescing / top-k pushdown / device-route knobs.
    - **pushdown** — the single-shard block-pushdown executor otherwise
      (zone-map prune + encoded-domain filter + late materialization).
    - **scalar / vectorized** — full-decode engines, only ever chosen by
      an explicit ``engine=`` pin (kept for baselines and A/B runs).

  Explicit ``engine=`` / ``n_shards=`` / ``device_route=`` arguments pin
  the corresponding decision and are recorded as ``Plan.pinned``; any of
  them also suppresses the MAV rewrite (a pinned scan knob demands a scan
  route), as do ``use_mv=False`` and snapshot (``ts=``) reads.

* ``ResultSet`` — typed result: named ``columns`` in output order, the
  result ``rows``, and provenance (the ``Plan`` that was executed plus the
  executor's ``ScanStats``), replacing the bare ``List[Dict]`` the engines
  return.

``core.engine.make_engine`` remains as a thin deprecated shim over the
same executors so pre-session callers keep working unchanged.
"""
from __future__ import annotations

import dataclasses
import os
import threading
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

import time

from . import cost, spans
from .engine import QAgg, Query, ScalarEngine, VectorEngine
from .errors import QueryTimeout
from .health import HealthRegistry
from .lsm import LSMStore, ScanStats
from .mview import (MAVDefinition, MJVDefinition, MLog, MLogPurged,
                    MaterializedAggView, MaterializedJoinView)
from .partition import ShardedScanExecutor
from .pushdown import PushdownExecutor
from .relation import PredOp, Predicate, Schema

#: Pending-mlog rows beyond which an MV rewrite is considered stale: the
#: realtime merge applies the tail row-at-a-time in Python, so past this
#: horizon a vectorized base-table scan is the cheaper (and equally fresh)
#: answer.  Per-``Database`` override via ``mv_stale_rows=``.
DEFAULT_MV_STALE_ROWS = 10_000

_AGG_OPS = ("count", "sum", "avg", "min", "max")
ROUTES = ("mav", "pushdown", "sharded", "scalar", "vectorized")


# ---------------------------------------------------------------------------
# Stage 1: the logical plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LogicalPlan:
    """Normalized query IR: schema-validated, predicate-canonical.  The
    physical planner and the MV rewriter both match against this — never
    against the raw ``Query`` — so normalization (e.g. GE+LE → BETWEEN)
    widens what every downstream route can recognize."""

    preds: Tuple[Predicate, ...]
    group_by: Tuple[str, ...]
    aggs: Tuple[QAgg, ...]
    sort_by: Tuple[str, ...]
    limit: Optional[int]
    project: Tuple[str, ...]

    def to_query(self) -> Query:
        return Query(preds=self.preds, group_by=self.group_by,
                     aggs=self.aggs, sort_by=self.sort_by, limit=self.limit,
                     project=self.project)

    def output_names(self, all_names: Sequence[str]) -> Tuple[str, ...]:
        """Result column names in output order."""
        if self.aggs:
            return self.group_by + tuple(a.alias for a in self.aggs)
        return tuple(self.project) or tuple(all_names)

    def cache_key(self) -> Tuple:
        """Fully-hashable identity of the normalized plan (predicate values
        keyed by repr, so IN-lists and other unhashable values are fine) —
        the ``CompiledPlan``/result-cache key component."""
        return (tuple(_pred_key(p) for p in self.preds), self.group_by,
                tuple((a.op, a.column, a.alias) for a in self.aggs),
                self.sort_by, self.limit, self.project)


def plan_logical(q: Query, schema: Optional[Schema] = None) -> LogicalPlan:
    """Normalize a ``Query`` into the ``LogicalPlan`` IR.

    * every referenced column is validated against ``schema`` (when given);
    * duplicate predicates collapse; a lone ``GE`` + ``LE`` pair over one
      column collapses into a single ``BETWEEN`` (the canonical range
      shape the zone maps, sorted-window fast path, and device planner
      all match on);
    * aggregate ops are validated and aliases must be unique;
    * predicates are ordered by column name (conjunction order is
      semantically free, and a canonical order keys the calibration
      EWMAs consistently)."""
    names = set(schema.names) if schema is not None else None

    def check(col: Optional[str], what: str) -> None:
        if col is not None and names is not None and col not in names:
            raise KeyError(f"unknown {what} column {col!r}")

    seen: Dict[Tuple, Predicate] = {}
    by_col: Dict[str, List[Predicate]] = {}
    for p in q.preds:
        check(p.column, "predicate")
        key = (p.column, p.op, repr(p.value), repr(p.value2))
        if key not in seen:
            seen[key] = p
            by_col.setdefault(p.column, []).append(p)
    preds: List[Predicate] = []
    for col in sorted(by_col):
        ps = by_col[col]
        ops = [p.op for p in ps]
        if sorted(ops, key=lambda o: o.name) == [PredOp.GE, PredOp.LE]:
            lo = next(p.value for p in ps if p.op == PredOp.GE)
            hi = next(p.value for p in ps if p.op == PredOp.LE)
            preds.append(Predicate(col, PredOp.BETWEEN, lo, hi))
        else:
            preds.extend(ps)

    aliases = set()
    for a in q.aggs:
        if a.op not in _AGG_OPS:
            raise ValueError(f"unknown aggregate op {a.op!r}")
        if a.column is None and a.op != "count":
            raise ValueError(f"{a.op} requires a column")
        check(a.column, "aggregate")
        if a.alias in aliases:
            raise ValueError(f"duplicate aggregate alias {a.alias!r}")
        aliases.add(a.alias)
    for g in q.group_by:
        check(g, "group-by")
    for c in q.project:
        check(c, "projection")
    out_names = tuple(q.group_by) + tuple(a.alias for a in q.aggs) \
        if q.aggs else (tuple(q.project) or tuple(names or ()))
    for s in q.sort_by:
        if out_names and s not in out_names:
            raise KeyError(f"sort column {s!r} is not an output column")
    if q.limit is not None and q.limit < 0:
        raise ValueError(f"negative limit {q.limit}")
    return LogicalPlan(tuple(preds), tuple(q.group_by), tuple(q.aggs),
                       tuple(q.sort_by), q.limit,
                       tuple(q.project) if not q.aggs else ())


# ---------------------------------------------------------------------------
# Stage 2: the physical plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Plan:
    """The chosen physical route plus the estimate that chose it — what
    ``db.explain`` returns and what rides along in ``ResultSet.plan``."""

    route: str                         # one of ROUTES
    table: str = ""
    reason: str = ""
    est_rows: float = 0.0              # planner estimate of surviving rows
    n_rows: int = 0                    # baseline rows at plan time
    selectivity: float = 0.0
    n_shards: int = 1
    device: bool = False
    device_route: str = ""             # '' | 'collective' | 'host'
    mv: Optional[str] = None           # MAV the query was rewritten onto
    mv_pending: int = 0                # mlog tail rows merged at read time
    pinned: bool = False               # an explicit hint decided the route
    logical: Optional[LogicalPlan] = None
    rewrite: Optional[Dict[str, Any]] = dataclasses.field(
        default=None, repr=False)      # MV emit mapping (execution detail)
    # Fault provenance: every degradation step the query took, in order
    # ("from->to: why" strings — plan-time entries first, then the
    # executor's ScanStats.degraded — plus "breaker(<rung>) ..." notes for
    # circuit-breaker pre-degrades and half-open probes), bounded
    # MLog.since retries, and every block repaired in place from a replica
    # while the query ran.
    degraded: List[str] = dataclasses.field(default_factory=list)
    mlog_retries: int = 0
    repaired: List[str] = dataclasses.field(default_factory=list)
    # breaker verdicts ({rung: "skip" | "probe"}) consulted at plan time —
    # execution detail the executors consume, not part of repr
    breaker: Dict[str, str] = dataclasses.field(
        default_factory=dict, repr=False)
    # the cost-chosen route before any breaker pre-degrade: execution
    # restores it and re-applies *fresh* breaker verdicts, so a plan
    # compiled while a breaker was open still probes once it cools down
    base_route: str = dataclasses.field(default="", repr=False)
    # the snapshot the execution actually read (current_ts captured at
    # execute entry when no ts= pin was given) — replaying a scan at this
    # ts reproduces the answer bit-identically
    ts: Optional[int] = None
    # True when the serving layer answered from its result cache instead
    # of executing
    cached: bool = False

    def describe(self) -> str:
        bits = [f"route={self.route}"]
        if self.mv:
            bits.append(f"mv={self.mv} (pending={self.mv_pending})")
        if self.route == "sharded":
            bits.append(f"n_shards={self.n_shards}")
        if self.device:
            bits.append(f"device_route={self.device_route or 'auto'}")
        bits.append(f"est_rows={self.est_rows:.0f}/{self.n_rows}")
        if self.pinned:
            bits.append("pinned")
        if self.degraded:
            bits.append("degraded=[" + "; ".join(self.degraded) + "]")
        if self.repaired:
            bits.append("repaired=[" + "; ".join(self.repaired) + "]")
        return f"Plan({', '.join(bits)}: {self.reason})"


def _pred_key(p: Predicate) -> Tuple:
    return (p.column, p.op, repr(p.value), repr(p.value2))


def mav_rewrite(logical: LogicalPlan,
                mav: MaterializedAggView) -> Optional[Dict[str, Any]]:
    """Match an aggregate query onto a MAV definition.  Sound iff:

    * the group-by tuples are identical (one container group per result
      row — no re-aggregation needed);
    * every non-group-column predicate of the query matches the MAV's
      definition predicates *exactly* (the container was built over rows
      passing those predicates, nothing more, nothing less); predicates
      over group columns become residual filters applied to container
      rows;
    * every query aggregate is readable from a container column — a
      same-(op, column) ``AggSpec``, ``count(*)`` from ``count_star``, or
      ``avg`` derived from a stored sum/count pair.

    Returns ``{'residual': preds, 'emit': [(alias, kind, src), ...]}`` or
    None when the query does not subsume the definition."""
    defn = mav.defn
    if not logical.aggs or logical.project:
        return None
    if tuple(defn.group_by) != logical.group_by:
        return None
    gset = set(defn.group_by)
    residual = tuple(p for p in logical.preds if p.column in gset)
    rest = [p for p in logical.preds if p.column not in gset]
    if {_pred_key(p) for p in rest} != {_pred_key(p) for p in defn.preds}:
        return None
    stored: Dict[Tuple[str, Optional[str]], str] = {}
    for a in defn.aggs:
        op = "count" if a.op == "count_star" else a.op
        col = None if a.op == "count_star" else a.column
        stored[(op, col)] = a.alias
    emit: List[Tuple[str, str, Any]] = []
    for a in logical.aggs:
        alias = stored.get((a.op, a.column))
        if alias is not None:
            emit.append((a.alias, a.op, alias))
            continue
        if a.op == "avg" and a.column is not None:
            s = stored.get(("sum", a.column))
            c = stored.get(("count", a.column))
            if s is not None and c is not None:
                emit.append((a.alias, "avg_ratio", (s, c)))
                continue
        return None
    return {"residual": residual, "emit": emit}


def _mav_pending(mav: MaterializedAggView, stale_rows: int,
                 plan: Optional["Plan"] = None) -> Optional[int]:
    """Delta freshness through the MLog: the number of pending (unapplied)
    mlog rows the realtime merge would fold in, or None when the rewrite
    must not run — the tail was purged (``MLogPurged``: the merge would be
    silently incomplete), the tail is past the staleness horizon (the
    Python row-at-a-time merge would cost more than a vectorized base
    scan), or the MAV has no mlog and its container predates the base.

    A purged tail gets one bounded retry (a concurrent purge may race a
    refresh that advances ``last_refresh_ts`` past it); when a ``plan`` is
    supplied the retry and the final purge fallback are recorded in its
    provenance."""
    if mav.mlog is None:
        return 0 if mav.last_refresh_ts >= mav.base.current_ts else None
    pending = None
    for attempt in range(2):
        try:
            pending = mav.mlog.since(mav.last_refresh_ts)
            break
        except MLogPurged as e:
            if attempt == 0:
                if plan is not None:
                    plan.mlog_retries += 1
                continue
            if plan is not None:
                plan.degraded.append(
                    f"mav({mav.name})->scan: purge_fallback at plan time: "
                    f"{e}")
            return None
    if len(pending) > stale_rows:
        return None
    return len(pending)


def plan_physical(logical: LogicalPlan, est: cost.ScanEstimate,
                  cal: cost.TableCalibration,
                  views: Sequence[MaterializedAggView] = (), *,
                  table: str = "", pinned_engine: Optional[str] = None,
                  n_shards: Optional[int] = None,
                  device_route: Optional[str] = None,
                  max_workers: Optional[int] = None,
                  mv_stale_rows: int = DEFAULT_MV_STALE_ROWS) -> Plan:
    """Choose the physical route for a normalized query: transparent MAV
    rewrite first (freshness-checked through the mlog), then cost-routed
    scan fan-out vs single-shard pushdown from the sketch estimate.
    Explicit pins (``pinned_engine`` / ``n_shards`` / ``device_route``)
    override the corresponding decision."""
    plan = Plan(route="pushdown", table=table, logical=logical,
                est_rows=est.est_rows, n_rows=est.n_rows,
                selectivity=est.selectivity)
    # the estimate carries the applied feedback factor (raw -> calibrated);
    # ``cal`` supplies the observation count behind it for the plan reason
    factor = est.est_rows / est.raw_rows \
        if est.calibrated and est.raw_rows > 0 else 1.0
    cal_note = (f", calibration x{factor:.2f} "
                f"({cal.n_obs.get(est.cal_key, 0)} obs)"
                if factor != 1.0 else "")
    if pinned_engine is not None:
        if pinned_engine not in ("scalar", "vectorized", "pushdown",
                                 "sharded"):
            raise ValueError(f"unknown engine {pinned_engine!r}")
        plan.route = pinned_engine
        plan.pinned = True
        plan.reason = f"engine={pinned_engine!r} pinned by caller"
        if pinned_engine == "sharded":
            plan.n_shards = n_shards or cost.choose_shards(est, max_workers)
            if device_route is not None:
                plan.device, plan.device_route = True, device_route
        return plan
    for mav in views:
        if n_shards is not None or device_route is not None:
            break                     # scan-knob pins demand a scan route:
                                      # the rewrite must not swallow them
        rw = mav_rewrite(logical, mav)
        if rw is None:
            continue
        pending = _mav_pending(mav, mv_stale_rows, plan)
        if pending is None:
            continue                  # purged / stale: base-table routes
        plan.route, plan.mv, plan.mv_pending = "mav", mav.name, pending
        plan.rewrite = rw
        plan.reason = (f"rewritten onto MAV {mav.name!r} "
                       f"({pending} pending mlog rows merged at read)")
        return plan
    plan.n_shards = n_shards or cost.choose_shards(est, max_workers)
    if device_route is not None:
        plan.route, plan.device, plan.device_route = \
            "sharded", True, device_route
        plan.pinned = True
        plan.reason = f"device_route={device_route!r} pinned by caller"
        return plan
    if plan.n_shards > 1:
        plan.route = "sharded"
        plan.reason = (f"est {est.est_rows:.0f} of {est.n_rows} rows survive"
                       f"{cal_note}: fan out to {plan.n_shards} shards")
    else:
        plan.route = "pushdown"
        plan.reason = (f"est {est.est_rows:.0f} of {est.n_rows} rows survive"
                       f" (selectivity {est.selectivity:.4f}{cal_note}): "
                       f"single-shard pushdown")
    plan.pinned = n_shards is not None
    return plan


# ---------------------------------------------------------------------------
# The compiled-plan artifact (plan layer / execute layer seam)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CompiledPlan:
    """An immutable, reusable planning artifact: everything ``execute``
    needs to run the query, plus the epochs it was compiled against.

    Compilation is **pure** — breaker verdicts are consulted without
    advancing cool-downs, calibration and MAV freshness are read-only — so
    compiling twice is always safe and a ``CompiledPlan`` can be cached and
    shared across threads.  ``key`` is hashable and moves whenever the
    answer *or* the routing could change: it folds in the normalized
    ``LogicalPlan``, the table epoch (every DML / baseline swap), and the
    calibration epoch (every feedback observation).  ``result_key`` drops
    the calibration component — feedback shifts routing, never answers —
    and is what result caches / shared-scan coalescing key on."""

    table: str
    logical: LogicalPlan
    plan: Plan                         # template — treated read-only; every
                                       # execution runs on a fresh copy
    epoch: Tuple[int, int]             # LSMStore.epoch at compile time
    cal_epoch: int                     # TableCalibration.epoch at compile
    ts: Optional[int]                  # snapshot pin (None = read current)
    hints: Tuple = ()                  # (engine, n_shards, device_route,
                                       # use_mv, max_workers) as compiled
    max_workers: Optional[int] = None  # per-plan worker-pool width override

    @property
    def key(self) -> Tuple:
        return (self.table, self.logical.cache_key(), self.hints, self.ts,
                self.epoch, self.cal_epoch)

    @property
    def result_key(self) -> Tuple:
        return (self.table, self.logical.cache_key(), self.hints, self.ts,
                self.epoch)

    def fresh_plan(self) -> Plan:
        """A mutable per-execution copy of the plan template: provenance
        lists are fresh (N threads sharing this artifact never race on
        them), breaker verdicts are cleared and the pre-breaker route is
        restored — execution re-applies breakers with *fresh, advancing*
        verdicts so cross-query health state keeps moving."""
        p = self.plan
        return dataclasses.replace(
            p, route=p.base_route or p.route,
            degraded=[d for d in p.degraded if not d.startswith("breaker(")],
            repaired=list(p.repaired), breaker={})


# ---------------------------------------------------------------------------
# Typed results
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ResultSet:
    """Typed query result: named columns in output order, result rows, and
    provenance — the executed ``Plan`` plus the executor's ``ScanStats``."""

    columns: Tuple[str, ...]
    rows: List[Dict[str, Any]]
    plan: Plan
    stats: Optional[ScanStats] = None

    def __len__(self) -> int:
        return len(self.rows)

    def __iter__(self) -> Iterator[Dict[str, Any]]:
        return iter(self.rows)

    def __getitem__(self, i):
        return self.rows[i]

    def column(self, name: str) -> List[Any]:
        if name not in self.columns:
            raise KeyError(name)
        return [r.get(name) for r in self.rows]

    def __repr__(self) -> str:
        deg = (f", degraded={self.plan.degraded!r}"
               if self.plan.degraded else "")
        return (f"ResultSet({len(self.rows)} rows, columns={self.columns}, "
                f"route={self.plan.route!r}{deg})")


# ---------------------------------------------------------------------------
# The session façade
# ---------------------------------------------------------------------------


class TableHandle:
    """One table inside a ``Database``: the LSM store plus its registered
    view and mlog state.  DML and storage maintenance delegate straight to
    the underlying ``LSMStore`` (``insert`` / ``update`` / ``delete`` /
    ``bulk_insert`` / ``major_compact`` / ...)."""

    def __init__(self, name: str, store: LSMStore, db: "Database"):
        self.name = name
        self.store = store
        self._db = db
        self.mavs: Dict[str, MaterializedAggView] = {}
        self.mjvs: Dict[str, MaterializedJoinView] = {}
        self._mlog: Optional[MLog] = None

    @property
    def schema(self) -> Schema:
        return self.store.schema

    def mlog(self) -> MLog:
        """The table's change log, created on first use (DAS: every DML on
        the store is recorded from that point on)."""
        if self._mlog is None:
            self._mlog = MLog(self.store)
        return self._mlog

    def query(self, q: Query, **hints) -> ResultSet:
        return self._db.query(q, table=self.name, **hints)

    def explain(self, q: Query, **hints) -> Plan:
        return self._db.explain(q, table=self.name, **hints)

    def __getattr__(self, attr):
        return getattr(self.store, attr)       # DML / maintenance passthrough

    def __repr__(self) -> str:
        return (f"TableHandle({self.name!r}, rows={self.store.baseline.nrows}"
                f"+{self.store.incremental_fraction():.2f} incr, "
                f"mavs={sorted(self.mavs)})")


class Database:
    """The unified session: attach or create tables, register materialized
    views, and run every query through the two-stage compiler.  See the
    module docstring for the routing rules."""

    def __init__(self, store: Optional[LSMStore] = None, name: str = "main",
                 mv_stale_rows: int = DEFAULT_MV_STALE_ROWS,
                 max_workers: Optional[int] = None,
                 health: Any = None,
                 durable: Optional[str] = None, group_commit: int = 1):
        self._tables: Dict[str, TableHandle] = {}
        self.mv_stale_rows = mv_stale_rows
        self.max_workers = max_workers
        # Durability (core/wal.py / core/recovery.py): durable=<dir> gives
        # every attached table a write-ahead log under <dir>/wal/ — each
        # committed mutation appends one checksummed, epoch-stamped record
        # before it is acknowledged, ``db.snapshot()`` checkpoints, and
        # ``Database.recover(<dir>)`` restores after a crash.  A directory
        # that already holds durable state must go through ``recover`` —
        # re-opening it blind would interleave a fresh log with stale
        # records, which is exactly the silent-loss mode the WAL rules out.
        self.durable = durable
        self.group_commit = max(1, int(group_commit))
        self._recovery: Optional[Dict[str, Any]] = None
        if durable is not None:
            from .recovery import WAL_DIR, snapshot_path
            wdir = os.path.join(durable, WAL_DIR)
            has_wal = os.path.isdir(wdir) and any(
                fn.endswith(".wal") for fn in os.listdir(wdir))
            if has_wal or os.path.exists(snapshot_path(durable)):
                raise ValueError(
                    f"durable root {durable!r} already contains a WAL or "
                    f"snapshot — use Database.recover({durable!r}) instead")
            os.makedirs(wdir, exist_ok=True)
        # Cross-query health registry + circuit breakers (core/health.py):
        # on by default — health=None builds a fresh HealthRegistry,
        # health=False disables cross-query state (every query re-walks
        # the full ladder, the pre-PR-7 behaviour), or pass a configured
        # HealthRegistry (custom threshold/cooldown) to share or tune it.
        self.health: Optional[HealthRegistry] = \
            HealthRegistry() if health is None \
            else (None if health is False else health)
        if store is not None:
            self.attach(name, store)

    # -------------------------------------------------------------- tables
    def attach(self, name: str, store: LSMStore) -> TableHandle:
        if name in self._tables:
            raise ValueError(f"table {name!r} already attached")
        h = TableHandle(name, store, self)
        self._tables[name] = h
        if self.durable is not None and store.wal is None:
            self._attach_wal(h)
        return h

    def _attach_wal(self, h: TableHandle) -> None:
        """Give a newly attached table its write-ahead log and open it with
        a ``create_table`` record.  A store attached with pre-existing
        contents is marked ``seeded``: its rows predate the log, so replay
        refuses to rebuild it unless a snapshot covers it — typed failure
        over a silently partial table."""
        from .recovery import wal_path
        from .wal import WriteAheadLog
        store = h.store
        store.wal = WriteAheadLog(wal_path(self.durable, h.name),
                                  self.group_commit, table=h.name)
        seeded = store.epoch != (0, 0) or store.baseline.nrows > 0 \
            or len(store.memtable) > 0 or bool(store.minors)
        store._log("create_table", schema=store.schema,
                   block_rows=store.block_rows,
                   memtable_limit=store.memtable_limit,
                   replication=store.replication, seeded=seeded)

    def create_table(self, name: str, schema: Schema, **kw) -> TableHandle:
        return self.attach(name, LSMStore(schema, **kw))

    def table(self, name: Optional[str] = None) -> TableHandle:
        if name is None:
            if len(self._tables) == 1:
                return next(iter(self._tables.values()))
            raise ValueError(
                f"table name required (attached: {sorted(self._tables)})")
        if name not in self._tables:
            raise KeyError(f"unknown table {name!r} "
                           f"(attached: {sorted(self._tables)})")
        return self._tables[name]

    @property
    def tables(self) -> Tuple[str, ...]:
        return tuple(sorted(self._tables))

    # --------------------------------------------------------------- views
    def create_mav(self, name: str, definition: MAVDefinition,
                   table: Optional[str] = None, container_mode: str = "row",
                   refresh_mode: str = "incremental") -> MaterializedAggView:
        """Register a materialized aggregate view; matching aggregate
        queries are transparently rewritten onto it from then on."""
        h = self.table(table)
        mav = MaterializedAggView(name, h.store, h.mlog(), definition,
                                  container_mode, refresh_mode)
        h.mavs[name] = mav
        # registration record (after construction, matching the event
        # order on disk: the constructor's full refresh already logged its
        # purge marker) so recovery re-registers the view
        h.store._log("create_mav", name=name, defn=definition,
                     container_mode=container_mode, refresh_mode=refresh_mode)
        return mav

    def create_mjv(self, name: str, definition: MJVDefinition,
                   left: str, right: str) -> MaterializedJoinView:
        lh, rh = self.table(left), self.table(right)
        mjv = MaterializedJoinView(name, lh.store, rh.store, lh.mlog(),
                                   rh.mlog(), definition)
        lh.mjvs[name] = mjv
        rh.mjvs[name] = mjv
        # logged to the left table's WAL; replay defers it until every
        # table's tail is restored (the right table may replay later)
        lh.store._log("create_mjv", name=name, defn=definition,
                      left=left, right=right)
        return mjv

    # ------------------------------------------------------------ planning
    def _plan(self, h: TableHandle, q: Query, engine: Optional[str],
              n_shards: Optional[int], device_route: Optional[str],
              ts: Optional[int], use_mv: bool,
              advance: bool = True,
              max_workers: Optional[int] = None) -> Plan:
        logical = plan_logical(q, h.store.schema)
        verdicts = cost.prune_verdicts(h.store, logical.preds) \
            if h.store.baseline.n_blocks and logical.preds else None
        # secondary calibration signal: the health registry's observed
        # per-table latency EWMA rides on the estimate into choose_shards
        lat = self.health.latency(h.name) if self.health is not None else None
        est = cost.estimate_scan(h.store, logical.preds, verdicts,
                                 latency_ewma_s=lat)
        # A snapshot read (ts=) pins the query to the scan paths: the MV
        # container only answers at current freshness.  A quarantined
        # (checksum-failed) block also disqualifies the rewrite: the
        # container may have absorbed the corrupt rows, so the scan path —
        # which raises BlockCorruption on touch — must answer instead.
        views = tuple(h.mavs.values()) \
            if use_mv and engine is None and ts is None \
            and not h.store.has_quarantined_blocks() else ()
        workers = self.max_workers if max_workers is None else max_workers
        plan = plan_physical(logical, est, cost.calibration(h.store), views,
                             table=h.name, pinned_engine=engine,
                             n_shards=n_shards, device_route=device_route,
                             max_workers=workers,
                             mv_stale_rows=self.mv_stale_rows)
        plan.base_route = plan.route
        # Circuit breakers (core/health.py): consult the table's breakers
        # and pre-degrade known-bad rungs at plan time instead of walking
        # the ladder again.  ``advance=False`` (explain / compile) reports
        # the verdicts without consuming cool-down ticks or arming probes —
        # planning stays pure; execution re-applies with advance=True.
        if self.health is not None and plan.route != "mav":
            self._apply_breakers(h, plan, advance)
        return plan

    def _apply_breakers(self, h: TableHandle, plan: Plan,
                        advance: bool) -> None:
        """Consult the table's breakers and fold the verdicts into
        ``plan``: an open 'sharded' breaker pre-degrades the fan-out to
        single-shard pushdown, a half-open one annotates the probe; the
        device-rung verdicts ride in ``plan.breaker`` for the executors."""
        plan.breaker = self.health.consult(h.name, advance=advance)
        verdict = plan.breaker.get("sharded")
        if verdict == "skip" and plan.route == "sharded":
            # availability over the cost choice (and over pins): the
            # fan-out itself is known-bad, answer single-shard
            plan.degraded.append(cost.breaker_note(
                "sharded", "skip", "pre-degraded sharded->pushdown"))
            plan.route = "pushdown"
        elif verdict == "probe" and plan.route == "sharded":
            plan.degraded.append(cost.breaker_note(
                "sharded", "probe", "attempting sharded fan-out"))
        if plan.route == "sharded":
            # per-shard verdicts (health.py ``sharded[<id>]`` breakers):
            # the fan-out still runs, but open shards fail-fast to one
            # attempt — recorded here so provenance shows the cause
            for rung in sorted(plan.breaker):
                if not rung.startswith("sharded["):
                    continue
                v = plan.breaker[rung]
                plan.degraded.append(cost.breaker_note(
                    rung, v, "shard fail-fast (single attempt)"
                    if v == "skip" else "probing shard"))

    def compile(self, q: Query, table: Optional[str] = None, *,
                engine: Optional[str] = None, n_shards: Optional[int] = None,
                device_route: Optional[str] = None, ts: Optional[int] = None,
                use_mv: bool = True,
                max_workers: Optional[int] = None) -> CompiledPlan:
        """Pure planning: normalize, estimate, route — no side effects on
        calibration, breakers, or MAV state — and freeze the result into an
        immutable, hashable :class:`CompiledPlan` keyed by the logical
        plan + table epoch + calibration epoch.  Safe to call from any
        thread and to cache: ``execute`` runs the artifact any number of
        times.  ``max_workers=`` overrides the session's fan-out pool
        width for this plan (the serving layer sizes it so server
        concurrency x shard fan-out stays within the core budget)."""
        h = self.table(table)
        epoch = h.store.epoch
        cal_epoch = cost.calibration(h.store).epoch
        plan = self._plan(h, q, engine, n_shards, device_route, ts, use_mv,
                          advance=False, max_workers=max_workers)
        return CompiledPlan(
            table=h.name, logical=plan.logical, plan=plan, epoch=epoch,
            cal_epoch=cal_epoch, ts=ts,
            hints=(engine, n_shards, device_route, use_mv, max_workers),
            max_workers=max_workers)

    def explain(self, q: Query, table: Optional[str] = None, *,
                engine: Optional[str] = None, n_shards: Optional[int] = None,
                device_route: Optional[str] = None, ts: Optional[int] = None,
                use_mv: bool = True) -> Plan:
        """The plan ``query`` would execute, without executing it — breaker
        pre-degrades included, but without consuming breaker cool-down
        ticks (explain never advances cross-query health state)."""
        return self._plan(self.table(table), q, engine, n_shards,
                          device_route, ts, use_mv, advance=False)

    # ---------------------------------------------------------- durability
    def snapshot(self, path: Optional[str] = None) -> str:
        """Checkpoint every attached table (``core/recovery.py``): write an
        epoch-consistent image and compact each WAL down to its uncovered
        tail.  ``path`` defaults to the durable root."""
        from . import recovery as _recovery
        return _recovery.snapshot(self, path)

    @classmethod
    def recover(cls, root: str, group_commit: int = 1,
                **db_kwargs: Any) -> "Database":
        """Restore a durable database after a crash: snapshot + WAL-tail
        replay + fresh logs.  Raises :class:`~.errors.RecoveryError` when a
        provably consistent store cannot be produced — committed-prefix or
        typed failure, never silent loss."""
        from . import recovery as _recovery
        return _recovery.recover(root, group_commit=group_commit,
                                 **db_kwargs)

    def flush_wal(self) -> None:
        """Force every table's buffered WAL tail to disk (the group-commit
        boundary — ``QueryServer.drain`` calls this so 'drained' implies
        'durable')."""
        for name in sorted(self._tables):
            wal = self._tables[name].store.wal
            if wal is not None:
                wal.flush()

    def health_report(self, table: Optional[str] = None) -> List[str]:
        """Human-readable cross-query health lines for ``table`` (latency /
        failure EWMAs, breaker states, and — on a recovered database —
        recovery provenance).  Empty when health tracking is disabled
        (``Database(..., health=False)``)."""
        if self.health is None:
            return []
        name = self.table(table).name
        lines = self.health.describe(name)
        if self._recovery is not None:
            ti = self._recovery["tables"].get(
                name, {"replayed": 0, "torn": False})
            lines.insert(0, (
                f"recovery: restored from "
                f"{'snapshot+wal' if self._recovery['snapshot'] else 'wal'}, "
                f"replayed={ti['replayed']} record(s)"
                + (", torn tail truncated" if ti["torn"] else "")))
        return lines

    # ----------------------------------------------------------- execution
    def query(self, q: Query, table: Optional[str] = None, *,
              engine: Optional[str] = None, n_shards: Optional[int] = None,
              device_route: Optional[str] = None, ts: Optional[int] = None,
              use_mv: bool = True,
              deadline_s: Optional[float] = None) -> ResultSet:
        """Plan and run ``q``; returns a typed ``ResultSet`` whose ``plan``
        and ``stats`` record how it was answered.  ``engine=`` pins one of
        'scalar' | 'vectorized' | 'pushdown' | 'sharded'; ``n_shards=`` and
        ``device_route=`` pin the fan-out knobs; ``use_mv=False`` disables
        the transparent MAV rewrite; ``ts=`` reads a snapshot (scan routes
        only); ``deadline_s=`` bounds scan-route wall time — past it the
        query raises ``QueryTimeout`` carrying partial-progress stats.

        A thin composition of the three serving layers:
        ``compile`` (pure plan) → ``execute`` (re-entrant run) →
        ``commit`` (calibration + health feedback)."""
        cplan = self.compile(q, table, engine=engine, n_shards=n_shards,
                             device_route=device_route, ts=ts, use_mv=use_mv)
        result = self.execute(cplan, deadline_s=deadline_s)
        self.commit(result)
        return result

    def execute(self, cplan: CompiledPlan, *,
                deadline_s: Optional[float] = None) -> ResultSet:
        """Run a :class:`CompiledPlan`.  Re-entrant: N threads may execute
        the same artifact (or different ones) against one store
        concurrently — every run gets a fresh ``Plan`` copy, reads at a
        snapshot captured on entry, and records the snapshot in
        ``plan.ts`` so the answer can be replayed bit-identically.

        Breakers advance here (one cool-down tick per execution, the
        verdicts re-applied fresh to the restored pre-breaker route), so a
        cached plan compiled under an open breaker still probes once the
        breaker cools.  A major compaction racing the run swaps the
        baseline mid-scan; that is detected by the baseline-generation
        bump and the run is retried (bounded) against the new baseline."""
        with spans.span("ob.execute"):
            return self._execute(cplan, deadline_s)

    def _execute(self, cplan: CompiledPlan,
                 deadline_s: Optional[float]) -> ResultSet:
        h = self.table(cplan.table)
        store = h.store
        for attempt in range(3):
            plan = cplan.fresh_plan()
            if self.health is not None and plan.route != "mav":
                self._apply_breakers(h, plan, advance=True)
            gen0 = store._baseline_gen
            t0 = time.monotonic()
            try:
                if plan.route == "mav":
                    rows, stats = self._execute_mav(h, plan)
                else:
                    ts_exec = cplan.ts if cplan.ts is not None \
                        else store.current_ts
                    plan.ts = ts_exec
                    rows, stats = self._execute_scan(
                        h, plan.logical.to_query(), plan, ts_exec,
                        deadline_s, cplan.max_workers)
            except QueryTimeout:
                raise                  # deterministic: re-running can only
                                       # blow the deadline again
            # lint: allow(broad-except) — compaction-race boundary: any
            # failure kind can be a symptom of the baseline swapping
            # mid-scan; re-raised verbatim unless the epoch moved
            except Exception:
                if store._baseline_gen != gen0 and attempt < 2:
                    continue           # compaction raced the scan: retry
                raise
            if plan.route != "mav" and store._baseline_gen != gen0 \
                    and attempt < 2:
                # the baseline was swapped while we scanned it — block
                # indices may straddle two builds, so the answer is not
                # trustworthy; re-run against the new baseline
                plan.degraded.append(
                    "execute: baseline swapped mid-scan (compaction "
                    "raced), re-ran")
                continue
            break
        if stats is not None:
            stats.latency_s = time.monotonic() - t0
            # execution-time degradation joins the plan-time entries so
            # ResultSet provenance shows the full ladder in order
            plan.degraded.extend(stats.degraded)
            plan.mlog_retries += stats.mlog_retries
            plan.repaired.extend(stats.repaired)
        return ResultSet(plan.logical.output_names(h.store.schema.names),
                         rows, plan, stats)

    def commit(self, result: ResultSet) -> None:
        """Post-execution side effects, the third stage of the query path:
        close the calibration loop (``cost.observe_scan`` on the estimate
        the executor carried out) and feed the health registry (latency /
        failure EWMAs, breaker transitions).  Idempotence is *not* assumed
        — call once per executed result, as ``query`` does.  Cached or
        coalesced results served without executing must not be
        committed."""
        stats = result.stats
        if stats is None or result.plan.cached:
            return
        h = self.table(result.plan.table)
        if stats.estimate is not None:
            cost.observe_scan(h.store, stats.estimate, stats.actual_rows)
        if self.health is not None:
            # feed the health registry: EWMAs update and rung outcomes
            # drive the breakers (the cross-query self-healing loop)
            self.health.observe(h.name, stats, latency_s=stats.latency_s)

    def _execute_scan(self, h: TableHandle, q: Query, plan: Plan,
                      ts: Optional[int],
                      deadline_s: Optional[float] = None,
                      max_workers: Optional[int] = None
                      ) -> Tuple[List[Dict[str, Any]], ScanStats]:
        store = h.store
        workers = self.max_workers if max_workers is None else max_workers
        if plan.route == "pushdown":
            return PushdownExecutor(
                breaker=plan.breaker, observe=False).execute_stats(
                store, q, ts, deadline_s=deadline_s)
        if plan.route == "sharded":
            ex = ShardedScanExecutor(n_shards=plan.n_shards,
                                     device=plan.device,
                                     device_route=plan.device_route or None,
                                     max_workers=workers,
                                     breaker=plan.breaker, observe=False)
            rows, stats = ex.execute_stats(store, q, ts,
                                           deadline_s=deadline_s)
            plan.n_shards = stats.n_shards
            return rows, stats
        # full-decode baselines ('scalar' / 'vectorized'): the engine does
        # the filtering, the store only materializes the needed columns
        needed = sorted(VectorEngine.columns_needed(q, store.schema.names))
        tbl, stats = store.scan(columns=needed, ts=ts)
        eng = ScalarEngine() if plan.route == "scalar" else VectorEngine()
        return eng.execute(tbl, q), stats

    def _execute_mav(self, h: TableHandle, plan: Plan
                     ) -> Tuple[List[Dict[str, Any]], ScanStats]:
        """Answer from the MAV container ⊕ pending-mlog merge, then apply
        the residual group-column predicates and emit the query's aliases.
        ``mav.query(realtime=True)`` itself falls back to a full container
        rebuild if the tail is purged between planning and here.

        Concurrent reads of one MAV serialize on a per-view lock (the
        realtime merge can trigger container mutation — purge fallback,
        dirty min/max recompute — which is not re-entrant), and the merge
        is pinned to the snapshot captured under that lock so the answer
        equals a base-table scan at exactly ``plan.ts``."""
        mav = h.mavs[plan.mv]
        logical, rw = plan.logical, plan.rewrite
        lock = mav.__dict__.setdefault("_read_lock", threading.Lock())
        with lock:
            purges0 = mav.stats.get("purge_full_refreshes", 0)
            retries0 = mav.stats.get("mlog_retries", 0)
            ts_exec = h.store.current_ts
            plan.ts = ts_exec
            tbl = mav.query(realtime=True, ts=ts_exec)
            mlog_retries = mav.stats.get("mlog_retries", 0) - retries0
            purged = mav.stats.get("purge_full_refreshes", 0) > purges0
        if rw["residual"] and len(tbl):
            mask = np.ones(len(tbl), bool)
            for p in rw["residual"]:
                mask &= p.eval(tbl.col(p.column))
            tbl = tbl.take(np.nonzero(mask)[0])
        rows: List[Dict[str, Any]] = []
        for r in tbl.rows():
            out = {g: r[g] for g in logical.group_by}
            for alias, kind, src in rw["emit"]:
                if kind == "avg_ratio":
                    s, c = src
                    out[alias] = (r[s] / r[c]) if r[c] else None
                elif kind == "sum":
                    out[alias] = r[src] if r[src] is not None else 0
                else:
                    out[alias] = r[src]
            rows.append(out)
        if not logical.group_by and not rows:
            # flat aggregate over an empty container: engine conventions
            # (count → 0, sum → 0, min/max/avg → None)
            rows = [{alias: 0 if kind in ("count", "sum") else None
                     for alias, kind, _ in rw["emit"]}]
        if logical.sort_by:
            rows = VectorEngine._sort(rows, logical.sort_by)
        if logical.limit is not None:
            rows = rows[: logical.limit]
        stats = ScanStats(used_pushdown=False)
        stats.rows_merged_incremental = plan.mv_pending
        stats.actual_rows = len(rows)
        stats.mlog_retries = mlog_retries
        if purged:
            # the tail was purged between planning and the realtime read:
            # the MAV answered from a full container rebuild instead
            stats.purge_fallback = True
            # grammar note: the from-token is the mav itself, not a rung —
            # "mav(<name>)->full-refresh" can never collide with a
            # health.rung_outcome "<rung>->" failure prefix
            stats.degraded.append(
                f"mav({mav.name})->full-refresh: purge_fallback "
                f"(mlog tail purged mid-query)")
        return rows, stats

    def __repr__(self) -> str:
        return f"Database(tables={self.tables})"
