"""Concurrent multi-tenant query serving over a :class:`Database`.

The paper's multi-tenant resource story (§II-C) applied to the AP query
path itself: a :class:`QueryServer` fronts one thread-safe ``Database``
and serves N concurrent clients through the three-layer split —
``compile`` (pure plan) → ``execute`` (re-entrant run) → ``commit``
(feedback) — with an admission scheduler between compile and execute:

* **tenant quotas** — per-tenant estimated-row budgets per time window
  (cgroup-style capping, the analogue of the paper's resource-isolated
  tenant units); an over-budget tenant's queries *defer* until the window
  rolls rather than degrade other tenants' latency;
* **latency-class priority** — 'interactive' tickets always dispatch
  ahead of 'batch' tickets, and one worker slot is reserved for
  interactive traffic so a batch flood can never occupy the whole pool
  (OLTP-priority scheduling transposed to AP serving);
* **epoch-invalidated caches** — compiled plans are reused while the
  table epoch (DML / baseline swaps) and calibration epoch (cost
  feedback) both stand still; results are cached under
  ``CompiledPlan.result_key``, which *embeds* the table epoch, so any
  write invalidates naturally — no explicit flush, stale keys are simply
  never looked up again;
* **shared-scan coalescing** — concurrent identical queries (same
  ``result_key``) attach to the one in-flight execution and share its
  answer instead of re-scanning (the multiple-query-optimization /
  shared-scan idea at admission granularity);
* **background scrubbing** — replica integrity passes are scheduled from
  the serving loop on idle ticks and every ``scrub_every`` admissions,
  with events surfaced through the health registry's notes.

Everything here is control plane: the data plane is ``Database.execute``,
which N workers enter concurrently (PR 8 made the storage/health/cost
layers re-entrant).
"""
from __future__ import annotations

import dataclasses
import heapq
import itertools
import os
import threading
import time
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

from . import cost, replica, spans
from .engine import Query
from .errors import ServerClosed
from .session import CompiledPlan, Database, ResultSet

__all__ = ["TenantQuota", "Ticket", "QueryServer"]

_CLASS_RANK = {"interactive": 0, "batch": 1}


@dataclasses.dataclass
class TenantQuota:
    """Per-tenant admission budget: estimated rows scanned per window.

    ``est_rows`` from the compiled plan is the charge unit — it is known
    *before* execution (admission must not require running the query) and
    tracks actual work closely once calibration warms up.  ``latency_class``
    sets the tenant's dispatch priority tier."""

    budget_rows: float = float("inf")
    latency_class: str = "interactive"     # 'interactive' | 'batch'

    def __post_init__(self) -> None:
        if self.latency_class not in _CLASS_RANK:
            raise ValueError(f"unknown latency class {self.latency_class!r}")


class Ticket:
    """A submitted query's handle: resolves to the :class:`ResultSet` (or
    raises the execution error) on ``result()``.  Records serving
    provenance — whether the answer came from the result cache, was
    coalesced onto another client's in-flight execution, or was deferred
    by quota before running."""

    def __init__(self, tenant: str, seq: int):
        self.tenant = tenant
        self.seq = seq
        self.submitted = time.monotonic()
        self.picked_at: Optional[float] = None     # popped by the scheduler
        self.dispatched_at: Optional[float] = None
        self.done_at: Optional[float] = None
        self.cache_hit = False
        self.coalesced = False
        self.deferred = False
        self._event = threading.Event()
        self._result: Optional[ResultSet] = None
        self._exc: Optional[BaseException] = None
        # filled by the server at submit time; consumed by the scheduler
        self._query: Optional[Query] = None
        self._table: Optional[str] = None
        self._hints: Dict[str, Any] = {}
        self._deadline_s: Optional[float] = None

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> ResultSet:
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"ticket #{self.seq} (tenant={self.tenant}) not done "
                f"within {timeout}s")
        if self._exc is not None:
            raise self._exc
        assert self._result is not None
        return self._result

    @property
    def latency_s(self) -> Optional[float]:
        return None if self.done_at is None else self.done_at - self.submitted

    def _resolve(self, result: Optional[ResultSet],
                 exc: Optional[BaseException] = None) -> None:
        self._result, self._exc = result, exc
        self.done_at = time.monotonic()
        self._event.set()


class _Inflight:
    """One running execution that later identical submissions attach to."""

    def __init__(self, leader: Ticket):
        self.leader = leader
        self.followers: List[Ticket] = []


class QueryServer:
    """Admission-scheduled, cache-fronted concurrent serving over one
    ``Database``.  ``submit`` never blocks the caller; the returned
    :class:`Ticket` resolves when a worker (or a cache) answers.

    ``workers`` sizes the execution pool — size it against the shard
    fan-out pool (``db.max_workers``): each admitted query gets a
    ``max_workers`` hint of roughly ``db.max_workers // workers`` so N
    concurrent fan-outs don't oversubscribe the host."""

    def __init__(self, db: Database, *, workers: int = 4,
                 quotas: Optional[Dict[str, TenantQuota]] = None,
                 window_s: float = 60.0,
                 plan_cache_size: int = 256,
                 result_cache_size: int = 512,
                 scrub_every: int = 64,
                 idle_scrub_s: float = 0.05,
                 snapshot_every_scrubs: int = 0):
        if workers < 1:
            raise ValueError("workers must be >= 1")
        self.db = db
        self.workers = workers
        self.quotas: Dict[str, TenantQuota] = dict(quotas or {})
        self.window_s = window_s
        self.scrub_every = scrub_every
        self.idle_scrub_s = idle_scrub_s
        # durability checkpointing (core/recovery.py): on a durable db,
        # every Nth *idle* scrub also takes a snapshot — the same
        # idle-gap slot the scrubs use, so checkpoints never contend with
        # admitted queries.  0 disables scheduled snapshots.
        self.snapshot_every_scrubs = snapshot_every_scrubs
        self._scrubs_since_snapshot = 0
        # fan-out budget per query so N workers' shard pools don't multiply
        fanout = db.max_workers or os.cpu_count() or 1
        self._per_query_workers = max(1, fanout // workers)
        self._plan_cache: "OrderedDict[Tuple, CompiledPlan]" = OrderedDict()
        self._plan_cache_size = plan_cache_size
        self._result_cache: "OrderedDict[Tuple, ResultSet]" = OrderedDict()
        self._result_cache_size = result_cache_size
        self._inflight: Dict[Tuple, _Inflight] = {}
        self._mu = threading.Lock()
        self._cv = threading.Condition(self._mu)
        self._heap: List[Tuple[int, int, Ticket]] = []
        self._batch_waiting: List[Tuple[int, int, Ticket]] = []
        self._deferred: List[Ticket] = []
        self._spend: Dict[str, float] = {}
        self._window_start = time.monotonic()
        self._batch_inflight = 0
        self._interactive_inflight = 0
        self._seq = itertools.count()
        self._closed = False
        self._paused = False
        self.metrics: Dict[str, int] = {
            "submitted": 0, "admitted": 0, "executed": 0, "completed": 0,
            "plan_cache_hits": 0, "cache_hits": 0, "coalesced": 0,
            "deferred_quota": 0, "scrubs": 0, "snapshots": 0, "errors": 0,
        }
        self._pool = ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix="qsrv-worker")
        self._scheduler = threading.Thread(
            target=self._run, name="qsrv-scheduler", daemon=True)
        self._scheduler.start()

    # ------------------------------------------------------------ public
    def submit(self, q: Query, table: Optional[str] = None, *,
               tenant: str = "default", engine: Optional[str] = None,
               n_shards: Optional[int] = None,
               device_route: Optional[str] = None, ts: Optional[int] = None,
               use_mv: bool = True,
               deadline_s: Optional[float] = None) -> Ticket:
        """Enqueue ``q`` for ``tenant``; returns immediately."""
        with self._cv:
            if self._closed:
                raise ServerClosed("QueryServer is closed")
            t = Ticket(tenant, next(self._seq))
            t._query, t._table = q, table
            t._hints = dict(engine=engine, n_shards=n_shards,
                            device_route=device_route, ts=ts, use_mv=use_mv)
            t._deadline_s = deadline_s
            self.metrics["submitted"] += 1
            heapq.heappush(self._heap, (self._rank(tenant), t.seq, t))
            self._cv.notify_all()
        return t

    def quota(self, tenant: str) -> TenantQuota:
        return self.quotas.get(tenant) or TenantQuota()

    def reset_quotas(self) -> None:
        """Roll the budget window now: clear tenant spend and re-admit
        every quota-deferred ticket."""
        with self._cv:
            self._roll_window_locked(force=True)
            self._cv.notify_all()

    def spend(self, tenant: str) -> float:
        with self._mu:
            return self._spend.get(tenant, 0.0)

    def pause(self) -> None:
        """Hold admission: submitted tickets queue but none dispatch until
        ``resume()``.  Lets a caller enqueue a whole batch and observe the
        scheduler's priority order deterministically."""
        with self._cv:
            self._paused = True

    def resume(self) -> None:
        with self._cv:
            self._paused = False
            self._cv.notify_all()

    def drain(self, timeout: float = 30.0) -> None:
        """Block until every submitted ticket has resolved."""
        deadline = time.monotonic() + timeout
        while True:
            with self._mu:
                idle = (not self._heap and not self._batch_waiting
                        and not self._deferred and not self._inflight)
            if idle:
                # drained implies durable: push the group-commit tail out
                # so every acknowledged write is on disk
                self.db.flush_wal()
                return
            if time.monotonic() > deadline:
                raise TimeoutError("QueryServer.drain timed out")
            time.sleep(0.002)

    def close(self) -> None:
        with self._cv:
            if self._closed:
                return
            self._closed = True
            self._cv.notify_all()
        self._scheduler.join(timeout=10.0)
        self._pool.shutdown(wait=True)
        with self._mu:
            pending = [t for _, _, t in self._heap + self._batch_waiting]
            pending += self._deferred
            self._heap.clear()
            self._batch_waiting.clear()
            self._deferred.clear()
        for t in pending:
            t._resolve(None, ServerClosed("QueryServer closed"))
        self.db.flush_wal()

    def __enter__(self) -> "QueryServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # --------------------------------------------------------- scheduling
    def _rank(self, tenant: str) -> int:
        return _CLASS_RANK[self.quota(tenant).latency_class]

    def _roll_window_locked(self, force: bool = False) -> None:
        """Under ``self._mu``.  Reset spend when the window elapsed and
        push quota-deferred tickets back onto the admission heap."""
        now = time.monotonic()
        if not force and now - self._window_start <= self.window_s:
            return
        self._window_start = now
        self._spend.clear()
        for t in self._deferred:
            heapq.heappush(self._heap, (self._rank(t.tenant), t.seq, t))
        self._deferred.clear()

    def _next_ticket_locked(self) -> Optional[Ticket]:
        """Under ``self._mu``.  Highest-priority runnable ticket.  Batch
        tickets dispatch only into interactive-idle gaps (the paper's
        OLTP-priority rule: analytical work is admitted only when the
        priority class has no pending or running work — on a shared core
        a *running* batch query steals cycles no reservation can protect),
        and at most ``workers - 1`` batch executions run at once so the
        pool is never fully occupied by batch."""
        if self._batch_waiting and self._batch_slot_free():
            return heapq.heappop(self._batch_waiting)[2]
        while self._heap:
            entry = heapq.heappop(self._heap)
            _, _, t = entry
            if self._rank(t.tenant) == _CLASS_RANK["batch"] \
                    and not self._batch_slot_free():
                heapq.heappush(self._batch_waiting, entry)
                continue
            return t
        return None

    def _batch_slot_free(self) -> bool:
        if self._interactive_inflight:
            return False
        cap = self.workers - 1 if self.workers > 1 else 1
        return self._batch_inflight < cap

    def _run(self) -> None:
        admitted_since_scrub = 0
        while True:
            idle_scrub = False
            with self._cv:
                while self._paused and not self._closed:
                    self._cv.wait(timeout=0.1)
                if self._closed:
                    return          # queued tickets resolve in close()
                self._roll_window_locked()
                ticket = self._next_ticket_locked()
                if ticket is None:
                    if self._closed:
                        return
                    if not self._cv.wait(timeout=self.idle_scrub_s):
                        # idle tick: nothing queued for a while — scrub
                        busy = bool(self._inflight) or self._batch_inflight
                        idle_scrub = not busy and admitted_since_scrub > 0
            if ticket is None:
                if idle_scrub:
                    admitted_since_scrub = 0
                    self._scrub("idle")
                continue
            ticket.picked_at = time.monotonic()
            try:
                with spans.request(ticket.seq), spans.span("ob.admit"):
                    self._admit(ticket)
            # lint: allow(broad-except) — scheduler boundary: *any*
            # compile-time failure must resolve the ticket (the submitter
            # is blocked in result()), never kill the scheduler thread
            except BaseException as exc:     # compile-time failure
                with self._mu:
                    self.metrics["errors"] += 1
                ticket._resolve(None, exc)
                continue
            admitted_since_scrub += 1
            if admitted_since_scrub >= self.scrub_every:
                admitted_since_scrub = 0
                self._scrub("periodic")

    def _compile(self, t: Ticket) -> CompiledPlan:
        """Plan-cache lookup with epoch validation; recompile on miss.
        Compilation is pure (no breaker advancement, no calibration
        writes), so doing it on the scheduler thread is safe and cheap."""
        hints = t._hints
        qkey = (t._table, repr(t._query),
                tuple(sorted(hints.items(), key=lambda kv: kv[0])))
        h = self.db.table(t._table)
        epoch = h.store.epoch
        cal_epoch = cost.calibration(h.store).epoch
        with self._mu:
            cached = self._plan_cache.get(qkey)
            if cached is not None and cached.epoch == epoch \
                    and cached.cal_epoch == cal_epoch:
                self._plan_cache.move_to_end(qkey)
                self.metrics["plan_cache_hits"] += 1
                return cached
        cplan = self.db.compile(t._query, t._table,
                                max_workers=self._per_query_workers, **hints)
        with self._mu:
            self._plan_cache[qkey] = cplan
            self._plan_cache.move_to_end(qkey)
            while len(self._plan_cache) > self._plan_cache_size:
                self._plan_cache.popitem(last=False)
        return cplan

    def _admit(self, t: Ticket) -> None:
        """Scheduler-thread admission: compile, then answer from the
        result cache, attach to an in-flight twin, defer on quota, or
        dispatch to the worker pool."""
        with spans.span("ob.plan"):
            cplan = self._compile(t)
        rkey = cplan.result_key
        with self._mu:
            hit = self._result_cache.get(rkey)
            if hit is not None:
                self._result_cache.move_to_end(rkey)
                self.metrics["cache_hits"] += 1
                self.metrics["completed"] += 1
                t.cache_hit = True
                t._resolve(self._cached_view(hit))
                return
            infl = self._inflight.get(rkey)
            if infl is not None:
                infl.followers.append(t)
                self.metrics["coalesced"] += 1
                t.coalesced = True
                return
            # quota: charge the *estimate* at admission (known pre-run)
            q = self.quota(t.tenant)
            spent = self._spend.get(t.tenant, 0.0)
            est = max(0.0, cplan.plan.est_rows)
            if spent + est > q.budget_rows:
                t.deferred = True
                self.metrics["deferred_quota"] += 1
                self._deferred.append(t)
                return
            self._spend[t.tenant] = spent + est
            self._inflight[rkey] = _Inflight(t)
            if self._rank(t.tenant) == _CLASS_RANK["batch"]:
                self._batch_inflight += 1
            else:
                self._interactive_inflight += 1
            self.metrics["admitted"] += 1
        t.dispatched_at = time.monotonic()
        self._pool.submit(self._work, t, cplan)

    def _work(self, t: Ticket, cplan: CompiledPlan) -> None:
        """Worker-thread execution: run, commit feedback, publish to the
        result cache, resolve the leader and every coalesced follower."""
        rkey = cplan.result_key
        result: Optional[ResultSet] = None
        exc: Optional[BaseException] = None
        try:
            with spans.request(t.seq):
                result = self.db.execute(cplan, deadline_s=t._deadline_s)
                self.db.commit(result)
        # lint: allow(broad-except) — worker boundary: the leader and its
        # coalesced followers must resolve no matter what escaped the
        # typed layers below; the exception is re-delivered via result()
        except BaseException as e:
            exc = e
        with self._cv:
            infl = self._inflight.pop(rkey, None)
            if self._rank(t.tenant) == _CLASS_RANK["batch"]:
                self._batch_inflight -= 1
            else:
                self._interactive_inflight -= 1
            if exc is None and result is not None:
                self.metrics["executed"] += 1
                self._result_cache[rkey] = result
                self._result_cache.move_to_end(rkey)
                while len(self._result_cache) > self._result_cache_size:
                    self._result_cache.popitem(last=False)
            else:
                self.metrics["errors"] += 1
            followers = infl.followers if infl is not None else []
            self.metrics["completed"] += 1 + len(followers)
            self._cv.notify_all()
        t._resolve(result, exc)
        for f in followers:
            if exc is not None:
                f._resolve(None, exc)
            else:
                f._resolve(self._cached_view(result))

    @staticmethod
    def _cached_view(rs: ResultSet) -> ResultSet:
        """A served-from-cache view of an executed result: same rows (read
        only by convention), plan copy flagged ``cached`` so ``commit``
        refuses to double-count it in calibration/health feedback."""
        plan = dataclasses.replace(
            rs.plan, cached=True, degraded=list(rs.plan.degraded),
            repaired=list(rs.plan.repaired))
        return ResultSet(rs.columns, rs.rows, plan, rs.stats)

    # ---------------------------------------------------------- scrubbing
    def _scrub(self, why: str) -> None:
        """Background integrity pass over every table with a live replica
        set; repair events land in the health registry's notes so
        ``health_report`` surfaces them."""
        # metrics share self._mu with the worker-side counters: an
        # unlocked += here raced _work's locked increments (lost updates
        # under the hammer).  The lock wraps only the counter, never the
        # scrub/snapshot work below — those take store/replica locks and
        # must not nest inside self._mu (lock-order).
        with self._mu:
            self.metrics["scrubs"] += 1
        for name in self.db.tables:
            h = self.db.table(name)
            sr = replica.replica_set(h.store)
            if sr is None:
                continue
            events = sr.scrub()
            if self.db.health is not None:
                for ev in events:
                    self.db.health.note(name, f"scrub({why}): {ev}")
        if why == "idle" and self.snapshot_every_scrubs \
                and self.db.durable is not None:
            with self._mu:
                self._scrubs_since_snapshot += 1
                due = self._scrubs_since_snapshot \
                    >= self.snapshot_every_scrubs
                if due:
                    self._scrubs_since_snapshot = 0
            if due:
                try:
                    self.db.snapshot()
                    with self._mu:
                        self.metrics["snapshots"] += 1
                    if self.db.health is not None:
                        for name in self.db.tables:
                            self.db.health.note(
                                name, "snapshot(idle): checkpointed, "
                                      "wal compacted")
                # lint: allow(broad-except) — idle-checkpoint boundary on
                # the scheduler thread: a failed snapshot becomes a health
                # note + error count, never a dead scheduler
                except Exception as e:   # noqa: BLE001 — scheduler thread
                    with self._mu:
                        self.metrics["errors"] += 1
                    if self.db.health is not None:
                        for name in self.db.tables:
                            self.db.health.note(
                                name, f"snapshot(idle) failed: "
                                      f"{type(e).__name__}: {e}")

    def __repr__(self) -> str:
        return (f"QueryServer(workers={self.workers}, "
                f"tenants={sorted(self.quotas)}, "
                f"metrics={self.metrics})")
