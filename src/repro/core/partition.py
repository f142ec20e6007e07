"""Mesh-sharded scan fan-out over the block-pushdown executor.

The paper's Mercury deployment answers petabyte-scale analytical queries by
fanning one scan out across data replicas and tree-merging partial
aggregates; this module is that layer over the local storage model.  A
``VirtualSSTable``'s encoded baseline blocks are **range-partitioned** into
contiguous shards — boundaries are chosen from the ``SkippingIndex`` leaf
sketches (per-block row counts), so shards carry near-equal row weight and,
because baseline blocks are pk-ordered, each shard is a pk range.  Every
shard then runs the same pushdown pipeline the single-shard executor uses
(zone-map prune → encoded-domain filter → late materialization) via
``pushdown.filter_blocks``, producing a ``GroupedPartial`` of
count/sum/min/max per group; partials — including one extra partial for the
merge-on-read incremental rows — are combined pairwise by ``tree_reduce``
with a ``Sketch.merge``-style union (counts/sums add, mins/maxs fold), and
finalized with ``VectorEngine`` result conventions, so the fan-out answer
matches the single-shard engines for any shard count.

The fan-out width is **cost-chosen** by default: ``ShardedScanExecutor()``
asks the granularity planner (``core/cost.py``) for a shard count sized to
the *estimated surviving* rows of the query — a selective probe runs
single-shard (fan-out overhead would dominate), a full scan fans out to the
cores — while an explicit ``n_shards`` pins the width for parity sweeps and
scaling benchmarks.  The same estimate picks the per-shard scan coalescing
and, on the device path, the fused-kernel tile height.

Shards execute concurrently on a thread pool sized to the host cores (the
per-shard work is numpy decode/filter/bincount, which releases the GIL).
With ``device=True`` the supported query shape is staged once through
``pushdown.stage_device`` and the cost model picks between two routes
(``cost.choose_device_route``): the **collective** route pads the per-shard
block slices to a common tile shape and hands ONE batched ``shard_map``
launch to ``kernels.fused_scan_agg.sharded_scan_agg`` — the fused kernel
runs per shard on its 'scan'-mesh device and the count/sum/min/max partials
tree-reduce on device via psum/pmin/pmax over packed group-code
accumulators, so no ``GroupedPartial`` ever crosses back to the host; the
**host** route keeps the legacy per-shard kernel launches (round-robin
placement via ``launch.mesh.scan_shard_devices``) with a host-side
tree-merge.

``Query(sort_by=<group columns>, limit=k)`` additionally activates
**limit-aware top-k pushdown**: because a group's sort rank is fixed by its
key (never by a merged aggregate), each shard keeps only a k-group partial
heap, the merge tree combines heaps instead of full grouped partials, and
the device collective route slices the first k non-empty groups out of the
reduced accumulator before anything is copied to the host.  Sorting by an
aggregate alias is not rank-stable under merge and keeps the full-merge
path.
"""
from __future__ import annotations

import dataclasses
import os
import threading
import time
from concurrent.futures import FIRST_COMPLETED, ThreadPoolExecutor, wait
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import cost
from . import faultinject
from . import pushdown as _pd
from . import replica as _replica
from . import spans
from .engine import (Query, VectorEngine, _item, null_aware_key_codes,
                     null_last_key, pack_sort_keys)
from .errors import (BlockCorruption, Deadline, KernelLaunchError,
                     KeyPackError, QueryTimeout, RouteExhausted, ShardFailure)
from .lsm import LSMStore, ScanStats, VirtualSSTable
from .relation import ColType, Column
from .skipping import Verdict

#: sentinel distinguishing "shard not finished" from a legitimate None result
_PENDING = object()


# ---------------------------------------------------------------------------
# Range partitioning
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BlockShard:
    """One shard's contiguous block range [lo_block, hi_block) of the
    baseline (== one pk range, since baseline blocks are pk-ordered)."""

    shard_id: int
    lo_block: int
    hi_block: int
    n_rows: int

    @property
    def n_blocks(self) -> int:
        return self.hi_block - self.lo_block

    def block_ids(self) -> range:
        return range(self.lo_block, self.hi_block)


def range_partition(base: VirtualSSTable, n_shards: int) -> List[BlockShard]:
    """Split the baseline's blocks into ``n_shards`` contiguous ranges of
    near-equal row weight, read off the skipping-index leaf sketches (no
    data access).  Shards may be empty when there are fewer blocks than
    shards."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    nb = base.n_blocks
    if nb == 0:
        return [BlockShard(s, 0, 0, 0) for s in range(n_shards)]
    weights = base.cols[base.schema.pk].index.leaf_counts()
    cum = np.concatenate([[0], np.cumsum(weights)])
    total = int(cum[-1])
    cuts = [int(np.searchsorted(cum, total * s / n_shards, side="left"))
            for s in range(1, n_shards)]
    edges = np.maximum.accumulate(np.asarray([0] + cuts + [nb]))
    return [BlockShard(s, int(edges[s]), int(edges[s + 1]),
                       int(cum[edges[s + 1]] - cum[edges[s]]))
            for s in range(n_shards)]


def tree_reduce(parts: Sequence[Any], combine: Callable[[Any, Any], Any]):
    """Pairwise (binary-tree) reduction — the merge topology a distributed
    scan would use across replicas, log-depth instead of a left fold."""
    parts = list(parts)
    if not parts:
        raise ValueError("tree_reduce of no partials")
    while len(parts) > 1:
        nxt = []
        for i in range(0, len(parts) - 1, 2):
            nxt.append(combine(parts[i], parts[i + 1]))
        if len(parts) % 2:
            nxt.append(parts[-1])
        parts = nxt
    return parts[0]


# ---------------------------------------------------------------------------
# Grouped partial aggregates (the unit that flows up the merge tree)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class GroupedPartial:
    """Per-group count/sum/min/max partials over one shard (or the
    incremental rows).  ``keys`` are python-value tuples in sorted order;
    flat (group-less) aggregation is the single-key ``[()]`` case.  Sums are
    int64 for integer columns (exact, associative) and float64 otherwise;
    min/max entries are only meaningful where ``rows_per_group > 0``."""

    group_cols: Tuple[str, ...]
    keys: List[Tuple[Any, ...]]                 # sorted; None (NULL) keys last
    rows_per_group: np.ndarray                  # int64 [G]
    sums: Dict[str, np.ndarray]                 # per agg column [G]
    mins: Dict[str, np.ndarray]
    maxs: Dict[str, np.ndarray]
    # SQL non-null counts per aggregated column (flat: one slot; grouped:
    # [G]) so count(col)/avg/min/max skip NULL slots in every shard shape.
    cnts: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)

    # ------------------------------------------------------------- build
    @classmethod
    def from_columns(cls, q: Query, cols: Dict[str, np.ndarray],
                     n_rows: int,
                     nulls: Optional[Dict[str, Optional[np.ndarray]]] = None,
                     topk_prefix: Optional[int] = None) -> "GroupedPartial":
        """Aggregate one shard's late-materialized columns, mirroring
        ``VectorEngine._groupby`` key discovery (packed sort keys when the
        ranges allow, record arrays otherwise) and array-indexed
        accumulation.  ``nulls`` strips NULL slots from each aggregated
        column before accumulation (SQL null-skipping, flat and grouped
        alike); the per-group non-null counts land in ``cnts``.

        ``topk_prefix = k`` is the limit-pushdown fast path for queries
        sorted by a leading prefix of the group columns: discovered keys
        are already in sort order, so the partial keeps only the first k
        groups and never accumulates the rows of the discarded ones."""
        gb = tuple(q.group_by)
        agg_cols = sorted({a.column for a in q.aggs if a.column})
        if gb:
            keyarrs = [np.asarray(cols[g]) for g in gb]
            kmasks = [(nulls.get(g) if nulls else None) for g in gb]
            if n_rows == 0:
                keys: List[Tuple[Any, ...]] = []
                codes = np.zeros(0, np.int64)
            elif any(m is not None and np.asarray(m).any() for m in kmasks):
                # NULL group keys: sentinel-slot dictionary codes, one
                # None group per column, ordered after every real key —
                # identical to VectorEngine._groupby
                keys, codes = null_aware_key_codes(keyarrs, kmasks)
            elif len(keyarrs) == 1:
                uniq, codes = np.unique(keyarrs[0], return_inverse=True)
                keys = [(_item(u),) for u in uniq]
            else:
                try:
                    packed = pack_sort_keys(keyarrs)
                    _, first, codes = np.unique(packed, return_index=True,
                                                return_inverse=True)
                    keys = [tuple(_item(k[i]) for k in keyarrs)
                            for i in first]
                except KeyPackError:
                    stacked = np.rec.fromarrays(keyarrs)
                    uniq, codes = np.unique(stacked, return_inverse=True)
                    keys = [tuple(_item(x) for x in u) for u in uniq]
            if topk_prefix is not None and len(keys) > topk_prefix:
                keys = keys[: topk_prefix]      # unique-key order == sort
                keep = codes < topk_prefix      # order for prefix sorts
                codes = codes[keep]
                cols = {c: np.asarray(cols[c])[keep] for c in agg_cols}
                if nulls:
                    nulls = {c: (None if m is None else m[keep])
                             for c, m in nulls.items()}
                n_rows = int(codes.shape[0])
        else:
            keys = [()]
            codes = np.zeros(n_rows, np.int64)
        G = len(keys)
        rows_per_group = np.bincount(codes, minlength=G).astype(np.int64)
        # Only compute the statistics the query's aggregates actually read
        # (count needs rows_per_group alone; ufunc.at min/max scatters are
        # far slower than bincount and would serialize the shard pool).
        need_sum = {a.column for a in q.aggs if a.op in ("sum", "avg")}
        need_min = {a.column for a in q.aggs if a.op == "min"}
        need_max = {a.column for a in q.aggs if a.op == "max"}
        sums: Dict[str, np.ndarray] = {}
        mins: Dict[str, np.ndarray] = {}
        maxs: Dict[str, np.ndarray] = {}
        cnts: Dict[str, np.ndarray] = {}
        for c in agg_cols:
            v = np.asarray(cols[c])
            ccodes = codes
            m = nulls.get(c) if nulls else None
            if m is not None:
                keep = ~np.asarray(m)
                v = v[keep]
                ccodes = codes[keep]
            if not gb:
                cnts[c] = np.asarray([v.shape[0]], np.int64)
            else:
                cnts[c] = (rows_per_group if m is None
                           else np.bincount(ccodes, minlength=G)
                           .astype(np.int64))
            if c in need_sum:
                if not gb and v.dtype.kind in "iub":
                    # flat int sums: overflow-exact Python ints (object
                    # array) — int64 accumulation wraps near 2^63 and the
                    # sketch partials these merge with are already exact
                    from .skipping import _exact_int_sum
                    s = np.asarray(
                        [_exact_int_sum(v.astype(np.int64, copy=False))],
                        dtype=object)
                elif v.dtype.kind in "iub":    # exact, associative int sums
                    s = np.zeros(G, np.int64)
                    np.add.at(s, ccodes, v.astype(np.int64))
                else:
                    s = np.bincount(ccodes, weights=v.astype(np.float64),
                                    minlength=G)
                sums[c] = s
            if c in need_min or c in need_max:
                if v.size:
                    mn = np.full(G, v.max(), v.dtype)
                    np.minimum.at(mn, ccodes, v)
                    mx = np.full(G, v.min(), v.dtype)
                    np.maximum.at(mx, ccodes, v)
                else:                    # unread: rows_per_group is all zero
                    mn = np.zeros(G, v.dtype)
                    mx = np.zeros(G, v.dtype)
                if c in need_min:
                    mins[c] = mn
                if c in need_max:
                    maxs[c] = mx
        return cls(gb, keys, rows_per_group, sums, mins, maxs, cnts)

    # ------------------------------------------------------------- merge
    @staticmethod
    def merge(a: "GroupedPartial", b: "GroupedPartial") -> "GroupedPartial":
        """Sketch.merge-style combination: union the group keys, add
        counts/sums, fold mins/maxs (guarded by per-side presence)."""
        if not a.keys:
            return b
        if not b.keys:
            return a
        keys = sorted(set(a.keys) | set(b.keys), key=null_last_key)
        pos = {k: i for i, k in enumerate(keys)}
        ia = np.asarray([pos[k] for k in a.keys], np.int64)
        ib = np.asarray([pos[k] for k in b.keys], np.int64)
        G = len(keys)
        rows = np.zeros(G, np.int64)
        rows[ia] += a.rows_per_group
        rows[ib] += b.rows_per_group
        sums: Dict[str, np.ndarray] = {}
        for c in a.sums:
            s = np.zeros(G, np.result_type(a.sums[c].dtype, b.sums[c].dtype))
            s[ia] += a.sums[c]
            s[ib] += b.sums[c]
            sums[c] = s
        cnts: Dict[str, np.ndarray] = {}
        for c in a.cnts:
            n = np.zeros(G, np.int64)
            n[ia] += a.cnts[c]
            n[ib] += b.cnts[c]
            cnts[c] = n

        def present(p: "GroupedPartial", c: str, idx_rows: np.ndarray):
            # per-column presence: a flat shard whose rows are all NULL in
            # ``c`` contributes no min/max even though it has rows
            return p.cnts[c] > 0 if c in p.cnts else idx_rows > 0

        mins = {c: _fold(G, ia, a.mins[c], present(a, c, a.rows_per_group),
                         ib, b.mins[c], present(b, c, b.rows_per_group),
                         np.minimum)
                for c in a.mins}
        maxs = {c: _fold(G, ia, a.maxs[c], present(a, c, a.rows_per_group),
                         ib, b.maxs[c], present(b, c, b.rows_per_group),
                         np.maximum)
                for c in a.maxs}
        return GroupedPartial(a.group_cols, keys, rows, sums, mins, maxs,
                              cnts)

    # ---------------------------------------------------------- finalize
    def finalize(self, q: Query) -> List[Dict[str, Any]]:
        """Emit result rows with ``VectorEngine`` conventions (grouped sums
        as floats, flat sums typed by the column, empty flat min/max as
        None), then the shared sort/limit tail."""
        rows: List[Dict[str, Any]] = []
        if not q.group_by:
            n = int(self.rows_per_group[0]) if self.keys else 0
            r: Dict[str, Any] = {}
            for a in q.aggs:
                if a.column is None:
                    r[a.alias] = n
                    continue
                # SQL null-skipping: per-column non-null count when tracked
                cn = (int(self.cnts[a.column][0])
                      if a.column in self.cnts and self.keys else n)
                if a.op == "count":
                    r[a.alias] = cn
                elif cn == 0:
                    r[a.alias] = 0 if a.op == "sum" else None
                elif a.op in ("sum", "avg"):
                    # object-dtype partials hold exact Python ints, so type
                    # by the value, not by a (possibly absent) array dtype
                    s = self.sums[a.column][0]
                    if a.op == "avg":
                        r[a.alias] = float(s) / cn
                    else:
                        r[a.alias] = (int(s)
                                      if isinstance(s, (int, np.integer))
                                      else float(s))
                else:
                    src = self.mins if a.op == "min" else self.maxs
                    r[a.alias] = _item(src[a.column][0])
            rows = [r]
        else:
            for g, key in enumerate(self.keys):
                r = dict(zip(q.group_by, key))
                n = int(self.rows_per_group[g])
                for a in q.aggs:
                    if a.column is None:
                        r[a.alias] = n
                        continue
                    # SQL null-skipping: per-group non-null count when
                    # tracked (count(col)/avg/min/max over an all-NULL
                    # group → 0/None/None, matching ScalarEngine)
                    cn = (int(self.cnts[a.column][g])
                          if a.column in self.cnts else n)
                    if a.op == "count":
                        r[a.alias] = cn
                    elif a.op == "sum":
                        r[a.alias] = float(self.sums[a.column][g])
                    elif a.op == "avg":
                        r[a.alias] = (float(self.sums[a.column][g]) / cn
                                      if cn else None)
                    elif cn == 0:
                        r[a.alias] = None
                    else:
                        src = self.mins if a.op == "min" else self.maxs
                        r[a.alias] = _item(src[a.column][g])
                rows.append(r)
        if q.sort_by:
            rows = VectorEngine._sort(rows, q.sort_by)
        if q.limit is not None:
            rows = rows[: q.limit]
        return rows


    # ------------------------------------------------------------- top-k
    def topk(self, q: Query, k: int) -> "GroupedPartial":
        """Limit-aware truncation of a partial heap: keep only the ``k``
        groups that can still reach the final top-k.  Sound because the
        sort columns are group columns (``topk_group_limit``), so a group's
        rank is decided by its key alone and never moves under merge: any
        group in the global top-k is preceded by < k groups globally, hence
        by < k groups inside every shard that contains it.  Ties on the
        sort columns break by the full key tuple — the same deterministic
        order ``VectorEngine``'s stable sort produces over key-sorted
        rows."""
        if not self.group_cols or len(self.keys) <= k:
            return self
        if q.sort_by == self.group_cols[: len(q.sort_by)]:
            keep = list(range(k))       # keys are kept sorted: a leading
                                        # prefix sort is already the order
        else:
            pos = [self.group_cols.index(c) for c in q.sort_by]
            order = sorted(range(len(self.keys)),
                           key=lambda i: (
                               null_last_key(self.keys[i][p] for p in pos),
                               null_last_key(self.keys[i])))
            keep = sorted(order[:k])    # self.keys is sorted: index order
        idx = np.asarray(keep, np.int64)  # == key order inside the heap
        take = lambda d: {c: s[idx] for c, s in d.items()}
        return GroupedPartial(self.group_cols, [self.keys[i] for i in keep],
                              self.rows_per_group[idx], take(self.sums),
                              take(self.mins), take(self.maxs),
                              take(self.cnts))


def topk_group_limit(q: Query) -> Optional[int]:
    """The per-shard partial-heap bound when limit pushdown is sound: a
    grouped query whose sort columns are all group columns (a group's rank
    is fixed before the merge) with an actual limit.  Sorting by an
    aggregate alias — whose value only exists after the full merge — is not
    pushable and returns None (full-merge-then-sort)."""
    if (q.limit is None or not q.group_by or not q.sort_by
            or not set(q.sort_by) <= set(q.group_by)):
        return None
    return int(q.limit)


def _fold(G: int, idx_a: np.ndarray, src_a: np.ndarray, pres_a: np.ndarray,
          idx_b: np.ndarray, src_b: np.ndarray, pres_b: np.ndarray,
          op) -> np.ndarray:
    """Presence-masked elementwise min/max scatter-merge of two partials'
    per-group extrema into the union key layout."""
    out = np.zeros(G, np.result_type(src_a.dtype, src_b.dtype))
    present = np.zeros(G, bool)
    out[idx_a[pres_a]] = src_a[pres_a]
    present[idx_a[pres_a]] = True
    tgt = idx_b[pres_b]
    vals = src_b[pres_b].astype(out.dtype, copy=False)
    out[tgt] = np.where(present[tgt], op(out[tgt], vals), vals)
    present[tgt] = True
    return out


# ---------------------------------------------------------------------------
# The fan-out executor
# ---------------------------------------------------------------------------


class ShardedScanExecutor:
    """Drop-in engine over an ``LSMStore``: range-partitions the baseline
    into ``n_shards`` pk-contiguous shards, scans them concurrently with the
    pushdown pipeline, and tree-reduces per-shard partial aggregates (plus
    one merge-on-read partial for incremental rows) into the same answer
    ``VectorEngine`` gives over a full scan — for any shard count."""

    name = "sharded"

    def __init__(self, n_shards: Optional[int] = None, device: bool = False,
                 engine: Optional[VectorEngine] = None,
                 max_workers: Optional[int] = None,
                 device_route: Optional[str] = None,
                 limit_pushdown: bool = True,
                 max_attempts: int = 3,
                 retry_backoff_s: float = 0.02,
                 hedge: bool = True,
                 breaker: Optional[Dict[str, str]] = None,
                 observe: bool = True):
        # n_shards None == cost-based: the planner picks the fan-out width
        # per query from the estimated surviving-row count (a selective
        # probe stays single-shard, a full scan fans out to the cores).
        # An explicit int pins the width (parity sweeps, scaling benches).
        self.n_shards = n_shards
        self.device = device
        self.engine = engine or VectorEngine()
        self.max_workers = max_workers
        # device_route None == cost-based (cost.choose_device_route);
        # 'collective' pins the single-launch shard_map route, 'host' the
        # per-shard launches + host merge (route benchmarks, parity tests).
        if device_route not in (None, "collective", "host"):
            raise ValueError(f"unknown device_route {device_route!r}")
        self.device_route = device_route
        # limit_pushdown False pins the full-merge-then-sort baseline even
        # for pushable top-k shapes (benchmarks measure the heap win).
        self.limit_pushdown = limit_pushdown
        # Fault-tolerance knobs: transient per-shard failures retry up to
        # max_attempts with exponential backoff; hedge=True re-dispatches
        # the slowest outstanding shard once when it runs past ~p95 of the
        # completed shard times (first finisher wins, merge order is still
        # by shard position so results stay bit-identical).
        self.max_attempts = max(int(max_attempts), 1)
        self.retry_backoff_s = retry_backoff_s
        self.hedge = hedge
        # Circuit-breaker verdicts from the session's HealthRegistry
        # ({rung: "skip" | "probe"}): "skip" pre-degrades a known-bad device
        # rung without attempting it (even past a device_route pin —
        # availability wins over the pin, and the override is recorded in
        # the degradation provenance); "probe" runs the rung normally as a
        # half-open probe.
        self.breaker = breaker or {}
        # observe=False defers the calibration feedback (cost.observe_scan)
        # to the caller — the session's commit step — so execution itself
        # has no shared-state side effects; the estimate rides out on
        # ``stats.estimate`` either way.
        self.observe = observe
        self.last_stats: Optional[ScanStats] = None

    # ------------------------------------------------------------------ API
    def execute(self, store: LSMStore, q: Query,
                ts: Optional[int] = None) -> List[Dict[str, Any]]:
        rows, _ = self.execute_stats(store, q, ts)
        return rows

    def execute_stats(self, store: LSMStore, q: Query,
                      ts: Optional[int] = None, *,
                      deadline_s: Optional[float] = None
                      ) -> Tuple[List[Dict[str, Any]], ScanStats]:
        ts = store.current_ts if ts is None else ts
        stats = ScanStats(used_pushdown=True)
        self.last_stats = stats
        deadline = Deadline.start(deadline_s)
        rmark = _replica.event_mark(store)
        try:
            return self._execute_stats(store, q, ts, stats, deadline)
        finally:
            # per-query repair provenance: every block healed while this
            # query ran (any shard, any route) rides out in stats.repaired
            _replica.collect(store, rmark, stats)

    def _execute_stats(self, store: LSMStore, q: Query, ts: int,
                       stats: ScanStats, deadline: Optional[Deadline]
                       ) -> Tuple[List[Dict[str, Any]], ScanStats]:
        # -- stages 0–1 shared with PushdownExecutor: merge-on-read
        # bookkeeping + global zone-map prune (verdicts sliced per shard)
        needed, over, inc_rows, verdicts = _pd.scan_preamble(
            store, q, ts, stats, deadline=deadline)

        # -- cost model: estimate surviving rows from the sketches, pick
        # the fan-out width and the per-shard scan granularity
        est = cost.estimate_scan(store, q.preds, verdicts)
        stats.est_rows = est.est_rows
        n_shards = (self.n_shards if self.n_shards is not None
                    else cost.choose_shards(est, self.max_workers))
        stats.n_shards = n_shards
        coalesce = cost.choose_coalesce(est, store.baseline.block_rows)
        stats.batch_blocks = coalesce
        shards = range_partition(store.baseline, n_shards)

        if self.device and not inc_rows and not over.size:
            out = self._try_device(store, q, shards, verdicts, stats, est,
                                   deadline)
            if out is not None:
                stats.estimate = est
                if self.observe:
                    cost.observe_scan(store, est, stats.actual_rows)
                return out, stats

        str_aggs = any(store.schema.spec(a.column).ctype == ColType.STR
                       for a in q.aggs if a.column)
        try:
            with spans.span("ob.host_scan"):
                if q.aggs and not str_aggs:
                    rows = self._execute_partials(
                        store, q, needed, shards, verdicts, over, inc_rows,
                        stats, coalesce, deadline)
                else:
                    rows = self._execute_gather(
                        store, q, needed, shards, verdicts, over, inc_rows,
                        stats, coalesce, deadline)
        except (QueryTimeout, BlockCorruption):
            raise                   # deterministic: retrying cannot help
        # lint: allow(broad-except) — degradation-ladder rung: any
        # remaining failure kind funnels into the single-shard fallback
        except Exception as e:
            # Last rung of the degradation ladder: a shard failed even
            # after retries (or the merge itself blew up), so fall back to
            # one unsharded full-decode scan through VectorEngine.  A
            # shard-attributable failure records its id so the health
            # registry opens the per-shard breaker, not the rung's.
            if isinstance(e, ShardFailure) \
                    and e.shard_id not in stats.failed_shards:
                stats.failed_shards.append(e.shard_id)
            stats.degraded.append(
                f"sharded->vectorized: {type(e).__name__}: {e}")
            return self._vectorized_fallback(store, q, ts, stats, e), stats
        stats.estimate = est
        if self.observe:
            cost.observe_scan(store, est, stats.actual_rows)
        return rows, stats

    def _vectorized_fallback(self, store, q, ts, stats, cause
                             ) -> List[Dict[str, Any]]:
        try:
            needed = sorted(VectorEngine.columns_needed(q,
                                                        store.schema.names))
            tbl, _ = store.scan(columns=list(needed), ts=ts)
            return self.engine.execute(tbl, q)
        except (QueryTimeout, BlockCorruption):
            raise
        # lint: allow(broad-except) — ladder floor: whatever failed is
        # wrapped into typed RouteExhausted with the provenance trail
        except Exception as e:
            raise RouteExhausted(stats.degraded, e) from cause

    # -------------------------------------------------- shard scheduling
    def _map_shards(self, fn, shards: Sequence[BlockShard],
                    stats: Optional[ScanStats] = None,
                    deadline: Optional[Deadline] = None) -> List[Any]:
        """Fault-tolerant shard fan-out.

        Each shard runs through a per-shard retry loop (transient errors
        back off exponentially up to ``max_attempts``; corruption and
        timeouts are deterministic and propagate immediately).  The pool
        path completes futures as they finish, enforces the per-query
        deadline with partial-progress accounting, and hedges the slowest
        outstanding shard once when it runs past ~p95 of the completed
        shard times.  Results are indexed by shard *position*, so the
        downstream merge order — and therefore float aggregation — is
        bit-identical whether the primary or the hedge twin wins."""
        active = [s for s in shards if s.n_blocks]
        if not active:
            return []
        if stats is None:
            stats = ScanStats()
        fp = faultinject.active()
        lock = threading.Lock()
        # shards whose primary has failed an attempt: their retry loop owns
        # them, so the hedge (which races stragglers, not failures) skips
        # them — a hedge twin would be an extra attempt outside the retry
        # budget and the per-shard breaker's fail-fast
        failing: set = set()

        def run(shard: BlockShard, attempt: int):
            if fp is not None:
                fp.on_shard_attempt(shard.shard_id, attempt)
            return fn(shard)

        def run_retry(shard: BlockShard):
            # an open per-shard breaker (health.py: ``sharded[<id>]``)
            # fail-fasts this shard to a single attempt with no backoff —
            # the shard still runs (its data cannot be skipped), but a
            # persistently bad shard stops burning the whole retry budget
            attempts = (1 if self.breaker.get(
                f"sharded[{shard.shard_id}]") == "skip"
                else self.max_attempts)
            last: Optional[BaseException] = None
            for attempt in range(attempts):
                if deadline is not None and deadline.expired():
                    raise QueryTimeout(deadline.seconds, deadline.elapsed(),
                                       stats=stats)
                try:
                    return run(shard, attempt)
                except (QueryTimeout, BlockCorruption):
                    raise           # deterministic: a retry cannot help
                # lint: allow(broad-except) — per-shard retry boundary:
                # transient faults arrive untyped; exhausted retries
                # re-raise as typed ShardFailure
                except Exception as e:
                    last = e
                    with lock:
                        failing.add(shard.shard_id)
                    if attempt + 1 >= attempts:
                        break
                    with lock:
                        stats.shard_retries += 1
                    if self.retry_backoff_s:
                        time.sleep(self.retry_backoff_s * (2 ** attempt))
            raise ShardFailure(shard.shard_id, attempts, last)

        def run_hedge(shard: BlockShard):
            # attempt=-1: injected attempt-0 delays/failures must not
            # re-fire on the hedge twin, or hedging could never win
            return run(shard, -1)

        workers = min(len(active),
                      self.max_workers or os.cpu_count() or 1)
        if workers <= 1:
            return [run_retry(s) for s in active]

        results: List[Any] = [_PENDING] * len(active)
        errors: Dict[int, BaseException] = {}
        done_times: List[float] = []
        hedged: Optional[int] = None
        t0 = time.monotonic()
        # one spare slot so the hedge twin never queues behind a straggler
        pool = ThreadPoolExecutor(max_workers=workers + 1)
        try:
            futs = {pool.submit(run_retry, s): i
                    for i, s in enumerate(active)}
            pending = set(futs)
            while any(r is _PENDING for r in results):
                if not pending:
                    # every future resolved yet a slot is unfilled: its
                    # primary (and hedge, if any) both failed
                    raise next(iter(errors.values()))
                timeout = (max(deadline.remaining(), 0.0)
                           if deadline is not None else None)
                if self.hedge and hedged is None and len(done_times) >= 2:
                    # poll so the straggler check below runs periodically
                    timeout = (0.02 if timeout is None
                               else min(timeout, 0.02))
                done, pending = wait(pending, timeout=timeout,
                                     return_when=FIRST_COMPLETED)
                now = time.monotonic()
                for f in done:
                    i = futs[f]
                    exc = f.exception()
                    if results[i] is not _PENDING:
                        continue        # hedge twin already resolved it
                    if exc is None:
                        results[i] = f.result()
                        done_times.append(now - t0)
                        continue
                    if isinstance(exc, (QueryTimeout, BlockCorruption)):
                        raise exc       # deterministic across twins
                    errors.setdefault(i, exc)
                    if any(futs[p] == i for p in pending):
                        continue        # the twin may still rescue it
                    e = errors[i]
                    if not isinstance(e, ShardFailure):
                        e = ShardFailure(active[i].shard_id, 1, e)
                    raise e
                if (deadline is not None and deadline.expired()
                        and any(r is _PENDING for r in results)):
                    n_done = sum(r is not _PENDING for r in results)
                    raise QueryTimeout(deadline.seconds, deadline.elapsed(),
                                       completed=n_done, total=len(active),
                                       stats=stats)
                if (self.hedge and hedged is None and pending
                        and len(done_times) >= 2):
                    p95 = float(np.percentile(done_times, 95))
                    with lock:
                        slow = [futs[p] for p in pending
                                if results[futs[p]] is _PENDING
                                and active[futs[p]].shard_id not in failing]
                    if slow and now - t0 > max(2.0 * p95, p95 + 0.05):
                        # all primaries started together, so every
                        # outstanding shard that has not failed is a
                        # straggler; re-dispatch the lowest position for
                        # determinism
                        i = min(slow)
                        hf = pool.submit(run_hedge, active[i])
                        futs[hf] = i
                        pending.add(hf)
                        hedged = i
                        with lock:
                            stats.hedges += 1
            return results
        finally:
            # wait=False: a straggler sleeping in an injected delay must
            # not block the query that already has its answer
            pool.shutdown(wait=False, cancel_futures=True)

    # ------------------------------------------------- partial-agg path
    def _execute_partials(self, store, q, needed, shards, verdicts, over,
                          inc_rows, stats, coalesce=1, deadline=None
                          ) -> List[Dict[str, Any]]:
        mat_cols = sorted(set(q.group_by)
                          | {a.column for a in q.aggs if a.column})
        flat = not q.group_by            # group-less: sketches can answer
                                         # clean blocks without decoding
        k = topk_group_limit(q) if self.limit_pushdown else None
        stats.topk_pushdown = k is not None
        # leading-prefix sorts skip straight to a k-group partial inside
        # the per-shard aggregation (discarded groups never accumulate)
        prefix_k = (k if k is not None
                    and q.sort_by == tuple(q.group_by)[: len(q.sort_by)]
                    else None)

        def scan_shard(shard: BlockShard):
            sstats = ScanStats()
            sketch = _pd._SketchAgg(q) if flat else None
            filtered = _pd.filter_blocks(store, q, needed, verdicts, over,
                                         shard.block_ids(), sstats, sketch,
                                         coalesce, deadline=deadline)
            cols, masks = _pd.PushdownExecutor._materialize(
                store, mat_cols, filtered, (), with_nulls=True)
            n = sum(fb.n_selected for fb in filtered)
            sstats.actual_rows = n + (sketch.n_rows if sketch else 0)
            partial = GroupedPartial.from_columns(q, cols, n, masks,
                                                  topk_prefix=prefix_k)
            if sketch is not None and sketch.n_rows:
                partial = GroupedPartial.merge(
                    partial, _sketch_to_partial(q, sketch))
            if k is not None:            # per-shard partial heap
                partial = partial.topk(q, k)
            return partial, sstats

        results = self._map_shards(scan_shard, shards, stats, deadline)
        partials = [p for p, _ in results]
        for _, sstats in results:
            stats.absorb(sstats)
        if inc_rows:
            cols, masks = _rows_to_columns(store, mat_cols, inc_rows)
            inc_part = GroupedPartial.from_columns(q, cols, len(inc_rows),
                                                   masks,
                                                   topk_prefix=prefix_k)
            partials.append(inc_part if k is None else inc_part.topk(q, k))
        if not partials:                 # empty baseline, no increments
            cols, masks = _rows_to_columns(store, mat_cols, [])
            partials = [GroupedPartial.from_columns(q, cols, 0)]
        combine = (GroupedPartial.merge if k is None else
                   lambda a, b: GroupedPartial.merge(a, b).topk(q, k))
        merged = tree_reduce(partials, combine)
        return merged.finalize(q)

    # ---------------------------------------------- gather (projection)
    def _execute_gather(self, store, q, needed, shards, verdicts, over,
                        inc_rows, stats, coalesce=1, deadline=None
                        ) -> List[Dict[str, Any]]:
        # Projection top-k pushdown: with sort columns materialized per
        # shard, each shard keeps only its limit-first rows (stable order
        # preserved, so cross-shard ties break exactly as the unsharded
        # stable sort would by original row position).
        k = (q.limit if q.limit is not None and not q.aggs and q.sort_by
             and set(q.sort_by) <= set(needed) and self.limit_pushdown
             else None)
        if k is not None:
            stats.topk_pushdown = True

        def scan_shard(shard: BlockShard):
            sstats = ScanStats()
            filtered = _pd.filter_blocks(store, q, needed, verdicts, over,
                                         shard.block_ids(), sstats, None,
                                         coalesce, deadline=deadline)
            cols, masks = _pd.PushdownExecutor._materialize(
                store, needed, filtered, (), with_nulls=True)
            n = sum(fb.n_selected for fb in filtered)
            sstats.actual_rows = n
            if k is not None and n > k:
                cols, masks, n = _topk_rows(cols, masks, n, q.sort_by, k)
            return cols, masks, n, sstats

        results = self._map_shards(scan_shard, shards, stats, deadline)
        for _, _, _, sstats in results:
            stats.absorb(sstats)
        parts = {name: [c[name] for c, _, n, _ in results if n]
                 for name in needed}
        nparts = {name: [m[name] for _, m, n, _ in results if n]
                  for name in needed}
        cols, masks = _pd.assemble_columns(store, needed, parts, inc_rows,
                                           nparts)
        n_rows = sum(n for _, _, n, _ in results) + len(inc_rows)
        return self.engine.finalize(q, lambda nm: cols[nm], n_rows,
                                    store.schema.names,
                                    nulls=lambda nm: masks[nm])

    # ------------------------------------------------------- device path
    def _try_device(self, store, q, shards, verdicts, stats, est=None,
                    deadline=None) -> Optional[List[Dict[str, Any]]]:
        """Stage the fused-kernel inputs once and fan the kernel out over
        the per-shard block slices, on the route the cost model picks (or
        ``self.device_route`` pins):

        * **collective** — pad the shard slices to a common tile shape and
          hand ONE batched ``shard_map`` launch to
          ``ops.sharded_scan_agg``; each 'scan'-mesh device runs the fused
          kernel over its shard slice and the per-group partials
          tree-reduce on device (psum/pmin/pmax), so the host receives one
          already-merged accumulator — and, for pushable top-k shapes,
          only its first ``limit`` non-empty groups.
        * **host** — the legacy per-shard kernel launches (round-robin
          device placement, async dispatch) with a host-side tree-merge:
          counts/sums add, mins/maxs fold — the same combination rule as
          ``GroupedPartial.merge``.

        Either route launches with the cost-model tile height (blocks fused
        per grid step) chosen from the selectivity estimate.

        Self-healing (PR 7): the deadline is checked before staging and
        between per-shard launches so ``deadline_s`` binds on the device
        paths; a transient collective failure retries the collective once
        in-route (``stats.kernel_retries``) before the rung drops; and an
        open circuit breaker from the session's health registry
        pre-degrades a known-bad rung without attempting it."""
        if self.breaker.get("per-shard-device") == "skip" \
                and (self.breaker.get("device-collective") == "skip"
                     or self.device_route == "host"):
            # both device rungs this executor could run are known-bad (or
            # the collective one is pinned away): skip staging entirely
            stats.degraded.append(cost.breaker_note(
                "per-shard-device", "skip",
                "pre-degraded to host-pushdown fan-out"))
            return None
        if deadline is not None:
            deadline.check(stats)
        plan = _pd.plan_device(store, q)
        if plan is None:
            return None
        if store.baseline.n_blocks == 0:
            return []
        stage = _pd.stage_device(store, plan)
        if stage is None:
            return None
        block_mask = verdicts != Verdict.NONE.value
        stats.blocks_skipped = int((~block_mask).sum())
        stats.blocks_scanned = int(block_mask.sum())
        stats.used_device = True
        tile = (cost.choose_device_tile(est, store.baseline.block_rows)
                if est is not None else 1)
        stats.device_tile_blocks = tile
        from ..kernels import ops
        from ..launch.mesh import make_scan_mesh, scan_shard_devices
        active = [s for s in shards if s.n_blocks]
        mesh = make_scan_mesh(len(active))
        stats.n_devices = int(mesh.devices.size)
        route = self.device_route or cost.choose_device_route(
            est, stats.n_devices, len(active))
        if route == "collective":
            verdict = self.breaker.get("device-collective")
            if verdict == "skip":
                # open breaker: pre-degrade the collective rung without
                # attempting it — even past a device_route pin
                # (availability over pin), recorded in the provenance
                stats.degraded.append(cost.breaker_note(
                    "device-collective", "skip",
                    "pre-degraded to per-shard-device"))
                route = "host"
            elif verdict == "probe":
                stats.degraded.append(cost.breaker_note(
                    "device-collective", "probe",
                    "attempting collective route"))
        stats.device_route = route
        fp = faultinject.active()
        out = None
        if route == "collective":
            # In-route retry: one transient collective failure relaunches
            # the collective before the rung drops (the first launch may
            # have failed on a transient — a second failure is treated as
            # persistent and degrades as before).
            for rattempt in range(2):
                try:
                    if fp is not None:
                        fp.on_kernel_launch("collective")
                    out = self._device_collective(q, plan, stage, active,
                                                  block_mask, mesh, tile,
                                                  stats, ops)
                    break
                except KernelLaunchError as e:
                    if rattempt == 0:
                        stats.kernel_retries += 1
                        if deadline is not None:
                            deadline.check(stats)
                        continue
                    # rung 1: the collective failed twice — fall back to
                    # per-shard device launches with a host-side merge
                    stats.degraded.append(
                        "device-collective->per-shard-device: "
                        f"{type(e).__name__}: {e}")
                    stats.device_route = route = "host"
        if out is None:
            verdict = self.breaker.get("per-shard-device")
            if verdict == "skip":
                stats.degraded.append(cost.breaker_note(
                    "per-shard-device", "skip",
                    "pre-degraded to host-pushdown fan-out"))
                stats.used_device = False
                stats.device_route = ""
                stats.blocks_skipped = 0
                stats.blocks_scanned = 0
                stats.n_devices = 0
                return None
            if verdict == "probe":
                stats.degraded.append(cost.breaker_note(
                    "per-shard-device", "probe",
                    "attempting per-shard launches"))
            try:
                devices = scan_shard_devices(len(shards), mesh)
                launched = launch_shard_kernels(plan, stage, active,
                                                block_mask, devices, tile,
                                                deadline=deadline,
                                                stats=stats)
                partials = [tuple(np.asarray(x) for x in o)
                            for o in launched]
                out = tree_reduce(partials, device_partial_combine) + (None,)
            except KernelLaunchError as e:
                # rung 2: per-shard kernel launches failed too — undo the
                # device accounting (the host pushdown path re-counts with
                # += as it scans) and hand the query back to the caller
                stats.degraded.append(
                    "per-shard-device->host-pushdown: "
                    f"{type(e).__name__}: {e}")
                stats.used_device = False
                stats.device_route = ""
                stats.blocks_skipped = 0
                stats.blocks_scanned = 0
                stats.n_devices = 0
                return None
        g_cnt, g_sums, g_mins, g_maxs, g_ids = out
        if g_ids is None:          # top-k-sliced runs record total already
            stats.actual_rows = int(np.asarray(g_cnt).sum())
        return _pd.emit_device_groups(q, plan, stage, np.asarray(g_cnt),
                                      np.asarray(g_sums, np.float64),
                                      np.asarray(g_mins),
                                      np.asarray(g_maxs), group_ids=g_ids)

    def _device_collective(self, q, plan, stage, active, block_mask, mesh,
                           tile, stats, ops):
        """Stack the per-shard staged slices into one [S, Nb, ...] launch
        batch and run the single-launch collective fan-out."""
        (deltas, bases, counts, codes, values, bmask), tile = \
            stack_device_stage(stage, active, block_mask, mesh, tile)
        stats.device_tile_blocks = tile
        k = topk_group_limit(q) if self.limit_pushdown else None
        if k is not None and q.sort_by != plan.group_cols[: len(q.sort_by)]:
            k = None          # packed order is lexicographic over the key
                              # columns in order: only prefix sorts slice
        stats.topk_pushdown = k is not None
        out = _pd.run_device_kernel(
            "collective", ops.sharded_scan_agg, deltas, bases, counts,
            plan.lo, plan.hi, codes, values, bmask, stats=stats,
            ndv=stage.ndv, mesh=mesh, coalesce=tile, topk=k or 0)
        if k is not None:
            g_ids, g_cnt, g_sums, g_mins, g_maxs, total = out
            stats.actual_rows = int(total)
            return (np.asarray(g_cnt), np.asarray(g_sums),
                    np.asarray(g_mins), np.asarray(g_maxs),
                    np.asarray(g_ids))
        g_cnt, g_sums, g_mins, g_maxs = out
        return (np.asarray(g_cnt), np.asarray(g_sums), np.asarray(g_mins),
                np.asarray(g_maxs), None)


def _sketch_to_partial(q: Query, sk: "_pd._SketchAgg") -> GroupedPartial:
    """Lift the flat partials a shard absorbed from clean-block sketches
    (verdict-ALL, never decoded) into a ``GroupedPartial`` so they merge
    with the shard's scanned rows.  ``_SketchAgg.absorb`` only accepts
    blocks whose sketches answer every aggregate the query needs, so each
    requested stat is present whenever non-null rows were absorbed; the
    sketch counts are already null-excluded (SQL count(col))."""
    need_sum = {a.column for a in q.aggs if a.op in ("sum", "avg")}
    need_min = {a.column for a in q.aggs if a.op == "min"}
    need_max = {a.column for a in q.aggs if a.op == "max"}
    agg_cols = sorted({a.column for a in q.aggs if a.column})
    # object dtype keeps integer sketch sums as exact Python ints through
    # the merge tree (int64 coercion would wrap the very sums Sketch.of
    # computes exactly); float sketch sums ride along unchanged
    sums = {c: np.asarray([sk.vsum.get(c, 0)], dtype=object)
            for c in sorted(need_sum) if c is not None}
    mins = {c: np.asarray([sk.vmin.get(c, 0)])
            for c in sorted(need_min) if c}
    maxs = {c: np.asarray([sk.vmax.get(c, 0)])
            for c in sorted(need_max) if c}
    cnts = {c: np.asarray([sk.cnt.get(c, 0)], np.int64) for c in agg_cols}
    return GroupedPartial((), [()], np.asarray([sk.n_rows], np.int64),
                          sums, mins, maxs, cnts)


def stack_device_stage(stage, shards: Sequence[BlockShard],
                       block_mask: np.ndarray, mesh, tile: int = 1):
    """Stack per-shard slices of a ``DeviceStage`` into the collective
    launch batch: [S, Nb, ...] arrays with the shard count padded to a
    multiple of the mesh size and block counts padded to the widest shard
    (padding blocks are zero-count and masked off).  Returns
    ((deltas, bases, counts, codes, values, block_mask), tile) with the
    tile factor clamped to a divisor of the padded width — tile fusing
    must never span a shard boundary, or padding blocks in the middle of a
    tile would break the kernel's valid-rows-prefix invariant.  Shared by
    ``ShardedScanExecutor._device_collective`` and the route benchmark."""
    from ..launch.mesh import scan_launch_shape
    with spans.span("ob.stack"):
        _, S = scan_launch_shape(len(shards), mesh)
        nbp = max(s.n_blocks for s in shards)
        bk = stage.deltas.shape[1]
        K, V = stage.codes.shape[1], stage.values.shape[1]
        out = (np.zeros((S, nbp, bk), np.int32),
               np.zeros((S, nbp), np.int32),
               np.zeros((S, nbp), np.int32),
               np.zeros((S, nbp, K, bk), np.int32),
               np.zeros((S, nbp, V, bk), np.float32),
               np.zeros((S, nbp), bool))
        srcs = (stage.deltas, stage.bases, stage.counts, stage.codes,
                stage.values, block_mask)
        for i, s in enumerate(shards):
            sl = slice(s.lo_block, s.hi_block)
            for dst, src in zip(out, srcs):
                dst[i, : s.n_blocks] = src[sl]
        tile = max(int(tile), 1)
        while nbp % tile:
            tile -= 1
        return out, tile


def launch_shard_kernels(plan, stage, shards: Sequence[BlockShard],
                         block_mask: np.ndarray, devices, tile: int = 1,
                         deadline=None, stats=None):
    """Per-shard-launch device route: dispatch the fused kernel for every
    shard's block slice (round-robin placement by shard id) and return the
    raw per-shard outputs.  Every shard's launch is compiled first, so a
    compile error fails the query before anything runs; then every kernel
    is dispatched before any result is blocked on — jax dispatch is async,
    so on a multi-device mesh the shards overlap — and a runtime fault of
    any of them raises ``KernelLaunchError``.  The per-query ``deadline``
    is checked between launches so ``deadline_s`` binds on this route too.
    Shared by ``ShardedScanExecutor._try_device`` and the route benchmark,
    so the bench always measures the loop the engine runs."""
    import jax
    from ..kernels import ops
    fp = faultinject.active()
    launches = []
    for shard in shards:
        sl = slice(shard.lo_block, shard.hi_block)
        dev = devices[shard.shard_id % len(devices)]
        ins = [stage.deltas[sl], stage.bases[sl], stage.counts[sl], plan.lo,
               plan.hi, stage.codes[sl], stage.values[sl], block_mask[sl]]
        if dev is not None:
            ins = [x if np.isscalar(x) else jax.device_put(x, dev)
                   for x in ins]
        launches.append((_pd.compile_device_kernel(
            ops.fused_scan_agg, *ins, ndv=stage.ndv, coalesce=tile), ins))
    outs = []
    for exe, ins in launches:
        if deadline is not None:
            deadline.check(stats, completed=len(outs), total=len(shards))
        if fp is not None:
            fp.on_kernel_launch("host")
        outs.append(_pd.dispatch_device_kernel("host", exe, *ins,
                                               stats=stats))
    return _pd.await_device_kernels("host", outs)


def device_partial_combine(a, b):
    """Host-merge rule for per-shard device partials — the same
    combination ``GroupedPartial.merge`` applies: counts/sums add,
    mins/maxs fold."""
    return (a[0] + b[0], a[1] + b[1],
            np.minimum(a[2], b[2]), np.maximum(a[3], b[3]))


def _topk_rows(cols: Dict[str, np.ndarray],
               masks: Dict[str, Optional[np.ndarray]], n: int,
               sort_by: Tuple[str, ...], k: int
               ) -> Tuple[Dict[str, np.ndarray],
                          Dict[str, Optional[np.ndarray]], int]:
    """Keep one shard's ``k`` sort-first rows, in original row order (the
    final stable sort then breaks cross-shard ties by position exactly as
    it would have over the untruncated concatenation).  Rows with NULL sort
    keys have no defined rank — such shards stay untruncated.

    Packable int keys take an O(n) ``argpartition`` pre-select instead of
    a full O(n log n) sort: every row whose key <= the k-th partitioned
    key is a candidate (ties included, so the position-stable tie-break is
    exact), and only the candidates are stably sorted."""
    if any(masks.get(c) is not None for c in sort_by):
        return cols, masks, n
    keys = [np.asarray(cols[c]) for c in sort_by]
    keep = None
    try:
        if all(np.issubdtype(c.dtype, np.integer) for c in keys):
            packed = pack_sort_keys(keys)
            if n > 4 * k:
                thresh = packed[np.argpartition(packed, k - 1)[:k]].max()
                cand = np.nonzero(packed <= thresh)[0]   # position order
                order = np.argsort(packed[cand], kind="stable")
                keep = np.sort(cand[order[:k]])
            else:
                keep = np.sort(np.argsort(packed, kind="stable")[:k])
    except KeyPackError:
        pass
    if keep is None:
        keep = np.sort(np.lexsort(list(reversed(keys)))[:k])
    return ({c: v[keep] for c, v in cols.items()},
            {c: (None if m is None else m[keep])
             for c, m in masks.items()}, int(keep.shape[0]))


def _rows_to_columns(store: LSMStore, names: Sequence[str],
                     rows: Sequence[Dict[str, Any]]
                     ) -> Tuple[Dict[str, np.ndarray],
                                Dict[str, Optional[np.ndarray]]]:
    """Batch merge-on-read incremental rows into schema-typed column arrays
    plus NULL masks (the row-format block the partial aggregator
    consumes)."""
    cols: Dict[str, np.ndarray] = {}
    masks: Dict[str, Optional[np.ndarray]] = {}
    for name in names:
        spec = store.schema.spec(name)
        col = Column.from_values(spec, [r[name] for r in rows])
        vals = col.values
        if spec.ctype == ColType.STR and vals.dtype.kind != "S":
            vals = vals.astype(np.bytes_)
        cols[name] = vals
        masks[name] = col.nulls
    return cols, masks
