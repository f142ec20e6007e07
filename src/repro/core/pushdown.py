"""Block-pushdown query executor (paper §III-F/G "query without
decompression" + §V-B vectorization).

Runs a ``Query`` directly over the LSM store's encoded ``ColumnBlock``s
instead of a fully-decoded table.  The operator pipeline is:

    block scan  →  zone-map prune  →  encoded-domain filter
                →  late materialization  →  aggregate / project

* **prune** — per-block ALL/SOME/NONE verdicts from the hierarchical
  ``SkippingIndex`` (conjunction over all predicates).  NONE blocks are never
  touched again; their encoded payload is never even looked at.
* **sketch answer** — for flat (group-less) aggregates, verdict-ALL blocks
  with null-free sketches are answered entirely from the per-block sketch
  (count/sum/min/max), i.e. the block is neither decoded nor DMA'd —
  multi-granularity pre-aggregation.
* **encoded filter** — surviving SOME blocks evaluate predicates in the
  encoded domain via ``EncodedColumn.eval_pred`` (FOR offsets, dictionary
  codes, prefix short-circuit), falling back to decode+eval only when the
  encoding cannot answer.
* **late materialization** — only the rows that survive the filter are
  decoded, and only for the columns the query actually outputs
  (``decode_idx`` gather).  ``BatchAttrs`` are propagated per block so clean
  blocks (``all_active``, no nulls) skip mask handling entirely.
* **merge-on-read** — incremental (row format) versions are filtered
  row-at-a-time and appended; baseline rows overridden by newer incremental
  versions are excluded from their blocks, so results are identical to
  ``VectorEngine`` over a full ``store.scan()``.

* **adaptive granularity** — before any block is touched, the cost model
  (``core/cost.py``) estimates per-query selectivity from the skipping-index
  sketches and chooses the scan granularity: full/dense scans fuse adjacent
  candidate blocks into large vector batches (one selection per
  ``TARGET_BATCH_ROWS``-sized batch), selective scans keep single-block
  batches, and a lone range predicate over a sorted block drops to
  *sub-block* granularity (a binary-searched row window instead of a
  full-lane compare).  ``PushdownExecutor(granularity=k)`` pins the legacy
  fixed behaviour (k = 1 == block-at-a-time) for sweeps and benchmarks.

The terminal stages (group-by, sort, limit, projection emission) are shared
with ``VectorEngine`` (``finalize``), so the two engines agree bit-for-bit;
only the scan→filter→materialize front end differs.  NULL bitmaps ride
along from the baseline (``BlockView.nulls``) so predicates and flat
aggregates follow SQL NULL semantics (count(col)/sum/min/max skip NULLs,
count(*) does not) — identical to the sketches' null-excluded stats.  An
optional device path routes the supported query shape (an optional range
predicate over FOR/plain int blocks + a 1–3-column group-by over int and/or
dictionary string keys + numeric aggregates over up to four value columns)
through the fused Pallas kernel ``kernels/fused_scan_agg.py``, launched
with cost-chosen tile shapes; the mesh-sharded fan-out in
``core/partition.py`` reuses ``filter_blocks`` / ``stage_device`` here to
run the same pipeline per shard and tree-reduce partials.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from . import cost
from . import faultinject
from . import spans
from .encoding import DeltaFOREncoded, DictEncoded, PlainEncoded
from .engine import Query, VectorEngine, _item
from .errors import (BlockCorruption, Deadline, KernelLaunchError,
                     QueryTimeout)
from .lsm import BlockView, LSMStore, ScanStats, eval_block_pred
from .replica import (collect as _collect_repairs,
                      event_mark as _repair_mark)
from .relation import ColType, Column, PredOp
from .skipping import Sketch, Verdict

#: Kernel tiles per deadline-bounded launch chunk: with an active deadline
#: a long fused scan splits into ``tile * this`` -block launches with a
#: deadline check between them, so ``deadline_s`` binds inside the scan.
DEADLINE_CHUNK_TILES = 8


@dataclasses.dataclass
class _FilteredBlock:
    """One vector batch that survived pruning: one or more candidate blocks
    fused by the granularity planner (``cost.choose_coalesce``), with a
    batch-level selection vector over the concatenated rows."""

    views: List[BlockView]
    sel: Optional[np.ndarray]     # batch row positions kept; None == all rows

    @property
    def nrows(self) -> int:
        return sum(v.nrows for v in self.views)

    @property
    def n_selected(self) -> int:
        return self.nrows if self.sel is None else int(self.sel.shape[0])


class _SketchAgg:
    """Partial flat aggregates absorbed from verdict-ALL block sketches."""

    def __init__(self, q: Query):
        self.q = q
        self.n_rows = 0
        self.cnt: Dict[str, int] = {}
        self.vsum: Dict[str, Any] = {}
        self.vmin: Dict[str, Any] = {}
        self.vmax: Dict[str, Any] = {}
        self._cols = {a.column for a in q.aggs if a.column}

    def absorb(self, view: BlockView) -> bool:
        """Fold one clean (verdict-ALL, no exclusions) block's sketches into
        the partials.  Returns False — absorbing nothing — when any needed
        sketch cannot answer (no sum for a sum/avg, no bounds despite
        non-null rows).  Sketch stats already exclude NULL slots, so
        count(col) absorbs ``count - null_count`` while count(*) keeps every
        row — the same SQL convention the scan side now follows."""
        sketches: Dict[str, Sketch] = {}
        for a in self.q.aggs:
            if a.column is None:
                continue
            s = view.sketches[a.column]
            nn = s.count - s.null_count
            if a.op in ("sum", "avg") and nn and s.vsum is None:
                return False
            if nn and s.vmin is None:
                return False
            sketches[a.column] = s
        for col, s in sketches.items():
            self.cnt[col] = self.cnt.get(col, 0) + (s.count - s.null_count)
            if s.vsum is not None:
                self.vsum[col] = self.vsum.get(col, 0) + s.vsum
            if s.vmin is not None:
                self.vmin[col] = (s.vmin if col not in self.vmin
                                  else min(self.vmin[col], s.vmin))
                self.vmax[col] = (s.vmax if col not in self.vmax
                                  else max(self.vmax[col], s.vmax))
        self.n_rows += view.nrows
        return True


def scan_preamble(store: LSMStore, q: Query, ts: int, stats: ScanStats,
                  deadline: Optional[Deadline] = None
                  ) -> Tuple[List[str], np.ndarray, List[Dict[str, Any]],
                             np.ndarray]:
    """Stages 0–1, shared by the single-shard executor and the sharded
    fan-out: merge-on-read bookkeeping (incremental versions, overridden
    baseline rows, vectorized live-row filter) and the zone-map prune.
    The per-query ``deadline`` threads into the live-row filter so
    write-heavy scans (large incremental sets) respect ``deadline_s``
    inside merge-on-read assembly too.  Returns (needed columns,
    overridden row ids, live incremental rows, per-block verdicts)."""
    base = store.baseline
    with spans.span("ob.preamble"):
        needed = sorted(VectorEngine.columns_needed(q, store.schema.names))
        inc = store._incremental_effective(ts)
        stats.rows_merged_incremental = len(inc)
        if deadline is not None:
            deadline.check(stats)
        over = np.asarray(sorted(i for i in (base.locate(pk) for pk in inc)
                                 if i >= 0), np.int64)
        inc_rows = store.live_incremental_rows(inc, q.preds,
                                               deadline=deadline)
        stats.blocks_total = base.n_blocks
        verdicts = cost.prune_verdicts(store, q.preds)
        return needed, over, inc_rows, verdicts


def assemble_columns(store: LSMStore, needed: Sequence[str],
                     parts: Dict[str, List[np.ndarray]],
                     inc_rows: Sequence[Dict[str, Any]],
                     nparts: Optional[Dict[str, List[Optional[np.ndarray]]]]
                     = None
                     ) -> Tuple[Dict[str, np.ndarray],
                                Dict[str, Optional[np.ndarray]]]:
    """Concatenate per-column value chunks (block decodes or shard outputs),
    append the merge-on-read incremental rows, and fall back to typed empty
    arrays for columns with no surviving data.  Returns (values, NULL masks);
    a column's mask is None when no chunk carries NULLs.  ``nparts`` aligns
    with ``parts`` chunk-for-chunk (None entries == null-free chunks)."""
    cols: Dict[str, np.ndarray] = {}
    masks: Dict[str, Optional[np.ndarray]] = {}
    for name in needed:
        chunks = list(parts.get(name, ()))
        nchunks = (list(nparts.get(name, ())) if nparts is not None
                   else [None] * len(chunks))
        if inc_rows:
            spec = store.schema.spec(name)
            inc_col = Column.from_values(spec, [r[name] for r in inc_rows])
            vals = inc_col.values
            if chunks and vals.dtype != chunks[0].dtype \
                    and spec.ctype != ColType.STR:
                vals = vals.astype(chunks[0].dtype)
            chunks.append(vals)
            nchunks.append(inc_col.nulls)
        if chunks:
            cols[name] = (np.concatenate(chunks) if len(chunks) > 1
                          else chunks[0])
            if any(m is not None and m.any() for m in nchunks):
                masks[name] = np.concatenate(
                    [np.zeros(c.shape[0], bool) if m is None else m
                     for c, m in zip(chunks, nchunks)])
            else:
                masks[name] = None
        else:
            spec = store.schema.spec(name)
            cols[name] = np.empty(
                (0,), dtype=spec.ctype.np_dtype
                if spec.ctype != ColType.STR else "S1")
            masks[name] = None
    return cols, masks


def filter_blocks(store: LSMStore, q: Query, needed: Sequence[str],
                  verdicts: np.ndarray, over: np.ndarray,
                  block_ids: Iterable[int], stats: ScanStats,
                  sketch: Optional[_SketchAgg] = None,
                  coalesce: int = 1,
                  sub_block: bool = True,
                  deadline: Optional[Deadline] = None
                  ) -> List["_FilteredBlock"]:
    """Stage 2 of the pushdown pipeline over an arbitrary block subset:
    zone-map verdict dispatch, null-aware encoded-domain predicate
    evaluation, merge-on-read exclusion of overridden baseline rows.
    ``coalesce`` is the planner-chosen scan granularity: up to that many
    surviving blocks fuse into one ``_FilteredBlock`` vector batch, sharing
    a single selection vector (one ``nonzero`` + one gather per batch
    instead of per block).  Shared by the single-shard executor (all
    blocks) and the sharded fan-out (one contiguous block range per shard,
    each with its own ``stats``)."""
    base = store.baseline
    filtered: List[_FilteredBlock] = []
    pend_views: List[BlockView] = []
    # pend entries: None (all rows), a bool mask, or an (lo, hi) row window
    # from the sub-block sorted fast path
    pend_masks: List[Any] = []

    def flush():
        if not pend_views:
            return
        views, masks = list(pend_views), list(pend_masks)
        pend_views.clear()
        pend_masks.clear()
        if all(m is None for m in masks):
            filtered.append(_FilteredBlock(views, None))
            return
        if any(isinstance(m, tuple) for m in masks):
            parts, off = [], 0
            for v, m in zip(views, masks):
                if m is None:
                    parts.append(np.arange(off, off + v.nrows))
                elif isinstance(m, tuple):
                    parts.append(np.arange(off + m[0], off + m[1]))
                else:
                    parts.append(np.nonzero(m)[0] + off)
                off += v.nrows
            sel = (np.concatenate(parts) if len(parts) > 1 else parts[0])
        else:
            full = [np.ones(v.nrows, bool) if m is None else m
                    for v, m in zip(views, masks)]
            sel = np.nonzero(np.concatenate(full) if len(full) > 1
                             else full[0])[0]
        if sel.size:
            filtered.append(_FilteredBlock(views, sel))

    # iterate candidate blocks only: pruned blocks are counted wholesale,
    # never visited (a selective scan over many small blocks must not pay
    # a Python iteration per skipped block)
    ids = np.asarray(block_ids if not isinstance(block_ids, range)
                     else np.arange(block_ids.start, block_ids.stop),
                     dtype=np.int64)
    live = ids[verdicts[ids] != Verdict.NONE.value] if ids.size else ids
    stats.blocks_skipped += int(ids.size - live.size)
    # sub-block granularity: a lone range predicate over a sorted block is
    # answered by a binary-searched row window (adaptive mode only — pinned
    # granularity stays block-at-a-time, the sweep baseline)
    single_pred = (q.preds[0] if sub_block and len(q.preds) == 1 else None)
    for b in live:
        if deadline is not None and deadline.expired():
            raise QueryTimeout(deadline.seconds, deadline.elapsed(),
                               stats=stats)
        b = int(b)
        lo, hi = base.block_bounds(b)
        excl = over[(over >= lo) & (over < hi)] - lo if over.size else None
        clean = verdicts[b] == Verdict.ALL.value and (
            excl is None or excl.size == 0)
        view = base.block_view(b, needed)
        if clean:
            if sketch is not None and sketch.absorb(view):
                stats.blocks_sketch_only += 1
                continue
            stats.blocks_sketch_only += 1 if q.preds else 0
            pend_views.append(view)
            pend_masks.append(None)
        else:
            stats.blocks_scanned += 1
            mask: Any = None
            if verdicts[b] != Verdict.ALL.value:
                window = None
                if single_pred is not None \
                        and view.nulls.get(single_pred.column) is None \
                        and (excl is None or excl.size == 0):
                    window = view.encoded[single_pred.column].pred_window(
                        single_pred)
                if window is not None:
                    wlo, whi = window
                    if whi <= wlo:
                        continue
                    mask = None if (wlo == 0 and whi == view.nrows) \
                        else window
                else:
                    for p in q.preds:
                        m = eval_block_pred(store.schema.spec(p.column),
                                            view.encoded[p.column], p,
                                            view.nulls.get(p.column))
                        mask = m if mask is None else (mask & m)
            if excl is not None and excl.size:
                if mask is None:
                    mask = np.ones(view.nrows, bool)
                else:
                    mask = mask.copy()
                mask[excl] = False
            if isinstance(mask, np.ndarray) and not mask.any():
                continue
            pend_views.append(view)
            pend_masks.append(mask)
        if len(pend_views) >= max(coalesce, 1):
            flush()
    flush()
    return filtered


class PushdownExecutor:
    """Drop-in engine over an ``LSMStore``: same results as ``VectorEngine``
    over ``store.scan()``, without ever fully decoding the baseline."""

    name = "pushdown"

    def __init__(self, engine: Optional[VectorEngine] = None,
                 device: bool = False,
                 granularity: Optional[int] = None,
                 breaker: Optional[Dict[str, str]] = None,
                 observe: bool = True):
        self.engine = engine or VectorEngine()
        self.device = device
        # observe=False defers the calibration feedback (cost.observe_scan)
        # to the caller: the session's commit step does it once per query,
        # keeping execution itself free of shared-state side effects.  The
        # planned estimate always rides out on ``stats.estimate``.
        self.observe = observe
        # granularity None == selectivity-adaptive (cost model chooses the
        # blocks-per-batch coalescing and the device tile shape per query);
        # an explicit int pins the coalescing factor (1 == legacy
        # block-at-a-time, used by the granularity-sweep benchmarks).
        self.granularity = granularity
        # Circuit-breaker verdicts from the session's HealthRegistry:
        # {"device": "skip"} pre-degrades the device kernel rung without
        # attempting it; "probe" runs it normally as a half-open probe.
        self.breaker = breaker or {}
        self.last_stats: Optional[ScanStats] = None

    # ------------------------------------------------------------------ API
    def execute(self, store: LSMStore, q: Query,
                ts: Optional[int] = None) -> List[Dict[str, Any]]:
        rows, stats = self.execute_stats(store, q, ts)
        return rows

    def execute_stats(self, store: LSMStore, q: Query,
                      ts: Optional[int] = None, *,
                      deadline_s: Optional[float] = None
                      ) -> Tuple[List[Dict[str, Any]], ScanStats]:
        ts = store.current_ts if ts is None else ts
        stats = ScanStats(used_pushdown=True)
        self.last_stats = stats
        deadline = Deadline.start(deadline_s)
        rmark = _repair_mark(store)
        try:
            return self._execute_stats(store, q, ts, stats, deadline)
        finally:
            # per-query repair provenance: blocks healed during this query
            _collect_repairs(store, rmark, stats)

    def _execute_stats(self, store: LSMStore, q: Query, ts: int,
                       stats: ScanStats, deadline: Optional[Deadline]
                       ) -> Tuple[List[Dict[str, Any]], ScanStats]:
        # -- stages 0–1: merge-on-read bookkeeping + zone-map prune ------
        needed, over, inc_rows, verdicts = scan_preamble(store, q, ts, stats,
                                                         deadline=deadline)
        nb = store.baseline.n_blocks

        # -- pre-scan cost model: estimate selectivity from the sketches,
        # choose the scan granularity (blocks fused per vector batch);
        # pinned-granularity executors skip planning entirely
        adaptive = self.granularity is None
        est = None
        if adaptive or self.device:
            est = cost.estimate_scan(store, q.preds, verdicts)
            stats.est_rows = est.est_rows
        coalesce = (cost.choose_coalesce(est, store.baseline.block_rows)
                    if adaptive else self.granularity)
        stats.batch_blocks = coalesce

        # -- optional fused device kernel for the supported shape --------
        if self.device and not inc_rows and not over.size:
            out = self._try_device(store, q, verdicts, stats, est, deadline)
            if out is not None:
                stats.estimate = est
                if self.observe:
                    cost.observe_scan(store, est, stats.actual_rows)
                return out, stats

        with spans.span("ob.host_scan"):
            # flat group-less aggregates can swallow clean blocks from
            # sketches
            sketch = _SketchAgg(q) if (q.aggs and not q.group_by) else None

            # -- stage 2: encoded-domain filter --------------------------
            filtered = filter_blocks(store, q, needed, verdicts, over,
                                     range(nb), stats, sketch, coalesce,
                                     sub_block=adaptive, deadline=deadline)
            stats.actual_rows = (
                sum(fb.n_selected for fb in filtered)
                + (sketch.n_rows if sketch is not None else 0))
            stats.estimate = est
            if self.observe:
                cost.observe_scan(store, est, stats.actual_rows)

            # -- stage 3+4: late materialization + terminal operators ----
            if sketch is not None:
                return self._finish_flat(q, sketch, filtered, inc_rows,
                                         store), stats
            cols, masks = self._materialize(store, needed, filtered, inc_rows,
                                            with_nulls=True)
            n_rows = sum(fb.n_selected for fb in filtered) + len(inc_rows)
            out = self.engine.finalize(q, lambda nm: cols[nm], n_rows,
                                       store.schema.names,
                                       nulls=lambda nm: masks[nm])
            return out, stats

    # ------------------------------------------------- late materialization
    @staticmethod
    def _materialize(store: LSMStore, needed: Sequence[str],
                     filtered: Sequence[_FilteredBlock],
                     inc_rows: Sequence[Dict[str, Any]],
                     with_nulls: bool = False):
        """Gather only surviving row slices of only the needed columns,
        batch-at-a-time: a coalesced batch pays one gather across its
        concatenated blocks when the selection is dense, and falls back to
        per-block ``decode_idx`` when it is sparse (late materialization
        stays O(|selected|)).  Returns the column dict, plus the per-column
        NULL masks when ``with_nulls``."""
        parts: Dict[str, List[np.ndarray]] = {n: [] for n in needed}
        nparts: Dict[str, List[Optional[np.ndarray]]] = \
            {n: [] for n in needed}
        for fb in filtered:
            views, sel = fb.views, fb.sel
            segs = offs = None
            dense = False
            if sel is not None and len(views) > 1:
                offs = [0]
                for v in views:
                    offs.append(offs[-1] + v.nrows)
                # Coalesced batches pay one whole-batch gather when most
                # rows survive; sparse selections keep per-block decode_idx
                # so late materialization stays O(|selected|).
                dense = sel.size * 2 >= fb.nrows
                if not dense:
                    segs = np.split(sel, np.searchsorted(sel, offs[1:-1]))
            for name in needed:
                nb_chunks: List[Optional[np.ndarray]]
                if sel is None:
                    chunks = [v.encoded[name].decode() for v in views]
                    nb_chunks = [v.nulls.get(name) for v in views]
                elif len(views) == 1:
                    chunks = [views[0].encoded[name].decode_idx(sel)]
                    bn = views[0].nulls.get(name)
                    nb_chunks = [None if bn is None else bn[sel]]
                elif dense:
                    dec = np.concatenate([v.encoded[name].decode()
                                          for v in views])
                    chunks = [dec[sel]]
                    if any(v.nulls.get(name) is not None for v in views):
                        bn = np.concatenate(
                            [np.zeros(v.nrows, bool)
                             if v.nulls.get(name) is None
                             else v.nulls[name] for v in views])
                        nb_chunks = [bn[sel]]
                    else:
                        nb_chunks = [None]
                else:
                    chunks, nb_chunks = [], []
                    for v, seg, off in zip(views, segs, offs[:-1]):
                        if not seg.size:
                            continue
                        local = seg - off
                        chunks.append(v.encoded[name].decode_idx(local))
                        bn = v.nulls.get(name)
                        nb_chunks.append(None if bn is None else bn[local])
                parts[name].extend(chunks)
                nparts[name].extend(nb_chunks)
        cols, masks = assemble_columns(store, needed, parts, inc_rows,
                                       nparts)
        return (cols, masks) if with_nulls else cols

    # -------------------------------------------------- flat agg combining
    def _finish_flat(self, q: Query, sketch: _SketchAgg,
                     filtered: Sequence[_FilteredBlock],
                     inc_rows: Sequence[Dict[str, Any]],
                     store: LSMStore) -> List[Dict[str, Any]]:
        """Combine sketch partials (verdict-ALL blocks) with materialized
        partials (scanned blocks + incremental rows).  Materialized NULL
        slots are dropped before aggregation, matching the sketches'
        null-excluded stats: count(col)/sum/min/max are SQL null-skipping
        while count(*) keeps every surviving row."""
        agg_cols = sorted({a.column for a in q.aggs if a.column})
        cols, masks = self._materialize(store, agg_cols, filtered, inc_rows,
                                        with_nulls=True)
        n_scan = (sum(fb.n_selected for fb in filtered) + len(inc_rows))
        r: Dict[str, Any] = {}
        for a in q.aggs:
            if a.column is None:
                r[a.alias] = sketch.n_rows + n_scan
                continue
            v = cols[a.column]
            m = masks.get(a.column)
            if m is not None:
                v = v[~m]
            cnt = sketch.cnt.get(a.column, 0) + int(v.shape[0])
            if cnt == 0:
                r[a.alias] = 0 if a.op in ("count", "sum") else None
                continue
            if a.op == "count":
                r[a.alias] = cnt
                continue
            vsum = sketch.vsum.get(a.column, 0)
            if v.size and v.dtype.kind in "iufb":
                vsum = vsum + _item(v.sum())
            if a.op == "sum":
                r[a.alias] = vsum
            elif a.op == "avg":
                r[a.alias] = float(vsum) / cnt
            elif a.op in ("min", "max"):
                cand = []
                if a.column in sketch.vmin:
                    cand.append(sketch.vmin[a.column] if a.op == "min"
                                else sketch.vmax[a.column])
                if v.size:
                    cand.append(_item(v.min() if a.op == "min" else v.max()))
                r[a.alias] = (min(cand) if a.op == "min" else max(cand)) \
                    if cand else None
        out = [r]
        if q.limit is not None:
            out = out[: q.limit]
        return out

    # ------------------------------------------------------- device path
    def _try_device(self, store: LSMStore, q: Query, verdicts: np.ndarray,
                    stats: ScanStats,
                    est: Optional["cost.ScanEstimate"] = None,
                    deadline: Optional[Deadline] = None
                    ) -> Optional[List[Dict[str, Any]]]:
        """Route the fused-kernel-supported shape to the Pallas device path:
        an optional range predicate over a FOR/plain int column, 1–3 group-by
        keys (int or dictionary string), numeric aggregates over up to four
        value columns.  The cost model picks the kernel tile height
        (blocks fused per grid step) from the selectivity estimate.  The
        per-query deadline is checked before staging/launch (``deadline_s``
        binds on the device path); an open ``"device"`` circuit breaker
        pre-degrades to the host pushdown scan without attempting the
        launch."""
        verdict = self.breaker.get("device")
        if verdict == "skip":
            stats.degraded.append(cost.breaker_note(
                "device", "skip", "pre-degraded to host-pushdown"))
            return None
        if verdict == "probe":
            stats.degraded.append(cost.breaker_note(
                "device", "probe", "attempting device kernel"))
        if deadline is not None:
            deadline.check(stats)
        plan = plan_device(store, q)
        if plan is None:
            return None
        if store.baseline.n_blocks == 0:
            return []
        stage = stage_device(store, plan)
        if stage is None:
            return None
        block_mask = verdicts != Verdict.NONE.value
        stats.blocks_skipped = int((~block_mask).sum())
        stats.blocks_scanned = int(block_mask.sum())
        stats.used_device = True
        tile = 1
        if est is not None and self.granularity is None:
            tile = cost.choose_device_tile(est, store.baseline.block_rows)
        stats.device_tile_blocks = tile
        from ..kernels import ops
        if deadline is not None:
            deadline.check(stats)
        fp = faultinject.active()
        nblocks = int(block_mask.shape[0])
        chunk = max(1, tile) * DEADLINE_CHUNK_TILES

        def launch(mask):
            if fp is not None:
                fp.on_kernel_launch("pushdown")
            return run_device_kernel(
                "pushdown", ops.fused_scan_agg, stage.deltas, stage.bases,
                stage.counts, plan.lo, plan.hi, stage.codes, stage.values,
                mask, stats=stats, ndv=stage.ndv, coalesce=tile)

        try:
            if deadline is not None and nblocks > chunk:
                # Deadline-bounded chunked launches: split the block range
                # into tile-multiple chunks and check the deadline between
                # them, so ``deadline_s`` binds *inside* a long device scan
                # instead of only before it.  Partials merge exactly like
                # the per-shard device partials (counts/sums add, mins/maxs
                # fold — absent groups hold the kernel's ±inf identities);
                # like the host tree-reduce, the float32 sum association
                # may differ from one launch by an ulp.
                merged = None
                idx = np.arange(nblocks)
                for s in range(0, nblocks, chunk):
                    deadline.check(stats)
                    cmask = block_mask & (idx >= s) & (idx < s + chunk)
                    if not cmask.any():
                        continue
                    stats.device_launch_chunks += 1
                    part = tuple(np.asarray(p) for p in launch(cmask))
                    merged = part if merged is None else (
                        merged[0] + part[0], merged[1] + part[1],
                        np.minimum(merged[2], part[2]),
                        np.maximum(merged[3], part[3]))
                if merged is None:         # every block pruned: one masked
                    merged = launch(block_mask)   # launch yields the
                g_cnt, g_sums, g_mins, g_maxs = merged  # identity planes
            else:
                g_cnt, g_sums, g_mins, g_maxs = launch(block_mask)
        except KernelLaunchError as e:
            # degrade to the host pushdown scan: undo the device accounting
            # (filter_blocks re-counts with += as it scans)
            stats.degraded.append(
                f"device->host-pushdown: {type(e).__name__}: {e}")
            stats.used_device = False
            stats.blocks_skipped = 0
            stats.blocks_scanned = 0
            stats.device_launch_chunks = 0
            return None
        g_cnt = np.asarray(g_cnt)
        stats.actual_rows = int(g_cnt.sum())
        return emit_device_groups(
            q, plan, stage, g_cnt,
            np.asarray(g_sums, np.float64), np.asarray(g_mins),
            np.asarray(g_maxs))


# ---------------------------------------------------------------------------
# Device planning / staging / emission — shared with the sharded fan-out
# (core/partition.py stages once, slices per shard, tree-merges partials).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DevicePlan:
    """The fused-kernel query shape: an optional int range predicate plus a
    packed multi-key group-by over up to four value columns."""

    pred_col: Optional[str]            # None == no predicate (q2 shape)
    lo: int
    hi: int
    group_cols: Tuple[str, ...]
    value_cols: Tuple[str, ...]        # () == pure count(*): zeros plane


@dataclasses.dataclass
class DeviceStage:
    """Kernel-ready staging of every baseline block (sliceable per shard)."""

    deltas: np.ndarray                 # [Nb, Bk] int32 FOR offsets
    bases: np.ndarray                  # [Nb] int32
    counts: np.ndarray                 # [Nb] int32
    codes: np.ndarray                  # [Nb, K, Bk] int32 global group codes
    values: np.ndarray                 # [Nb, V, Bk] f32
    gdicts: List[np.ndarray]           # per-key sorted global dictionaries
    ndv: Tuple[int, ...]


_DEVICE_MAX_GROUPS = 1 << 12           # largest packed group domain the
                                       # kernel's VMEM accumulators compile
                                       # for on a v5e (test_tpu_compile.py)
_DEVICE_BIG = 1 << 30                  # int32-safe bound for staged ints


_COMPILED: Dict[tuple, Any] = {}      # (kernel, static, arg avals) → exe
_COMPILED_MAX = 64                     # distinct launch shapes kept


def _arg_key(a) -> tuple:
    return (np.shape(a), str(np.result_type(a)), getattr(a, "sharding", None))


def compile_device_kernel(kernel, *args, **static):
    """The compiled executable of a jitted kernel wrapper for these
    argument shapes, dtypes and placements (``static`` are its static
    arguments), compiled once and then reused.  A trace, lowering or
    compile error propagates and fails the query."""
    key = (kernel, tuple(sorted(static.items())),
           tuple(_arg_key(a) for a in args))
    exe = _COMPILED.get(key)
    if exe is None:
        with spans.span("ob.kernel_compile"):
            exe = kernel.lower(*args, **static).compile()
        if len(_COMPILED) >= _COMPILED_MAX:
            _COMPILED.clear()
        _COMPILED[key] = exe
    return exe


def host_bytes(args) -> int:
    """Bytes of the host arguments a launch copies to the device: numpy
    arrays and Python scalars at the dtype JAX gives them.  Arguments
    already on the device count 0."""
    import jax
    n = 0
    for a in args:
        if not isinstance(a, jax.Array):
            dt = np.dtype(jax.dtypes.canonicalize_dtype(np.result_type(a)))
            n += int(np.size(a)) * dt.itemsize
    return n


def dispatch_device_kernel(route: str, exe, *args,
                           stats: Optional[ScanStats] = None):
    """Dispatch a compiled launch without waiting for it.  Host arguments
    are handed to the device here; their bytes add to ``stats.h2d_bytes``.
    A runtime fault raised at dispatch is wrapped as
    ``KernelLaunchError``."""
    import jax
    if stats is not None:
        stats.h2d_bytes += host_bytes(args)
    try:
        with spans.span("ob.dispatch"):
            return exe(*args)
    except jax.errors.JaxRuntimeError as e:
        raise KernelLaunchError(route, e) from e


def await_device_kernels(route: str, outs):
    """Block on dispatched launches; a runtime fault of any of them is
    wrapped as ``KernelLaunchError``, the one error the device rungs
    degrade on."""
    import jax
    try:
        with spans.span("ob.wait"):
            return jax.block_until_ready(outs)
    except jax.errors.JaxRuntimeError as e:
        raise KernelLaunchError(route, e) from e


def run_device_kernel(route: str, kernel, *args,
                      stats: Optional[ScanStats] = None, **static):
    """Compile (once per launch shape), run and wait for one device launch:
    compile errors propagate, runtime faults raise ``KernelLaunchError``.
    The launch's host bytes add to ``stats.h2d_bytes``."""
    exe = compile_device_kernel(kernel, *args, **static)
    return await_device_kernels(
        route, dispatch_device_kernel(route, exe, *args, stats=stats))


def plan_device(store: LSMStore, q: Query) -> Optional[DevicePlan]:
    """Match the fused-kernel query shape; None if unsupported."""
    if not q.group_by or len(q.group_by) > 3 or not q.aggs:
        return None
    sch = store.schema
    base = store.baseline

    def clean_col(name: str) -> bool:
        idx = base.cols[name].index
        s = idx.nodes[idx.root].sketch if idx.root >= 0 else None
        return s is None or s.null_count == 0

    for g in q.group_by:
        if sch.spec(g).ctype not in (ColType.INT, ColType.STR):
            return None
        # NULL group *keys* are allowed: staging reserves a sentinel slot
        # per key in the packed code domain (emitted as None on the host
        # side); predicate and value columns must stay clean below.
    val_cols = tuple(sorted({a.column for a in q.aggs
                             if a.column is not None}))
    if len(val_cols) > 4:
        return None
    for c in val_cols:
        if sch.spec(c).ctype not in (ColType.INT, ColType.FLOAT):
            return None
        if not clean_col(c):
            return None
    if len(q.preds) > 1:
        return None
    if not q.preds:                    # q2 shape: group-by without predicate
        return DevicePlan(None, 0, 0, tuple(q.group_by), val_cols)
    p = q.preds[0]
    if sch.spec(p.column).ctype != ColType.INT or not clean_col(p.column):
        return None
    # The kernel stages deltas/bases/bounds as int32 and shifts bounds by
    # -base; restrict column values and bounds to ±2^30 so no assignment
    # truncates and no base shift overflows.
    big = _DEVICE_BIG
    idx = base.cols[p.column].index
    vmin, vmax = idx.try_aggregate("min"), idx.try_aggregate("max")
    if vmin is not None and (vmin <= -big or vmax >= big):
        return None
    # The kernel's window [lo, hi] is inclusive over *integer* column values;
    # float constants round inward (ceil on lower bounds, floor on upper) so
    # e.g. d >= 100.5 becomes d >= 101 — never int() truncation.
    if p.op == PredOp.BETWEEN:
        lo, hi = math.ceil(p.value), math.floor(p.value2)
    elif p.op == PredOp.GE:
        lo, hi = math.ceil(p.value), big
    elif p.op == PredOp.GT:
        lo, hi = math.floor(p.value) + 1, big
    elif p.op == PredOp.LE:
        lo, hi = -big, math.floor(p.value)
    elif p.op == PredOp.LT:
        lo, hi = -big, math.ceil(p.value) - 1
    elif p.op == PredOp.EQ:
        if not float(p.value).is_integer():
            return None                # no int row can match; host handles it
        lo = hi = int(p.value)
    else:
        return None
    lo, hi = max(lo, -big), min(hi, big)     # column values all inside ±2^30
    for enc in base.cols[p.column].blocks:
        if not isinstance(enc, (DeltaFOREncoded, PlainEncoded, DictEncoded)):
            return None
    return DevicePlan(p.column, lo, hi, tuple(q.group_by), val_cols)


def _global_dict(base, name: str) -> np.ndarray:
    """Sorted global value dictionary of one group column, assembled from
    per-block domains (block dictionaries where dict-encoded — strings never
    decode row-wise on that path)."""
    domains = []
    for enc in base.cols[name].blocks:
        domains.append(enc.dictionary if isinstance(enc, DictEncoded)
                       else np.unique(enc.decode()))
    return np.unique(np.concatenate(domains)) if domains else np.empty((0,))


def stage_device(store: LSMStore, plan: DevicePlan) -> Optional[DeviceStage]:
    """Build the [Nb, ...] kernel inputs: FOR offsets of the predicate
    column (zeros when predicate-less), per-key global group codes, f32
    value planes.  None when the packed group domain is too large."""
    with spans.span("ob.stage"):
        return _stage(store, plan)


def _stage(store: LSMStore, plan: DevicePlan) -> Optional[DeviceStage]:
    base = store.baseline
    nb, bk = base.n_blocks, base.block_rows
    with spans.span("ob.stage.dicts"):
        gdicts = [_global_dict(base, g) for g in plan.group_cols]
    # NULL group keys: a key column whose baseline carries NULLs gets one
    # reserved sentinel slot (code == len(gdict), the largest code) in its
    # packed domain; ``emit_device_groups`` decodes it back to None.
    key_nulls = [base.cols[g].null_blocks is not None
                 for g in plan.group_cols]
    ndv = tuple(max(int(d.shape[0]), 1) + (1 if hn else 0)
                for d, hn in zip(gdicts, key_nulls))
    packed_domain = 1
    for d in ndv:
        packed_domain *= d
    if packed_domain > _DEVICE_MAX_GROUPS:
        return None
    n_vals = max(len(plan.value_cols), 1)
    deltas = np.zeros((nb, bk), np.int32)
    bases = np.zeros((nb,), np.int32)
    counts = np.zeros((nb,), np.int32)
    codes = np.zeros((nb, len(plan.group_cols), bk), np.int32)
    values = np.zeros((nb, n_vals, bk), np.float32)
    remaps = [{} for _ in plan.group_cols]     # block dict id -> global codes
    with spans.span("ob.stage.blocks"):
        for b in range(nb):
            blo, bhi = base.block_bounds(b)
            n = bhi - blo
            counts[b] = n
            if plan.pred_col is not None:
                cst = base.cols[plan.pred_col]
                cst.verify_block(b)    # raw payload access skips decode_block
                enc = cst.blocks[b]
                if isinstance(enc, DeltaFOREncoded):   # in the offset domain
                    deltas[b, :n] = enc.deltas
                    bases[b] = enc.base
                else:
                    deltas[b, :n] = enc.decode()
            for k, g in enumerate(plan.group_cols):
                base.cols[g].verify_block(b)
                genc = base.cols[g].blocks[b]
                if isinstance(genc, DictEncoded):  # map codes, never decode
                    remap = remaps[k].get(id(genc))
                    if remap is None:
                        remap = np.searchsorted(gdicts[k], genc.dictionary)
                        remaps[k][id(genc)] = remap
                    codes[b, k, :n] = remap[genc.codes]
                else:
                    codes[b, k, :n] = np.searchsorted(gdicts[k], genc.decode())
                if key_nulls[k]:
                    nmask = base.cols[g].block_nulls(b)
                    if nmask is not None:              # NULL rows → sentinel
                        codes[b, k, :n][nmask] = gdicts[k].shape[0]
            for v, c in enumerate(plan.value_cols):
                values[b, v, :n] = base.cols[c].decode_block(b)
    return DeviceStage(deltas, bases, counts, codes, values, gdicts, ndv)


def emit_device_groups(q: Query, plan: DevicePlan, stage: DeviceStage,
                       g_cnt: np.ndarray, g_sums: np.ndarray,
                       g_mins: np.ndarray, g_maxs: np.ndarray,
                       group_ids: Optional[np.ndarray] = None
                       ) -> List[Dict[str, Any]]:
    """Unpack per-packed-group kernel partials into result rows (group order
    = lexicographic over the sorted dictionaries, matching VectorEngine's
    unique-key order), then the shared sort/limit tail.  With ``group_ids``
    the accumulators are already top-k-sliced on device: position ``j``
    holds packed group ``group_ids[j]`` (zero-count slots are padding from
    a result smaller than k)."""
    with spans.span("ob.emit"):
        strides = []
        acc = 1
        for d in reversed(stage.ndv):
            strides.append(acc)
            acc *= d
        strides = list(reversed(strides))
        vidx = {c: v for v, c in enumerate(plan.value_cols)}
        out: List[Dict[str, Any]] = []
        cols_live = np.nonzero(g_cnt)[0]
        packed = cols_live if group_ids is None else group_ids[cols_live]
        for j, g in zip(cols_live, packed):
            r: Dict[str, Any] = {}
            for k, col in enumerate(plan.group_cols):
                di = (g // strides[k]) % stage.ndv[k]
                # the reserved sentinel slot (>= dictionary size) is NULL
                r[col] = (None if di >= stage.gdicts[k].shape[0]
                          else _item(stage.gdicts[k][di]))
            n = int(g_cnt[j])
            for a in q.aggs:
                if a.op == "count":
                    r[a.alias] = n
                    continue
                v = vidx[a.column]
                if a.op == "sum":
                    r[a.alias] = float(g_sums[v, j])
                elif a.op == "avg":
                    r[a.alias] = float(g_sums[v, j]) / n
                elif a.op == "min":
                    r[a.alias] = float(g_mins[v, j])
                elif a.op == "max":
                    r[a.alias] = float(g_maxs[v, j])
            out.append(r)
        if q.sort_by:
            out = VectorEngine._sort(out, q.sort_by)
        if q.limit is not None:
            out = out[: q.limit]
        return out
