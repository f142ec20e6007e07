"""Hybrid LSM store (paper §III-A/B): columnar baseline + row incremental.

The paper's C1 contribution: all user data is split into *baseline* data
(output of major compaction, stored column-wise, one virtual SSTable composed
of per-column SSTables) and *incremental* data (MemTable + minor SSTables,
stored row-wise, full DML capability).  Queries merge the two on the fly
("merge-on-read"), so freshness ≈ 0 while the analytical path stays columnar.

This module is the host-side reference implementation used by the data
pipeline, telemetry store and benchmarks.  The device-side twin — the hybrid
KV-cache store in ``repro.serve.kv_store`` — follows the same
baseline/incremental/compaction contract with jnp buffers and the
``hybrid_decode`` Pallas kernel as its merge-on-read reader.

MVCC: every mutation carries a commit timestamp; reads are served *as of* a
snapshot ts (the paper's snapshot-based read model).  Major compaction folds
everything ≤ its version into a new columnar baseline ("daily compaction"),
guaranteeing deterministic, replica-identical output for a given version.
"""
from __future__ import annotations

import dataclasses
import enum
import threading
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .encoding import (EncodedColumn, choose_encoding, payload_checksum,
                       shrink_bytes)
from . import spans
from .errors import BlockCorruption
from .replica import collect as _collect_repairs, event_mark as _repair_mark
from .relation import And, Column, ColType, PredOp, Predicate, Schema, Table
from .skipping import Sketch, SkippingIndex, Verdict, DEFAULT_BLOCK_ROWS
from .vec import BatchAttrs


class DmlType(enum.Enum):
    INSERT = "I"
    UPDATE = "U"
    DELETE = "D"


@dataclasses.dataclass(frozen=True)
class Version:
    """One MVCC row version."""

    ts: int
    op: DmlType
    row: Optional[Dict[str, Any]]  # None for DELETE


# ---------------------------------------------------------------------------
# Row-format incremental structures
# ---------------------------------------------------------------------------


class MemTable:
    """In-memory row store: pk -> version chain (newest last)."""

    def __init__(self, schema: Schema):
        self.schema = schema
        self.rows: Dict[Any, List[Version]] = {}
        self.min_ts: Optional[int] = None
        self.max_ts: Optional[int] = None

    def __len__(self):
        return sum(len(v) for v in self.rows.values())

    def apply(self, ts: int, op: DmlType, row: Optional[Dict[str, Any]], pk: Any):
        self.rows.setdefault(pk, []).append(Version(ts, op, row))
        self.min_ts = ts if self.min_ts is None else min(self.min_ts, ts)
        self.max_ts = ts if self.max_ts is None else max(self.max_ts, ts)

    def get(self, pk: Any, ts: int) -> Optional[Version]:
        chain = self.rows.get(pk)
        if not chain:
            return None
        for v in reversed(chain):
            if v.ts <= ts:
                return v
        return None

    def effective(self, ts: int) -> Dict[Any, Version]:
        out = {}
        for pk, chain in self.rows.items():
            for v in reversed(chain):
                if v.ts <= ts:
                    out[pk] = v
                    break
        return out


class MinorSSTable:
    """Frozen, immutable row-format run (paper: incremental *minor* SSTable —
    row format, read-only)."""

    def __init__(self, schema: Schema, rows: Dict[Any, List[Version]]):
        self.schema = schema
        self.rows = {pk: list(chain) for pk, chain in rows.items()}
        all_ts = [v.ts for chain in rows.values() for v in chain]
        self.min_ts = min(all_ts) if all_ts else 0
        self.max_ts = max(all_ts) if all_ts else 0

    def __len__(self):
        return sum(len(v) for v in self.rows.values())

    def get(self, pk: Any, ts: int) -> Optional[Version]:
        chain = self.rows.get(pk)
        if not chain:
            return None
        for v in reversed(chain):
            if v.ts <= ts:
                return v
        return None

    def effective(self, ts: int) -> Dict[Any, Version]:
        out = {}
        for pk, chain in self.rows.items():
            for v in reversed(chain):
                if v.ts <= ts:
                    out[pk] = v
                    break
        return out


# ---------------------------------------------------------------------------
# Columnar baseline structures
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ColumnSSTable:
    """One column's SSTable: encoded blocks + embedded skipping index
    (paper: 'each column data is stored as an independent SSTable' with the
    data-skipping index integrated directly into the SSTable structure).
    ``null_blocks`` is the per-block NULL bitmap (None for null-free
    columns): encodings store fill values in NULL slots, so the bitmap is
    what keeps decode consistent with the sketches' null counts."""

    name: str
    blocks: List[EncodedColumn]
    index: SkippingIndex
    block_rows: int
    nrows: int
    null_blocks: Optional[List[np.ndarray]] = None
    # build-time CRC32 per block (None: pre-checksum SSTable, verification
    # disabled); ``quarantined`` collects block ids that failed verification
    # — the store excludes itself from MAV rewrites while any block is
    # quarantined, and the failed read raises ``BlockCorruption``.
    checksums: Optional[List[int]] = None
    quarantined: set = dataclasses.field(default_factory=set)
    _verified: Optional[List[bool]] = dataclasses.field(
        default=None, repr=False)
    # attached ColumnReplicas handle (core/replica.py) when the store runs
    # with replication — verify_block uses it to repair a corrupt block in
    # place instead of failing the query
    replicas: Optional[Any] = dataclasses.field(
        default=None, repr=False, compare=False)
    # serializes the verify-memo slow path so concurrent readers agree on
    # quarantine state and a repair runs exactly once; the memoized fast
    # path stays lock-free (a list read is atomic under the GIL)
    _vlock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)

    def nbytes(self) -> int:
        return sum(b.nbytes() for b in self.blocks) + self.index.nbytes()

    def verify_block(self, b: int) -> None:
        """Checksum-verify block ``b`` against its build-time CRC, memoized
        (one CRC pass per block per SSTable lifetime, so the clean-path
        overhead is a list lookup).  On mismatch, tries in-place repair from
        an attached replica set (core/replica.py): a verified replica copy
        replaces the corrupt payload, the quarantine is lifted and the read
        proceeds bit-identically.  Only when no healthy copy exists does the
        block stay quarantined and ``BlockCorruption`` raise.  Thread-safe:
        the unverified slow path is double-checked under a per-SSTable lock,
        so N concurrent readers of a corrupt block see one repair and one
        consistent quarantine transition."""
        if self.checksums is None:
            return
        v = self._verified
        if v is not None and v[b]:
            return                     # memoized fast path, lock-free
        with self._vlock:
            if self._verified is None:
                self._verified = [False] * len(self.blocks)
            if self._verified[b]:
                return                 # verified while we waited
            got = payload_checksum(self.blocks[b])
            if got != self.checksums[b]:
                self.quarantined.add(b)
                if self.replicas is not None and self.replicas.repair(self, b):
                    self.quarantined.discard(b)
                    self._verified[b] = True
                    return
                raise BlockCorruption(self.name, b, self.checksums[b], got)
            self._verified[b] = True

    def mark_unverified(self, b: int) -> None:
        """Drop block ``b``'s memoized verification (fault injection and
        the scrub pass: a just-corrupted block must be re-checked on its
        next read).  Takes ``_vlock`` so the write cannot interleave with
        ``verify_block``'s double-checked slow path."""
        with self._vlock:
            if self._verified is not None:
                self._verified[b] = False

    def decode_block(self, b: int) -> np.ndarray:
        self.verify_block(b)
        return self.blocks[b].decode()

    def block_nulls(self, b: int) -> Optional[np.ndarray]:
        """Bool NULL mask of block ``b`` (None when the block is null-free)."""
        if self.null_blocks is None:
            return None
        m = self.null_blocks[b]
        return m if m is not None and m.any() else None

    def decode_all(self) -> np.ndarray:
        if not self.blocks:
            return np.empty((0,))
        return np.concatenate([self.decode_block(b)
                               for b in range(len(self.blocks))])


@dataclasses.dataclass
class BlockView:
    """One block of the columnar baseline, *without* decoding: per-column
    encoded payloads + per-column leaf sketches + batch attrs.  This is the
    unit the pushdown executor iterates — zone-map pruning reads ``sketches``,
    encoded-domain predicates read ``encoded``, and late materialization
    calls ``encoded[c].decode_idx(sel)`` only for surviving rows."""

    bid: int                              # block ordinal
    lo: int                               # first row (global baseline index)
    hi: int                               # one past last row
    encoded: Dict[str, EncodedColumn]
    sketches: Dict[str, Sketch]
    nulls: Dict[str, Optional[np.ndarray]]  # per-column NULL masks (or None)
    attrs: BatchAttrs

    @property
    def nrows(self) -> int:
        return self.hi - self.lo


@dataclasses.dataclass
class VirtualSSTable:
    """Baseline = per-column SSTables glued into one virtual SSTable, with a
    sorted pk array as the row locator."""

    schema: Schema
    version: int                       # compaction version (max folded ts)
    pks: np.ndarray                    # sorted primary keys
    cols: Dict[str, ColumnSSTable]
    block_rows: int

    @property
    def nrows(self) -> int:
        return int(self.pks.shape[0])

    def nbytes(self) -> int:
        return sum(c.nbytes() for c in self.cols.values()) + self.pks.nbytes

    @property
    def n_blocks(self) -> int:
        if self.nrows == 0:
            return 0
        return (self.nrows + self.block_rows - 1) // self.block_rows

    def block_bounds(self, b: int) -> Tuple[int, int]:
        lo = b * self.block_rows
        return lo, min(lo + self.block_rows, self.nrows)

    def block_view(self, b: int, columns: Sequence[str]) -> BlockView:
        lo, hi = self.block_bounds(b)
        for c in columns:
            self.cols[c].verify_block(b)
        encoded = {c: self.cols[c].blocks[b] for c in columns}
        sketches = {c: self.cols[c].index.leaf_sketch(b) for c in columns}
        nulls = {c: self.cols[c].block_nulls(b) for c in columns}
        null_count = max((s.null_count for s in sketches.values()), default=0)
        return BlockView(b, lo, hi, encoded, sketches, nulls,
                         BatchAttrs.for_block(null_count))

    def iter_blocks(self, columns: Sequence[str]) -> Iterable[BlockView]:
        """Block-iteration API for the pushdown executor: encoded blocks plus
        per-block sketches, no decoding."""
        for b in range(self.n_blocks):
            yield self.block_view(b, columns)

    def locate(self, pk: Any) -> int:
        """Row index of pk, or -1."""
        i = int(np.searchsorted(self.pks, pk))
        if i < self.nrows and self.pks[i] == pk:
            return i
        return -1

    def row(self, i: int) -> Dict[str, Any]:
        b, off = divmod(i, self.block_rows)
        out = {}
        for name, cst in self.cols.items():
            bn = cst.block_nulls(b)
            if bn is not None and bn[off]:
                out[name] = None
                continue
            v = cst.decode_block(b)[off]
            out[name] = v.item() if hasattr(v, "item") else v
        return out

    @staticmethod
    def build(schema: Schema, table: Table, version: int,
              block_rows: int = DEFAULT_BLOCK_ROWS) -> "VirtualSSTable":
        pk_name = schema.pk
        order = np.argsort(table.col(pk_name).values, kind="stable")
        sorted_tbl = table.take(order)
        cols: Dict[str, ColumnSSTable] = {}
        n = len(sorted_tbl)
        decoded_peers: Dict[str, np.ndarray] = {}
        for spec in schema.columns:
            vals = sorted_tbl.col(spec.name).values
            nulls = sorted_tbl.col(spec.name).nulls
            blocks: List[EncodedColumn] = []
            for s in range(0, max(n, 1), block_rows):
                if n == 0:
                    break
                peers = {k: v[s:s + block_rows] for k, v in decoded_peers.items()}
                blocks.append(choose_encoding(vals[s:s + block_rows], peers=peers))
            index = SkippingIndex.build(vals, nulls, block_rows=block_rows)
            null_blocks = None
            if nulls is not None and n and nulls.any():
                null_blocks = [np.ascontiguousarray(nulls[s:s + block_rows])
                               for s in range(0, n, block_rows)]
            cols[spec.name] = ColumnSSTable(spec.name, blocks, index,
                                            block_rows, n, null_blocks,
                                            checksums=[payload_checksum(b)
                                                       for b in blocks])
            decoded_peers[spec.name] = vals
        return VirtualSSTable(schema, version, sorted_tbl.col(pk_name).values,
                              cols, block_rows)


# ---------------------------------------------------------------------------
# The LSM store
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ScanStats:
    blocks_total: int = 0
    blocks_skipped: int = 0
    blocks_sketch_only: int = 0
    blocks_scanned: int = 0
    rows_merged_incremental: int = 0
    used_pushdown: bool = False
    used_device: bool = False          # fused Pallas kernel answered the scan
    n_shards: int = 0                  # >0: mesh-sharded fan-out ran
    est_rows: float = 0.0              # planner estimate of surviving rows
    actual_rows: int = 0               # observed baseline rows surviving the
                                       # predicates (feeds cost calibration)
    batch_blocks: int = 1              # blocks fused per vector batch
    device_tile_blocks: int = 1        # blocks fused per kernel tile
    device_launch_chunks: int = 0      # >0: deadline-bounded chunked device
                                       # launches (deadline checked between
                                       # tile chunks, partials merged)
    device_route: str = ""             # 'collective' | 'host' when used_device
    n_devices: int = 0                 # scan-mesh size the device fan-out saw
    h2d_bytes: int = 0                 # host argument bytes handed to the
                                       # device, over every launch, chunk
                                       # and retry (0 for device arrays)
    topk_pushdown: bool = False        # per-shard limit-aware top-k ran
    # --- fault-tolerance provenance ------------------------------------
    degraded: List[str] = dataclasses.field(default_factory=list)
    #                                  # route-degradation ladder steps, in
    #                                  # order, each "from->to: why"
    shard_retries: int = 0             # shard attempts beyond the first
    hedges: int = 0                    # straggler back-up dispatches
    purge_fallback: bool = False       # MAV read fell back to full refresh
    mlog_retries: int = 0              # bounded MLog.since retries that ran
    kernel_retries: int = 0            # in-route collective retries (a
                                       # transient launch failure retried
                                       # without dropping a ladder rung)
    repaired: List[str] = dataclasses.field(default_factory=list)
    #                                  # block-repair events this query
    #                                  # triggered ("repaired col/block b
    #                                  # from replica r")
    failed_shards: List[int] = dataclasses.field(default_factory=list)
    #                                  # shard ids whose retry budget
    #                                  # exhausted (keys the per-shard
    #                                  # breakers in core/health.py)
    # the cost.ScanEstimate the executor planned against, carried out so
    # the session's post-execution commit step can close the calibration
    # loop (cost.observe_scan) without the executor mutating shared state
    estimate: Optional[Any] = dataclasses.field(
        default=None, repr=False, compare=False)
    # wall seconds the execution took (stamped by Database.execute) — what
    # the commit step feeds the health registry's latency EWMA
    latency_s: float = dataclasses.field(
        default=0.0, repr=False, compare=False)

    def absorb(self, other: "ScanStats") -> None:
        """Fold one shard's counters into the query-level stats (the
        fan-out gives every shard its own ScanStats so parallel scans
        never race on these integers)."""
        self.blocks_skipped += other.blocks_skipped
        self.blocks_sketch_only += other.blocks_sketch_only
        self.blocks_scanned += other.blocks_scanned
        self.actual_rows += other.actual_rows


class LSMStore:
    """Multi-level LSM with hybrid row/column layout.

    Write path: MemTable (row) → freeze → minor SSTables (row) →
    major compaction → columnar baseline.  Read path: merge-on-read at a
    snapshot ts, with predicate/aggregate pushdown into the columnar baseline.
    """

    def __init__(self, schema: Schema, block_rows: int = DEFAULT_BLOCK_ROWS,
                 memtable_limit: int = 4096, replication: int = 1):
        self.schema = schema
        self.block_rows = block_rows
        self.memtable_limit = memtable_limit
        # replication >= 2: keep k-way replica copies of every baseline block
        # (re-cloned after each compaction) so a corrupt block is repaired in
        # place instead of quarantined for the store's lifetime
        self.replication = replication
        self.memtable = MemTable(schema)
        self.minors: List[MinorSSTable] = []
        self.baseline: VirtualSSTable = VirtualSSTable.build(
            schema, Table.empty(schema), version=0, block_rows=block_rows)
        self._ts = 0
        # serializes writers (DML, freeze, compaction) against each other
        # and against the incremental merge-on-read walk, so concurrent
        # readers never iterate a memtable/minor dict mid-mutation.
        # Baseline reads stay lock-free: a compaction swaps the whole
        # VirtualSSTable object, readers keep the reference they grabbed.
        self._lock = threading.RLock()
        # bumped on every baseline swap (bulk load / major compaction) —
        # with _ts (bumped by every DML) it forms the table ``epoch`` that
        # keys plan/result caches: any write or compaction moves the epoch
        self._baseline_gen = 0
        self.redo_log: List[Tuple[int, DmlType, Any, Optional[Dict[str, Any]]]] = []
        self.mlog_sinks: List[Any] = []  # MLog observers (mview.py)
        # durability (core/wal.py): a durable Database attaches a
        # WriteAheadLog here; every committed mutation then appends one
        # epoch-stamped record at its commit point, under this same lock.
        # None (the default) keeps the store purely in-memory.
        self.wal: Optional[Any] = None
        self._refresh_replicas()

    def _log(self, kind: str, **data: Any) -> None:
        """Append one WAL record stamped with the post-mutation epoch.
        Called at each mutation's commit point — usually under
        ``self._lock``, but registration markers (create_table/mav/mjv,
        mlog purge) log without it (recovery detaches ``wal`` while
        replaying, so replays never re-log themselves)."""
        if self.wal is not None:
            # lint: allow(lock-discipline) — WriteAheadLog.append takes
            # its own lock; the epoch ints read here are GIL-atomic
            self.wal.append(kind, self._ts, self._baseline_gen, data)

    @property
    def epoch(self) -> Tuple[int, int]:
        """Monotone change marker ``(current_ts, baseline_gen)``: the first
        component moves on every DML, the second on every baseline swap
        (major compaction / bulk load).  Two equal epochs guarantee every
        read answers identically, which is exactly the invalidation rule
        the serving layer's plan/result caches key on."""
        return (self._ts, self._baseline_gen)

    def _refresh_replicas(self) -> None:
        """(Re-)attach the replica set to the current baseline when the
        store runs with replication (every new baseline invalidates the
        previous clones — a replica is only a valid repair source for the
        exact build it was cloned from)."""
        if self.replication >= 2:
            from .replica import enable_replication
            enable_replication(self, self.replication)

    # --- write path ---------------------------------------------------------

    def _next_ts_locked(self) -> int:
        self._ts += 1
        return self._ts

    @property
    def current_ts(self) -> int:
        return self._ts

    def _old_row(self, pk: Any, ts: int) -> Optional[Dict[str, Any]]:
        v = self._find_version(pk, ts)
        if v is not None:
            return v.row if v.op != DmlType.DELETE else None
        i = self.baseline.locate(pk)
        return self.baseline.row(i) if i >= 0 else None

    def insert(self, row: Dict[str, Any]) -> int:
        with spans.span("ob.write"), self._lock:
            pk = row[self.schema.pk]
            ts = self._next_ts_locked()
            if self._old_row(pk, ts) is not None:
                raise KeyError(f"duplicate pk {pk}")
            self._write_locked(ts, DmlType.INSERT, pk, dict(row), old=None)
            return ts

    def update(self, pk: Any, changes: Dict[str, Any]) -> int:
        with self._lock:
            ts = self._next_ts_locked()
            old = self._old_row(pk, ts)
            if old is None:
                raise KeyError(f"update of missing pk {pk}")
            new = dict(old)
            new.update(changes)
            new[self.schema.pk] = changes.get(self.schema.pk, pk)
            self._write_locked(ts, DmlType.UPDATE, pk, new, old=old)
            if new[self.schema.pk] != pk:  # pk change = delete+insert
                self.memtable.apply(ts, DmlType.DELETE, None, pk)
                self.memtable.apply(ts, DmlType.INSERT, new,
                                    new[self.schema.pk])
            return ts

    def delete(self, pk: Any) -> int:
        with spans.span("ob.write"), self._lock:
            ts = self._next_ts_locked()
            old = self._old_row(pk, ts)
            if old is None:
                raise KeyError(f"delete of missing pk {pk}")
            self._write_locked(ts, DmlType.DELETE, pk, None, old=old)
            return ts

    def _write_locked(self, ts: int, op: DmlType, pk: Any,
                      row: Optional[Dict[str, Any]],
                      old: Optional[Dict[str, Any]]):
        if self.wal is not None:
            # write-ahead: the statement is durable before it is applied
            # (UPDATE logs the full post-image, so replaying
            # ``update(pk, row)`` reproduces the merge — and the
            # pk-change delete+insert — exactly)
            if op == DmlType.INSERT:
                self._log("insert", row=row)
            elif op == DmlType.DELETE:
                self._log("delete", pk=pk)
            else:
                self._log("update", pk=pk, row=row)
        if not (op == DmlType.UPDATE and row is not None
                and row[self.schema.pk] != pk):
            self.memtable.apply(ts, op, row, pk)
        self.redo_log.append((ts, op, pk, row))
        for sink in self.mlog_sinks:  # DAS: DML updates base + mlog together
            sink.record(ts, op, pk, old, row)
        if len(self.memtable) >= self.memtable_limit:
            self.freeze_memtable()

    # --- compaction ----------------------------------------------------------

    def bulk_insert(self, columns: Dict[str, Any]) -> int:
        """Full direct load (paper §IV-B): bypass the transaction layer and
        write the data directly as a columnar baseline SSTable.  Only legal
        on an empty store (the paper uses it for hidden-table MV rebuilds
        and ≥10 GB initial loads).  Returns the baseline version."""
        with self._lock:
            assert self.baseline.nrows == 0 and len(self.memtable) == 0 \
                and not self.minors, "direct load requires an empty store"
            n = len(next(iter(columns.values())))
            cols = {}
            for spec in self.schema.columns:
                vals = np.asarray(columns[spec.name])
                if spec.ctype == ColType.STR and vals.dtype.kind != "S":
                    vals = vals.astype(np.bytes_)
                cols[spec.name] = Column(spec, vals)
            tbl = Table(self.schema, cols)
            ts = self._next_ts_locked()
            self.baseline = VirtualSSTable.build(self.schema, tbl, ts,
                                                 self.block_rows)
            self._baseline_gen += 1
            assert self.baseline.nrows == n
            self._refresh_replicas()
            self._log("bulk_insert", columns=columns)
            return ts

    def bulk_insert_rows(self, columns: Dict[str, Any]) -> int:
        """Incremental direct load (paper §IV-C): structure the data
        directly into ROW-format storage (one minor SSTable), bypassing the
        per-statement write path.  Works on any store state."""
        with self._lock:
            names = list(columns.keys())
            arrays = [np.asarray(columns[n]) for n in names]
            n = len(arrays[0])
            ts = self._next_ts_locked()
            rows: Dict[Any, List[Version]] = {}
            pk_i = names.index(self.schema.pk)
            for r in range(n):
                row = {nm: (a[r].item() if hasattr(a[r], "item") else a[r])
                       for nm, a in zip(names, arrays)}
                rows[row[self.schema.pk]] = [Version(ts, DmlType.INSERT, row)]
            self.minors.append(MinorSSTable(self.schema, rows))
            self._log("bulk_rows", columns=columns)
            return ts

    def freeze_memtable(self):
        """Dump MemTable to a row-format minor SSTable."""
        with self._lock:
            if len(self.memtable) == 0:
                return
            self.minors.append(MinorSSTable(self.schema, self.memtable.rows))
            self.memtable = MemTable(self.schema)

    def minor_compact(self):
        """Merge all minor SSTables into one (still row format)."""
        with self._lock:
            if len(self.minors) <= 1:
                return
            merged: Dict[Any, List[Version]] = {}
            for m in self.minors:
                for pk, chain in m.rows.items():
                    merged.setdefault(pk, []).extend(chain)
            for chain in merged.values():
                chain.sort(key=lambda v: v.ts)
            self.minors = [MinorSSTable(self.schema, merged)]

    def major_compact(self, version: Optional[int] = None) -> int:
        """'Daily compaction': fold all increments ≤ version into a new
        columnar baseline.  Deterministic for a given version (replica
        consistency).  Returns the new baseline version."""
        with self._lock:
            version = self._ts if version is None else version
            self.freeze_memtable()
            tbl = self._merged_table(version)
            self.baseline = VirtualSSTable.build(self.schema, tbl, version,
                                                 self.block_rows)
            self._baseline_gen += 1
            # Drop folded increments; keep versions newer than the
            # compaction point.
            kept: List[MinorSSTable] = []
            for m in self.minors:
                newer = {pk: [v for v in chain if v.ts > version]
                         for pk, chain in m.rows.items()}
                newer = {pk: c for pk, c in newer.items() if c}
                if newer:
                    kept.append(MinorSSTable(self.schema, newer))
            self.minors = kept
            self._refresh_replicas()
            # baseline-swap marker: compaction is deterministic for a given
            # version, so replaying it reproduces the exact baseline (and
            # keeps the ``_baseline_gen`` epoch component continuous)
            self._log("major_compact", version=version)
            return version

    # --- read path ------------------------------------------------------------

    def _find_version(self, pk: Any, ts: int) -> Optional[Version]:
        with self._lock:
            v = self.memtable.get(pk, ts)
            if v is not None:
                return v
            best = None
            for m in self.minors:
                cand = m.get(pk, ts)
                if cand is not None and (best is None or cand.ts > best.ts):
                    best = cand
            return best

    def _incremental_effective(self, ts: int) -> Dict[Any, Version]:
        # under the store lock: concurrent DML mutates the memtable dicts
        # (and a freeze/compact replaces the minors list) while this walks
        # them — the snapshot filter (v.ts <= ts) makes the *result*
        # deterministic, the lock makes the iteration safe
        with self._lock:
            out: Dict[Any, Version] = {}
            for m in self.minors:
                for pk, v in m.effective(ts).items():
                    if pk not in out or v.ts > out[pk].ts:
                        out[pk] = v
            for pk, v in self.memtable.effective(ts).items():
                if pk not in out or v.ts > out[pk].ts:
                    out[pk] = v
            return {pk: v for pk, v in out.items()
                    if v.ts > self.baseline.version}

    def live_incremental_rows(self, inc: Dict[Any, Version],
                              preds: Sequence[Predicate] = (),
                              deadline: Optional[Any] = None,
                              ) -> List[Dict[str, Any]]:
        """Predicate filter over live (non-DELETE) incremental versions —
        the merge-on-read half shared by ``scan``, the pushdown executor and
        the sharded fan-out.  The live rows are batched into a row-format
        block (one materialized ``Column`` per predicate column) and run
        through the same vectorized ``Predicate.eval`` path as baseline
        blocks, instead of row-at-a-time Python evaluation.  Checks the
        per-query ``deadline`` between materialization stages so a
        write-heavy scan (large incremental set) can't blow past
        ``deadline_s`` inside merge-on-read assembly."""
        if deadline is not None:
            deadline.check()
        live = [v.row for v in inc.values() if v.op != DmlType.DELETE]
        if not live or not preds:
            return live
        mask = np.ones(len(live), bool)
        for p in preds:
            if deadline is not None:
                deadline.check()
            col = Column.from_values(self.schema.spec(p.column),
                                     [r[p.column] for r in live])
            mask &= p.eval(col)
        return [r for r, keep in zip(live, mask) if keep]

    def _merged_table(self, ts: int) -> Table:
        """The table as of ``ts``, built column-wise: decoded baseline rows
        that no incremental version overrides, then the live incremental
        rows.  Column types (string widths, NULL fills) come out exactly as
        ``Table.from_rows`` over the merged rows would give them."""
        base = self.baseline
        inc = self._incremental_effective(ts)
        keep = np.ones(base.nrows, bool)
        if inc and base.nrows:
            keep[[i for i in map(base.locate, inc) if i >= 0]] = False
        cols: Dict[str, Column] = {}
        for spec in self.schema.columns:
            cst = base.cols[spec.name]
            vals = cst.decode_all()[keep] if base.nrows \
                else np.empty((0,), spec.ctype.np_dtype)
            nulls = None
            if cst.null_blocks is not None and base.nrows:
                nulls = np.concatenate(cst.null_blocks)[keep]
                vals[nulls] = b"" if spec.ctype == ColType.STR else 0
                nulls = nulls if nulls.any() else None
            if spec.ctype != ColType.STR:
                vals = vals.astype(spec.ctype.np_dtype, copy=False)
            cols[spec.name] = Column(spec, vals, nulls)
        tbl = Table(self.schema, cols)
        live = [v.row for v in inc.values() if v.op != DmlType.DELETE]
        if live:
            tbl = tbl.concat(Table.from_rows(self.schema, live))
        for name, c in tbl.columns.items():
            if c.spec.ctype == ColType.STR:
                c.values = shrink_bytes(c.values.astype(np.bytes_, copy=False))
        return tbl

    def get(self, pk: Any, ts: Optional[int] = None) -> Optional[Dict[str, Any]]:
        ts = self._ts if ts is None else ts
        v = self._find_version(pk, ts)
        if v is not None and v.ts > self.baseline.version:
            return None if v.op == DmlType.DELETE else dict(v.row)
        i = self.baseline.locate(pk)
        return self.baseline.row(i) if i >= 0 else None

    def scan(self, preds: Sequence[Predicate] = (), ts: Optional[int] = None,
             columns: Optional[Sequence[str]] = None,
             ) -> Tuple[Table, ScanStats]:
        """Merge-on-read scan with predicate pushdown into the baseline."""
        ts = self._ts if ts is None else ts
        columns = list(columns or self.schema.names)
        stats = ScanStats(used_pushdown=bool(preds))
        _rmark = _repair_mark(self)
        inc = self._incremental_effective(ts)
        stats.rows_merged_incremental = len(inc)

        # -- baseline: zone-map prune, then encoded-domain eval per block ----
        base = self.baseline
        nb = (base.nrows + self.block_rows - 1) // self.block_rows
        stats.blocks_total = nb
        keep_rows: List[np.ndarray] = []
        if base.nrows:
            verdicts = np.full(nb, Verdict.ALL.value, np.int8)
            for p in preds:
                verdicts = np.minimum(verdicts, base.cols[p.column].index.prune(p))
            for b in range(nb):
                lo = b * self.block_rows
                hi = min(lo + self.block_rows, base.nrows)
                if verdicts[b] == Verdict.NONE.value:
                    stats.blocks_skipped += 1
                    continue
                if verdicts[b] == Verdict.ALL.value and preds:
                    mask = np.ones(hi - lo, bool)
                    stats.blocks_sketch_only += 1
                else:
                    mask = np.ones(hi - lo, bool)
                    for p in preds:
                        cst = base.cols[p.column]
                        mask &= eval_block_pred(self.schema.spec(p.column),
                                                cst.blocks[b], p,
                                                cst.block_nulls(b))
                    stats.blocks_scanned += 1
                idx = np.nonzero(mask)[0] + lo
                keep_rows.append(idx)
        base_idx = np.concatenate(keep_rows) if keep_rows else np.empty((0,), np.int64)
        # Exclude baseline rows overridden by newer incremental versions.
        if inc and base_idx.size:
            over = np.asarray([base.locate(pk) for pk in inc], np.int64)
            over = over[over >= 0]
            if over.size:
                base_idx = base_idx[~np.isin(base_idx, over)]

        # -- vectorized columnar projection (paper §V 'storage
        # vectorization'): decode each surviving block once, gather by
        # column — never materializes per-row dicts.
        base_cols: Dict[str, np.ndarray] = {}
        base_nulls: Dict[str, Optional[np.ndarray]] = {}
        if base_idx.size:
            blk_ids = np.unique(base_idx // self.block_rows)
            # base_idx ascends, so each block's rows are one slice of it
            cuts = np.searchsorted(base_idx, np.append(blk_ids, blk_ids[-1] + 1)
                                   * self.block_rows)
            for name in columns:
                parts = []
                nparts = []
                cst = base.cols[name]
                for i, b in enumerate(blk_ids):
                    lo = int(b) * self.block_rows
                    dec = cst.decode_block(int(b))
                    sel = base_idx[cuts[i]:cuts[i + 1]] - lo
                    parts.append(dec[sel])
                    bn = cst.block_nulls(int(b))
                    nparts.append(np.zeros(sel.shape[0], bool)
                                  if bn is None else bn[sel])
                base_cols[name] = np.concatenate(parts)
                nmask = np.concatenate(nparts)
                base_nulls[name] = nmask if nmask.any() else None
        else:
            base_cols = {name: None for name in columns}
            base_nulls = {name: None for name in columns}

        # -- incremental rows: vectorized predicate eval (row format) -------
        inc_rows = self.live_incremental_rows(inc, preds)
        sub_schema = Schema(tuple(self.schema.spec(c) for c in columns))
        out_cols: Dict[str, Column] = {}
        for name in columns:
            spec = self.schema.spec(name)
            parts = []
            nparts = []
            if base_cols.get(name) is not None:
                parts.append(base_cols[name])
                nparts.append(base_nulls[name]
                              if base_nulls[name] is not None
                              else np.zeros(base_cols[name].shape[0], bool))
            if inc_rows:
                inc_col = Column.from_values(spec,
                                             [r[name] for r in inc_rows])
                vals = inc_col.values
                if parts and vals.dtype != parts[0].dtype:
                    vals = vals.astype(parts[0].dtype)
                parts.append(vals)
                nparts.append(inc_col.nulls if inc_col.nulls is not None
                              else np.zeros(len(inc_rows), bool))
            if parts:
                merged = (np.concatenate(parts) if len(parts) > 1
                          else parts[0])
                nmask = (np.concatenate(nparts) if len(nparts) > 1
                         else nparts[0])
            else:
                merged = np.empty(
                    (0,), dtype=spec.ctype.np_dtype
                    if spec.ctype != ColType.STR else "S1")
                nmask = np.zeros(0, bool)
            out_cols[name] = Column(spec, merged,
                                    nmask if nmask.any() else None)
        tbl = Table(sub_schema, out_cols)
        _collect_repairs(self, _rmark, stats)
        return tbl, stats

    # --- aggregate pushdown -----------------------------------------------------

    def aggregate(self, agg: str, column: Optional[str] = None,
                  preds: Sequence[Predicate] = (), ts: Optional[int] = None,
                  ) -> Tuple[Any, ScanStats]:
        """count/sum/min/max/avg with pushdown: answered from skipping-index
        sketches wherever blocks are fully covered and unaffected by
        incremental data; falls back to merged scan otherwise."""
        ts = self._ts if ts is None else ts
        stats = ScanStats(used_pushdown=True)
        _rmark = _repair_mark(self)
        inc = self._incremental_effective(ts)
        base = self.baseline
        col = column or self.schema.pk
        overridden = [pk for pk in inc if base.locate(pk) >= 0]
        non_distributive = agg in ("min", "max")

        if not preds and not inc and base.nrows:
            idx = base.cols[col].index
            v = idx.try_aggregate("count_star" if agg == "count" and column is None else agg)
            if v is not None:
                stats.blocks_sketch_only = idx.n_blocks
                stats.blocks_total = idx.n_blocks
                return v, stats

        if inc and (non_distributive or preds):
            # Correct-but-slower path: merged scan (same answer as oracle).
            tbl, sstats = self.scan(preds, ts, columns=[col])
            return _agg_over(tbl.col(col), agg, column is None), sstats

        if not base.nrows and not inc:
            return (0 if agg == "count" else None), stats

        # Distributive aggregate with pushdown: sketch-covered blocks + scan
        # of partial blocks + incremental correction (count/sum only).
        nb = (base.nrows + self.block_rows - 1) // self.block_rows
        stats.blocks_total = nb
        verdicts = np.full(nb, Verdict.ALL.value, np.int8)
        for p in preds:
            verdicts = np.minimum(verdicts, base.cols[p.column].index.prune(p))
        total_count, total_sum = 0, 0.0
        vmin, vmax = None, None
        for b in range(nb):
            lo = b * self.block_rows
            hi = min(lo + self.block_rows, base.nrows)
            if verdicts[b] == Verdict.NONE.value:
                stats.blocks_skipped += 1
                continue
            if verdicts[b] == Verdict.ALL.value:
                leaf = base.cols[col].index.nodes[b].sketch
                total_count += leaf.count - (0 if column is None else leaf.null_count)
                if leaf.vsum is not None:
                    total_sum += leaf.vsum
                if leaf.vmin is not None:
                    vmin = leaf.vmin if vmin is None else min(vmin, leaf.vmin)
                    vmax = leaf.vmax if vmax is None else max(vmax, leaf.vmax)
                stats.blocks_sketch_only += 1
                continue
            stats.blocks_scanned += 1
            mask = np.ones(hi - lo, bool)
            for p in preds:
                cst = base.cols[p.column]
                mask &= eval_block_pred(self.schema.spec(p.column),
                                        cst.blocks[b], p, cst.block_nulls(b))
            # count(*) counts every matching row; count/sum/min/max over a
            # column skip its NULL slots (fill values in the decode).
            bn = base.cols[col].block_nulls(b)
            vmask = mask if bn is None else (mask & ~bn)
            vals = base.cols[col].decode_block(b)[vmask]
            total_count += int(mask.sum() if column is None else vmask.sum())
            if vals.size and vals.dtype.kind in "iuf":
                total_sum += float(vals.sum())
            if vals.size:
                vmin = vals.min() if vmin is None else min(vmin, vals.min())
                vmax = vals.max() if vmax is None else max(vmax, vals.max())
        # Incremental correction for distributive aggs:
        for pk, v in inc.items():
            i = base.locate(pk)
            if i >= 0:  # subtract old baseline contribution
                old = base.row(i)
                if _row_matches(old, preds, self.schema):
                    if column is None or old[col] is not None:
                        total_count -= 1
                    if isinstance(old[col], (int, float)):
                        total_sum -= old[col]
            if v.op != DmlType.DELETE and _row_matches(v.row, preds, self.schema):
                if column is None or v.row[col] is not None:
                    total_count += 1
                if isinstance(v.row[col], (int, float)):
                    total_sum += v.row[col]
        stats.rows_merged_incremental = len(inc)
        _collect_repairs(self, _rmark, stats)
        if agg == "count":
            return total_count, stats
        if agg == "sum":
            return total_sum, stats
        if agg == "avg":
            return (total_sum / total_count if total_count else None), stats
        if agg == "min":
            return vmin, stats
        if agg == "max":
            return vmax, stats
        raise ValueError(agg)

    # --- introspection ------------------------------------------------------

    def has_quarantined_blocks(self) -> bool:
        """True when any baseline block failed checksum verification —
        such a store is excluded from MAV rewrite eligibility (a container
        built over corrupted blocks cannot be trusted)."""
        return any(c.quarantined for c in self.baseline.cols.values())

    def incremental_fraction(self) -> float:
        inc = len(self.memtable) + sum(len(m) for m in self.minors)
        total = inc + self.baseline.nrows
        return inc / total if total else 0.0

    def nbytes(self) -> Dict[str, int]:
        return {
            "baseline": self.baseline.nbytes(),
            "incremental_rows": len(self.memtable) + sum(len(m) for m in self.minors),
        }


def eval_block_pred(spec, enc: EncodedColumn, pred: Predicate,
                    nulls: Optional[np.ndarray]) -> np.ndarray:
    """Null-aware predicate mask over one encoded baseline block.

    Encodings store fill values in NULL slots and know nothing about the
    bitmap, so the encoded-domain fast path (``eval_pred``) must be masked
    with the block's NULL bitmap afterwards (a NULL never satisfies a value
    predicate), and IS_NULL / NOT_NULL are answered from the bitmap alone.
    Shared by ``LSMStore.scan``/``aggregate`` and the pushdown executors.
    """
    if pred.op in (PredOp.IS_NULL, PredOp.NOT_NULL):
        m = nulls if nulls is not None else np.zeros(len(enc), bool)
        return m.copy() if pred.op == PredOp.IS_NULL else ~m
    m = enc.eval_pred(pred)
    if m is None:
        return pred.eval(Column(spec, enc.decode(), nulls))
    return m & ~nulls if nulls is not None else m


def _row_matches(row: Dict[str, Any], preds: Sequence[Predicate], sch: Schema) -> bool:
    for p in preds:
        col = Column.from_values(sch.spec(p.column), [row[p.column]])
        if not p.eval(col)[0]:
            return False
    return True


def _agg_over(col: Column, agg: str, count_star: bool):
    v = col.values
    valid = v if col.nulls is None else v[~col.nulls]
    if agg == "count":
        return len(v) if count_star else len(valid)
    if valid.size == 0:
        return None
    if agg == "sum":
        return float(valid.sum()) if valid.dtype.kind == "f" else int(valid.sum())
    if agg == "avg":
        return float(valid.mean())
    if agg == "min":
        m = valid.min()
        return m.item() if hasattr(m, "item") else m
    if agg == "max":
        m = valid.max()
        return m.item() if hasattr(m, "item") else m
    raise ValueError(agg)
