"""Per-table append-only write-ahead log (paper §IV durability; PolarDB-IMCI
REDO replay and L-Store lineage recovery are the references in PAPERS.md).

Every committed mutation of an :class:`~.lsm.LSMStore` attached to a durable
``Database`` appends one checksummed, epoch-stamped record *before* it is
acknowledged: DML (insert/update/delete, with the update logged as the full
post-image so replaying ``store.update(pk, row)`` reproduces the original
merge exactly), direct loads, major-compaction baseline-swap markers,
MAV/MJV registrations, and mlog purge horizons.  Recovery
(``core/recovery.py``) replays the tail past the last snapshot through the
normal DML path and cross-checks the produced ``(ts, gen)`` epoch against
every record's stamp, so a divergent replay is a typed
:class:`~.errors.RecoveryError`, never a silently different store.

On-disk format, per frame::

    b"WR" | <u32 payload length> | <u32 crc32(payload)> | payload

with the payload a pickled ``(kind, seq, ts, gen, data)`` tuple — or, for
a group-commit batch flushed together, a pickled *list* of those tuples
(one pickle + one crc + one write per batch is what amortizes the framing
cost to sub-microsecond per record).  ``seq``
is the per-table monotone record number — the snapshot stores the seq it
covers, replay starts right after it.  The CRC catches every single-bit
flip (it is the same CRC32 the block checksums use); the frame length makes
torn tails self-delimiting:

* **torn tail** — the file ends mid-record (crash between ``write`` and
  completion): :func:`scan_wal` returns the longest valid prefix, which is
  exactly the committed prefix, and flags ``torn`` so the next append can
  truncate the garbage.
* **corrupt record** — a *complete* frame whose magic or CRC does not
  match (bit rot, not a crash): the suffix cannot be trusted, so the scan
  raises :class:`~.errors.RecoveryError` instead of replaying around it.

Group commit: ``WriteAheadLog(group_commit=k)`` buffers appends and writes
them as one batch frame every ``k`` records (the serving path's batching —
``QueryServer.drain`` and ``db.flush_wal`` force the tail out).  A crash
loses at most the unflushed suffix of *unacknowledged-as-flushed* records,
which still recovers a committed prefix; ``group_commit=1`` (the default)
makes every append durable before the statement returns.
"""
from __future__ import annotations

import os
import pickle
import struct
import threading
import zlib
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from . import faultinject, spans
from .errors import RecoveryError

#: Frame magic: marks the start of every record.
MAGIC = b"WR"

#: Frame header after the magic: ``<u32 payload length, u32 crc32>``.
HEADER = struct.Struct("<II")

#: Record kinds recovery knows how to replay (doc + validation surface).
KINDS = ("create_table", "insert", "update", "delete", "bulk_insert",
         "bulk_rows", "major_compact", "create_mav", "create_mjv", "purge")


@dataclass(frozen=True)
class WalRecord:
    """One decoded log record.

    ``ts``/``gen`` are the table epoch *after* the mutation (for markers
    like ``purge`` that move neither, the epoch at append time) — replay
    asserts the restored store reproduces them exactly.
    """

    kind: str
    seq: int
    ts: int
    gen: int
    data: Dict[str, Any] = field(default_factory=dict)


def _frame_payload(obj: Any) -> bytes:
    """Frame one payload object: magic + length + crc32 + pickle."""
    payload = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    return MAGIC + HEADER.pack(len(payload), zlib.crc32(payload)) + payload


def _frame(kind: str, seq: int, ts: int, gen: int,
           data: Dict[str, Any]) -> bytes:
    """Frame one record on its own (the ``group_commit=1`` shape)."""
    return _frame_payload((kind, seq, ts, gen, data))


def encode_record(rec: WalRecord) -> bytes:
    return _frame(rec.kind, rec.seq, rec.ts, rec.gen, rec.data)


def decode_frame(buf: bytes) -> List[WalRecord]:
    """Decode one *complete* frame (magic + header + full payload) into its
    records — one for a single-record payload, several for a group-commit
    batch.  Raises :class:`RecoveryError` on bad magic, a CRC mismatch, or
    an unpicklable payload — a complete-but-wrong frame is corruption, not
    a torn tail."""
    if buf[:2] != MAGIC:
        raise RecoveryError(f"bad WAL record magic {buf[:2]!r}")
    length, crc = HEADER.unpack_from(buf, 2)
    payload = buf[2 + HEADER.size:2 + HEADER.size + length]
    if len(payload) != length:
        raise RecoveryError("WAL record shorter than its declared length")
    if zlib.crc32(payload) != crc:
        raise RecoveryError(
            f"WAL record checksum mismatch: expected {crc:#010x}, "
            f"got {zlib.crc32(payload):#010x}")
    try:
        obj = pickle.loads(payload)
        raw = obj if isinstance(obj, list) else [obj]
        return [WalRecord(kind, seq, ts, gen, data)
                for kind, seq, ts, gen, data in raw]
    except RecoveryError:
        raise
    # lint: allow(broad-except) — typed-wrap boundary: decode failures
    # of any kind are corruption, reported as RecoveryError
    except Exception as e:                 # checksum passed, pickle didn't:
        raise RecoveryError(               # still corruption, still typed
            f"WAL record payload undecodable: {type(e).__name__}: {e}")


def decode_record(buf: bytes) -> WalRecord:
    """Decode a frame that must hold exactly one record."""
    records = decode_frame(buf)
    if len(records) != 1:
        raise RecoveryError(
            f"expected a single-record frame, got {len(records)} records")
    return records[0]


def scan_wal(path: str) -> Tuple[List[WalRecord], bool, int]:
    """Read every complete, verified record from ``path``.

    Returns ``(records, torn, valid_bytes)``: the longest valid prefix, a
    flag for a torn (incomplete) tail frame, and the byte offset the valid
    prefix ends at (where a post-recovery append must resume).  A complete
    frame that fails its magic/CRC check raises :class:`RecoveryError` —
    truncation yields an *incomplete* frame, so a bad complete frame means
    bit rot and the suffix past it cannot be trusted.  A missing file is an
    empty log."""
    if not os.path.exists(path):
        return [], False, 0
    with open(path, "rb") as f:
        buf = f.read()
    records: List[WalRecord] = []
    off = 0
    frame_head = 2 + HEADER.size
    while off < len(buf):
        rest = len(buf) - off
        if rest < frame_head:
            return records, True, off          # torn mid-header
        length, _ = HEADER.unpack_from(buf, off + 2)
        if rest < frame_head + length:
            return records, True, off          # torn mid-payload
        records.extend(decode_frame(buf[off:off + frame_head + length]))
        off += frame_head + length
    return records, False, off


class WriteAheadLog:
    """Append side of one table's log.

    ``append`` assigns the next ``seq``, stamps the record with the caller's
    epoch, and buffers it; the buffer is written (one ``os.write``, then
    flush) every ``group_commit`` records or on :meth:`flush`.  All methods
    are thread-safe — DML already serializes under the store lock, but
    snapshots and the serving drain flush from other threads."""

    def __init__(self, path: str, group_commit: int = 1, table: str = ""):
        self.path = path
        self.table = table
        self.group_commit = max(1, int(group_commit))
        self.seq = 0                      # last assigned record number
        # buffered (kind, seq, ts, gen, data) tuples; framed at flush so
        # the per-statement commit path stays a lock + list append
        self._pending: List[Tuple[str, int, int, int, Dict[str, Any]]] = []
        self._lock = threading.Lock()
        self._fd: Optional[int] = None    # persistent O_APPEND descriptor

    @classmethod
    def open_for_append(cls, path: str, group_commit: int = 1,
                        table: str = "") -> Tuple["WriteAheadLog",
                                                  List[WalRecord], bool]:
        """Open an existing (or absent) log for appending: scan it, truncate
        a torn tail so new frames never land after garbage, and continue the
        seq numbering.  Returns ``(wal, records, torn)``."""
        records, torn, valid = scan_wal(path)
        if torn:
            with open(path, "rb+") as f:
                f.truncate(valid)
        wal = cls(path, group_commit, table)
        wal.seq = records[-1].seq if records else 0
        return wal, records, torn

    def append(self, kind: str, ts: int, gen: int,
               data: Optional[Dict[str, Any]] = None) -> int:
        """Log one record; returns its seq.  The deterministic kill points
        (``FaultPlan.crash_wal_append``) fire here — *before* the record is
        buffered, or *after* it is flushed — so crash tests pin the exact
        durability boundary of a statement."""
        fp = faultinject.active()
        if fp is not None:
            fp.on_wal_append(self.table, "before")
        with self._lock:
            self.seq += 1
            seq = self.seq
            self._pending.append((kind, seq, ts, gen, data or {}))
            if len(self._pending) >= self.group_commit:
                self._flush_locked()
        if fp is not None:
            fp.on_wal_append(self.table, "after")
        return seq

    def flush(self) -> None:
        """Force the buffered tail to disk (group-commit boundary)."""
        with self._lock:
            self._flush_locked()

    def _flush_locked(self) -> None:
        if not self._pending:
            return
        with spans.span("ob.wal_flush"):
            if len(self._pending) == 1:
                buf = _frame(*self._pending[0])
            else:
                # one frame per group-commit batch: a single pickle + crc32 +
                # write amortizes the framing to well under a microsecond per
                # record, which is what makes the serving path's batched WAL
                # nearly free on the clean path
                buf = _frame_payload(list(self._pending))
            # the append descriptor stays open across flushes (reopening per
            # statement at group_commit=1 would dominate the clean-path cost);
            # compact() closes it around the atomic rewrite
            if self._fd is None:
                self._fd = os.open(
                    self.path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
            os.write(self._fd, buf)
            self._pending.clear()

    def pending(self) -> int:
        with self._lock:
            return len(self._pending)

    def compact(self, snapshot_seq: int) -> int:
        """Drop records a snapshot now covers: rewrite the log keeping only
        ``seq > snapshot_seq`` (atomic temp + ``os.replace``, called strictly
        *after* the snapshot itself replaced).  Returns records kept."""
        with self._lock:
            self._flush_locked()
            if self._fd is not None:      # the rewrite swaps the inode:
                os.close(self._fd)        # a stale descriptor would append
                self._fd = None           # to the unlinked file
            records, torn, _ = scan_wal(self.path)
            keep = [r for r in records if r.seq > snapshot_seq]
            tmp = self.path + ".tmp"
            with open(tmp, "wb") as f:
                for rec in keep:
                    f.write(encode_record(rec))
                f.flush()
            os.replace(tmp, self.path)
            return len(keep)
