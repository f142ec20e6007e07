"""Column encodings (paper §III-E) — queryable without decompression.

OceanBase Mercury's first compression level is a set of built-in, in-database
encodings that (a) support direct query evaluation on encoded data and
(b) are designed for fully-vectorized execution.  We implement the encodings
the paper names — delta (frame-of-reference), dictionary, prefix /
multi-prefix, inter-column equality and inter-column prefix ("substring") —
plus RLE-constant, over numpy column buffers.  The second level ("general
compression", LZ4 in the paper) is modelled with zlib (the only codec
available offline); it is only used for at-rest byte counting, never for the
query path, exactly as in the paper.

TPU adaptation note: decode paths are expressed as vectorizable gathers /
affine transforms (code * 1 + base, dict[code], prefix_len-sliced copies) so
the same layouts can be consumed by Pallas kernels operating on int32 code
lanes; see kernels/columnar_scan.py which evaluates predicates directly on
dictionary codes and FOR deltas, and kernels/hybrid_decode.py which fuses
int8 dequantization (an encoding) into attention.
"""
from __future__ import annotations

import dataclasses
import math
import zlib
from typing import Any, List, Optional, Tuple

import numpy as np

from .relation import Column, ColType, ColumnSpec, PredOp, Predicate

# ---------------------------------------------------------------------------
# Base
# ---------------------------------------------------------------------------


class EncodedColumn:
    """Base class: an immutable encoded block of one column."""

    kind: str = "plain"

    def __len__(self) -> int:  # pragma: no cover - overridden
        raise NotImplementedError

    def decode(self) -> np.ndarray:
        raise NotImplementedError

    def decode_idx(self, idx: np.ndarray) -> np.ndarray:
        """Late materialization: decode only the rows in ``idx``.

        Encodings with random access (plain/delta/dict/const) override this
        with an O(|idx|) gather; the base fallback decodes the whole block.
        """
        return self.decode()[idx]

    def nbytes(self) -> int:
        raise NotImplementedError

    # --- encoded-domain query support -------------------------------------
    def eval_pred(self, pred: Predicate) -> Optional[np.ndarray]:
        """Evaluate a predicate directly on encoded data.

        Returns a bool mask, or None when this encoding cannot answer the
        predicate without decoding (caller then decodes and evaluates).
        """
        return None

    def pred_window(self, pred: Predicate) -> Optional[Tuple[int, int]]:
        """Row window [lo, hi) containing exactly the matches of a *range*
        predicate, for encodings that know the block is internally sorted —
        sub-block scan granularity: two binary searches replace a full-block
        compare, and the caller materializes only the window.  None when the
        encoding cannot answer (unsorted block, unsupported op)."""
        return None

    def agg_min_max(self) -> Optional[Tuple[Any, Any]]:
        return None


def payload_checksum(enc: EncodedColumn) -> int:
    """CRC32 over an encoded block's payload — every dataclass field, with
    ndarray fields hashed by raw bytes and scalars by repr.  Computed once
    at baseline build time and re-checked (memoized) on first decode/view,
    so a bit flip in any encoded buffer surfaces as ``BlockCorruption``
    instead of a silently wrong answer."""
    crc = zlib.crc32(enc.kind.encode())

    def fold(crc: int, v) -> int:
        if isinstance(v, np.ndarray):
            return zlib.crc32(np.ascontiguousarray(v).tobytes(), crc)
        if isinstance(v, (list, tuple)):
            for x in v:
                crc = fold(crc, x)
            return crc
        if isinstance(v, EncodedColumn):
            return zlib.crc32(str(payload_checksum(v)).encode(), crc)
        return zlib.crc32(repr(v).encode(), crc)

    for f in dataclasses.fields(enc):
        crc = zlib.crc32(f.name.encode(), crc)
        crc = fold(crc, getattr(enc, f.name))
    return crc


def clone_block(enc: EncodedColumn) -> EncodedColumn:
    """Deep, independent copy of an encoded block: every ndarray payload is
    materialized into fresh memory (no aliasing with the source), nested
    encodings recurse, scalars copy by value.  This is the replica-copy
    primitive of ``core/replica.py`` — a clone must keep verifying against
    the source's build-time ``payload_checksum`` while staying immune to
    corruption of the source's buffers (and vice versa)."""

    def dup(v):
        if isinstance(v, np.ndarray):
            return np.ascontiguousarray(v).copy()
        if isinstance(v, list):
            return [dup(x) for x in v]
        if isinstance(v, tuple):
            return tuple(dup(x) for x in v)
        if isinstance(v, EncodedColumn):
            return clone_block(v)
        return v

    return dataclasses.replace(
        enc, **{f.name: dup(getattr(enc, f.name))
                for f in dataclasses.fields(enc)})


def _pack_codes(codes: np.ndarray) -> np.ndarray:
    """Narrow integer codes to the smallest unsigned dtype that fits."""
    if codes.size == 0:
        return codes.astype(np.uint8)
    hi = int(codes.max(initial=0))
    for dt in (np.uint8, np.uint16, np.uint32):
        if hi <= np.iinfo(dt).max:
            return codes.astype(dt)
    return codes.astype(np.uint64)


# ---------------------------------------------------------------------------
# Plain
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PlainEncoded(EncodedColumn):
    kind = "plain"
    values: np.ndarray

    def __len__(self):
        return int(self.values.shape[0])

    def decode(self):
        return self.values

    def decode_idx(self, idx):
        return self.values[idx]

    def nbytes(self):
        return self.values.nbytes

    def eval_pred(self, pred):
        return None  # caller evaluates on .decode() (no savings, but correct)

    def agg_min_max(self):
        if len(self) == 0:
            return None
        return self.values.min(), self.values.max()


# ---------------------------------------------------------------------------
# Delta / frame-of-reference for fixed-width numerics (paper's "delta")
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DeltaFOREncoded(EncodedColumn):
    """Store min + per-row offsets in the narrowest dtype ("delta" encoding).

    Supports direct range/equality predicates by transforming the constant
    into the offset domain — query without decompression.
    """

    kind = "delta_for"
    base: int
    deltas: np.ndarray  # unsigned, narrow
    out_dtype: np.dtype

    def __len__(self):
        return int(self.deltas.shape[0])

    @property
    def is_sorted(self) -> bool:
        """Whether this block's rows are non-decreasing (cached O(n) check):
        sorted FOR blocks answer range predicates with a binary-searched row
        window instead of a full-lane compare (``pred_window``)."""
        s = getattr(self, "_is_sorted", None)
        if s is None:
            d = self.deltas
            s = bool(d.shape[0] < 2 or np.all(d[1:] >= d[:-1]))
            object.__setattr__(self, "_is_sorted", s)
        return s

    @staticmethod
    def encode(values: np.ndarray) -> "DeltaFOREncoded":
        assert np.issubdtype(values.dtype, np.integer)
        base = int(values.min()) if values.size else 0
        deltas = (values.astype(np.int64) - base)
        return DeltaFOREncoded(base, _pack_codes(deltas), values.dtype)

    def decode(self):
        return (self.deltas.astype(np.int64) + self.base).astype(self.out_dtype)

    def decode_idx(self, idx):
        return (self.deltas[idx].astype(np.int64) + self.base).astype(self.out_dtype)

    def nbytes(self):
        return self.deltas.nbytes + 8

    def eval_pred(self, pred):
        if pred.op in (PredOp.IS_NULL, PredOp.NOT_NULL, PredOp.IN):
            return None
        d = self.deltas.astype(np.int64)
        # Shift the constant into the offset domain without int() truncation:
        # a float constant (e.g. d >= 100.5) must keep its fractional part so
        # the comparison matches the decoded-domain evaluation exactly.
        v = pred.value - self.base
        if pred.op == PredOp.EQ:
            return d == v
        if pred.op == PredOp.NE:
            return d != v
        if pred.op == PredOp.LT:
            return d < v
        if pred.op == PredOp.LE:
            return d <= v
        if pred.op == PredOp.GT:
            return d > v
        if pred.op == PredOp.GE:
            return d >= v
        if pred.op == PredOp.BETWEEN:
            return (d >= v) & (d <= pred.value2 - self.base)
        return None

    def _search(self, v, side: str) -> int:
        """Binary search in the offset domain without dtype promotion: a
        float or out-of-range needle would silently upcast (and copy) the
        whole delta array, turning the O(log n) probe into O(n).  Fractional
        constants round to the equivalent integer bound ('left' of v ==
        'left' of ceil(v); 'right' of v == 'right' of floor(v)), so the
        window still equals ``eval_pred`` exactly."""
        if isinstance(v, float):
            v = int(v) if v.is_integer() else (
                math.ceil(v) if side == "left" else math.floor(v))
        d = self.deltas
        if v < 0:
            return 0
        if v > np.iinfo(d.dtype).max:
            return int(d.shape[0])
        return int(np.searchsorted(d, d.dtype.type(v), side))

    def pred_window(self, pred):
        """Sub-block granularity on sorted FOR blocks: the match set of a
        range predicate is one contiguous row run, found with two binary
        searches in the offset domain."""
        if pred.op not in (PredOp.EQ, PredOp.LT, PredOp.LE, PredOp.GT,
                           PredOp.GE, PredOp.BETWEEN) or not self.is_sorted:
            return None
        n = len(self)
        v = pred.value - self.base
        if pred.op == PredOp.EQ:
            return (self._search(v, "left"), self._search(v, "right"))
        if pred.op == PredOp.LT:
            return (0, self._search(v, "left"))
        if pred.op == PredOp.LE:
            return (0, self._search(v, "right"))
        if pred.op == PredOp.GT:
            return (self._search(v, "right"), n)
        if pred.op == PredOp.GE:
            return (self._search(v, "left"), n)
        return (self._search(v, "left"),
                self._search(pred.value2 - self.base, "right"))

    def agg_min_max(self):
        if len(self) == 0:
            return None
        d = self.deltas
        return self.base + int(d.min()), self.base + int(d.max())


# ---------------------------------------------------------------------------
# Dictionary (low-NDV) — the group-by pushdown substrate (paper §III-G)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DictEncoded(EncodedColumn):
    kind = "dict"
    dictionary: np.ndarray  # sorted unique values
    codes: np.ndarray       # narrow unsigned, index into dictionary

    def __len__(self):
        return int(self.codes.shape[0])

    @staticmethod
    def encode(values: np.ndarray) -> "DictEncoded":
        dictionary, codes = np.unique(values, return_inverse=True)
        return DictEncoded(dictionary, _pack_codes(codes))

    def decode(self):
        return self.dictionary[self.codes]

    def decode_idx(self, idx):
        return self.dictionary[self.codes[idx]]

    def nbytes(self):
        return self.dictionary.nbytes + self.codes.nbytes

    @property
    def ndv(self) -> int:
        return int(self.dictionary.shape[0])

    def eval_pred(self, pred):
        # Evaluate the predicate once per dictionary entry, then gather by
        # code: O(NDV + N) instead of O(N) value comparisons on wide data.
        if pred.op in (PredOp.IS_NULL, PredOp.NOT_NULL):
            return None
        dcol = Column(ColumnSpec("d", _ctype_of(self.dictionary)), self.dictionary)
        dmask = Predicate("d", pred.op, pred.value, pred.value2).eval(dcol)
        return dmask[self.codes]

    def agg_min_max(self):
        if self.ndv == 0:
            return None
        return self.dictionary[0], self.dictionary[-1]  # dictionary is sorted


# ---------------------------------------------------------------------------
# RLE-constant
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ConstEncoded(EncodedColumn):
    kind = "const"
    value: np.ndarray  # 0-d
    count: int

    def __len__(self):
        return self.count

    def decode(self):
        return np.broadcast_to(self.value, (self.count,)).copy()

    def decode_idx(self, idx):
        return np.broadcast_to(self.value, (len(idx),)).copy()

    def nbytes(self):
        return int(self.value.nbytes) + 4

    def eval_pred(self, pred):
        if pred.op in (PredOp.IS_NULL, PredOp.NOT_NULL):
            return None
        col = Column(ColumnSpec("c", _ctype_of(self.value.reshape(1))), self.value.reshape(1))
        one = Predicate("c", pred.op, pred.value, pred.value2).eval(col)[0]
        return np.full(self.count, bool(one))

    def agg_min_max(self):
        v = self.value[()] if self.value.shape == () else self.value
        return v, v


# ---------------------------------------------------------------------------
# Prefix / multi-prefix for byte-string columns
# ---------------------------------------------------------------------------


def _common_prefix_len(a: bytes, b: bytes) -> int:
    n = min(len(a), len(b))
    i = 0
    while i < n and a[i] == b[i]:
        i += 1
    return i


def shrink_bytes(values: np.ndarray) -> np.ndarray:
    """Retype a bytes array to its widest element (``S1`` when empty), the
    dtype ``np.asarray(list_of_bytes, np.bytes_)`` gives the same values."""
    width = int(np.strings.str_len(values).max()) if values.size else 0
    return values.astype(f"S{max(width, 1)}")


def _drop_prefix(values: np.ndarray, k: np.ndarray) -> np.ndarray:
    """Row-wise ``values[i][k[i]:]`` over a fixed-width bytes array."""
    if not k.any():
        return shrink_bytes(values)
    n, w = values.shape[0], values.dtype.itemsize
    mat = np.ascontiguousarray(values).view(np.uint8).reshape(n, w)
    idx = k.astype(np.int64)[:, None] + np.arange(w)
    out = np.where(idx < w, np.take_along_axis(mat, np.minimum(idx, w - 1),
                                               axis=1), 0).astype(np.uint8)
    return shrink_bytes(out.view(f"S{w}").reshape(n))


@dataclasses.dataclass
class MultiPrefixEncoded(EncodedColumn):
    """Paper's multi-prefix encoding: a small dictionary of shared prefixes,
    per-row (prefix_id, suffix).  Single shared prefix is the degenerate
    1-entry case (classic prefix encoding)."""

    kind = "multi_prefix"
    prefixes: List[bytes]
    prefix_ids: np.ndarray
    suffixes: np.ndarray  # bytes array
    out_dtype: np.dtype

    def __len__(self):
        return int(self.prefix_ids.shape[0])

    @staticmethod
    def encode(values: np.ndarray, max_prefixes: int = 16) -> "MultiPrefixEncoded":
        # Greedy prefix pool: bucket rows by their first 4 bytes, take the
        # longest common prefix within each of the most frequent buckets
        # (ties by first occurrence).  The common prefix of a bucket is the
        # common prefix of its lexicographic min and max.
        _, first, inv, counts = np.unique(values.astype("S4"),
                                          return_index=True,
                                          return_inverse=True,
                                          return_counts=True)
        prefixes: List[bytes] = []
        for head in np.lexsort((first, -counts))[:max_prefixes]:
            bucket = np.sort(values[inv.reshape(-1) == head])
            lo, hi = bytes(bucket[0]), bytes(bucket[-1])
            p = lo[: _common_prefix_len(lo, hi)]
            if len(p) >= 2:
                prefixes.append(p)
        ids = np.zeros(len(values), np.int64)  # 0 == no prefix
        best_len = np.zeros(len(values), np.int64)
        for j, p in enumerate(prefixes):   # first longest matching prefix
            hit = (len(p) > best_len) & np.strings.startswith(values, p)
            ids[hit] = j + 1
            best_len[hit] = len(p)
        return MultiPrefixEncoded(prefixes, _pack_codes(ids),
                                  _drop_prefix(values, best_len),
                                  values.dtype)

    def decode(self):
        table = [b""] + self.prefixes
        out = [table[int(i)] + bytes(s) for i, s in zip(self.prefix_ids, self.suffixes)]
        return np.asarray(out, dtype=self.out_dtype)

    def nbytes(self):
        return (sum(len(p) + 1 for p in self.prefixes) + self.prefix_ids.nbytes
                + int(self.suffixes.nbytes))

    def eval_pred(self, pred):
        # Prefix equality can short-circuit: rows whose prefix already
        # mismatches the constant's head never match EQ.
        if pred.op != PredOp.EQ or not isinstance(pred.value, (bytes, str)):
            return None
        target = pred.value.encode() if isinstance(pred.value, str) else pred.value
        table = [b""] + self.prefixes
        cand = np.asarray([target.startswith(p) for p in table])
        maybe = cand[self.prefix_ids]
        out = np.zeros(len(self), bool)
        idx = np.nonzero(maybe)[0]
        for i in idx:
            p = table[int(self.prefix_ids[i])]
            out[i] = p + bytes(self.suffixes[i]) == target
        return out


# ---------------------------------------------------------------------------
# Inter-column encodings (equality / prefix-of) — paper Fig 8 drivers
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class InterColumnEqualEncoded(EncodedColumn):
    """Column B mostly equals column A: store only exceptions."""

    kind = "inter_eq"
    ref: np.ndarray             # decoded reference column (not counted: shared)
    exc_idx: np.ndarray
    exc_vals: np.ndarray

    def __len__(self):
        return int(self.ref.shape[0])

    @staticmethod
    def encode(ref: np.ndarray, values: np.ndarray) -> "InterColumnEqualEncoded":
        neq = np.nonzero(ref != values)[0]
        return InterColumnEqualEncoded(ref, neq.astype(np.int64), values[neq])

    def decode(self):
        out = self.ref.copy()
        out[self.exc_idx] = self.exc_vals.astype(out.dtype, copy=False)
        return out

    def nbytes(self):
        # The reference column is stored once elsewhere; this encoding pays
        # only for the exception list.
        return self.exc_idx.nbytes + int(self.exc_vals.nbytes) + 8


@dataclasses.dataclass
class InterColumnPrefixEncoded(EncodedColumn):
    """Column A is a prefix of column B (paper: 'one column is the prefix of
    the other'): store the full column once and only B's suffixes."""

    kind = "inter_prefix"
    ref: np.ndarray
    suffixes: np.ndarray
    exc_idx: np.ndarray   # rows where A is NOT a prefix of B
    exc_vals: np.ndarray
    out_dtype: np.dtype

    def __len__(self):
        return int(self.ref.shape[0])

    @staticmethod
    def encode(ref: np.ndarray, values: np.ndarray) -> "InterColumnPrefixEncoded":
        ok = np.strings.startswith(values, ref)
        # exception rows keep an empty suffix: drop the whole value
        cut = np.where(ok, np.strings.str_len(ref), np.strings.str_len(values))
        exc_i = np.nonzero(~ok)[0].astype(np.int64)
        return InterColumnPrefixEncoded(ref, _drop_prefix(values, cut), exc_i,
                                        shrink_bytes(values[exc_i]), values.dtype)

    def decode(self):
        out = [bytes(a) + bytes(s) for a, s in zip(self.ref, self.suffixes)]
        arr = np.asarray(out, dtype=np.bytes_)
        if self.exc_idx.size:
            arr = arr.astype(max(arr.dtype, self.exc_vals.dtype))
            arr[self.exc_idx] = self.exc_vals
        return arr.astype(self.out_dtype, copy=False) if arr.dtype != self.out_dtype else arr

    def nbytes(self):
        return (int(self.suffixes.nbytes) + self.exc_idx.nbytes
                + int(self.exc_vals.nbytes) + 8)


# ---------------------------------------------------------------------------
# Adaptive selection (paper §III-B "adaptive store") + 2-level compression
# ---------------------------------------------------------------------------


def _ctype_of(arr: np.ndarray) -> ColType:
    if arr.dtype.kind in "S":
        return ColType.STR
    if arr.dtype.kind == "f":
        return ColType.FLOAT
    if arr.dtype.kind == "b":
        return ColType.BOOL
    return ColType.INT


def choose_encoding(values: np.ndarray,
                    peers: Optional[dict] = None,
                    allow_intercolumn: bool = True,
                    new_encodings: bool = True) -> EncodedColumn:
    """Pick the smallest applicable encoding (greedy cost-based, like the
    paper's adaptive store).  ``peers`` maps name->decoded peer columns for
    inter-column candidates.  ``new_encodings=False`` restricts the search
    to the original algorithms (plain/const/delta-FOR/dict) — the Fig 8
    baseline; the NEW encodings are multi-prefix + the inter-column pair."""
    n = values.shape[0]
    if n == 0:
        return PlainEncoded(values)
    cands: List[EncodedColumn] = [PlainEncoded(values)]
    uniq = np.unique(values)
    if uniq.shape[0] == 1:
        cands.append(ConstEncoded(np.asarray(values[0]), n))
    if np.issubdtype(values.dtype, np.integer):
        cands.append(DeltaFOREncoded.encode(values))
    if uniq.shape[0] <= max(256, n // 4):
        cands.append(DictEncoded.encode(values))
    if values.dtype.kind == "S" and new_encodings:
        cands.append(MultiPrefixEncoded.encode(values))
    if allow_intercolumn and new_encodings and peers:
        for _, ref in peers.items():
            if ref.shape != values.shape:
                continue
            if ref.dtype == values.dtype:
                eq = InterColumnEqualEncoded.encode(ref, values)
                if eq.exc_idx.size <= n // 4:
                    cands.append(eq)
            if ref.dtype.kind == "S" and values.dtype.kind == "S":
                pe = InterColumnPrefixEncoded.encode(ref, values)
                if pe.exc_idx.size <= n // 4:
                    cands.append(pe)
    return min(cands, key=lambda e: e.nbytes())


def general_compress_nbytes(enc: EncodedColumn, level: int = 1) -> int:
    """Second-level 'general compression' size (zlib stands in for LZ4)."""
    payloads = []
    for f in dataclasses.fields(enc):  # type: ignore[arg-type]
        v = getattr(enc, f.name)
        if isinstance(v, np.ndarray):
            payloads.append(v.tobytes())
        elif isinstance(v, list):
            payloads.append(b"".join(x if isinstance(x, bytes) else bytes(x) for x in v))
    blob = b"".join(payloads)
    return len(zlib.compress(blob, level))


def encode_column(col: Column, peers: Optional[dict] = None) -> EncodedColumn:
    return choose_encoding(col.values, peers=peers)
