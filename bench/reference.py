"""The plain reference and the comparison that decides ``correct``.

The reference imports nothing of the program.  It regenerates each table
from the seed with the benchmark's own generator, applies the refresh
writes the run acknowledged up to a query's snapshot, and answers the query
with a straightforward filter, group and float64 sum in numpy.

A query whose predicate is a range over one integer column, whose groups
are few and whose aggregates are counts, sums and averages is answered
from a cube of the baseline built once per shape (predicate column, group
keys, value columns): per predicate value and group, the row count and
the float64 sum of each value column.  The answer sums the cube's rows in
the range, then takes away the baseline rows the snapshot's deletes removed
and adds the inserted rows it sees, each through the same predicate.  Its
cost per answer does not grow with the table, so every answer of a fast
program's window can be checked.  Any other query goes the direct way
(``direct``), which the tests hold the cube to.

What the comparison holds the program to, each number with its limit:

* ``max_rel_err``: the largest relative error of any float aggregate of any
  answer.  The device sums float32 planes in one float32 accumulator per
  group over thousands of grid steps; on a TPU v5e sound runs read
  up to about 4e-6 at SF 1, a one-pass bfloat16 contraction in the kernel
  about 9e-5, and the reference computed from bfloat16 inputs (the
  control) 3e-4 to 2e-3.  ``PERF.md`` gives the readings the limit was
  set from.
* ``wrong_answers``: answers whose group keys or counts differ from the
  reference's (exact).
* ``unanswered``: queries due in the window that raised or never resolved.
* ``lost_writes``: acknowledged refresh writes not read back after the
  window (an inserted row not returned as written, a deleted one
  returned).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .traffic import RefQuery

LIMITS = {"max_rel_err": 2e-5, "wrong_answers": 0, "unanswered": 0,
          "lost_writes": 0}

CUBE_OPS = ("count", "sum", "avg")
CUBE_MAX_VALUES = 1 << 16       # predicate values a cube spans
CUBE_MAX_GROUPS = 1 << 12       # groups: the product of the keys' value sets


def _key(v) -> Any:
    """A group key as a plain comparable value."""
    if isinstance(v, (bytes, np.bytes_)):
        return bytes(v)
    if isinstance(v, (np.integer, int)):
        return int(v)
    if isinstance(v, (np.floating, float)):
        return float(v)
    return v


@dataclasses.dataclass
class Cube:
    """The baseline's rows of one shape, counted and summed per predicate
    value and group: ``cnt[v - lo, g]``, ``sums[c][v - lo, g]``."""

    lo: int
    cnt: np.ndarray
    sums: Dict[str, np.ndarray]
    uniq: List[np.ndarray]


@dataclasses.dataclass
class Writes:
    """The acknowledged writes as arrays: each insert's and delete's
    snapshot timestamp and primary key, and the inserted rows' columns;
    ``at``: the lengths of the lists they were made from."""

    at: Tuple[int, int]
    ins_ts: np.ndarray
    ins_pk: np.ndarray
    del_ts: np.ndarray
    del_pk: np.ndarray
    rows: List[Dict[str, Any]]
    cols: Dict[str, np.ndarray]

    def col(self, c: str) -> np.ndarray:
        if c not in self.cols:
            self.cols[c] = np.asarray([r[c] for r in self.rows])
        return self.cols[c]


def _in_range(q: RefQuery, x: np.ndarray) -> np.ndarray:
    """The rows of ``x`` (``q``'s predicate column) inside ``q``'s range."""
    mask = np.ones(x.shape[0], bool)
    if q.lo is not None:
        mask &= x >= q.lo
    if q.hi is not None:
        mask &= x <= q.hi
    return mask


class RefTable:
    """One table's rows as the reference sees them: the generated baseline
    plus the acknowledged refresh writes, each with the snapshot timestamp
    that made it visible."""

    def __init__(self, columns: Dict[str, np.ndarray], pk: str = "l_pk"):
        self.cols = columns
        self.pk = pk
        self.inserts: List[Tuple[int, Dict[str, Any]]] = []
        self.deletes: List[Tuple[int, int]] = []
        self._code_cache: Dict[str, Tuple[np.ndarray, np.ndarray]] = {}
        self._cubes: Dict[Tuple, Optional[Cube]] = {}
        self._pk_sorted: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self._writes: Optional[Writes] = None

    def _codes(self, col: str) -> Tuple[np.ndarray, np.ndarray]:
        """Sorted distinct values of a baseline column and each row's index
        into them, computed once."""
        hit = self._code_cache.get(col)
        if hit is None:
            u, c = np.unique(self.cols[col], return_inverse=True)
            hit = self._code_cache[col] = (u, c.reshape(-1))
        return hit

    def _state(self, ts: Optional[int], values: Sequence[str],
               keys: Sequence[str]):
        """Value columns and group-key codes as of snapshot ``ts`` (None:
        after every write): the baseline less the deleted rows, plus the
        inserted ones."""
        dels = [pk for t, pk in self.deletes if ts is None or t <= ts]
        gone = set(dels)
        ins = [r for t, r in self.inserts
               if (ts is None or t <= ts) and r[self.pk] not in gone]
        keep = (~np.isin(self.cols[self.pk], np.asarray(dels))
                if dels else None)

        def part(a: np.ndarray) -> np.ndarray:
            return a if keep is None else a[keep]

        out = {}
        for c in values:
            base = part(self.cols[c])
            if ins:
                base = np.concatenate(
                    [base, np.asarray([r[c] for r in ins], base.dtype)])
            out[c] = base
        codes = []
        for g in keys:
            u, c = self._codes(g)
            c = part(c)
            if ins:
                add = np.asarray([r[g] for r in ins], u.dtype)
                at = np.minimum(np.searchsorted(u, add), len(u) - 1)
                if (u[at] != add).any():       # a value the baseline lacks
                    u = np.unique(np.concatenate([u, add]))
                    c = np.searchsorted(u, part(self.cols[g]))
                    at = np.searchsorted(u, add)
                c = np.concatenate([c, at])
            codes.append((u, c))
        return out, codes

    def prepare(self, q: RefQuery, ts: Optional[int]):
        """What ``q`` reads at snapshot ``ts``: its value columns, the
        predicate's row mask, each row's packed group code (over the
        product of the keys' value sets) with that product, and the keys'
        value sets."""
        vals = sorted({c for _, c, _ in q.aggs if c is not None})
        names = sorted(set(vals) | ({q.column} if q.column else set()))
        st, codes = self._state(ts, names, q.group_by)
        n = len(codes[0][1]) if codes else len(st[names[0]])
        mask = np.ones(n, bool)
        if q.column is not None:
            col = st[q.column]
            if q.lo is not None:
                mask &= col >= q.lo
            if q.hi is not None:
                mask &= col <= q.hi
        packed = np.zeros(n, np.int64)
        size = 1
        for u, c in codes:
            packed = packed * len(u) + c
            size *= len(u)
        return {c: st[c] for c in vals}, mask, packed, size, \
            [u for u, _ in codes]

    def answer(self, q: RefQuery, ts: Optional[int] = None
               ) -> List[Dict[str, Any]]:
        """Rows of ``q`` at snapshot ``ts``, summed in float64: from the
        cube where it serves ``q``, else directly."""
        rows = self.from_cube(q, ts)
        return self.direct(q, ts) if rows is None else rows

    def direct(self, q: RefQuery, ts: Optional[int] = None
               ) -> List[Dict[str, Any]]:
        """Rows of ``q`` at snapshot ``ts`` from every row the snapshot
        holds, summed in float64."""
        vals, mask, packed, size, uniq = self.prepare(q, ts)
        p = packed[mask]
        cnt = np.bincount(p, minlength=size)
        sums = {c: np.bincount(p, weights=v[mask].astype(np.float64),
                               minlength=size) for c, v in vals.items()}
        return emit(q, uniq, cnt, sums)

    def writes(self) -> Writes:
        """``inserts`` and ``deletes`` as arrays, rebuilt when either list
        has grown."""
        at = (len(self.inserts), len(self.deletes))
        if self._writes is None or self._writes.at != at:
            self._writes = Writes(
                at,
                np.asarray([t for t, _ in self.inserts], np.int64),
                np.asarray([r[self.pk] for _, r in self.inserts], np.int64),
                np.asarray([t for t, _ in self.deletes], np.int64),
                np.asarray([pk for _, pk in self.deletes], np.int64),
                [r for _, r in self.inserts], {})
        return self._writes

    def seen(self, ts: int) -> int:
        """How many acknowledged writes snapshot ``ts`` sees."""
        w = self.writes()
        return int(np.count_nonzero(w.ins_ts <= ts) +
                   np.count_nonzero(w.del_ts <= ts))

    def _packed(self, keys: Sequence[str],
                rows: Optional[np.ndarray] = None):
        """Packed baseline group codes of every row (or of ``rows``), the
        number of groups and the keys' value sets."""
        n = self.cols[self.pk].shape[0] if rows is None else rows.shape[0]
        packed, size, uniq = np.zeros(n, np.int64), 1, []
        for g in keys:
            u, c = self._codes(g)
            packed = packed * len(u) + (c if rows is None else c[rows])
            size *= len(u)
            uniq.append(u)
        return packed, size, uniq

    def _cube(self, column: Optional[str], keys: Tuple[str, ...],
              vals: Tuple[str, ...]) -> Optional[Cube]:
        """The cube of one shape, built on first use; None where the
        predicate column is not integer or the cube would be too large."""
        shape = (column, keys, vals)
        if shape in self._cubes:
            return self._cubes[shape]
        cube = None
        n = self.cols[self.pk].shape[0]
        x = np.zeros(n, np.int64) if column is None else self.cols[column]
        if x.dtype.kind in "iu" and n:
            lo = int(x.min())
            span = int(x.max()) - lo + 1
            packed, size, uniq = self._packed(keys)
            if span <= CUBE_MAX_VALUES and size <= CUBE_MAX_GROUPS:
                idx = (x.astype(np.int64) - lo) * size + packed
                cnt = np.bincount(idx, minlength=span * size)
                sums = {c: np.bincount(idx, weights=np.asarray(
                    self.cols[c], np.float64), minlength=span * size
                ).reshape(span, size) for c in vals}
                cube = Cube(lo, cnt.reshape(span, size), sums, uniq)
        self._cubes[shape] = cube
        return cube

    def _baseline_rows(self, pks: np.ndarray) -> np.ndarray:
        """Indices of the baseline rows whose primary key is in ``pks``
        (distinct)."""
        if self._pk_sorted is None:
            order = np.argsort(self.cols[self.pk], kind="stable")
            self._pk_sorted = (order, self.cols[self.pk][order])
        order, spk = self._pk_sorted
        a = np.searchsorted(spk, pks, "left")
        n = np.searchsorted(spk, pks, "right") - a
        first = np.repeat(a - (np.cumsum(n) - n), n)
        return order[first + np.arange(first.shape[0])]

    def from_cube(self, q: RefQuery, ts: Optional[int] = None
                  ) -> Optional[List[Dict[str, Any]]]:
        """Rows of ``q`` at snapshot ``ts`` (None: after every write) from
        the cube, or None where the cube does not serve ``q``: another
        aggregate than count, sum or avg, a predicate column that is not
        integer, more values or groups than a cube holds, or a row
        inserted by ``ts`` in ``q``'s range with a group value the baseline
        lacks (the direct way extends the keys' value sets)."""
        if any(op not in CUBE_OPS for op, _, _ in q.aggs):
            return None
        vals = tuple(sorted({c for _, c, _ in q.aggs if c is not None}))
        cube = self._cube(q.column, q.group_by, vals)
        if cube is None:
            return None
        span = cube.cnt.shape[0]
        a, b = 0, span
        if q.column is not None and q.lo is not None:
            a = min(max(q.lo - cube.lo, 0), span)
        if q.column is not None and q.hi is not None:
            b = min(max(q.hi - cube.lo + 1, 0), span)
        cnt = cube.cnt[a:b].sum(axis=0)
        sums = {c: s[a:b].sum(axis=0) for c, s in cube.sums.items()}
        if (self.inserts or self.deletes) and not self._apply_writes(
                q, ts, cube, cnt, sums):
            return None
        return emit(q, cube.uniq, cnt, sums)

    def _apply_writes(self, q: RefQuery, ts: Optional[int], cube: Cube,
                      cnt: np.ndarray, sums: Dict[str, np.ndarray]) -> bool:
        """Take from ``cnt`` and ``sums`` the baseline rows deleted by
        ``ts`` and add the rows inserted by then and not deleted, those in
        ``q``'s range; False where an inserted row's group value is not in
        the cube's value sets."""
        w = self.writes()
        last = np.iinfo(np.int64).max if ts is None else ts
        dels = np.unique(w.del_pk[w.del_ts <= last])
        size = cnt.shape[0]
        gone = self._baseline_rows(dels)
        if q.column is not None:
            gone = gone[_in_range(q, self.cols[q.column][gone])]
        g = self._packed(q.group_by, gone)[0]
        cnt -= np.bincount(g, minlength=size)
        for c in sums:
            sums[c] -= np.bincount(g, weights=np.asarray(
                self.cols[c][gone], np.float64), minlength=size)
        new = np.nonzero((w.ins_ts <= last) & ~np.isin(w.ins_pk, dels))[0]
        if q.column is not None and new.shape[0]:
            new = new[_in_range(q, w.col(q.column)[new])]
        if not new.shape[0]:
            return True
        g = np.zeros(new.shape[0], np.int64)
        for key, u in zip(q.group_by, cube.uniq):
            v = w.col(key)[new]
            at = np.minimum(np.searchsorted(u, v), len(u) - 1)
            if (u[at] != v).any():
                return False
            g = g * len(u) + at
        cnt += np.bincount(g, minlength=size)
        for c in sums:
            sums[c] += np.bincount(g, weights=np.asarray(
                w.col(c)[new], np.float64), minlength=size)
        return True


def emit(q: RefQuery, uniq: Sequence[np.ndarray], cnt: np.ndarray,
         sums: Dict[str, np.ndarray]) -> List[Dict[str, Any]]:
    """Result rows from per-group counts and sums: every non-empty group,
    its keys decoded, sorted by the query's sort keys."""
    rows = []
    for g in np.nonzero(cnt)[0]:
        r: Dict[str, Any] = {}
        rem = int(g)
        for name, u in reversed(list(zip(q.group_by, uniq))):
            r[name] = _key(u[rem % len(u)])
            rem //= len(u)
        for op, c, alias in q.aggs:
            if op == "count":
                r[alias] = int(cnt[g])
            elif op == "sum":
                r[alias] = float(sums[c][g])
            elif op == "avg":
                r[alias] = float(sums[c][g]) / int(cnt[g])
            else:
                raise ValueError(f"reference has no aggregate {op!r}")
        rows.append(r)
    rows.sort(key=lambda r: tuple(r[s] for s in q.sort_by))
    return rows


def compare(q: RefQuery, got: Sequence[Dict[str, Any]],
            want: Sequence[Dict[str, Any]]) -> Tuple[Optional[str], float]:
    """(what differs exactly, or None; the largest relative float error)."""
    gk = [tuple(_key(r[k]) for k in q.group_by) for r in got]
    wk = [tuple(r[k] for k in q.group_by) for r in want]
    if gk != wk:
        return f"groups {gk} != reference {wk}", 0.0
    worst = 0.0
    for g, w, key in zip(got, want, wk):
        for op, _, alias in q.aggs:
            a, b = g[alias], w[alias]
            if op == "count":
                if int(a) != b:
                    return f"{alias} {a} != {b} for {key}", worst
                continue
            if a is None:
                return f"{alias} missing for {key}", worst
            err = abs(float(a) - b) / max(abs(b), 1e-30)
            worst = max(worst, err)
    return None, worst


def verdict(numbers: Dict[str, float]) -> bool:
    """Every compared number within its limit."""
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)
